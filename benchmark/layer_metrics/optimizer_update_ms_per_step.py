"""Device self time per step of the ops whose root lies under
``bps.optimizer``: the inner optax update (AdamW) and the parameter
write, averaged over chips."""

from benchmark.harness import scopes

SPEC = {"name": "optimizer.update_ms_per_step", "unit": "ms",
        "layer": "training.optimizer", "source": "program_span"}


def read(ctx):
    res = scopes.for_run(ctx)
    if res is None or not res["optimizer_s"]:
        return None
    return 1e3 * res["optimizer_s"]
