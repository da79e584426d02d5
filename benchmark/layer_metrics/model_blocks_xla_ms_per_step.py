"""The part of the model no kernel roofline covers: device self time per
step under ``bps.model`` outside Pallas kernels and outside ``bps.head``
— the matmul, norm and activation fusions, forward and backward,
averaged over chips."""

from benchmark.harness import scopes

SPEC = {"name": "model.blocks_xla_ms_per_step", "unit": "ms",
        "layer": "models.transformer", "source": "program_span"}


def read(ctx):
    res = scopes.for_run(ctx)
    if res is None or not res["model_blocks_xla_s"]:
        return None
    return 1e3 * res["model_blocks_xla_s"]
