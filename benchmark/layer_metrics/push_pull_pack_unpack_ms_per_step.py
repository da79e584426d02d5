"""The copies bucketing itself costs: device self time per step under
``bps.push_pull/pack/*`` and ``/unpack`` plus the non-collective ops of
``/reduce/*`` (wire casts, pads, the averaging divide), averaged over
chips.  A one-chip step has no ``push_pull`` and reports nothing."""

from benchmark.harness import scopes

SPEC = {"name": "push_pull.pack_unpack_ms_per_step", "unit": "ms",
        "layer": "common.partition", "source": "program_span"}


def read(ctx):
    res = scopes.for_run(ctx)
    if res is None or not res["has_push_pull"]:
        return None
    return 1e3 * res["pack_unpack_s"]
