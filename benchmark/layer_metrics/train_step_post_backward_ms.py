"""What runs after the gradients exist: per step, from the end of the
last backward op under ``bps.model`` / ``bps.head`` to the end of the
step program (median over the traced steps, worst chip).  By elapsed
time, so fusion attribution cannot blur it.  On one chip that is the
optimizer; on four it is bucket packing, every reduction, unpacking and
the optimizer, in series unless something overlaps."""

from benchmark.harness import scopes

SPEC = {"name": "train_step.post_backward_ms", "unit": "ms",
        "layer": "training.step", "source": "program_span"}


def read(ctx):
    res = scopes.for_run(ctx)
    if res is None or res["post_backward_s"] is None:
        return None
    return 1e3 * res["post_backward_s"]
