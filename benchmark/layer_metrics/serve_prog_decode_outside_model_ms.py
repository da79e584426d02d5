"""Device milliseconds a launch of the decode program
(``jit(decode_fn)``) spent outside the model: self time of the ops that
do not lie under ``bps.model`` — the token pick under
``bps.serve/select``, the masks and whatever the compiler added
(``unscoped``).  By scope, and the largest such ops by name: the
``decode_scopes`` note.  ``None`` on a program without the scopes."""

from benchmark.harness import host_spans

SPEC = {"name": "serve_prog.decode_outside_model_ms", "unit": "ms",
        "layer": "serving.engine", "source": "program_span"}


def read(ctx):
    res = host_spans.decode_scopes_for_run(ctx)
    if res is None:
        return None
    return 1e3 * res["outside_model_s"]
