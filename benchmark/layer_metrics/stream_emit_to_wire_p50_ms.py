"""Median time from the tick thread's ``_emit`` of a token to the
connection thread's ``sendall`` of its frame returning
(``serve.emit_to_wire_s`` on the STATS reply after the window; a bounded
reservoir of the most recent 4096 tokens): the hand-off between the two
threads that a client's token gap contains.  ``None`` on a program from
before the histogram."""

SPEC = {"name": "stream.emit_to_wire_p50_ms", "unit": "ms",
        "layer": "serving.frontend", "source": "program_counter"}


def read(ctx):
    if ctx.serve is None:
        return None
    after = ctx.serve["stats_after"]
    if not after.get("emit_to_wire_n"):
        return None
    ctx.note(event="emit_to_wire", n=after["emit_to_wire_n"],
             p99_ms=1e3 * after["emit_to_wire_p99_s"])
    return 1e3 * after["emit_to_wire_p50_s"]
