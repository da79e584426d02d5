"""Tokens generated per decode tick over the window (exact counters:
``serve.tokens_generated`` / ``serve.decode_ticks``, after - before) —
the batch a tick really carries."""

SPEC = {"name": "sched.tokens_per_decode_tick", "unit": "tokens",
        "layer": "serving.scheduler", "source": "program_counter"}


def read(ctx):
    if ctx.serve is None:
        return None
    before, after = ctx.serve["stats_before"], ctx.serve["stats_after"]
    ticks = (after.get("serve.decode_ticks", 0)
             - before.get("serve.decode_ticks", 0))
    if ticks <= 0:
        return None
    tokens = (after.get("serve.tokens_generated", 0)
              - before.get("serve.tokens_generated", 0))
    ctx.note(event="ticks", decode_ticks=ticks, tokens_generated=tokens)
    return tokens / ticks
