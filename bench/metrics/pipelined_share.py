"""Share of the window's decode steps launched while the step before
had not been pulled yet (``EngineStats.pipelined_steps /
decode_steps``), in %: the steps that did not wait for the host's pull
of the last tokens.  A program without the counter reads nothing."""


def read(ctx):
    steps = ctx.engine_stats.get("decode_steps")
    if not steps or "pipelined_steps" not in ctx.engine_stats:
        return None
    return 100.0 * ctx.engine_stats["pipelined_steps"] / steps
