"""Device-to-host pulls per decode step in the window
(``EngineStats.host_syncs / decode_steps``): each admission and each
decode step pulls its tokens and their confidences, so the decode's
pull is the floor of 2 and admissions add the rest."""


def read(ctx):
    steps = ctx.engine_stats.get("decode_steps")
    if not steps or "host_syncs" not in ctx.engine_stats:
        return None
    return ctx.engine_stats["host_syncs"] / steps
