"""Share of decode slot-steps in the window that served a live row
(``EngineStats.busy_slot_steps / total_slot_steps``), in %."""


def read(ctx):
    total = ctx.engine_stats["total_slot_steps"]
    if not total:
        return None
    return 100.0 * ctx.engine_stats["busy_slot_steps"] / total
