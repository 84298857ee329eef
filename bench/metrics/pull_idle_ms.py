"""Device idle milliseconds per scheduler tick of the window spent
while the host waited on and copied the decode step's tokens and
confidences (the program's ``engine.pull`` span): the idle gaps the
trace puts on that span, innermost, over the window's ticks with work.
None for a program without the engine's own spans."""

# spans the program opens inside the engine's tick (repro/serving)
PROGRAM_SPANS = ("engine.schedule", "engine.top_up", "engine.admit",
                 "engine.prefill", "engine.first_token", "engine.insert",
                 "engine.decode", "engine.pull", "engine.retire")


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.window.ticks:
        return None
    if not any(n in PROGRAM_SPANS for n in tr.idle_by_span):
        return None
    return 1000.0 * tr.idle_by_span.get("engine.pull", 0.0) / ctx.window.ticks
