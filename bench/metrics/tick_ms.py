"""Host milliseconds per scheduler tick that had work: the window's
seconds over its ticks."""


def read(ctx):
    if not ctx.window.ticks:
        return None
    return 1000.0 * ctx.window.seconds / ctx.window.ticks
