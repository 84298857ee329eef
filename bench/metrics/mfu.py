"""The whole step's share of the chip's bf16 peak, in %: the
operations the served instance needed for the window's real prefill
and decode tokens (``iolmbench.flops.needed_ops``) over the window's
seconds times the peak."""
from iolmbench.flops import needed_ops


def read(ctx):
    if ctx.peak is None or not ctx.window.seconds:
        return None
    ops = needed_ops(ctx.sizes, ctx.instance, ctx.admits, ctx.decodes)
    return 100.0 * ops / (ctx.window.seconds * ctx.peak["bf16_flops_per_s"])
