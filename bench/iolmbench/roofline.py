"""A kernel's share of its roofline: the least time its calls in the
window could take on this chip, over their device time in the trace.
Returns None where there is nothing to read (no trace, no chip, or no
operation of the kernel), never 0."""
from __future__ import annotations

from typing import Optional

from iolmbench import flops, spec


def _share(ctx, kernel: str, calls) -> Optional[float]:
    tr = ctx.trace
    if tr is None or ctx.peak is None or not calls:
        return None
    dev = tr.kernel_s.get(kernel)
    if not dev:
        return None
    least = flops.least_seconds(spec.kernel_counts(kernel), calls, ctx.peak)
    return 100.0 * least / dev


def matmul_share(ctx, kernel: str) -> Optional[float]:
    calls = flops.matmul_calls(ctx.instance, ctx.admits, ctx.decodes,
                               ctx.slots).get(kernel)
    return _share(ctx, kernel, calls)


def attention_share(ctx, kernel: str) -> Optional[float]:
    return _share(ctx, kernel, flops.attention_calls(ctx.sizes, ctx.decodes,
                                                     ctx.block))
