"""A kernel's share of its roofline: the least time its calls in the
window could take on this chip, over their device time in the trace.
Returns None where there is nothing to read (no trace, no chip, or no
operation of the kernel), never 0."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

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


def expert_reach(ctx) -> Dict[Tuple[str, int], float]:
    """(expert stack path, M) -> the experts a call of ``M`` rows
    reached, from the program's counters ``moe_prefill_experts`` and
    ``moe_decode_experts`` (their contract is in ``flops``): each over
    its phase's calls, the window's admissions or decode steps times the
    MoE layers.  A phase reads ``top_k``, as ``flops.expert_calls`` does,
    where the program keeps no counter for it, or where its calls differ
    in width: the mean could then charge a narrow admission for experts
    that a wide, compute-bound one reached.  An ``M`` of both phases
    takes the mean over its calls."""
    stacks = [w for w in ctx.instance if "experts" in w]
    layers = sum({w["path"].rsplit(".", 1)[0]:
                  w["per_forward"] // w["experts"] for w in stacks}.values())
    phases = (([p for _, p, _ in ctx.admits],
               ctx.engine_stats.get("moe_prefill_experts")),
              ([ctx.slots] * len(ctx.decodes),
               ctx.engine_stats.get("moe_decode_experts")))
    out = {}
    for w in stacks:
        per_m: Dict[int, list] = {}
        for rows, total in phases:
            if not rows:
                continue
            r = (total / (len(rows) * layers)
                 if total is not None and len(set(rows)) == 1
                 else w["top_k"])
            for M in rows:
                per_m.setdefault(M, []).append(r)
        out.update({(w["path"], M): sum(rs) / len(rs)
                    for M, rs in per_m.items()})
    return out


def expert_share(ctx, kernel: str) -> Optional[float]:
    """The expert kernel's share, its calls reaching the experts that
    ``expert_reach`` reads from the program's count.

    A call's least time, ``max(ops / peak, bytes / bandwidth)``, is
    convex in the experts it reaches (its bytes grow with them, its
    operations do not).  So over calls of one shape, which every mean
    here is taken over, the sum at their mean reach is no more than the
    sum at each call's own reach, and this averaging cannot lift the
    share above 100 %."""
    return _share(ctx, kernel, flops.expert_calls(
        ctx.instance, ctx.admits, ctx.decodes, ctx.slots,
        reached=expert_reach(ctx)))


def attention_share(ctx, kernel: str) -> Optional[float]:
    return _share(ctx, kernel, flops.attention_calls(ctx.sizes, ctx.decodes,
                                                     ctx.block))
