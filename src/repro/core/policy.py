"""Recipe search: derive IOLM-DB-Perf and IOLM-DB-Acc variants per query.

The paper evaluates two instance-optimized variants per workload
(Table 1): *Perf* (highest throughput) and *Acc* (highest accuracy,
normalized against the uncompressed baseline = 1).  This module
reproduces that policy: enumerate a family-aware recipe grid, compress,
score each candidate by

  - accuracy  = agreement with the BASELINE model's outputs on held-out
    rows (exact-match of greedy decodes — the paper's normalization)
  - cost      = measured rows/s where runnable (small models), plus an
    analytic FLOPs+bytes proxy that scales to big models

and pick argmax-throughput subject to an accuracy floor (Perf) and
argmax-accuracy with bytes tie-break (Acc).
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.compressed import param_bytes
from repro.core.pipeline import InstanceOptimizer, Recipe


# ---------------------------------------------------------------------------
# recipe space
# ---------------------------------------------------------------------------

def default_recipe_space(cfg, *, aggressive: bool = True) -> List[Recipe]:
    """Family-aware candidate grid, ordered roughly mild -> aggressive."""
    rs: List[Recipe] = [
        Recipe(name="w8-gptq", wbits=8, quant_method="gptq"),
        Recipe(name="w8-absmax", wbits=8, quant_method="absmax"),
        Recipe(name="w8-smooth", wbits=8, smooth_alpha=0.5),
        Recipe(name="w8-24", wbits=8, nm=(2, 4)),
        Recipe(name="w4-gptq", wbits=4, group=64),
    ]
    if aggressive:
        rs += [
            Recipe(name="w8-ffn75", wbits=8, ffn_keep_frac=0.75),
            Recipe(name="w8-24-ffn75", wbits=8, nm=(2, 4),
                   ffn_keep_frac=0.75),
            Recipe(name="w4-24", wbits=4, group=64, nm=(2, 4)),
        ]
        if cfg.family != "rwkv" and cfg.n_kv_heads >= 2:
            rs.append(Recipe(name="w8-kv50", wbits=8, kv_keep_frac=0.5))
        if cfg.family == "moe":
            keep = max(cfg.top_k, cfg.n_experts // 2)
            rs.append(Recipe(name="w8-expert50", wbits=8, experts_keep=keep))
            rs.append(Recipe(name="w8-expert25", wbits=8,
                             experts_keep=max(cfg.top_k, cfg.n_experts // 4)))
    return rs


# ---------------------------------------------------------------------------
# evaluation
# ---------------------------------------------------------------------------

def greedy_decode(params, cfg, prompts: jnp.ndarray, max_new: int,
                  *, lengths=None) -> np.ndarray:
    """Greedy generation for a [B, S] right-padded prompt batch.

    ``lengths`` [B]: true prompt lengths (defaults to S).  First-token
    logits are gathered at each row's last REAL position and decode
    positions advance per row.
    """
    from repro.models import api
    B, S = prompts.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    max_len = S + max_new
    logits, cache = api.prefill(params, cfg, {"tokens": prompts},
                                max_len=max_len, compact_local=False)
    last = jnp.take_along_axis(logits, (lengths - 1)[:, None, None],
                               axis=1)[:, 0]
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
    outs = [tok]

    step = jax.jit(lambda p, c, t, pos: api.decode_step(
        p, cfg, c, t, pos, max_len=max_len))
    for t in range(max_new - 1):
        lg, cache = step(params, cache, tok, lengths + t)
        tok = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
        outs.append(tok)
    return np.asarray(jnp.concatenate(outs, axis=1))


@dataclass
class EvalResult:
    accuracy: float          # exact-match agreement with baseline
    token_agreement: float   # per-token agreement (softer signal)
    rows_per_s: float
    bytes: int
    cost_proxy: float        # analytic decode cost (bytes/token moved)


def make_agreement_eval(base_params, base_cfg, prompts, *, max_new: int = 16,
                        lengths=None, timed: bool = True) -> Callable:
    """Returns eval_fn(params, cfg) scoring agreement vs the baseline."""
    ref = greedy_decode(base_params, base_cfg, prompts, max_new,
                        lengths=lengths)

    def eval_fn(params, cfg) -> EvalResult:
        t0 = time.time()
        out = greedy_decode(params, cfg, prompts, max_new, lengths=lengths)
        dt = time.time() - t0
        exact = float(np.mean(np.all(out == ref, axis=1)))
        tok = float(np.mean(out == ref))
        nbytes = param_bytes(params)
        return EvalResult(accuracy=exact, token_agreement=tok,
                          rows_per_s=prompts.shape[0] / max(dt, 1e-9),
                          bytes=nbytes,
                          cost_proxy=float(nbytes))
    return eval_fn


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

@dataclass
class Candidate:
    recipe: Recipe
    result: EvalResult
    report: Any
    params: Any = None
    cfg: Any = None


@dataclass
class SearchOutcome:
    baseline: EvalResult
    candidates: List[Candidate]
    perf: Optional[Candidate]
    acc: Optional[Candidate]
    # recipes that raised during compression or evaluation, with the
    # exception as "Type: message" — never dropped silently
    dropped: List[Tuple[str, str]] = field(default_factory=list)

    def table(self) -> str:
        rows = [f"{'recipe':24s} {'acc':>5s} {'tok':>5s} {'rows/s':>8s} "
                f"{'MB':>8s}"]
        rows.append(f"{'baseline':24s} {self.baseline.accuracy:5.2f} "
                    f"{self.baseline.token_agreement:5.2f} "
                    f"{self.baseline.rows_per_s:8.2f} "
                    f"{self.baseline.bytes / 1e6:8.1f}")
        for c in self.candidates:
            tag = ""
            if self.perf is c:
                tag += " <- Perf"
            if self.acc is c:
                tag += " <- Acc"
            rows.append(f"{c.recipe.name:24s} {c.result.accuracy:5.2f} "
                        f"{c.result.token_agreement:5.2f} "
                        f"{c.result.rows_per_s:8.2f} "
                        f"{c.result.bytes / 1e6:8.1f}{tag}")
        rows += [f"{name:24s} dropped: {err}" for name, err in self.dropped]
        return "\n".join(rows)


def search(optimizer: InstanceOptimizer, eval_fn: Callable,
           recipes: List[Recipe], *, acc_floor: float = 0.9,
           keep_params: bool = False) -> SearchOutcome:
    """Compress with every recipe, evaluate, select Perf/Acc variants."""
    with tracing.span("iolm.search"):
        with tracing.span("iolm.eval", recipe="baseline"):
            baseline = eval_fn(optimizer.params, optimizer.cfg)
        cands: List[Candidate] = []
        dropped: List[Tuple[str, str]] = []
        for r in recipes:
            try:
                with tracing.span("iolm.compress", recipe=r.name) as rec:
                    params2, cfg2, report = optimizer.apply(r)
                    if rec:     # keep its device work out of the eval
                        jax.block_until_ready(params2)
                with tracing.span("iolm.eval", recipe=r.name):
                    res = eval_fn(params2, cfg2)
            except Exception as e:  # a recipe inapplicable to this family
                dropped.append((r.name, f"{type(e).__name__}: {e}"))
                continue
            cands.append(Candidate(recipe=r, result=res, report=report,
                                   params=params2 if keep_params else None,
                                   cfg=cfg2))
    perf = acc = None
    ok = [c for c in cands if c.result.accuracy >= acc_floor]
    pool = ok or cands
    if pool:
        perf = max(pool, key=lambda c: (c.result.rows_per_s,
                                        -c.result.bytes))
        acc = max(cands, key=lambda c: (c.result.accuracy,
                                        c.result.token_agreement,
                                        -c.result.bytes))
    return SearchOutcome(baseline=baseline, candidates=cands, perf=perf,
                         acc=acc, dropped=dropped)
