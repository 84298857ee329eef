"""Which kernel calls a window made, with their shapes, and the
operations the served instance needed for its real tokens.

Counts come from the served instance's own weights and from what the
engine admitted and decoded in the window (``serve.EngineProbe``):

* a prefill admission of ``n`` rows padded to ``b`` tokens runs every
  projection once over ``n * b`` rows (the unembedding included: the
  prefill program computes logits at every position);
* a decode step runs every projection over all ``slots`` rows, and
  ``paged_attention`` once per layer over the slots that held a request,
  a window layer (``'L'`` in ``attn_pattern``) over at most
  ``window_size`` of each slot's positions.

``needed_ops`` counts what the instance needs for the real tokens
only: each row's suffix tokens through every kept weight and through
attention over the prefix and the suffix before it, the unembedding at
the row's last prompt position, and each decoded token through every
kept weight, attention over its live length, and the unembedding.  On
a window layer a query needs at most ``window_size`` keys.  In a
mixture-of-experts layer a token needs its router (2 d E) and
``top_k`` of the ``E`` experts; shared and residual MLPs it needs
whole.  Padding, idle slots, unrouted experts and pruned tiles do not
count.

Expert stacks (``wi``/``wg``/``wo`` under ``.moe.``, ``[.., E, K, N]``)
are left out of ``matmul_calls``: however the program dispatches them
today, they are the expert layer's work, not a plain matmul's.
``expert_calls`` lists them, with ``expert_ops`` and ``expert_bytes``
as their least operations and bytes.  A PR that changes the MoE
dispatch brings its expert kernel's own ``bench/kernels/<kernel>.py``
(whose ``ops`` and ``bytes_moved`` are these two) and its roofline
reader under ``bench/metrics/``, which call ``expert_calls``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np


def _is_expert(path: str) -> bool:
    """A stack of expert matrices: ``wi``/``wg``/``wo`` under ``.moe.``."""
    return (".moe." in f".{path}."
            and path.rsplit(".", 1)[-1] in ("wi", "wg", "wo"))


def instance_matmuls(params, top_k: int = 0) -> List[Dict[str, Any]]:
    """One entry per projection of the served instance: its kernel, K,
    N, how many per forward (the layer stack's depth, times the experts
    of an expert stack) and the kernel's own shape arguments.  An
    expert stack also gives ``experts``, the ``E`` it holds, and
    ``top_k``, the experts each token is routed to."""
    from repro.core.compressed import BlockSparseTensor, QTensor
    out = []
    leaves = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, (QTensor,
                                                 BlockSparseTensor)))[0]
    for path, x in leaves:
        name = jax.tree_util.keystr(path, simple=True, separator=".")
        if isinstance(x, QTensor):
            K, N = x.shape[-2:]
            stack = x.q.shape[:-2]
            out.append({"path": name, "kernel": "quant_matmul"
                        if x.bits == 8 else "int4_matmul", "K": K, "N": N,
                        "per_forward": int(np.prod(stack)),
                        "group": x.group, "kept": K * N})
        elif isinstance(x, BlockSparseTensor):
            K, N = x.w.shape[-2:]
            stack = x.w.shape[:-2]
            depth = int(np.prod(stack))
            kept_tiles = int(np.asarray(x.mask).sum()) // depth
            out.append({"path": name, "kernel": "block_sparse_matmul",
                        "K": K, "N": N, "per_forward": depth, "bs": x.bs,
                        "kept": kept_tiles * x.bs * x.bs,
                        "kept_tiles": kept_tiles})
        else:
            continue
        if _is_expert(name):
            if not top_k:
                raise ValueError(f"expert stack {name} needs the "
                                 f"configuration's top_k")
            out[-1].update(experts=int(stack[-1]), top_k=int(top_k))
    return out


def _rows(admits, decodes, slots: int) -> List[int]:
    """Rows of every call the window made: each admission's padded
    tokens, then ``slots`` per decode step."""
    return [padded for _, padded, _ in admits] + [slots] * len(decodes)


def matmul_calls(instance: Sequence[Dict[str, Any]],
                 admits: Sequence[Tuple[int, int, Any]], decodes: Sequence,
                 slots: int) -> Dict[str, List[Tuple[Dict, int]]]:
    """kernel -> [(shape keyword arguments, number of calls)], expert
    stacks left out."""
    rows = _rows(admits, decodes, slots)
    calls: Dict[str, List[Tuple[Dict, int]]] = {}
    for w in instance:
        if "experts" in w:
            continue
        k = w["kernel"]
        for M in sorted(set(rows)):
            n = rows.count(M) * w["per_forward"]
            if k == "quant_matmul":
                shape = {"M": M, "K": w["K"], "N": w["N"],
                         "group": w["group"]}
            elif k == "block_sparse_matmul":
                shape = {"M": M, "K": w["K"], "N": w["N"], "bs": w["bs"],
                         "kept": w["kept_tiles"]}
            else:
                continue
            calls.setdefault(k, []).append((shape, n))
    return calls


def _expert_weight_bytes(w: Dict[str, Any]) -> float:
    """Bytes of one expert's matrix as the instance holds it: the kept
    bf16 tiles, or the codes and their f32 scales."""
    if w["kernel"] == "block_sparse_matmul":
        return 2.0 * w["kept"]
    code = 1.0 if w["kernel"] == "quant_matmul" else 0.5
    return code * w["K"] * w["N"] + 4.0 * (w["K"] // w["group"]) * w["N"]


def expert_calls(instance: Sequence[Dict[str, Any]],
                 admits: Sequence[Tuple[int, int, Any]], decodes: Sequence,
                 slots: int,
                 reached: Optional[Mapping[Tuple[str, int], float]] = None
                 ) -> List[Tuple[Dict, int]]:
    """[(shape keyword arguments, number of calls)] of the expert
    stacks: one call per layer per admission or decode step, over its
    ``M`` token rows.  ``reached`` maps (stack path, M) to the experts
    such a call reached, where the program reports it; otherwise a call
    is taken to reach ``top_k``, the fewest any routing can, so that a
    share of the roofline built on it cannot pass 100 %."""
    rows = _rows(admits, decodes, slots)
    calls = []
    for w in instance:
        if "experts" not in w:
            continue
        layers = w["per_forward"] // w["experts"]
        for M in sorted(set(rows)):
            r = (reached or {}).get((w["path"], M), w["top_k"])
            calls.append(({"M": M, "K": w["K"], "N": w["N"],
                           "kept": w["kept"], "top_k": w["top_k"],
                           "reached": r,
                           "weight_bytes": _expert_weight_bytes(w)},
                          rows.count(M) * layers))
    return calls


def expert_ops(M: int, K: int, N: int, kept: int, top_k: int,
               reached: float, weight_bytes: float) -> float:
    """Least operations of an expert call: each of the ``M`` rows
    through its ``top_k`` experts' kept weights."""
    return 2.0 * M * top_k * kept


def expert_bytes(M: int, K: int, N: int, kept: int, top_k: int,
                 reached: float, weight_bytes: float) -> float:
    """Least bytes of an expert call: the bf16 rows in and out once,
    and the weights of each expert it reached once."""
    return 2.0 * M * K + reached * weight_bytes + 2.0 * M * N


def _layer_kinds(m: Dict[str, Any]) -> Tuple[int, int, int]:
    """(full layers, window layers, window)."""
    pat = m.get("attn_pattern") or "G" * m["n_layers"]
    local = pat.count("L")
    return m["n_layers"] - local, local, int(m.get("window_size", 4096))


def attention_calls(m: Dict[str, Any], decodes: Sequence[Sequence[int]],
                    block: int) -> List[Tuple[Dict, int]]:
    """``paged_attention`` calls: one per layer per decode step, a
    window layer's over at most ``window_size`` positions a slot."""
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    full, local, win = _layer_kinds(m)
    out = []
    for ls in decodes:
        shape = {"H": m["n_heads"], "Kh": m["n_kv_heads"], "D": hd,
                 "block": block}
        capped = [min(n, win) for n in ls]
        if not local or capped == list(ls):
            out.append((dict(lengths=list(ls), **shape), m["n_layers"]))
            continue
        if full:
            out.append((dict(lengths=list(ls), **shape), full))
        out.append((dict(lengths=capped, **shape), local))
    return out


def least_seconds(counts, calls: Sequence[Tuple[Dict, int]],
                  peak: Dict[str, float]) -> float:
    """The least time the chip could take for these calls: per call the
    larger of operations over peak bf16 FLOP/s and bytes over HBM
    bytes/s, summed."""
    return sum(n * max(counts.ops(**shape) / peak["bf16_flops_per_s"],
                       counts.bytes_moved(**shape) / peak["hbm_bytes_per_s"])
               for shape, n in calls)


def needed_ops(m: Dict[str, Any], instance: Sequence[Dict[str, Any]],
               admits, decodes) -> float:
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    full, local, win = _layer_kinds(m)
    # per (query, key) in all full layers, and in all window layers
    att, att_win = (4.0 * m["n_heads"] * hd * full,
                    4.0 * m["n_heads"] * hd * local)
    head = [w for w in instance if w["path"] == "unembed"]
    body = [w for w in instance if w["path"] != "unembed"]
    kept = sum(w["kept"] * w["per_forward"] for w in body
               if "experts" not in w)
    experts = [w for w in body if "experts" in w]
    kept += sum(w["kept"] * (w["per_forward"] // w["experts"]) * w["top_k"]
                for w in experts)
    if experts:                          # the router, in every layer
        kept += m["d_model"] * experts[0]["experts"] * m["n_layers"]
    per_tok = 2.0 * kept
    head_ops = 2.0 * sum(w["kept"] for w in head)
    total = 0.0
    for _, _, rows in admits:
        for suffix, prefix in rows:
            keys = sum(prefix + i + 1 for i in range(suffix))
            keys_win = sum(min(prefix + i + 1, win) for i in range(suffix))
            total += (suffix * per_tok + att * keys + att_win * keys_win
                      + head_ops)
    for lengths in decodes:
        total += (len(lengths) * (per_tok + head_ops) + att * sum(lengths)
                  + att_win * sum(min(n, win) for n in lengths))
    return total
