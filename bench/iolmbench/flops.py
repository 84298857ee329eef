"""Which kernel calls a window made, with their shapes, and the
operations the served instance needed for its real tokens.

Counts come from the served instance's own weights and from what the
engine admitted and decoded in the window (``serve.EngineProbe``):

* a prefill admission of ``n`` rows padded to ``b`` tokens runs every
  projection once over ``n * b`` rows (the unembedding included: the
  prefill program computes logits at every position);
* a decode step runs every projection over all ``slots`` rows, and
  ``paged_attention`` once per layer over the slots that held a request.

``needed_ops`` counts what the instance needs for the real tokens
only: each row's suffix tokens through every kept weight and through
attention over the prefix and the suffix before it, the unembedding at
the row's last prompt position, and each decoded token through every
kept weight, attention over its live length, and the unembedding.
Padding, idle slots and pruned tiles do not count.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import numpy as np


def instance_matmuls(params) -> List[Dict[str, Any]]:
    """One entry per projection of the served instance: its kernel, K,
    N, how many per forward (the layer stack's depth) and the kernel's
    own shape arguments."""
    from repro.core.compressed import BlockSparseTensor, QTensor
    out = []
    leaves = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, (QTensor,
                                                 BlockSparseTensor)))[0]
    for path, x in leaves:
        name = jax.tree_util.keystr(path, simple=True, separator=".")
        if isinstance(x, QTensor):
            K, N = x.shape[-2:]
            depth = int(np.prod(x.q.shape[:-2]))
            out.append({"path": name, "kernel": "quant_matmul"
                        if x.bits == 8 else "int4_matmul", "K": K, "N": N,
                        "per_forward": depth, "group": x.group,
                        "kept": K * N})
        elif isinstance(x, BlockSparseTensor):
            K, N = x.w.shape[-2:]
            depth = int(np.prod(x.w.shape[:-2]))
            kept_tiles = int(np.asarray(x.mask).sum()) // depth
            out.append({"path": name, "kernel": "block_sparse_matmul",
                        "K": K, "N": N, "per_forward": depth, "bs": x.bs,
                        "kept": kept_tiles * x.bs * x.bs,
                        "kept_tiles": kept_tiles})
    return out


def matmul_calls(instance: Sequence[Dict[str, Any]],
                 admits: Sequence[Tuple[int, int, Any]], decodes: Sequence,
                 slots: int) -> Dict[str, List[Tuple[Dict, int]]]:
    """kernel -> [(shape keyword arguments, number of calls)]."""
    rows = [padded for _, padded, _ in admits] + [slots] * len(decodes)
    calls: Dict[str, List[Tuple[Dict, int]]] = {}
    for w in instance:
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


def attention_calls(m: Dict[str, Any], decodes: Sequence[Sequence[int]],
                    block: int) -> List[Tuple[Dict, int]]:
    """``paged_attention`` calls: one per layer per decode step."""
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return [({"lengths": list(ls), "H": m["n_heads"],
              "Kh": m["n_kv_heads"], "D": hd, "block": block},
             m["n_layers"]) for ls in decodes]


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
    att = 4.0 * m["n_heads"] * hd * m["n_layers"]   # per (query, key)
    head = [w for w in instance if w["path"] == "unembed"]
    body = [w for w in instance if w["path"] != "unembed"]
    per_tok = 2.0 * sum(w["kept"] * w["per_forward"] for w in body)
    head_ops = 2.0 * sum(w["kept"] for w in head)
    total = 0.0
    for _, _, rows in admits:
        for suffix, prefix in rows:
            keys = sum(prefix + i + 1 for i in range(suffix))
            total += suffix * per_tok + att * keys + head_ops
    for lengths in decodes:
        total += len(lengths) * (per_tok + head_ops) + att * sum(lengths)
    return total
