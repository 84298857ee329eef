"""Random weights from the run seed, made on the device in one jitted
call, in the types they are served in.

They are the benchmark's data: the program is handed them, and the
plain reference reads the same arrays, so the reference never takes
weights that the program made.  The tree has the layout the program's
``transformer`` family (``dense`` and ``moe``) takes:

  embed [V, d]      unembed [d, V] (untied)      ln_f {w [d]}
  blocks: [ one stack per position of the repeating unit of
            ``attn_pattern`` (one stack without a pattern), each block
            stacked over the unit's R repeats ]
  tail:   [ one unstacked block per layer after the last whole unit ]

with the unit, R and the tail found as the program's
``models/transformer.py`` ``pattern_unit`` finds them.  A block is

  dense: {ln1 {w}, attn {wq, wk, wv, wo}, ln2 {w}, mlp {wi, wo[, wg]}}
  moe:   {ln1 {w}, attn {...}, ln2 {w},
          moe {router [d, E] float32, wi, wg [E, d, ffe], wo [E, ffe, d]}
          [, shared_mlp {wi, wo[, wg]} of width n_shared_experts * ffe]
          [, dense_mlp {wi, wo[, wg]} of width d_ff]}

and a stacked leaf has R in front of these shapes.  Every leaf but the
router is in ``param_dtype`` (bf16).

Scales: N(0, 1/d_in) for every projection, the router and each expert
included; the output projections (attention's, the MLPs' and the
experts' ``wo``) further scaled by 1/sqrt(2 n_layers); embeddings
N(0, 0.02^2); norm weights 1 — the usual initialisation of a pre-norm
decoder.  Each leaf draws from the run's key folded with its index in
the flattened tree, so a dense configuration without a pattern gets
the arrays it got before the other layouts were added.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from iolmbench.traffic import seed_words

def weight_key(seed: int, salt: int):
    """A threefry key from any whole-number seed and the configuration's
    salt."""
    words = seed_words(seed, salt, n=2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def pattern_unit(m: Dict[str, Any]) -> Tuple[str, int, int]:
    """(unit, repeats, tail layers): the smallest repeating unit of the
    layer pattern (``'L'`` window, ``'G'`` full per layer), as the
    program's ``pattern_unit`` splits it."""
    pat = m.get("attn_pattern") or "G" * m["n_layers"]
    if len(pat) != m["n_layers"]:
        raise ValueError(f"attn_pattern {pat!r} does not give each of "
                         f"{m['n_layers']} layers a kind")
    n = len(pat)
    for U in range(1, n + 1):
        R = n // U
        unit = pat[:U]
        if (unit * R == pat[:U * R] and pat[U * R:] == unit[:n - U * R]
                and (R >= 2 or U == n)):
            return unit, R, n - U * R
    return pat, 1, 0


def _block(m: Dict[str, Any], lead: Tuple[int, ...]) -> Dict[str, Any]:
    d = m["d_model"]
    hd = m.get("head_dim") or d // m["n_heads"]
    H, K = m["n_heads"], m["n_kv_heads"]
    dt = jnp.dtype(m.get("param_dtype", "bfloat16"))

    def leaf(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(lead + shape, dtype)

    def mlp(ff):
        p = {"wi": leaf(d, ff), "wo": leaf(ff, d)}
        if m.get("mlp_gated", True):
            p["wg"] = leaf(d, ff)
        return p

    block = {"ln1": {"w": leaf(d)}, "ln2": {"w": leaf(d)},
             "attn": {"wq": leaf(d, H * hd), "wk": leaf(d, K * hd),
                      "wv": leaf(d, K * hd), "wo": leaf(H * hd, d)}}
    if m["family"] == "dense":
        block["mlp"] = mlp(m["d_ff"])
        return block
    E, ffe = m["n_experts"], m["moe_d_ff"]
    block["moe"] = {"router": leaf(d, E, dtype=jnp.float32),
                    "wi": leaf(E, d, ffe), "wg": leaf(E, d, ffe),
                    "wo": leaf(E, ffe, d)}
    if m.get("n_shared_experts"):
        block["shared_mlp"] = mlp(m["n_shared_experts"] * ffe)
    if m.get("dense_residual"):
        block["dense_mlp"] = mlp(m["d_ff"])
    return block


def shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """The tree for the configuration sizes ``m``, as
    ``jax.ShapeDtypeStruct`` leaves."""
    norm = m.get("norm_type", "rmsnorm")
    if m["family"] not in ("dense", "moe") or m.get("post_norms") \
            or norm != "rmsnorm":
        raise ValueError(f"no weight layout for family {m['family']!r} "
                         f"with post_norms {m.get('post_norms')!r} and "
                         f"norm {norm!r}")
    d, V = m["d_model"], m["vocab_size"]
    dt = jnp.dtype(m.get("param_dtype", "bfloat16"))
    unit, R, tail = pattern_unit(m)
    tree = {"embed": jax.ShapeDtypeStruct((V, d), dt),
            "ln_f": {"w": jax.ShapeDtypeStruct((d,), dt)},
            "blocks": [_block(m, (R,)) for _ in unit],
            "tail": [_block(m, ()) for _ in range(tail)]}
    if not m.get("tie_embeddings", False):
        tree["unembed"] = jax.ShapeDtypeStruct((d, V), dt)
    return tree


def _std(path: str, shape, n_layers: int) -> float:
    if path.endswith(".w"):
        return 0.0                       # norm weight: ones
    if path == "embed":
        return 0.02
    std = 1.0 / math.sqrt(shape[-2])
    if path.endswith(".wo"):
        std /= math.sqrt(2 * n_layers)
    return std


def make(m: Dict[str, Any], seed: int, salt: int):
    """The whole tree as device arrays, from one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes(m))
    specs = [(jax.tree_util.keystr(p, simple=True, separator="."), s)
             for p, s in flat]

    def build(key):
        out = []
        for i, (path, s) in enumerate(specs):
            std = _std(path, s.shape, m["n_layers"])
            if std == 0.0:
                out.append(jnp.ones(s.shape, s.dtype))
                continue
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, s.shape, jnp.float32) * std)
                       .astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(weight_key(seed, salt))
