"""Random weights from the run seed, made on the device in one jitted
call, in the type they are served in (bf16).

They are the benchmark's data: the program is handed them, and the
plain reference reads the same arrays, so the reference never takes
weights that the program made.  The tree has the layout the program's
dense family takes (one scanned block stack, no unrolled tail):

  embed [V, d]      unembed [d, V]      ln_f {w [d]}
  blocks: [ {ln1 {w}, attn {wq, wk, wv, wo}, ln2 {w},
             mlp {wi, wo[, wg]}} stacked over the layers ]
  tail: []

Scales: N(0, 1/d_in) for every projection, the two output projections
of a block further scaled by 1/sqrt(2 n_layers), embeddings N(0, 0.02^2),
norm weights 1 — the usual initialisation of a pre-norm decoder.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from iolmbench.traffic import seed_words


def weight_key(seed: int, salt: int):
    """A threefry key from any whole-number seed and the configuration's
    salt."""
    words = seed_words(seed, salt, n=2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes of the tree for the configuration sizes ``m``."""
    d, L = m["d_model"], m["n_layers"]
    hd = m.get("head_dim") or d // m["n_heads"]
    H, K, ff, V = m["n_heads"], m["n_kv_heads"], m["d_ff"], m["vocab_size"]
    mlp = {"wi": (L, d, ff), "wo": (L, ff, d)}
    if m.get("mlp_gated", True):
        mlp["wg"] = (L, d, ff)
    tree = {"embed": (V, d), "ln_f": {"w": (d,)},
            "blocks": [{"ln1": {"w": (L, d)}, "ln2": {"w": (L, d)},
                        "attn": {"wq": (L, d, H * hd), "wk": (L, d, K * hd),
                                 "wv": (L, d, K * hd), "wo": (L, H * hd, d)},
                        "mlp": mlp}],
            "tail": []}
    if not m.get("tie_embeddings", False):
        tree["unembed"] = (d, V)
    return tree


def _std(path: str, shape, n_layers: int) -> float:
    if path.endswith(".w"):
        return 0.0                       # norm weight: ones
    if path == "embed":
        return 0.02
    std = 1.0 / math.sqrt(shape[-2])
    if path.endswith("attn.wo") or path.endswith("mlp.wo"):
        std /= math.sqrt(2 * n_layers)
    return std


def make(m: Dict[str, Any], seed: int, salt: int):
    """The whole tree as bf16 device arrays, from one jitted call."""
    tree = shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    specs = [(jax.tree_util.keystr(p, simple=True, separator="."), s)
             for p, s in flat]

    def build(key):
        out = []
        for i, (path, shape) in enumerate(specs):
            std = _std(path, shape, m["n_layers"])
            if std == 0.0:
                out.append(jnp.ones(shape, jnp.bfloat16))
                continue
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, shape, jnp.float32) * std)
                       .astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(weight_key(seed, salt))
