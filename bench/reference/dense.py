"""Plain reference of the dense decoder the two dense configurations
state, in float32 with full-precision matmuls, layer by layer.

It follows the configuration's block, as the program's dense family
defines it (and as the configuration file lists under ``assumed``):

  x = embed[tokens]
  per layer:  h = rmsnorm(x) * ln1;  q, k, v = h Wq, h Wk, h Wv
              rotary positions on q and k (half-split, base rope_theta)
              causal grouped-query softmax attention, 1/sqrt(head_dim)
              x = x + attn Wo
              h = rmsnorm(x) * ln2
              x = x + (silu(h Wg) * (h Wi)) Wo      (gated)
                  or  gelu_tanh(h Wi) Wo             (plain)
  logits = (rmsnorm(x) * ln_f) Wunembed

``wbits`` states the served instance's weights: every projection and
the unembedding are rounded to ``wbits``-bit integers with one absmax
scale per group of ``group`` input rows and output column (the
``w8-absmax`` recipe), then used in float32; the embedding and norms
stay as given.  The reference imports nothing of the program.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST
# options of the program's dense family this reference does not follow
_UNSUPPORTED = ("post_norms", "rms_offset", "emb_scale", "attn_softcap",
                "final_softcap", "attn_pattern", "tie_embeddings")


def check_supported(m: Dict[str, Any]) -> None:
    if m.get("family") != "dense" or any(m.get(k) for k in _UNSUPPORTED):
        raise ValueError(f"the dense reference does not follow "
                         f"{ {k: m.get(k) for k in _UNSUPPORTED if m.get(k)} } "
                         f"(family {m.get('family')!r})")


def group_size(d_in: int, group: int) -> int:
    """Largest divisor of ``d_in`` not above ``group``."""
    g = min(group, d_in)
    while d_in % g:
        g -= 1
    return g


def absmax_round(w, bits: int, group: int):
    """``w`` [d_in, d_out] rounded to ``bits``-bit integers, one scale
    per (group of input rows, output column), back in float32."""
    if bits >= 16:
        return w.astype(jnp.float32)
    d_in, d_out = w.shape
    g = group_size(d_in, group)
    qmax = (1 << (bits - 1)) - 1
    wg = w.astype(jnp.float32).reshape(d_in // g, g, d_out)
    scale = jnp.max(jnp.abs(wg), axis=1, keepdims=True) / qmax + 1e-12
    q = jnp.clip(jnp.round(wg / jnp.maximum(scale, 1e-12)), -qmax - 1, qmax)
    return (q * scale).reshape(d_in, d_out)


def _rms(x, w):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * w.astype(jnp.float32)


def _rope(x, positions, theta: float):
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(x, w):
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


@partial(jax.jit, static_argnames=("m", "bits", "group"))
def _layer(x, lw, *, m, bits: int, group: int):
    B, T, d = x.shape
    H, K = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // H
    W = {k: absmax_round(v, bits, group) for k, v in lw["attn"].items()}
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    h = _rms(x, lw["ln1"]["w"])
    q = _rope(_mm(h, W["wq"]).reshape(B, T, H, hd), pos, m["rope_theta"])
    k = _rope(_mm(h, W["wk"]).reshape(B, T, K, hd), pos, m["rope_theta"])
    v = _mm(h, W["wv"]).reshape(B, T, K, hd)
    q = q.reshape(B, T, K, H // K, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k, precision=HIGHEST)
    s = s / math.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bkgts,bskd->btkgd", p, v, precision=HIGHEST)
    x = x + _mm(a.reshape(B, T, H * hd), W["wo"])
    M = {k: absmax_round(v, bits, group) for k, v in lw["mlp"].items()}
    h = _rms(x, lw["ln2"]["w"])
    if "wg" in M:
        f = jax.nn.silu(_mm(h, M["wg"])) * _mm(h, M["wi"])
    else:
        f = jax.nn.gelu(_mm(h, M["wi"]), approximate=True)
    return x + _mm(f, M["wo"])


@partial(jax.jit, static_argnames=("bits", "group"))
def _head(x, read_pos, ln_f, unembed, *, bits: int, group: int):
    h = jnp.take_along_axis(x, read_pos[..., None], axis=1)
    return _mm(_rms(h, ln_f), absmax_round(unembed, bits, group))


def logits_at(weights, m: Dict[str, Any], tokens, read_pos, *,
              bits: int, group: int = 128) -> np.ndarray:
    """float32 logits [B, P, V] at ``read_pos`` [B, P] of the token
    rows ``tokens`` [B, T] (right-padded: causal attention keeps the
    padding out of every earlier position)."""
    check_supported(m)
    frozen = tuple(sorted((k, v) for k, v in m.items()
                          if isinstance(v, (int, float, str, bool))))
    mh = _Frozen(frozen)
    x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    stack = weights["blocks"][0]
    for i in range(m["n_layers"]):
        lw = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
        x = _layer(x, lw, m=mh, bits=bits, group=group)
    out = _head(x, jnp.asarray(read_pos), weights["ln_f"]["w"],
                weights["unembed"], bits=bits, group=group)
    return np.asarray(out)


class _Frozen(dict):
    """A hashable view of the configuration's scalar sizes, for use as
    a static argument."""

    def __init__(self, items):
        super().__init__(items)
        self._key = items

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key
