"""Pallas TPU kernel: tiled online-softmax attention (flash attention).

Supports the assigned architectures' attention variants in one kernel:
GQA head grouping, causal masking, sliding-window (gemma local layers),
and gemma2-style tanh logit softcap.  The online-softmax running state
(m, l, acc) lives in VMEM scratch and persists across the KV grid
dimension; causal/window-excluded KV tiles are skipped via ``pl.when``
so the MXU does no work for fully-masked tiles.

Layout: the ops.py wrapper flattens heads into the batch dimension —
q [BH, S, D], k/v [BK, T, D] — and passes the (static) GQA group size so
the kernel's index maps pick the right KV head for each Q head.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nkv: int, bq: int, bkv: int, scale: float, causal: bool,
            window: int, softcap: float, t_real: int, q_offset: int):
    i = pl.program_id(1)   # query block
    j = pl.program_id(2)   # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) \
        + q_offset
    kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)

    # tile-level skip: can any (q, k) pair in this tile pair attend?
    first_q = i * bq + q_offset
    last_q = first_q + bq - 1
    first_k, last_k = j * bkv, j * bkv + bkv - 1
    live = first_k < t_real
    if causal:
        live &= first_k <= last_q
    if window:
        live &= last_k >= first_q - window + 1

    @pl.when(live)
    def _tile():
        q = q_ref[0]              # [bq, D]
        k = k_ref[0]              # [bkv, D]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = kpos < t_real
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                      # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)           # [bq, 1]
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nkv - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, group: int, causal: bool = True,
                           window: int = 0, softcap: float = 0.0,
                           t_real: int = 0, q_offset: int = 0,
                           bq: int = 256, bkv: int = 256,
                           interpret: bool = False):
    """q [BH, S, D], k/v [BK, T, D] with BH = BK * group -> [BH, S, D].

    ``t_real``: true KV length (<= padded T); ``q_offset``: absolute
    position of q row 0 (for decode/chunked prefill).
    """
    BH, S, D = q.shape
    BK, T, _ = k.shape
    assert BH == BK * group, (BH, BK, group)
    bq, bkv = min(bq, S), min(bkv, T)
    assert S % bq == 0 and T % bkv == 0, (S, T, bq, bkv)
    t_real = t_real or T
    grid = (BH, S // bq, T // bkv)
    scale = 1.0 / math.sqrt(D)
    return pl.pallas_call(
        functools.partial(_kernel, nkv=T // bkv, bq=bq, bkv=bkv, scale=scale,
                          causal=causal, window=window, softcap=softcap,
                          t_real=t_real, q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, D), lambda h, i, j: (h // group, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda h, i, j: (h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, D), jnp.float32),   # running numerator
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
