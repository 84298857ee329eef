"""Pallas TPU kernel: paged KV-cache decode attention.

The serving engine stores K/V in fixed-size blocks inside one global
pool — ``[num_blocks, block_size, Kh, D]`` — and each decode slot owns a
block *table* (``[slots, T // block_size]`` int32) mapping its logical
positions onto pool blocks.  Shared prompt prefixes alias the same
blocks across slots, so the kernel must gather K/V through the table
instead of reading a contiguous ``[slot, T, ...]`` tensor.

Decode is one query token per slot, so the kernel computes an *exact*
softmax (not the online/flash recurrence): the grid walks the slot's
blocks, one step holding a block's K/V for every KV head, accumulating
each head's full ``[T, G]`` score matrix and a gathered ``[T, D]`` V
copy in VMEM scratch (T = max_len fits comfortably for
serving-sized contexts), then on the last block applies the
length/window mask and the same max-subtracted softmax as the reference
``_sdpa`` — keeping greedy decode token-identical to the jnp path.

The block table and per-slot lengths ride in scalar-prefetch operands
(``PrefetchScalarGridSpec``) so the K/V index maps can dereference the
table while Pallas schedules the block DMAs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, s_ref, v_scr, *,
            nblk: int, bs: int, kh: int, scale: float, softcap: float,
            window: int):
    s_idx = pl.program_id(0)   # slot
    j = pl.program_id(1)       # block within the slot's table
    rows = pl.ds(pl.multiple_of(j * bs, bs), bs)
    # One grid step holds the block's K/V for every KV head: a per-head
    # block would put a dim of 1 against Kh in the block's second-minor
    # position, which the TPU lowering refuses for Kh > 1.
    for h in range(kh):
        q = q_ref[0, h]                 # [G, D]
        k = k_ref[0, :, h, :]           # [bs, D]
        # scores for this block, [bs, G]; contraction over D is exact
        # math, so blocking T cannot change the result vs the one-shot
        # einsum.
        s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s_ref[h, rows, :] = s
        v_scr[h, rows, :] = v_ref[0, :, h, :]

    @pl.when(j == nblk - 1)
    def _done():
        length = len_ref[s_idx]
        kpos = jax.lax.broadcasted_iota(jnp.int32, (nblk * bs, 1), 0)
        valid = kpos < length
        if window:
            valid &= kpos >= length - window
        for h in range(kh):
            logits = jnp.where(valid, s_ref[h], NEG_INF)   # [T, G]
            m = jnp.max(logits, axis=0, keepdims=True)
            p = jnp.exp(logits - m)
            probs = p / p.sum(axis=0, keepdims=True)
            out = jax.lax.dot_general(probs.astype(v_scr.dtype), v_scr[h],
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            o_ref[0, h] = out.astype(o_ref.dtype)


def paged_attention_kernel(q, k_pool, v_pool, tables, lengths, *,
                           softcap: float = 0.0, window: int = 0,
                           interpret: bool = False):
    """q [S, Kh, G, D], pools [nb, bs, Kh, D], tables [S, nblk] int32,
    lengths [S] int32 -> [S, Kh, G, D].  One decode token per slot."""
    S, Kh, G, D = q.shape
    nb, bs, Khp, _ = k_pool.shape
    St, nblk = tables.shape
    assert Kh == Khp and S == St and lengths.shape == (S,), \
        (q.shape, k_pool.shape, tables.shape, lengths.shape)
    T = nblk * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nblk),
        in_specs=[
            pl.BlockSpec((1, Kh, G, D), lambda s, j, tbl, ln: (s, 0, 0, 0)),
            pl.BlockSpec((1, bs, Kh, D),
                         lambda s, j, tbl, ln: (tbl[s, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, Kh, D),
                         lambda s, j, tbl, ln: (tbl[s, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Kh, G, D),
                               lambda s, j, tbl, ln: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Kh, T, G), jnp.float32),     # full score matrix
            pltpu.VMEM((Kh, T, D), v_pool.dtype),    # gathered V
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nblk=nblk, bs=bs, kh=Kh,
                          scale=1.0 / math.sqrt(D),
                          softcap=softcap, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Kh, G, D), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(tables, lengths, q, k_pool, v_pool)
