"""Jit'd public wrappers around the Pallas kernels.

Shape plumbing lives here: flattening batch dims, padding to tile
multiples, head/batch reshapes for attention, and the interpret-mode
fallback so the kernels run (slowly, but bit-faithfully) on CPU for
tests; on a TPU the compiled kernels always run.
``repro.core.compressed.matmul`` and the model layers call these when
the active KernelBackend resolves to ``"pallas"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro import tracing
from repro.kernels.block_sparse import block_sparse_matmul_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.paged_attention import paged_attention_kernel
from repro.kernels.quant_matmul import quant_matmul_kernel, tiles


def _off_tpu() -> bool:
    """Whether a kernel called with ``interpret=None`` runs off-TPU.

    Resolved per call from the live default backend, never cached
    (tests change the effective platform after import).  On a TPU
    process this is always False: the compiled kernel runs, and an
    input the TPU cannot take fails loudly instead of falling back to
    the interpreter or the reference formula."""
    return jax.default_backend() != "tpu"


def _pad_rows(x2, bm):
    M = x2.shape[0]
    pad = (-M) % bm
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, M


def quant_matmul(x, q, scale, *, group: int, in_scale=None, layer=None,
                 interpret=None):
    """x [..., K] @ dequant(q, scale) with int8 codes kept in HBM.

    When the device resolution says off-TPU (``interpret=None`` and no
    TPU input), this computes the reference dequantize-then-einsum
    formula verbatim instead of emulating the tiled kernel: the tiling
    changes f32 accumulation order, and the ``"pallas"`` backend must
    be BYTE-identical to ``"reference"`` on CPU (the serving identity
    gate in tests/test_paged_cache.py and ``benchmarks/roofline.py
    --smoke``).  Pass ``interpret=True`` explicitly to run the real
    kernel under the Pallas interpreter (tests/test_kernels.py).

    Under ``jax.vmap`` of ``x`` alone, the batch is folded into rows:
    one kernel call over every row that shares the weight, so each
    weight tile is fetched once per row tile of the whole batch.  A
    batched ``q`` or ``scale`` keeps the kernel's batched grid.  Each
    call records one ``kernel.quant_matmul`` span at trace time, whose
    attributes describe the call as it is lowered (``rows``, ``K``,
    ``N``, the tiles, ``folded``, and ``weight_passes``: how many times
    the call fetches each weight tile).

    With ``layer``, ``q``, ``scale`` and ``in_scale`` are stacks over a
    leading layer axis, and the kernel reads that layer in place."""
    if layer is not None and in_scale is not None:
        in_scale = in_scale[layer]
    if interpret is None:
        if _off_tpu():
            from repro.core.compressed import QTensor, _q_matmul_jnp
            if layer is not None:
                q, scale = q[layer], scale[layer]
            return _q_matmul_jnp(x, QTensor(q, scale, 8, group, q.shape,
                                            in_scale))
        interpret = False
    if in_scale is not None:
        x = (x.astype(jnp.float32) * in_scale).astype(x.dtype)
    layer = jnp.zeros((), jnp.int32) if layer is None else layer
    with tracing.span("kernel.quant_matmul") as rec:
        return _foldable_quant_matmul(group, interpret, rec)(x, q, scale,
                                                             layer)


def _foldable_quant_matmul(group, interpret, rec):
    """The kernel call as a ``custom_vmap`` function whose batching rule
    folds a batched ``x`` over a shared weight and layer into rows.
    ``rec`` (the call's span record, or None) takes the attributes of
    the last trace, which is the one lowered: a batching rule traces
    after the unbatched body it replaces."""
    def call(x, q, scale, layer, *, folded=False, batch=1):
        K, N = q.shape[-2:]
        x2 = x.reshape(-1, K)
        M = x2.shape[0]
        bm, bn, bk = tiles(M, K, N, group, x.dtype.itemsize)
        row_tiles = -(-M // bm)
        if rec is not None:
            rec.attrs.update(rows=M, K=K, N=N, bm=bm, bn=bn, bk=bk,
                             folded=folded,
                             weight_passes=row_tiles * batch)
        x2, _ = _pad_rows(x2, bm)
        y = quant_matmul_kernel(x2, q, scale, group=group, bm=bm, bn=bn,
                                bk=bk, layer=layer, interpret=interpret)
        return y[:M].reshape(*x.shape[:-1], N)

    def rule(axis_size, in_batched, x, q, scale, layer):
        if any(in_batched[1:]):
            axes = [0 if b else None for b in in_batched]
            return jax.vmap(functools.partial(call, batch=axis_size),
                            in_axes=axes)(x, q, scale, layer), True
        return folded(x, q, scale, layer), True

    plain = custom_vmap(call)
    folded = custom_vmap(functools.partial(call, folded=True))
    plain.def_vmap(rule)
    folded.def_vmap(rule)
    return plain


def block_sparse_matmul(x, w, idx, *, bs: int, interpret=None):
    """x [..., K] @ block-sparse w, skipping pruned tiles via idx.

    Off-TPU (``interpret=None`` resolution) this is the reference dense
    einsum over the zero-filled ``w`` (same byte-identity contract as
    ``quant_matmul``); ``interpret=True`` runs the gather kernel under
    the interpreter."""
    if interpret is None:
        if _off_tpu():
            return jnp.einsum("...i,io->...o", x, w.astype(x.dtype),
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)
        interpret = False
    K, N = w.shape
    x2 = x.reshape(-1, K)
    bm = 128 if x2.shape[0] >= 128 else 8
    x2, M = _pad_rows(x2, bm)
    y = block_sparse_matmul_kernel(x2, w, idx, bs=bs, bm=bm,
                                   interpret=interpret)
    return y[:M].reshape(*x.shape[:-1], N)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0,
                    interpret=None):
    """q [B, S, H, D], k/v [B, T, Kh, D] -> [B, S, H, D] (GQA-aware)."""
    if interpret is None:
        interpret = _off_tpu()
    B, S, H, D = q.shape
    _, T, Kh, _ = k.shape
    G = H // Kh
    # flatten heads into batch: [B*H, S, D] / [B*Kh, T, D]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Kh, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Kh, T, D)
    bq = 256 if S % 256 == 0 else _largest_tile(S)
    bkv = 256 if T % 256 == 0 else _largest_tile(T)
    t_real = T
    pad_t = (-T) % bkv
    if pad_t:
        kf = jnp.pad(kf, ((0, 0), (0, pad_t), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_t), (0, 0)))
    o = flash_attention_kernel(qf, kf, vf, group=G, causal=causal,
                               window=window, softcap=softcap,
                               t_real=t_real, q_offset=q_offset,
                               bq=bq, bkv=bkv, interpret=interpret)
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    softcap: float = 0.0, window: int = 0, interpret=None):
    """Paged-KV decode attention.

    q [S, 1, H, D] (one decode token per slot), k/v pools
    [num_blocks, block_size, Kh, D], tables [S, T // block_size] int32
    block ids per slot, lengths [S] int32 valid KV lengths
    -> [S, 1, H, D].
    """
    if interpret is None:
        interpret = _off_tpu()
    S, one, H, D = q.shape
    assert one == 1, q.shape
    _, _, Kh, _ = k_pool.shape
    G = H // Kh
    # heads split as (Kh, G) — the same ordering layers._masked_decode uses
    # when it reshapes [B, 1, H, D] -> [B, 1, K, H//K, D].
    qr = q[:, 0].reshape(S, Kh, G, D)
    o = paged_attention_kernel(qr, k_pool, v_pool,
                               jnp.asarray(tables, jnp.int32),
                               jnp.asarray(lengths, jnp.int32),
                               softcap=softcap, window=window,
                               interpret=interpret)
    return o.reshape(S, 1, H, D)


def _largest_tile(n: int, cap: int = 256) -> int:
    t = 1
    for c in (8, 16, 32, 64, 128, 256):
        if c <= cap and n % c == 0:
            t = c
    return t
