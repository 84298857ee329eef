"""Jit'd public wrappers around the Pallas kernels.

Shape plumbing lives here: flattening batch dims, padding to tile
multiples, head/batch reshapes for attention, and the interpret-mode
fallback so the kernels run (slowly, but bit-faithfully) on CPU for
tests; on a TPU the compiled kernels always run.
``repro.core.compressed.matmul`` and the model layers call these when
the active KernelBackend resolves to ``"pallas"``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.block_sparse import block_sparse_matmul_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.paged_attention import paged_attention_kernel
from repro.kernels.quant_matmul import quant_matmul_kernel


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


def quant_matmul(x, q, scale, *, group: int, in_scale=None,
                 interpret=None):
    """x [..., K] @ dequant(q, scale) with int8 codes kept in HBM.

    When the device resolution says off-TPU (``interpret=None`` and no
    TPU input), this computes the reference dequantize-then-einsum
    formula verbatim instead of emulating the tiled kernel: the tiling
    changes f32 accumulation order, and the ``"pallas"`` backend must
    be BYTE-identical to ``"reference"`` on CPU (the serving identity
    gate in tests/test_paged_cache.py and ``benchmarks/roofline.py
    --smoke``).  Pass ``interpret=True`` explicitly to run the real
    kernel under the Pallas interpreter (tests/test_kernels.py)."""
    K, N = q.shape
    if interpret is None:
        if _off_tpu():
            from repro.core.compressed import QTensor, _q_matmul_jnp
            return _q_matmul_jnp(x, QTensor(q, scale, 8, group, (K, N),
                                            in_scale))
        interpret = False
    if in_scale is not None:
        x = (x.astype(jnp.float32) * in_scale).astype(x.dtype)
    x2 = x.reshape(-1, K)
    bm = 128 if x2.shape[0] >= 128 else 8
    x2, M = _pad_rows(x2, bm)
    # K tile: whole groups whose [bk // group, bn] scale block has a
    # multiple of 8 rows, else all of K — the TPU lowering refuses any
    # other second-minor block size of the [K // group, N] scale array
    tile = 8 * group * max(1, 512 // (8 * group))
    bk = tile if K % tile == 0 else K
    y = quant_matmul_kernel(x2, q, scale, group=group, bm=bm, bk=bk,
                            bn=128 if N % 128 == 0 else N,
                            interpret=interpret)
    return y[:M].reshape(*x.shape[:-1], N)


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
