"""Pallas TPU kernel: int8 group-quantized matmul with dequant-in-VMEM.

Hardware adaptation of the paper's INT8 CUDA GEMM: the
weight lives in HBM as int8 (+ f32 group scales), halving the memory
roofline term that dominates decode; each grid step copies one
``[bk, bn]`` int8 tile into VMEM, dequantizes it to bf16 *in VMEM*, and
feeds the MXU.  Accumulation is f32 in a VMEM scratch tile across the K
grid dimension.

Tiles come from the whole call (``tiles``): every weight tile is
fetched and dequantized once per row tile, so one row tile covers up
to ``ROW_CAP`` rows, and column tiles are as wide as ``BN_CAP`` and the
VMEM budget allow.  ``bk`` takes whole quant groups and leaves each
output element's f32 summation order over K as it was.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_CAP = 512                  # most rows in one row tile
BN_CAP = 2048                  # widest column tile
VMEM_BUDGET = 32 * 2 ** 20     # the kernel's VMEM limit, and its tiles' budget


def _kernel(layer_ref, x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk: int,
            group: int, out_dtype):
    del layer_ref                       # read by the index maps
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequantize the int8 tile in VMEM: [bk, bn] * scale[bk/g, bn]
    q = q_ref[...].astype(jnp.float32)
    bk, bn = q.shape
    s = s_ref[...]                                    # [bk // g, bn]
    w = (q.reshape(bk // group, group, bn) * s[:, None, :]) \
        .reshape(bk, bn).astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def k_tile(K: int, group: int) -> int:
    """Whole groups whose ``[bk // group, bn]`` scale block has a multiple
    of 8 rows, else all of K: the TPU lowering refuses any other
    second-minor block size of the ``[K // group, N]`` scale array."""
    tile = 8 * group * max(1, 512 // (8 * group))
    return tile if K % tile == 0 else K


def row_tile(M: int, cap: int = ROW_CAP) -> int:
    """One tile of all M rows up to ``cap`` (a block as tall as the array
    needs no padding); above it, equal tiles rounded up to 8 rows, so
    padding adds fewer than 8 rows a tile."""
    if M <= cap:
        return M
    per = -(-M // -(-M // cap))
    return -(-per // 8) * 8


def vmem_bytes(bm: int, bn: int, bk: int, group: int, itemsize: int) -> int:
    """The kernel's VMEM working set, from above: double-buffered x, codes,
    scales and output, the f32 accumulator and one f32 product, and the
    bf16 dequantized tile, each padded to whole (8, 128) tiles (int8
    codes to (32, 128))."""
    def tile(rows, cols, nbytes, sub=8):
        return -(-rows // sub) * sub * (-(-cols // 128) * 128) * nbytes
    buffers = (tile(bm, bk, itemsize) + tile(bk, bn, 1, 32)
               + tile(bk // group, bn, 4) + tile(bm, bn, itemsize))
    return 2 * buffers + 2 * tile(bm, bn, 4) + tile(bk, bn, 2)


def tiles(M: int, K: int, N: int, group: int, itemsize: int):
    """``(bm, bn, bk)`` for x [M, K] (``itemsize`` bytes an element) times
    [K, N] codes: the tallest row tile, then the widest ``bn`` whose
    working set fits ``VMEM_BUDGET``: a multiple of 128 dividing N, or,
    where 128 does not divide N, all of N, else a multiple of 128 whose
    last column block runs past N (its extra columns are dropped)."""
    bk = k_tile(K, group)
    lanes = range(min(N, BN_CAP) // 128 * 128, 0, -128)
    if N % 128:
        widths = [N, *lanes]
    else:
        widths = [b for b in lanes if N % b == 0]
    cap = ROW_CAP
    while True:
        bm = row_tile(M, cap)
        for bn in widths:
            if vmem_bytes(bm, bn, bk, group, itemsize) <= VMEM_BUDGET:
                return bm, bn, bk
        if cap == 8:
            raise ValueError(f"no quant_matmul tile fits VMEM for "
                             f"M={M} K={K} N={N} group={group}")
        cap //= 2


def quant_matmul_kernel(x, q, scale, *, group: int, bm: int, bn: int,
                        bk: int, layer=0, interpret: bool = False):
    """x [M, K] @ dequant(q [K, N] int8, scale [K/g, N] f32) -> [M, N].

    ``q`` and ``scale`` may be stacks ``[L, K, N]`` and ``[L, K/g, N]``:
    the kernel then reads layer ``layer`` (a scalar, prefetched) in
    place.  Rows and K must tile exactly (the ops.py wrapper pads rows);
    a last column block may run past N."""
    if q.ndim == 2:
        q, scale = q[None], scale[None]
    M, K = x.shape
    _, K2, N = q.shape
    assert K == K2 and scale.shape[1:] == (K // group, N), (
        x.shape, q.shape, scale.shape)
    assert M % bm == 0 and K % bk == 0, (M, K, bm, bk)
    assert bk % group == 0, (bk, group)
    nk = K // bk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // bm, pl.cdiv(N, bn), nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, l: (i, k)),
            pl.BlockSpec((None, bk, bn), lambda i, j, k, l: (l[0], k, j)),
            pl.BlockSpec((None, bk // group, bn),
                         lambda i, j, k, l: (l[0], k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, l: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, group=group, out_dtype=x.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET),
        name="quant_matmul",
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), x, q, scale)
