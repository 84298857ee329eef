"""Pallas TPU kernel: int8 group-quantized matmul with dequant-in-VMEM.

Hardware adaptation of the paper's INT8 CUDA GEMM: the
weight lives in HBM as int8 (+ f32 group scales), halving the memory
roofline term that dominates decode; each grid step copies one
``[bk, bn]`` int8 tile into VMEM, dequantizes it to bf16 *in VMEM*, and
feeds the MXU.  Accumulation is f32 in a VMEM scratch tile across the K
grid dimension.

Tile choice (v5e): bm=*rows*, bn=128 (lane width), bk=512.  The working
set per step is  x[bm,bk] bf16 + q[bk,bn] int8 + scale[bk/g,bn] f32 +
acc[bm,bn] f32  ≈ 128·512·2 + 512·128·1 + 4·128·4 + 128·128·4 ≈ 0.26 MB
— comfortably inside the ~16 MB VMEM with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk: int, group: int,
            out_dtype):
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


def quant_matmul_kernel(x, q, scale, *, group: int, bm: int = 128,
                        bn: int = 128, bk: int = 512,
                        interpret: bool = False):
    """x [M, K] bf16 @ dequant(q [K, N] int8, scale [K/g, N] f32) -> [M, N].

    Shapes must tile exactly (the ops.py wrapper pads).
    """
    M, K = x.shape
    K2, N = q.shape
    assert K == K2 and scale.shape == (K // group, N), (x.shape, q.shape,
                                                        scale.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk % group == 0, (bk, group)
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, group=group, out_dtype=x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="quant_matmul",
        interpret=interpret,
    )(x, q, scale)
