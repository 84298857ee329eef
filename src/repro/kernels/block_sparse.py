"""Pallas TPU kernel: block-sparse matmul that skips pruned MXU tiles.

The paper uses 2:4 fine-grained sparsity on Ampere sparse tensor cores;
TPUs have no sparse MXU, so the hardware adaptation prunes
whole ``bs x bs`` blocks (bs = 128, the MXU tile) and *skips them
entirely*: the grid's K dimension runs over only the ``keep`` surviving
input blocks of each output block column, gathered through a scalar-
prefetched index array.  FLOPs and HBM traffic both drop by the density
factor — this is where sparsity actually pays on TPU.

idx: [N/bs, keep] int32 — kept input-block rows per output block column
(uniform ``keep`` per column, enforced by sparsify.block_sparse_mask).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def block_sparse_matmul_kernel(x, w, idx, *, bs: int, bm: int = 128,
                               interpret: bool = False):
    """x [M, K] @ w [K, N] skipping pruned blocks -> [M, N].

    ``w`` is the dense zero-filled weight (only kept blocks are read);
    ``idx`` [N/bs, keep] selects which K-blocks each N-block consumes.
    """
    M, K = x.shape
    K2, N = w.shape
    nbn, keep = idx.shape
    assert K == K2 and N % bs == 0 and K % bs == 0 and nbn == N // bs
    bm = min(bm, M)
    assert M % bm == 0, (M, bm)
    grid = (M // bm, nbn, keep)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bs), lambda i, j, k, idx_ref: (i, idx_ref[j, k])),
            pl.BlockSpec((bs, bs), lambda i, j, k, idx_ref: (idx_ref[j, k], j)),
        ],
        out_specs=pl.BlockSpec((bm, bs), lambda i, j, k, idx_ref: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bs), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nk=keep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        name="block_sparse_matmul",
        interpret=interpret,
    )(idx, x, w)
