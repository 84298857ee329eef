"""Gradient compression for cross-pod all-reduce (int8 + error feedback).

The multi-pod mesh pays ~4 bytes/param/step of inter-pod DCI traffic for
gradient all-reduce.  This module implements a *compressed all-reduce*:

    reduce-scatter phase:  all_to_all of int8-quantized gradient chunks
    local sum:             f32 accumulation of the received chunks
    all-gather phase:      all_gather of the requantized int8 partials

Wire bytes drop 4x (int8 + one f32 scale per chunk vs f32 everywhere).
Quantization error is carried in a local *error-feedback residual* that
is added to the next step's gradient before quantization — the standard
convergence-preserving trick (1-bit Adam lineage).

Everything is expressed with ``lax`` collectives inside ``shard_map`` so
XLA sees real all_to_all/all_gather ops on the pod axis (verifiable in
the dry-run HLO, testable on host devices).
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quantize(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    q = jnp.clip(jnp.rint(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def init_residual(params) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _compressed_allreduce_leaf(g: jax.Array, res: jax.Array, axis: str,
                               n: int):
    """Mean-all-reduce one gradient leaf over ``axis`` (n shards) with int8
    wire format and error feedback.  Runs inside shard_map."""
    shape = g.shape
    gf = g.astype(jnp.float32) + res
    flat = gf.reshape(-1)
    pad = (-flat.size) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)

    # --- reduce-scatter (int8 on the wire) ---
    q, scale = _quantize(chunks)                       # one scale per step
    sent = q.astype(jnp.float32) * scale               # what peers receive
    local_err = chunks - sent                          # error feedback
    recv = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                              tiled=False)             # [n, chunk]
    scales = jax.lax.all_gather(scale, axis)           # [n]
    part = jnp.sum(recv.astype(jnp.float32) * scales[:, None], axis=0) / n

    # --- all-gather (int8 on the wire) ---
    q2, scale2 = _quantize(part)
    sent2 = q2.astype(jnp.float32) * scale2
    idx = jax.lax.axis_index(axis)
    local_err += jnp.zeros_like(chunks).at[idx].set(part - sent2) * n
    got = jax.lax.all_gather(q2, axis)                 # [n, chunk]
    scs = jax.lax.all_gather(scale2, axis)
    out = (got.astype(jnp.float32) * scs[:, None]).reshape(-1)
    out = out[: gf.size].reshape(shape)
    new_res = local_err.reshape(-1)[: gf.size].reshape(shape)
    return out.astype(g.dtype), new_res


def compressed_allreduce(grads, residual, *, axis: str, mesh):
    """Mean-all-reduce every leaf over the mesh ``axis`` with int8 wire
    format; returns (grads, new_residual).  Leaves are assumed replicated
    over ``axis`` pre-call (each pod holds its own pod-local mean)."""
    n = mesh.shape[axis]
    if n == 1:
        return grads, residual


    def body(g_tree, r_tree):
        pairs = jax.tree.map(
            functools.partial(_compressed_allreduce_leaf, axis=axis, n=n),
            g_tree, r_tree)
        is_t = lambda x: isinstance(x, tuple)
        return (jax.tree.map(lambda t: t[0], pairs, is_leaf=is_t),
                jax.tree.map(lambda t: t[1], pairs, is_leaf=is_t))

    # replicate in/out over all axes; internal collectives act on `axis`
    gspec = jax.tree.map(lambda _: P(), grads)
    rspec = jax.tree.map(lambda _: P(), residual)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(gspec, rspec),
                   out_specs=(gspec, rspec), check_vma=False)
    return fn(grads, residual)
