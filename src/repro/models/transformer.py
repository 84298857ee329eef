"""Decoder-only transformer LM (dense / moe / vlm families).

The layer stack is executed with ``lax.scan`` over the *repeating pattern
unit* of the architecture (e.g. gemma2's (local, global) pair, gemma3's
(5xlocal, global) sextet, or a single block for uniform stacks), with any
remainder layers unrolled.  Per-layer parameters are stacked along the
scan axis, which keeps the HLO compact for 40+ layer models and lets the
IOLM compression passes vmap over layers.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.compressed import QLayer, QTensor
from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models.layers import matmul, norm

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# pattern-unit machinery
# ---------------------------------------------------------------------------

def pattern_unit(cfg) -> Tuple[str, int, int]:
    """(unit, n_repeats, n_tail) — smallest repeating unit of the pattern."""
    pat = cfg.pattern()
    n = len(pat)
    for U in range(1, n + 1):
        R = n // U
        if R < 1:
            continue
        unit = pat[:U]
        if (unit * R == pat[:U * R] and pat[U * R:] == unit[:n - U * R]
                and (R >= 2 or U == n)):
            return unit, R, n - U * R
    return pat, 1, 0


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def init_block(key, cfg, dtype) -> Params:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: Params = {
        "ln1": L.norm_init(d, dtype, cfg.norm_type),
        "attn": L.init_attention(ks[0], cfg, dtype),
        "ln2": L.norm_init(d, dtype, cfg.norm_type),
    }
    if cfg.post_norms:
        p["ln1_post"] = L.norm_init(d, dtype, cfg.norm_type)
        p["ln2_post"] = L.norm_init(d, dtype, cfg.norm_type)
    if cfg.family == "moe":
        p["moe"] = L.init_moe(ks[1], cfg, dtype)
        if cfg.n_shared_experts:
            # n parallel shared experts == one MLP with concatenated hidden
            p["shared_mlp"] = L.init_mlp(ks[2], cfg, dtype,
                                         d_ff=cfg.n_shared_experts * cfg.moe_d_ff)
        if cfg.dense_residual:
            p["dense_mlp"] = L.init_mlp(ks[3], cfg, dtype, d_ff=cfg.d_ff)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg, dtype)
    return p


def _theta(cfg, kind: str) -> float:
    if kind == "L" and cfg.rope_theta_local:
        return cfg.rope_theta_local
    return cfg.rope_theta


def block_apply(p: Params, x, cfg, *, kind: str, positions, train: bool,
                use_flash: bool = False):
    """Full-sequence block (train / prefill without cache)."""
    h = norm(x, p["ln1"], cfg)
    a = L.attention_block(p["attn"], h, cfg, kind=kind, positions=positions,
                          theta=_theta(cfg, kind), use_flash=use_flash)
    if "ln1_post" in p:
        a = norm(a, p["ln1_post"], cfg)
    x = x + a
    h = norm(x, p["ln2"], cfg)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        m, aux = L.moe_block(p["moe"], h, cfg, train=train)
        if "shared_mlp" in p:
            m = m + L.mlp_block(p["shared_mlp"], h)
        if "dense_mlp" in p:
            m = m + L.mlp_block(p["dense_mlp"], h)
    else:
        m = L.mlp_block(p["mlp"], h)
    if "ln2_post" in p:
        m = norm(m, p["ln2_post"], cfg)
    return x + m, aux


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key, cfg) -> Params:
    dtype = cfg.dtype
    unit, R, tail = pattern_unit(cfg)
    k_emb, k_blocks, k_tail, k_ln = jax.random.split(key, 4)
    params = L.init_embed(k_emb, cfg, dtype)
    blocks = []
    for u in range(len(unit)):
        ku = jax.random.fold_in(k_blocks, u)
        member = jax.vmap(lambda k: init_block(k, cfg, dtype))(
            jax.random.split(ku, R))
        blocks.append(member)
    params["blocks"] = blocks
    params["tail"] = [init_block(jax.random.fold_in(k_tail, i), cfg, dtype)
                      for i in range(tail)]
    params["ln_f"] = L.norm_init(cfg.d_model, dtype, cfg.norm_type)
    return params


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill logits)
# ---------------------------------------------------------------------------

def forward(params: Params, cfg, tokens=None, *, img_embs=None, train: bool = False,
            use_flash: bool = False, remat: bool = True, capture: bool = False):
    """Returns (logits [B,S,V], aux dict).  ``capture`` additionally returns
    per-layer block inputs (for IOLM calibration) and disables remat."""
    x = L.embed(params, cfg, tokens)
    if cfg.family == "vlm" and img_embs is not None:
        x = jnp.concatenate([img_embs.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    unit, R, tail = pattern_unit(cfg)

    def body(xc, member_params):
        aux = jnp.zeros((), jnp.float32)
        cap = []
        for u, kind in enumerate(unit):
            if capture:
                cap.append(xc)
            xc, a = block_apply(member_params[u], xc, cfg, kind=kind,
                                positions=positions, train=train,
                                use_flash=use_flash)
            xc = constrain(xc)
            aux = aux + a
        ys = (aux, cap) if capture else (aux, ())
        return xc, ys

    scan_body = body
    if remat and not capture:
        scan_body = jax.checkpoint(body)
    x, (auxs, caps) = jax.lax.scan(scan_body, x, params["blocks"],
                                   unroll=cfg.scan_unroll)
    aux_total = auxs.sum()
    captures = {"blocks": caps, "tail": []}
    for i, p in enumerate(params["tail"]):
        if capture:
            captures["tail"].append(x)
        x, a = block_apply(p, x, cfg, kind=unit[i % len(unit)],
                           positions=positions, train=train, use_flash=use_flash)
        aux_total = aux_total + a
    x = norm(x, params["ln_f"], cfg)
    logits = L.unembed(params, cfg, x)
    aux = {"moe_aux": aux_total}
    if capture:
        aux["captures"] = captures
        aux["final_hidden"] = x
    return logits, aux


def loss_fn(params: Params, cfg, tokens, labels, *, img_embs=None,
            xent_chunk: int = 0, remat: bool = True, aux_weight: float = 0.01):
    """Causal LM loss.  ``xent_chunk`` > 0 streams the vocab projection over
    sequence chunks so [B,S,V] logits are never materialized (critical for
    256k-vocab train cells)."""
    if xent_chunk:
        # run trunk without unembed by capturing final hidden
        x = L.embed(params, cfg, tokens)
        if cfg.family == "vlm" and img_embs is not None:
            x = jnp.concatenate([img_embs.astype(x.dtype), x], axis=1)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        unit, R, tail = pattern_unit(cfg)

        def body(xc, member_params):
            aux = jnp.zeros((), jnp.float32)
            for u, kind in enumerate(unit):
                xc, a = block_apply(member_params[u], xc, cfg, kind=kind,
                                    positions=positions, train=True)
                xc = constrain(xc)
                aux = aux + a
            return xc, aux

        sb = jax.checkpoint(body) if remat else body
        x, auxs = jax.lax.scan(sb, x, params["blocks"],
                               unroll=cfg.scan_unroll)
        aux_total = auxs.sum()
        for i, p in enumerate(params["tail"]):
            x, a = block_apply(p, x, cfg, kind=unit[i % len(unit)],
                               positions=positions, train=True)
            aux_total = aux_total + a
        x = norm(x, params["ln_f"], cfg)
        if cfg.family == "vlm":
            x = x[:, -tokens.shape[1]:]           # loss only on text positions
        nchunks = max(x.shape[1] // xent_chunk, 1)

        def xent_body(c, xs):
            xc, yc = xs
            logits = L.unembed(params, cfg, xc)
            ll = _xent(logits, yc)
            return c + ll, None

        xcs = x.reshape(x.shape[0], nchunks, -1, x.shape[-1]).swapaxes(0, 1)
        ycs = labels.reshape(labels.shape[0], nchunks, -1).swapaxes(0, 1)
        total, _ = jax.lax.scan(jax.checkpoint(xent_body) if remat else xent_body,
                                jnp.zeros((), jnp.float32), (xcs, ycs),
                                unroll=cfg.scan_unroll)
        loss = total / labels.size
        return loss + aux_weight * aux_total
    logits, aux = forward(params, cfg, tokens, img_embs=img_embs, train=True,
                          remat=remat)
    if cfg.family == "vlm":
        logits = logits[:, -tokens.shape[1]:]
    loss = _xent(logits, labels) / labels.size
    return loss + aux_weight * aux["moe_aux"]


def _xent(logits, labels) -> jax.Array:
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (lse - gold.astype(jnp.float32)).sum()


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, *, compact_local: bool = True):
    """Cache pytree mirroring the block structure.

    Local ('L') layers get a circular ``window``-sized buffer when
    ``compact_local`` (dry-run decode: gemma3 long_500k keeps only ~4
    global layers at 500k); the serving engine uses absolute slots
    (``compact_local=False``) to support per-row lengths.
    """
    unit, R, tail = pattern_unit(cfg)
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = cfg.dtype

    def entry(kind, stacked: bool):
        T = max_len
        if kind == "L" and compact_local:
            T = min(cfg.window_size, max_len)
        shape = (R, batch, T, K, hd) if stacked else (batch, T, K, hd)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    return {
        "blocks": [entry(kind, True) for kind in unit],
        "tail": [entry(unit[i % len(unit)], False) for i in range(tail)],
    }


def cache_spec(cfg, batch: int, max_len: int, *, compact_local: bool = True):
    """ShapeDtypeStructs matching init_cache (for dry-run lowering)."""
    return jax.eval_shape(
        lambda: init_cache(cfg, batch, max_len, compact_local=compact_local))


def _decode_attn_block(p, c, x, cfg, *, kind: str, pos, max_len: int):
    """One decode block: writes this step's k/v into cache, attends.

    pos: [B] int32 per-row position of the incoming token.
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    h = norm(x, p["ln1"], cfg)
    positions = pos[:, None]
    q, k, v = L._qkv(p["attn"], h, cfg, positions, _theta(cfg, kind))
    T = c["k"].shape[1]
    idx = pos % T                                   # circular for compact local
    from repro.distributed.sharding import OPT
    if OPT["masked_cache_update"]:
        # §Perf: masked select keeps the cache sharding through the update
        # (a batch-indexed scatter loses it -> SPMD replicates the cache)
        onehot = (jnp.arange(T)[None, :] == idx[:, None])      # [B, T]
        m = onehot[:, :, None, None]
        ck = jnp.where(m, k[:, :1].astype(c["k"].dtype), c["k"])
        cv = jnp.where(m, v[:, :1].astype(c["v"].dtype), c["v"])
    else:
        bidx = jnp.arange(B)
        ck = c["k"].at[bidx, idx].set(k[:, 0])
        cv = c["v"].at[bidx, idx].set(v[:, 0])
    if kind == "L":
        if T < max_len:                             # compact circular buffer
            slots = jnp.arange(T)[None, :]
            valid = (slots <= pos[:, None]) | (pos[:, None] >= T)
            kv_len = jnp.where(pos + 1 < T, pos + 1, T)
            out = _masked_decode(q, ck, cv, valid, cfg.attn_softcap)
        else:                                       # absolute slots + window
            slots = jnp.arange(T)[None, :]
            valid = (slots <= pos[:, None]) & (slots > pos[:, None] - cfg.window_size)
            out = _masked_decode(q, ck, cv, valid, cfg.attn_softcap)
    else:
        slots = jnp.arange(T)[None, :]
        valid = slots <= pos[:, None]
        out = _masked_decode(q, ck, cv, valid, cfg.attn_softcap)
    a = matmul(out.reshape(B, 1, -1), p["attn"]["wo"])
    if "ln1_post" in p:
        a = norm(a, p["ln1_post"], cfg)
    return a, {"k": ck, "v": cv}


def _masked_decode(q, k_cache, v_cache, valid, cap):
    """q [B,1,H,D], cache [B,T,K,D], valid [B,T] bool."""
    B, _, H, D = q.shape
    K = k_cache.shape[2]
    qg = q.reshape(B, 1, K, H // K, D)
    mask = valid[:, None, None, None, :]
    out = L._sdpa(qg, k_cache, v_cache, mask, cap)
    return out.reshape(B, 1, H, D)


def _mlp_section(p, h, cfg):
    """Inference-mode FFN half of a block (dense / moe / shared / residual)."""
    if "moe" in p:
        m, _ = L.moe_block(p["moe"], h, cfg, train=False)
        if "shared_mlp" in p:
            m = m + L.mlp_block(p["shared_mlp"], h)
        if "dense_mlp" in p:
            m = m + L.mlp_block(p["dense_mlp"], h)
    else:
        m = L.mlp_block(p["mlp"], h)
    if "ln2_post" in p:
        m = norm(m, p["ln2_post"], cfg)
    return m


def block_decode(p, c, x, cfg, *, kind: str, pos, max_len: int):
    a, c2 = _decode_attn_block(p, c, x, cfg, kind=kind, pos=pos, max_len=max_len)
    x = x + a
    h = norm(x, p["ln2"], cfg)
    return x + _mlp_section(p, h, cfg), c2


def decode_step(params: Params, cfg, cache, tokens, pos, *, max_len: int):
    """One token for every row.  tokens [B,1]; pos scalar or [B] int32.
    Returns (logits [B,1,V], new_cache)."""
    B = tokens.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    x = L.embed(params, cfg, tokens)
    unit, R, tail = pattern_unit(cfg)

    def body(xc, xs):
        member_params, member_cache = xs
        new_caches = []
        for u, kind in enumerate(unit):
            xc, c2 = block_decode(member_params[u], member_cache[u], xc, cfg,
                                  kind=kind, pos=pos, max_len=max_len)
            new_caches.append(c2)
        return xc, new_caches

    x, new_block_cache = jax.lax.scan(body, x,
                                      (params["blocks"], cache["blocks"]),
                                      unroll=cfg.scan_unroll)
    new_tail = []
    for i, p in enumerate(params["tail"]):
        x, c2 = block_decode(p, cache["tail"][i], x, cfg,
                             kind=unit[i % len(unit)], pos=pos, max_len=max_len)
        new_tail.append(c2)
    x = norm(x, params["ln_f"], cfg)
    logits = L.unembed(params, cfg, x)
    return logits, {"blocks": new_block_cache, "tail": new_tail}


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------
#
# The serving engine's paged layout replaces the per-slot contiguous
# [slots, T, K, hd] tensors with one global pool of fixed-size blocks —
# stacked entries [R, num_blocks, bs, K, hd], tail entries
# [num_blocks, bs, K, hd] — plus a per-slot block table [slots, T // bs]
# of int32 block ids shared by every layer (block id b addresses index b
# in every layer's pool).  Admission scatters per-row prefill KV into the
# table's blocks, and a shared template prefix is seeded once and aliased
# by table entries instead of being copied per row.  Decode runs batched
# over all slots (the pool is shared, so the per-row vmap of the
# contiguous path does not apply) and attends through the table — either
# by gathering in jnp (reference backend) or inside the paged Pallas
# kernel (pallas backend).

def init_paged_cache(cfg, num_blocks: int, block_size: int):
    """Block-pool cache pytree mirroring the block structure."""
    unit, R, tail = pattern_unit(cfg)
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = cfg.dtype

    def entry(stacked: bool):
        shape = ((R, num_blocks, block_size, K, hd) if stacked
                 else (num_blocks, block_size, K, hd))
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    return {"blocks": [entry(True) for _ in unit],
            "tail": [entry(False) for _ in range(tail)]}


def paged_write_blocks(pool_entry, row_entry, write_ids, *, block_size: int):
    """Scatter vmapped per-row contiguous KV into pool blocks.

    ``row_entry`` leaves are [n, R, 1, T, K, hd] (stacked) or
    [n, 1, T, K, hd] (tail); ``write_ids`` [n, T // block_size] names the
    destination block per chunk (the engine points skipped chunks — e.g.
    prefix blocks already aliased — at its trash block)."""
    bs = block_size
    ids = write_ids.reshape(-1)

    def one(pool, rows):
        n = rows.shape[0]
        K, hd = rows.shape[-2], rows.shape[-1]
        if rows.ndim == 6:                      # stacked [n, R, 1, T, K, hd]
            R, T = rows.shape[1], rows.shape[3]
            r = rows.reshape(n, R, T // bs, bs, K, hd)
            r = jnp.moveaxis(r, 0, 1).reshape(R, n * (T // bs), bs, K, hd)
            return pool.at[:, ids].set(r.astype(pool.dtype))
        T = rows.shape[2]                       # tail [n, 1, T, K, hd]
        r = rows.reshape(n * (T // bs), bs, K, hd)
        return pool.at[ids].set(r.astype(pool.dtype))

    return jax.tree.map(one, pool_entry, row_entry)


def paged_insert(cfg, state, rows, write_ids, *, block_size: int):
    """Scatter an admission batch's row caches (from vmapped prefill)
    into the paged pools at ``write_ids`` [n, T // block_size]."""
    return {
        "blocks": [paged_write_blocks(state["blocks"][u], rows["blocks"][u],
                                      write_ids, block_size=block_size)
                   for u in range(len(state["blocks"]))],
        "tail": [paged_write_blocks(state["tail"][i], rows["tail"][i],
                                    write_ids, block_size=block_size)
                 for i in range(len(state["tail"]))],
    }


def paged_seed(cfg, state, entry_state, write_ids, *, block_size: int):
    """Write a prefix-cache entry's KV (a batch=1 contiguous cache) into
    the shared blocks named by ``write_ids`` [1, T // block_size]."""
    rows = jax.tree.map(lambda a: a[None], entry_state)
    return paged_insert(cfg, state, rows, write_ids, block_size=block_size)


def _paged_attn_block(p, c, x, cfg, *, kind: str, pos, tables,
                      block_size: int, max_len: int, backend: str):
    """Decode attention against block pools ``c`` ({"k","v"}
    [nb, bs, K, hd]) through ``tables`` [B, T // bs].  pos: [B] int32."""
    B = x.shape[0]
    h = norm(x, p["ln1"], cfg)
    positions = pos[:, None]
    q, k, v = L._qkv(p["attn"], h, cfg, positions, _theta(cfg, kind))
    nb, bs, K, hd = c["k"].shape
    nblk = max_len // bs
    # scatter this step's k/v into each slot's current block; every slot
    # writes a distinct flat index (tables point active slots past any
    # aliased prefix blocks, idle slots at their private blocks).
    flat = tables[jnp.arange(B), pos // bs] * bs + pos % bs
    ck = c["k"].reshape(nb * bs, K, hd).at[flat].set(
        k[:, 0].astype(c["k"].dtype)).reshape(nb, bs, K, hd)
    cv = c["v"].reshape(nb * bs, K, hd).at[flat].set(
        v[:, 0].astype(c["v"].dtype)).reshape(nb, bs, K, hd)
    win = cfg.window_size if kind == "L" else 0
    if backend == "pallas":
        from repro.kernels import ops as kops
        out = kops.paged_attention(q, ck, cv, tables, pos + 1,
                                   softcap=cfg.attn_softcap, window=win)
    else:
        gk = ck[tables].reshape(B, nblk * bs, K, hd)
        gv = cv[tables].reshape(B, nblk * bs, K, hd)
        slots = jnp.arange(nblk * bs)[None, :]
        valid = slots <= pos[:, None]
        if win:
            valid &= slots > pos[:, None] - win
        out = _masked_decode(q, gk, gv, valid, cfg.attn_softcap)
    a = matmul(out.reshape(B, 1, -1), p["attn"]["wo"])
    if "ln1_post" in p:
        a = norm(a, p["ln1_post"], cfg)
    return a, {"k": ck, "v": cv}


def paged_block_decode(p, c, x, cfg, *, kind: str, pos, tables,
                       block_size: int, max_len: int, backend: str):
    a, c2 = _paged_attn_block(p, c, x, cfg, kind=kind, pos=pos, tables=tables,
                              block_size=block_size, max_len=max_len,
                              backend=backend)
    x = x + a
    h = norm(x, p["ln2"], cfg)
    return x + _mlp_section(p, h, cfg), c2


def _layer_of(stacked, i):
    """Layer ``i`` of scan-stacked params.  An int8 weight matrix stays in
    its stack as a ``QLayer`` (the decode step reads every weight once
    per token, and a slice would first copy it); every other leaf is
    indexed."""
    def one(a):
        if isinstance(a, QTensor) and a.bits == 8 and a.q.ndim == 3:
            return QLayer(a, i)
        return jax.tree_util.tree_map(lambda c: c[i], a)
    return jax.tree_util.tree_map(
        one, stacked, is_leaf=lambda a: isinstance(a, QTensor))


def paged_decode_step(params: Params, cfg, cache, tables, tokens, pos, *,
                      block_size: int, max_len: int,
                      backend: str = "reference"):
    """One token for every slot against the paged pools.  tokens [B,1];
    pos [B] int32; tables [B, max_len // block_size] int32.
    Returns (logits [B,1,V], new_cache)."""
    B = tokens.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    x = L.embed(params, cfg, tokens)
    unit, R, tail = pattern_unit(cfg)

    def body(xc, xs):
        i, member_cache = xs
        member_params = _layer_of(params["blocks"], i)
        new_caches = []
        for u, kind in enumerate(unit):
            xc, c2 = paged_block_decode(
                member_params[u], member_cache[u], xc, cfg, kind=kind,
                pos=pos, tables=tables, block_size=block_size,
                max_len=max_len, backend=backend)
            new_caches.append(c2)
        return xc, new_caches

    x, new_block_cache = jax.lax.scan(body, x,
                                      (jnp.arange(R), cache["blocks"]),
                                      unroll=cfg.scan_unroll)
    new_tail = []
    for i, p in enumerate(params["tail"]):
        x, c2 = paged_block_decode(p, cache["tail"][i], x, cfg,
                                   kind=unit[i % len(unit)], pos=pos,
                                   tables=tables, block_size=block_size,
                                   max_len=max_len, backend=backend)
        new_tail.append(c2)
    x = norm(x, params["ln_f"], cfg)
    logits = L.unembed(params, cfg, x)
    return logits, {"blocks": new_block_cache, "tail": new_tail}


# ---------------------------------------------------------------------------
# prefill: forward + cache population
# ---------------------------------------------------------------------------

def prefill(params: Params, cfg, tokens, *, img_embs=None, max_len: int,
            compact_local: bool = True, use_flash: bool = False):
    """Run the prompt, return (logits [B,S,V], populated cache).

    Rows are assumed right-padded; the caller tracks true lengths and
    gathers last-valid-token logits (engine does this).  Cache slots are
    absolute (or circular-compact for local layers in dry-run mode).
    """
    x = L.embed(params, cfg, tokens)
    if cfg.family == "vlm" and img_embs is not None:
        x = jnp.concatenate([img_embs.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    unit, R, tail = pattern_unit(cfg)
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim

    def kv_entry(kind, k, v):
        T = max_len if not (kind == "L" and compact_local) \
            else min(cfg.window_size, max_len)
        if S >= T:
            kk, vv = k[:, S - T:], v[:, S - T:]
            shift = (S - T) % T
            kk = jnp.roll(kk, shift, axis=1)
            vv = jnp.roll(vv, shift, axis=1)
        else:
            pad = [(0, 0), (0, T - S), (0, 0), (0, 0)]
            kk, vv = jnp.pad(k, pad), jnp.pad(v, pad)
        return {"k": kk, "v": vv}

    def block_prefill(p, xc, kind):
        h = norm(xc, p["ln1"], cfg)
        q, k, v = L._qkv(p["attn"], h, cfg, positions, _theta(cfg, kind))
        if use_flash:
            from repro.kernels import ops as kops
            win = cfg.window_size if kind == "L" else 0
            out = kops.flash_attention(q, k, v, causal=True, window=win,
                                       softcap=cfg.attn_softcap)
        else:
            out = L.best_attention(q, k, v, kind=kind, cfg=cfg)
        a = matmul(out.reshape(B, S, -1), p["attn"]["wo"])
        if "ln1_post" in p:
            a = norm(a, p["ln1_post"], cfg)
        xc = xc + a
        h = norm(xc, p["ln2"], cfg)
        return xc + _mlp_section(p, h, cfg), kv_entry(kind, k, v)

    def body(xc, member_params):
        caches = []
        for u, kind in enumerate(unit):
            xc, c = block_prefill(member_params[u], xc, kind)
            xc = constrain(xc)
            caches.append(c)
        return xc, caches

    x, block_caches = jax.lax.scan(jax.checkpoint(body), x,
                                   params["blocks"], unroll=cfg.scan_unroll)
    tail_caches = []
    for i, p in enumerate(params["tail"]):
        x, c = block_prefill(p, x, unit[i % len(unit)])
        tail_caches.append(c)
    x = norm(x, params["ln_f"], cfg)
    logits = L.unembed(params, cfg, x)
    return logits, {"blocks": block_caches, "tail": tail_caches}


# ---------------------------------------------------------------------------
# continued prefill: suffix chunk against a prefilled prefix cache
# ---------------------------------------------------------------------------

def _masked_chunk(q, k_cache, v_cache, valid, cap):
    """q [B,S,H,D], cache [B,T,K,D], valid [B,S,T] bool (True = attend)."""
    B, S, H, D = q.shape
    K = k_cache.shape[2]
    qg = q.reshape(B, S, K, H // K, D)
    mask = valid[:, None, None]                     # [B,1,1,S,T]
    out = L._sdpa(qg, k_cache, v_cache, mask, cap)
    return out.reshape(B, S, H, D)


def _chunk_attn_block(p, c, x, cfg, *, kind: str, start, max_len: int):
    """Attention half of one block over an S-token chunk whose first token
    sits at absolute position ``start`` (traced scalar): the chunk's k/v
    are written into the cache at slots [start, start+S) and queries
    attend to every cached slot <= their own position (windowed for local
    layers).  With a template prefix at slots [0, start) this IS per-row
    prefill restricted to the row suffix."""
    B, S, _ = x.shape
    h = norm(x, p["ln1"], cfg)
    positions = jnp.broadcast_to(start + jnp.arange(S, dtype=jnp.int32),
                                 (B, S))
    q, k, v = L._qkv(p["attn"], h, cfg, positions, _theta(cfg, kind))
    T = c["k"].shape[1]
    ck = jax.lax.dynamic_update_slice_in_dim(
        c["k"], k.astype(c["k"].dtype), start, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        c["v"], v.astype(c["v"].dtype), start, axis=1)
    slots = jnp.arange(T, dtype=jnp.int32)[None, None, :]       # [1,1,T]
    qpos = positions[:, :, None]                                # [B,S,1]
    valid = slots <= qpos
    if kind == "L":
        valid = valid & (slots > qpos - cfg.window_size)
    out = _masked_chunk(q, ck, cv, valid, cfg.attn_softcap)
    a = matmul(out.reshape(B, S, -1), p["attn"]["wo"])
    if "ln1_post" in p:
        a = norm(a, p["ln1_post"], cfg)
    return a, {"k": ck, "v": cv}


def block_prefill_from(p, c, x, cfg, *, kind: str, start, max_len: int):
    """Full block (attn + FFN) for a suffix chunk seeded from cache ``c``
    — the multi-token generalization of ``block_decode`` (hybrid reuses
    it for its shared attention sites)."""
    a, c2 = _chunk_attn_block(p, c, x, cfg, kind=kind, start=start,
                              max_len=max_len)
    x = x + a
    h = norm(x, p["ln2"], cfg)
    return x + _mlp_section(p, h, cfg), c2


def prefill_from(params: Params, cfg, cache, tokens, start, *, max_len: int):
    """Prefill only the suffix ``tokens`` [B,S] whose shared prefix
    (absolute positions [0, start)) is already resident in ``cache``.

    Returns (logits [B,S,V], populated cache) exactly like ``prefill``
    run on prefix+suffix, but spending trunk FLOPs on S tokens instead
    of start+S.  Cache slots are absolute (engine serving layout,
    ``compact_local=False``)."""
    x = L.embed(params, cfg, tokens)
    start = jnp.asarray(start, jnp.int32)
    unit, R, tail = pattern_unit(cfg)

    def body(xc, xs):
        member_params, member_cache = xs
        new_caches = []
        for u, kind in enumerate(unit):
            xc, c2 = block_prefill_from(member_params[u], member_cache[u],
                                        xc, cfg, kind=kind, start=start,
                                        max_len=max_len)
            xc = constrain(xc)
            new_caches.append(c2)
        return xc, new_caches

    x, new_block_cache = jax.lax.scan(body, x,
                                      (params["blocks"], cache["blocks"]),
                                      unroll=cfg.scan_unroll)
    new_tail = []
    for i, p in enumerate(params["tail"]):
        x, c2 = block_prefill_from(p, cache["tail"][i], x, cfg,
                                   kind=unit[i % len(unit)], start=start,
                                   max_len=max_len)
        new_tail.append(c2)
    x = norm(x, params["ln_f"], cfg)
    logits = L.unembed(params, cfg, x)
    return logits, {"blocks": new_block_cache, "tail": new_tail}
