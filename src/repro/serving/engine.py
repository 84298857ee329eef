"""Serving engine: asynchronous continuous batching over fixed decode slots.

TPU-adapted vLLM-style serving (see README.md in this package): XLA
wants static shapes, so the engine keeps a **fixed set of decode
slots**.  Families that support it (``api.supports_paged``) store KV in
a **paged layout**: one global pool of fixed-size blocks shared by all
slots plus a per-slot block table, so admission scatters per-row
prefill KV into table-addressed blocks and a shared template prefix is
seeded once and *aliased* by every row's table instead of copied —
decode attends through the table (reference gather or the paged Pallas
kernel, per the engine's ``KernelBackend``).  Other families — and
sharded/mesh engines — keep the contiguous layout: stacked per-row
state with a leading slot axis, decode as ``vmap`` of the model's
single-row decode.  Either way slot admission is ONE jitted batched
scatter for the whole admission batch, compiled once per admission
width, and both layouts produce byte-identical greedy outputs
(tests/test_paged_cache.py).

The engine is an async core with three entry points:

  ``submit(text)``  enqueue a request; duplicate prompts attach as
                    followers to an in-flight leader (queued OR already
                    decoding) and never touch a slot; finished prompts
                    short-circuit through the result cache.
  ``step()``        one engine tick: admit a batch into free slots
                    (one bucketed prefill + one batched insert), run one
                    vmapped decode step for all slots, retire rows that
                    hit EOS / max_new.  Returns requests finished this
                    tick — callers may keep ``submit()``-ing between
                    ticks while decode is in flight.
  ``drain()``       tick until queue and slots are empty.

``step()`` is internally split into ``step_begin()`` (admit + launch
the tick's decode, without blocking on its result) and
``step_finish()`` (block, retire).  A multi-device scheduler uses the
split directly: it calls ``step_begin()`` on every engine first —
XLA dispatch is asynchronous, so decode steps of engines **placed on
distinct devices** execute concurrently — and only then collects with
``step_finish()``.  ``step() == step_finish(step_begin())``, so the
serial path is unchanged.

Placement: ``Engine(..., device=d)`` commits the params (and all slot
state) to one jax device, so a ``ModelPool`` can spread its resident
fleet over ``jax.devices()``.  ``Engine(..., mesh=m)`` instead shards
the params with the DP/TP rules of ``distributed/sharding.py``
(``param_shardings=``/``cache_shardings=`` override them) — the
tensor-parallel path for a model too big for one device.  Both default
to ``None`` ≡ the historical single-implicit-device behavior.

``generate(texts)`` is the synchronous convenience wrapper
(submit-all + drain) used by the benchmarks.

Sampling is part of the jitted decode step: a static ``SamplingConfig``
(greedy / temperature / top-k, see sampler.py) is closed over at
compile time and a PRNG key derived from ``fold_in(base, step_counter)``
is threaded through, so ``temperature=0`` lowers to exactly the old
``jnp.argmax`` decode.

The result cache (cache.py) short-circuits duplicate rows before they
ever reach a slot, and the instance-optimized (compressed) model drops
in transparently because every linear goes through compressed.matmul.

Template-heavy OLAP prompts additionally share one prefilled prompt
prefix: ``submit(text, prefix=template)`` splits the prompt at the
template boundary, a ``PrefixCache`` stores the template's prefilled
state once per (template, model version), and admission seeds every
row's slot state from it so per-row prefill processes only the row
suffix (see README.md §Prefix-sharing KV cache).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compressed import kernel_backend
from repro.kernels.backend import resolve_backend
from repro.launch.mesh import auto_axes
from repro.models import api
from repro.serving.batcher import Batcher, Request, bucket_len
from repro.serving.cache import PrefixCache, ResultCache
from repro.serving.paged import BlockTableAllocator
from repro.serving.sampler import SamplingConfig, sample, token_confidence
from repro.training.data import ByteTokenizer

# Default bound on un-finished requests resident during generate_stream;
# the single source for the streaming chunk (olap operators import it).
DEFAULT_CHUNK = 64


@dataclass
class EngineStats:
    rows: int = 0
    tokens_out: int = 0
    prefills: int = 0
    decode_steps: int = 0
    cache_hits: int = 0
    truncated: int = 0           # prompts clipped to the top bucket
    peak_inflight: int = 0       # max queued+active requests ever resident
    busy_slot_steps: int = 0     # slot-steps that decoded a live row
    total_slot_steps: int = 0    # slot-steps executed (busy + idle)
    prefix_hits: int = 0         # rows seeded from a shared prefix state
    prefill_tokens: int = 0      # padded prompt tokens actually prefilled
    prefill_tokens_saved: int = 0  # prefix tokens NOT re-prefilled per row
    backend: str = ""            # resolved KernelBackend ("reference"/"pallas")
    kv_blocks_in_use: int = 0    # peak KV blocks reachable (paged layout)
    kv_blocks_shared: int = 0    # peak blocks aliased by >1 slot (paged)
    confidence_sum: float = 0.0  # sum of per-row min answer-token prob
    confidence_rows: int = 0     # rows with a finite confidence signal
    wall_s: float = 0.0

    @property
    def rows_per_s(self) -> float:
        return self.rows / self.wall_s if self.wall_s else 0.0

    @property
    def mean_confidence(self) -> float:
        """Mean per-row cascade confidence (min answer-token probability
        over the row's emitted tokens) across finished rows."""
        return (self.confidence_sum / self.confidence_rows
                if self.confidence_rows else 0.0)

    @property
    def slot_utilization(self) -> float:
        """Fraction of decode-step slot work spent on live rows."""
        return (self.busy_slot_steps / self.total_slot_steps
                if self.total_slot_steps else 0.0)


class StepPending(NamedTuple):
    """Handle between ``step_begin`` and ``step_finish``: the requests
    already finished at admission, plus the launched decode's output
    arrays — a ``(tokens, confidences)`` pair straight out of the jitted
    step — or ``None`` when this tick dispatched no decode (empty
    slots), so schedulers can tell real in-flight work from a no-op."""
    finished: List["Request"]
    nxt: Any


class Engine:
    def __init__(self, params, cfg, *, tokenizer: Optional[ByteTokenizer] = None,
                 slots: int = 8, max_len: int = 256,
                 buckets: Sequence[int] = (32, 64, 128),
                 use_result_cache: bool = True, version: str = "base",
                 use_prefix_cache: bool = True,
                 prefix_cache: Optional[PrefixCache] = None,
                 extra_inputs: Optional[Dict] = None,
                 sampling: Optional[SamplingConfig] = None,
                 device=None, mesh=None,
                 param_shardings=None, cache_shardings=None,
                 backend: str = "auto", kv_layout: str = "auto",
                 kv_block_size: int = 32):
        if device is not None and mesh is not None:
            raise ValueError("pass device= (single-device placement) OR "
                             "mesh= (sharded), not both")
        if kv_layout not in ("auto", "paged", "contiguous"):
            raise ValueError(f"kv_layout must be auto/paged/contiguous, "
                             f"got {kv_layout!r}")
        # KernelBackend is resolved once per engine ("auto" -> pallas on
        # TPU, reference elsewhere) and scoped around every jit trace
        # site via kernel_backend() — no process-global flag.
        self.backend = resolve_backend(backend)
        if mesh is not None:
            # the sharding rules rely on GSPMD propagation (Auto axes)
            mesh = auto_axes(mesh)
        self.device = device
        self.mesh = mesh
        self._cache_shardings = cache_shardings
        if mesh is not None:
            from repro.distributed import sharding as SH
            if param_shardings is None:
                param_shardings = SH.param_shardings(cfg, params, mesh)
            params = jax.device_put(params, param_shardings)
            # distinct placements must never share prefilled state: a
            # re-admitted model on a different device would hand jit
            # operands committed to two devices.  The tag keys the
            # prefix cache per placement (same-placement re-admission
            # still reuses entries).
            self._placement_tag = ("@mesh" + "x".join(
                str(s) for s in mesh.devices.shape) + ":" + ",".join(
                str(d.id) for d in mesh.devices.flat))
        elif device is not None:
            params = jax.device_put(params, device)
            self._placement_tag = f"@{device.platform}:{device.id}"
        else:
            self._placement_tag = ""
        self.params = params
        self.cfg = cfg
        self.tok = tokenizer or ByteTokenizer(max(cfg.vocab_size, 260))
        self.slots = slots
        self.max_len = max_len
        # Bucket ladder invariants: non-empty, strictly below max_len (a
        # prompt filling the whole cache leaves no room to decode), sorted,
        # deduplicated.  Out-of-range user buckets clamp instead of vanish.
        cap = max(1, max_len - 1)
        ladder = sorted({min(int(b), cap) for b in buckets if int(b) > 0})
        self.buckets = tuple(ladder) or (cap,)
        self.result_cache = ResultCache() if use_result_cache else None
        self.version = version
        # prefix sharing needs a family that can seed per-row state from a
        # stored prompt prefix, and no extra per-row inputs (img/enc) that
        # would sit ahead of the text tokens.  ``prefix_cache`` lets a
        # ModelPool share ONE cache across its resident engines — entries
        # stay isolated per model because every key includes the engine's
        # version (scheduler.py; leak-tested in tests/test_scheduler.py).
        self.prefix_cache = (
            (prefix_cache if prefix_cache is not None else PrefixCache())
            if use_prefix_cache and api.supports_prefix(cfg)
            and not (extra_inputs or {}) else None)
        self._prefix_ids_memo: Dict[str, tuple] = {}
        self.batcher = Batcher(self.buckets)
        self.stats = EngineStats()
        self.stats.backend = self.backend
        self.sampling = sampling or SamplingConfig()
        self._rid = 0
        self.extra_inputs = extra_inputs or {}

        # --- KV layout: paged (block pool + per-slot table) vs contiguous ---
        # Paged needs a family with positional KV in the standard layout
        # and an unsharded cache (mesh/cache_shardings keep the stacked
        # layout — block gathers would defeat the sharding rules).  The
        # block size is the largest power of two <= kv_block_size that
        # divides max_len; "auto" falls back to contiguous when that
        # degenerates below 8 positions per block.
        bs = 1
        while bs * 2 <= kv_block_size and max_len % (bs * 2) == 0:
            bs *= 2
        want_paged = (kv_layout != "contiguous" and api.supports_paged(cfg)
                      and mesh is None and cache_shardings is None
                      and not (kv_layout == "auto" and bs < 8))
        self._paged = want_paged
        self._block_size = bs if want_paged else 0
        self._seed = None
        self._alloc = None
        self._tables_dev = None
        self._tables_dirty = True
        if self._paged:
            self._alloc = BlockTableAllocator(slots, max_len // bs)
            if self.prefix_cache is not None:
                self.prefix_cache.add_evict_listener(self._on_prefix_evict)

        # async serving state -------------------------------------------
        self._active: Dict[int, Request] = {}           # slot -> request
        self._leaders: Dict[tuple, Request] = {}        # in-flight dedup
        self._followers: Dict[tuple, List[Request]] = {}
        self._cur_tok = np.zeros((self.slots,), np.int32)
        self._cur_pos = np.zeros((self.slots,), np.int32)
        self._key = jax.random.PRNGKey(self.sampling.seed)
        # PRNG stream positions are private state, NOT stats: resetting
        # engine.stats must never replay sampled tokens
        self._admit_ctr = 0
        self._decode_ctr = 0

        # --- jit'd single-row prefill, vmapped over the admission batch ---
        # ln is the row's REAL token count: recurrent families must not
        # absorb the bucket's right-padding into their carried state
        def row_prefill(params, toks, ln):
            batch = {"tokens": toks[None]}
            batch.update({k: v[None] for k, v in self.extra_inputs.items()})
            logits, cache = api.prefill(params, cfg, batch,
                                        max_len=max_len, compact_local=False,
                                        lengths=ln[None])
            return logits[0], cache

        self._prefill = {}
        for b in self.buckets:
            self._prefill[b] = jax.jit(
                jax.vmap(row_prefill, in_axes=(None, 0, 0)))

        # --- suffix-only prefill seeded from a shared prefix state ---
        # prefix_state is the batch=1 cache pytree of the prefilled
        # template prefix, broadcast (in_axes=None) to every admitted
        # row; each row processes only its suffix tokens and returns a
        # fully-populated per-row state for the batched slot insert.
        def row_prefill_from(params, prefix_state, toks, plen, ln):
            logits, cache = api.prefill_from(params, cfg, prefix_state,
                                             toks[None], plen,
                                             max_len=max_len,
                                             lengths=ln[None])
            return logits[0], cache

        self._prefill_from = {}
        if self.prefix_cache is not None:
            for b in self.buckets:
                self._prefill_from[b] = jax.jit(
                    jax.vmap(row_prefill_from,
                             in_axes=(None, None, 0, None, 0)))

        sampling_cfg = self.sampling  # static: closed over at trace time

        if self._paged:
            # --- paged admission scatter + prefix seeding + decode ---
            # write_ids [n, max_len // bs] name the destination block per
            # KV chunk (trash ids suppress chunks covered by aliased
            # prefix blocks); recurrent rows scatter at slot_idxs.
            blk = self._block_size

            def insert(slot_state, row_states, slot_idxs, write_ids):
                return api.paged_insert(cfg, slot_state, row_states,
                                        slot_idxs, write_ids, block_size=blk)

            self._insert = jax.jit(insert, donate_argnums=(0,))

            def seed(slot_state, entry_state, write_ids):
                return api.paged_seed(cfg, slot_state, entry_state,
                                      write_ids, block_size=blk)

            self._seed = jax.jit(seed, donate_argnums=(0,))

            # decode runs batched over ALL slots (the block pool is
            # shared, so the per-row vmap of the contiguous path does
            # not apply) and attends through the block tables
            def step(params, slot_state, tables, toks, pos, ctr):
                logits, state = api.paged_decode_step(
                    params, cfg, slot_state, tables, toks[:, None], pos,
                    block_size=blk, max_len=max_len, backend=self.backend)
                key = jax.random.fold_in(self._key, ctr)
                nxt = sample(logits[:, -1], key,
                             temperature=sampling_cfg.temperature,
                             top_k=sampling_cfg.top_k)
                # cascade confidence, from arrays already live in the
                # jitted step — no host callback (jit_audit JIT001)
                conf = token_confidence(logits[:, -1], nxt)
                return nxt, conf, state

            self._decode = jax.jit(step, donate_argnums=(1,))
        else:
            # --- batched slot-state scatter (uniform leading axis) ---
            # row_states carry the vmapped admission axis in front; one
            # call scatters the whole admission batch into its free slots.
            def insert(slot_state, row_states, slot_idxs):
                return jax.tree.map(
                    lambda s, r: s.at[slot_idxs].set(r.astype(s.dtype)),
                    slot_state, row_states)

            self._insert = jax.jit(insert, donate_argnums=(0,))

            # --- vmapped decode step over slots, sampling fused in ---
            def row_decode(params, cache, tok, pos):
                logits, cache = api.decode_step(params, cfg, cache,
                                                tok[None, None], pos[None],
                                                max_len=max_len)
                return logits[0, -1], cache

            def step(params, slot_state, toks, pos, ctr):
                logits, state = jax.vmap(
                    row_decode, in_axes=(None, 0, 0, 0))(params, slot_state,
                                                         toks, pos)
                key = jax.random.fold_in(self._key, ctr)
                nxt = sample(logits, key,
                             temperature=sampling_cfg.temperature,
                             top_k=sampling_cfg.top_k)
                # cascade confidence, from arrays already live in the
                # jitted step — no host callback (jit_audit JIT001)
                conf = token_confidence(logits, nxt)
                return nxt, conf, state

            self._decode = jax.jit(step, donate_argnums=(1,))
        self._slot_state = None

    # ------------------------------------------------------------------
    def _init_slots(self):
        if self._paged:
            state = api.init_paged_cache(self.cfg, self.slots,
                                         self._alloc.num_blocks,
                                         self._block_size)
            if self.device is not None:
                state = jax.device_put(state, self.device)
            self._slot_state = state
            return
        one = api.init_cache(self.cfg, 1, self.max_len, compact_local=False)
        state = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (self.slots,) + a.shape).copy(),
            one)
        if self.mesh is not None:
            if self._cache_shardings is None:
                from repro.distributed import sharding as SH
                shapes = jax.eval_shape(lambda: state)
                self._cache_shardings = SH.cache_shardings(
                    self.cfg, shapes, self.mesh)
            state = jax.device_put(state, self._cache_shardings)
        elif self.device is not None:
            state = jax.device_put(state, self.device)
        self._slot_state = state

    # -- paged block-table plumbing -------------------------------------
    def _tables(self):
        """Device mirror of the allocator's block tables, refreshed only
        when host-side bookkeeping changed since the last decode."""
        if self._tables_dirty or self._tables_dev is None:
            t = jnp.asarray(self._alloc.tables)
            if self.device is not None:
                t = jax.device_put(t, self.device)
            self._tables_dev = t
            self._tables_dirty = False
        return self._tables_dev

    def _on_prefix_evict(self, key, entry) -> None:
        """PrefixCache eviction: release the cache's reference on the
        entry's shared blocks (slots still aliasing them keep them
        pinned until they retire)."""
        self._alloc.drop_prefix(key)

    def _release_slot(self, s: int) -> None:
        if self._paged:
            self._alloc.release(s)
            self._tables_dirty = True

    def _paged_admit_ids(self, slot_idxs, pk, plen, entry):
        """Block-table bookkeeping for one admission wave.

        Seeds the prefix's FULL blocks into shared storage on first
        sight (partial tail blocks stay private — the per-row prefill
        state covers them), points every admitted row's table at the
        shared prefix + its private remainder, and returns the
        [n, nblk] write-id matrix for the jitted KV scatter, with
        aliased chunks aimed at the trash block."""
        A = self._alloc
        shared = None
        if pk is not None:
            n_full = plen // self._block_size
            shared = A.lookup(pk)
            if shared is None and n_full:
                shared = A.seed_blocks(pk, n_full)
                if shared is not None:
                    w = np.full((1, A.nblk), A.trash, np.int32)
                    w[0, :n_full] = shared
                    self._slot_state = self._seed(
                        self._slot_state, entry.state, jnp.asarray(w))
        w_ids = np.empty((len(slot_idxs), A.nblk), np.int32)
        for i, s in enumerate(slot_idxs):
            s = int(s)
            w_ids[i] = A.private(s)
            if shared is not None and len(shared):
                A.alias(s, pk)
                w_ids[i, :len(shared)] = A.trash
            else:
                A.occupy(s)
        self._tables_dirty = True
        return w_ids

    # -- async API ------------------------------------------------------
    def _encode_prefix(self, prefix: str):
        """Memoized template encode: the prefix is identical across an
        operator's whole row stream, so the per-row hot path must not
        re-encode it (or rebuild its cache-key tuple) per submit."""
        hit = self._prefix_ids_memo.get(prefix)
        if hit is None:
            p_ids = self.tok.encode(prefix, bos=True)
            hit = (p_ids, self.prefix_cache.key(
                p_ids, self.version + self._placement_tag))
            self._prefix_ids_memo[prefix] = hit
        return hit

    def _split_prefix(self, text: str, prefix: Optional[str]):
        """(prefix_ids, suffix_ids, prefix_key) when the shared-template
        split is usable, else (None, full_ids, None).  The byte
        tokenizer concatenates (enc(a+b) == enc(a)+enc(b)), so splitting
        at the template boundary preserves the exact token stream; the
        split is refused whenever the full prompt would have been
        clipped to the top bucket (truncation semantics — and outputs —
        stay byte-identical to the full-prompt path) or the stacked
        prefix+suffix bucket would not leave a decode slot below
        max_len."""
        if (prefix is not None and self.prefix_cache is not None
                and len(text) > len(prefix) and text.startswith(prefix)):
            p_ids, pkey = self._encode_prefix(prefix)
            s_ids = self.tok.encode(text[len(prefix):]) + [self.tok.SEP]
            if len(p_ids) + len(s_ids) <= self.buckets[-1] \
                    and len(p_ids) + bucket_len(len(s_ids), self.buckets) \
                    <= self.max_len - 1:
                return p_ids, s_ids, pkey
            # token stream of the refused split == the full encode
            return None, p_ids + s_ids, None
        return None, self.tok.encode(text, bos=True) + [self.tok.SEP], None

    def submit(self, text: str, *, max_new: int = 32,
               prefix: Optional[str] = None) -> Request:
        """Enqueue one request; resolves immediately on a cache hit and
        attaches as a follower when its prompt is already in flight.
        ``prefix`` marks the shared template prefix of ``text`` (operators
        pass their prompt template): rows sharing it are prefilled from
        one cached prefix state and bucketed on their suffix only."""
        prefix_ids, ids, pkey = self._split_prefix(text, prefix)
        req = Request(rid=self._rid, prompt_ids=ids, max_new=max_new,
                      src=text)
        if prefix_ids is not None:
            req.prefix_ids = prefix_ids
            req.prefix_key = pkey
        self._rid += 1
        if self.result_cache is not None:
            req.cache_key = self.result_cache.key(text, max_new, self.version)
            hit = self.result_cache.peek(req.cache_key)
            if hit is not None:
                # cache values are (text, confidence) pairs so cascade
                # acceptance survives the dedup short-circuit
                text, conf = hit
                self.result_cache.record_hit(req.cache_key)
                self.stats.cache_hits += 1
                req.out_ids = self.tok.encode(text)
                req.confidence = conf
                self._finalize(req, text)
                return req
            if req.cache_key in self._leaders:
                # duplicate of a queued OR actively decoding request:
                # ride on the leader, never touch a slot.  Exactly one
                # cache accounting event (a hit) for this lookup.
                self.result_cache.record_hit(req.cache_key)
                self.stats.cache_hits += 1
                req.follower = True
                self._followers.setdefault(req.cache_key, []).append(req)
                req.prompt_ids = []
                return req
            self.result_cache.record_miss()
            self._leaders[req.cache_key] = req
        self.batcher.add(req)
        inflight = len(self.batcher) + len(self._active)
        self.stats.peak_inflight = max(self.stats.peak_inflight, inflight)
        return req

    def step(self) -> List[Request]:
        """One engine tick (admit -> decode -> retire); returns the
        requests that finished during this tick."""
        return self.step_finish(self.step_begin())

    def step_begin(self):
        """First half of a tick: admit a batch and LAUNCH the decode
        step, without blocking on its result (XLA dispatch is async —
        the returned handle's arrays are still being computed).  Pair
        each call with exactly one ``step_finish(handle)`` before the
        next ``step_begin``; the multi-device scheduler dispatches
        ``step_begin`` on every engine (distinct devices then compute
        concurrently) before collecting any of them."""
        # every jit trace under this tick dispatches compressed matmuls
        # (and paged attention) on THIS engine's backend
        with kernel_backend(self.backend):
            return self._step_begin()

    def _step_begin(self):
        if self._slot_state is None:
            self._init_slots()
        finished: List[Request] = []
        free = [s for s in range(self.slots) if s not in self._active]
        # --- admit: one bucketed prefill + ONE batched slot insert ---
        if free and len(self.batcher):
            take = self.batcher.take(len(free))
            if take:
                top = self.buckets[-1]
                for r in take:
                    if len(r.prompt_ids) > top:
                        r.truncated = True
                        self.stats.truncated += 1
                b = bucket_len(max(len(r.prompt_ids) for r in take),
                               self.buckets)
                toks = np.zeros((len(take), b), np.int32)
                for i, r in enumerate(take):
                    ids = r.prompt_ids[-b:]
                    toks[i, :len(ids)] = ids
                lens = np.array([min(len(r.prompt_ids), b) for r in take])
                pk = take[0].prefix_key     # uniform across the batch
                if pk is not None:
                    # seed every row from the shared prefilled prefix and
                    # prefill only the suffixes.  A fresh entry costs one
                    # prefix-length prefill; every other row in this and
                    # all later admissions skips it entirely.
                    entry = self.prefix_cache.get(pk)
                    fresh = entry is None
                    if fresh:
                        entry = self._build_prefix_entry(
                            pk, take[0].prefix_ids)
                    plen = entry.prefix_len
                    logits, rows = self._prefill_from[b](
                        self.params, entry.state, jnp.asarray(toks),
                        jnp.int32(plen), jnp.asarray(lens, jnp.int32))
                    seeded = len(take) - (1 if fresh else 0)
                    entry.hits += seeded
                    self.stats.prefix_hits += seeded
                    self.stats.prefill_tokens_saved += plen * seeded
                else:
                    plen = 0
                    entry = None
                    logits, rows = self._prefill[b](
                        self.params, jnp.asarray(toks),
                        jnp.asarray(lens, jnp.int32))
                self.stats.prefills += 1
                self.stats.prefill_tokens += len(take) * b
                # rows are right-padded: gather each row's logits at its
                # last REAL position, not at the padding tail
                last_logits = jnp.take_along_axis(
                    logits, jnp.asarray(lens - 1)[:, None, None],
                    axis=1)[:, 0]
                # per-wave key: fold in a counter that advances every
                # admission so successive waves draw independent samples
                # (mirrors the decode path's per-step fold_in)
                self._admit_ctr += 1
                admit_key = (jax.random.fold_in(self._key,
                                                self._admit_ctr + (1 << 30))
                             if self.sampling.temperature > 0 else None)
                first_dev = sample(
                    last_logits, admit_key,
                    temperature=self.sampling.temperature,
                    top_k=self.sampling.top_k)
                first = np.asarray(first_dev).astype(np.int32)
                # first token is sampled off the prefill logits (outside
                # the decode loop), so its confidence is computed here too
                first_conf = np.asarray(
                    token_confidence(last_logits, first_dev), np.float64)
                slot_idxs = np.asarray(free[:len(take)], np.int32)
                if self._paged:
                    w_ids = self._paged_admit_ids(slot_idxs, pk, plen, entry)
                    self._slot_state = self._insert(
                        self._slot_state, rows, jnp.asarray(slot_idxs),
                        jnp.asarray(w_ids))
                else:
                    self._slot_state = self._insert(
                        self._slot_state, rows, jnp.asarray(slot_idxs))
                for i, r in enumerate(take):
                    s = int(slot_idxs[i])
                    t0 = int(first[i])
                    r.out_ids.append(t0)
                    r.confidence = min(r.confidence, float(first_conf[i]))
                    if t0 == self.tok.EOS or len(r.out_ids) >= r.max_new:
                        # prefill token already ends the row (EOS) or
                        # exhausts the budget: retire without ever
                        # occupying a decode slot
                        self._release_slot(s)
                        finished.extend(self._retire(r))
                        continue
                    self._active[s] = r
                    self._cur_tok[s] = t0
                    self._cur_pos[s] = plen + int(lens[i])
        if not self._active:
            return StepPending(finished, None)
        # --- decode one token for every active slot (launch only) ---
        if self._paged:
            used, sh = self._alloc.stats()
            self.stats.kv_blocks_in_use = max(self.stats.kv_blocks_in_use,
                                              used)
            self.stats.kv_blocks_shared = max(self.stats.kv_blocks_shared, sh)
            nxt, conf, self._slot_state = self._decode(
                self.params, self._slot_state, self._tables(),
                jnp.asarray(self._cur_tok), jnp.asarray(self._cur_pos),
                jnp.int32(self._decode_ctr))
        else:
            nxt, conf, self._slot_state = self._decode(
                self.params, self._slot_state, jnp.asarray(self._cur_tok),
                jnp.asarray(self._cur_pos), jnp.int32(self._decode_ctr))
        self._decode_ctr += 1
        self.stats.decode_steps += 1
        self.stats.busy_slot_steps += len(self._active)
        self.stats.total_slot_steps += self.slots
        return StepPending(finished, (nxt, conf))

    def step_finish(self, pending: StepPending) -> List[Request]:
        """Second half of a tick: block on the launched decode, then
        retire/advance every active slot.  Returns all requests that
        finished during the whole tick (admission-retired + decoded)."""
        finished, nxt = pending
        if nxt is None:
            return finished
        nxt, conf = nxt
        nxt = np.asarray(nxt)
        conf = np.asarray(conf)
        # --- retire / advance ---
        for s in list(self._active):
            r = self._active[s]
            t = int(nxt[s])
            r.out_ids.append(t)
            r.confidence = min(r.confidence, float(conf[s]))
            self._cur_tok[s] = t
            self._cur_pos[s] += 1
            if t == self.tok.EOS or len(r.out_ids) >= r.max_new \
                    or self._cur_pos[s] >= self.max_len - 1:
                del self._active[s]
                self._release_slot(s)
                finished.extend(self._retire(r))
        return finished

    def has_work(self) -> bool:
        """True while any request is queued or actively decoding — the
        scheduler's cheap should-I-tick-this-engine probe (a bare
        ``step()`` on an idle engine would still allocate slot state)."""
        return bool(len(self.batcher) or self._active)

    def drain(self) -> List[Request]:
        """Tick until every queued and active request has finished."""
        finished: List[Request] = []
        while self.has_work():
            finished.extend(self.step())
        return finished

    # -- introspection --------------------------------------------------
    def jit_targets(self) -> Dict[str, object]:
        """Every jitted callable on the tick hot path, by stable name —
        the surface the static auditor (analysis/jit_audit.py) wraps
        and the jit-cache accounting in tests keys on.  Bucket-laddered
        targets are suffixed ``[bucket]``."""
        out: Dict[str, object] = {"_insert": self._insert,
                                  "_decode": self._decode}
        if self._seed is not None:
            out["_seed"] = self._seed
        for b, fn in self._prefill.items():
            out[f"_prefill[{b}]"] = fn
        for b, fn in self._prefill_from.items():
            out[f"_prefill_from[{b}]"] = fn
        return out

    # -- prefix sharing -------------------------------------------------
    def _build_prefix_entry(self, key, prefix_ids):
        """One-time prefill of a template prefix (batch=1, absolute
        slots); the stored state seeds every row that shares it.  Runs
        eagerly: once per (template, version), off the jit hot path."""
        toks = jnp.asarray(np.asarray(prefix_ids, np.int32)[None])
        _, cache = api.prefill(self.params, self.cfg, {"tokens": toks},
                               max_len=self.max_len, compact_local=False)
        self.stats.prefills += 1
        self.stats.prefill_tokens += len(prefix_ids)
        return self.prefix_cache.put(key, cache, len(prefix_ids))

    # -- completion plumbing -------------------------------------------
    def _retire(self, req: Request) -> List[Request]:
        """Finalize a decoded leader plus any followers riding on it;
        returns every request completed by this retirement."""
        text = self.tok.decode([t for t in req.out_ids if t != self.tok.EOS])
        done = [req]
        if self.result_cache is not None and req.cache_key is not None:
            self.result_cache.put(req.cache_key, (text, req.confidence))
            self._leaders.pop(req.cache_key, None)
            for f in self._followers.pop(req.cache_key, []):
                f.out_ids = list(req.out_ids)
                f.confidence = req.confidence
                self._finalize(f, text)
                done.append(f)
        self._finalize(req, text)
        return done

    def _finalize(self, req: Request, text: str) -> None:
        req.text = text
        req.done = True
        req.prompt_ids = []      # drop prompt residency as soon as possible
        self.stats.rows += 1
        self.stats.tokens_out += len(req.out_ids)
        if np.isfinite(req.confidence):
            self.stats.confidence_sum += req.confidence
            self.stats.confidence_rows += 1

    # -- synchronous convenience wrappers ------------------------------
    def generate(self, texts: Sequence[str], *, max_new: int = 32,
                 prefix: Optional[str] = None) -> List[str]:
        """Continuous-batching run over all texts; returns decoded rows."""
        t0 = time.time()
        reqs = [self.submit(t, max_new=max_new, prefix=prefix)
                for t in texts]
        self.drain()
        self.stats.wall_s += time.time() - t0
        return [r.text for r in reqs]

    def generate_stream(self, prompts, *, max_new: int = 32,
                        chunk: int = DEFAULT_CHUNK,
                        prefix: Optional[str] = None,
                        return_requests: bool = False):
        """The streaming operator contract: consume ``prompts`` (any
        iterable) lazily, keeping at most ``chunk`` of THIS call's
        requests un-finished at a time — decode ticks overlap with
        prompt construction, and peak prompt residency is bounded by
        ``chunk + slots`` instead of the prompt count.  Requests
        submitted outside this call are ignored by the throttle (their
        completions don't loosen the bound).  Returns decoded rows in
        prompt order; ``return_requests=True`` returns the finished
        ``Request`` objects instead so the cascade path can read the
        per-row confidence next to the text."""
        t0 = time.time()
        reqs: List[Request] = []
        inflight = set()                  # queued/active rids owned here
        for p in prompts:
            r = self.submit(p, max_new=max_new, prefix=prefix)
            reqs.append(r)
            # followers hold no prompt and no slot, so they don't count
            # against the residency bound the throttle enforces
            if not r.done and not r.follower:
                inflight.add(r.rid)
            while len(inflight) >= max(1, chunk):
                for f in self.step():
                    inflight.discard(f.rid)
        self.drain()
        self.stats.wall_s += time.time() - t0
        if return_requests:
            return reqs
        return [r.text for r in reqs]
