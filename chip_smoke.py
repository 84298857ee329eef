"""Chip smoke: IOLM-DB's main path once on a TPU, at full model width.

  python chip_smoke.py             # one chip: gemma3-1b through the stack
  python chip_smoke.py --chips 4   # four chips: TP engine + pool placement
  python chip_smoke.py --chips 4 --only tp   # one of the two

One chip.  gemma3-1b at every published width (26 layers, d_model 1152,
4 q / 1 kv heads of 256, d_ff 6912, vocab 262144, 5:1 local:global with
a 512 window; only ``max_seq`` is cut to the engine's context), random
weights from ``--seed``, the repo's byte tokenizer.  The path is the one
a user drives:

  IOLMSession(backend="pallas", objective="acc", recipes=pinned)
    -> Query.llm_map (calibrate, recipe search, compressed instance in
       the ModelPool), then the same query again (ModelCache hit)
    -> Scheduler.run_queries and the HTTP service (rows must match)
    -> paged decode through the compiled Pallas kernels

and the run fails on: no TPU, a pinned recipe dropped by an exception,
a scheduler degradation (fault quarantine), a kernel resolved to
interpret mode or to the reference formula, a compressed engine not on
the pallas backend, a scheduler/HTTP row mismatch, a kernel call
whose output leaves its reference formula, on the same operands inside
the compiled model, by more than ``KERNEL_RTOL``, or end-to-end logits
that fail the truth gate below.

End-to-end logits are not compared with a fixed bound on their gap to
the reference backend alone: a random-weight bf16 model of this depth
moves far from its own exact value through bf16 rounding alone.  So the
compressed instance also runs in float32 (its int8 codes dequantized,
f32 activations, full-precision matmuls), and the pallas logits must be
no more than ``TRUTH_FACTOR`` times as far from that f32 truth as the
reference backend's logits are, or within ``TRUTH_FLOOR`` of it.

Four chips (``--chips 4``): (b) the tensor-parallel engine serves
mistral-nemo-12b (about 24.5 GB in bf16, more than one chip holds) on
a 2x2 mesh, and at a depth one chip holds its greedy tokens and
last-position logits are compared with a one-chip engine on the same
weights, under the same truth gate (TP in place of pallas, the one-chip
run in place of the reference); then (a) a device-aware ModelPool
places four compressed gemma3-1b instances, one per chip, and its
``Scheduler.run_queries`` rows must equal a fresh one-chip session's.

Outputs are random-weight gibberish: what is checked is that the path
ran on the chip and agrees with itself.  The last line printed is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
The persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when set, else in ``.jax_cache`` beside this file.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Truth gate (relative L2 of logits to the f32 run of the same model):
# the run under test may be TRUTH_FACTOR times as far from the truth as
# the plain bf16 run, or TRUTH_FLOOR from it.  The two bf16 runs round
# in different places (another kernel, another reduction order) but
# equally often, so they sit about equally far from the truth; a wrong
# kernel wiring, mask or sharding rule moves logits by O(1) — relative
# L2 of 1 or more — which fails while the plain run stays below 0.5.
TRUTH_FACTOR = 2.0
TRUTH_FLOOR = 2e-2
# Relative L2 gap allowed between one kernel call's output and its
# reference formula on the same operands.  The kernels sum in f32 in
# another order and round the result to bf16, so a few elements move
# by one bf16 ulp (2^-8): about 1e-3.  A wrong mask, scale or block
# index moves the output by O(1).
KERNEL_RTOL = 1e-2

MAX_LEN = 256                 # engine context: max_seq is cut to this
ENGINE_KW = dict(slots=8, max_len=MAX_LEN, buckets=(192,),
                 kv_block_size=32)
POOL_BUDGET = 8 * 2 ** 30     # bytes per device
MAX_NEW = 16
ROWS = 32                     # table rows per one-chip query
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Phases:
    """Wall time per phase, with JAX's tracing + compilation time apart
    (summed from jax.monitoring's compile-duration events)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.rows = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.compile_s += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0 = time.perf_counter(), self.compile_s
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        self.rows.append((name, wall, comp))
        log(f"phase {name}: wall {wall:.3f}s compile {comp:.3f}s "
            f"run {wall - comp:.3f}s")


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


# ---------------------------------------------------------------------------
# checks on what ran
# ---------------------------------------------------------------------------

def kernels_in(lowered) -> set:
    """Pallas kernels (by ``pallas_call`` name) compiled into a lowered
    program.  Interpret mode and the reference formulas lower to plain
    HLO, so a kernel missing here did not run on the chip."""
    return set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))


def expected_kernels(params) -> set:
    """The kernels a compressed param tree's decode must run."""
    import jax
    from repro.core.compressed import BlockSparseTensor, QTensor
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, (QTensor, BlockSparseTensor)))
    want = {"paged_attention"}
    if any(isinstance(x, QTensor) and x.bits == 8 for x in leaves):
        want.add("quant_matmul")
    if any(isinstance(x, BlockSparseTensor) for x in leaves):
        want.add("block_sparse_matmul")
    return want


def decode_kernels(engine) -> set:
    """Kernels in the engine's own jitted paged decode step, lowered
    under the engine's backend with its live state."""
    import jax.numpy as jnp
    from repro.core.compressed import kernel_backend
    with kernel_backend(engine.backend):
        low = engine.jit_targets()["_decode"].lower(
            engine.params, engine._slot_state, engine._tables(),
            jnp.asarray(engine._cur_tok), jnp.asarray(engine._cur_pos),
            jnp.int32(0))
    return kernels_in(low)


def f32_model(cfg, params, shardings=None):
    """The same model in float32: int8 codes times their group scales
    (and any input scale), block-sparse tiles zero-filled, every other
    float leaf upcast.  Returns (cfg, params)."""
    import jax
    import jax.numpy as jnp
    from repro.core.compressed import BlockSparseTensor, QTensor

    def up(x):
        if isinstance(x, QTensor):
            w = x.unpack().astype(jnp.float32)
            *lead, k, n = w.shape
            w = (w.reshape(*lead, k // x.group, x.group, n)
                 * x.scale[..., :, None, :]).reshape(w.shape)
            return w if x.in_scale is None else w * x.in_scale[..., None]
        if isinstance(x, BlockSparseTensor):
            return x.w.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(jnp.float32)
        return x

    conv = jax.jit(lambda p: jax.tree_util.tree_map(
        up, p, is_leaf=lambda x: isinstance(x, (QTensor,
                                                BlockSparseTensor))),
        out_shardings=shardings)
    return cfg.replace(param_dtype="float32"), conv(params)


def truth_gate(what: str, got, plain, truth) -> dict:
    """Fail unless ``got`` is about as close to the f32 ``truth`` as the
    plain bf16 run ``plain`` (TRUTH_FACTOR, TRUTH_FLOOR)."""
    gaps = {"to_truth": rel_gap(got, truth),
            "plain_to_truth": rel_gap(plain, truth),
            "to_plain": rel_gap(got, plain)}
    bound = max(TRUTH_FACTOR * gaps["plain_to_truth"], TRUTH_FLOOR)
    log(f"{what} logits, relative L2: {gaps}; bound on to_truth {bound}")
    check(finite(got, plain, truth), f"non-finite {what} logits")
    check(gaps["to_truth"] <= bound, f"{what} logits {gaps['to_truth']} "
          f"from the f32 truth, over {bound}")
    return gaps


def step_logits_fn(cfg, backend: str, *, max_len: int, block_size: int,
                   precision=None):
    """jit: prefill a right-padded batch (vmapped per row, as the engine
    admits), scatter it into a paged pool, and take one paged decode
    step fed ``nxt`` [n, 1] — given, not the argmax, so that two
    backends decode the same token even where their prefill argmax
    flips on a near-tie.  Returns (last-prompt-position logits [n, V],
    decode-step logits [n, V]) on ``backend``, matmuls at
    ``precision`` (None: the default)."""
    import jax
    import jax.numpy as jnp
    from repro.core.compressed import kernel_backend
    from repro.models import api

    def row(params, toks):
        return api.prefill(params, cfg, {"tokens": toks[None]},
                           max_len=max_len, compact_local=False)

    def f(params, toks, lens, nxt):
        n = toks.shape[0]
        nblk = max_len // block_size
        logits, rows = jax.vmap(row, in_axes=(None, 0))(params, toks)
        last = jnp.take_along_axis(
            logits[:, 0], (lens - 1)[:, None, None], axis=1)[:, 0]
        tables = jnp.arange(n * nblk, dtype=jnp.int32).reshape(n, nblk)
        state = api.init_paged_cache(cfg, n, n * nblk + 1, block_size)
        state = api.paged_insert(cfg, state, rows, jnp.arange(n), tables,
                                 block_size=block_size)
        step, _ = api.paged_decode_step(
            params, cfg, state, tables, nxt, lens, block_size=block_size,
            max_len=max_len, backend=backend)
        return (last.astype(jnp.float32),
                step[:, -1].astype(jnp.float32))

    jitted = jax.jit(f)

    def run(*args):
        prec = (jax.default_matmul_precision(precision) if precision
                else contextlib.nullcontext())
        with kernel_backend(backend), prec:
            low = jitted.lower(*args)
            return kernels_in(low), low.compile()(*args)
    return run


@contextlib.contextmanager
def kernel_audit():
    """While active, every ``quant_matmul``, ``block_sparse_matmul`` and
    ``paged_attention`` call traced into a program also computes its
    reference formula on the same operands and, when the compiled
    program runs, records the relative L2 gap under the kernel's name.
    Yields ``{name: [gap per call]}``."""
    import jax
    import jax.numpy as jnp
    from repro.core.compressed import QTensor, _q_matmul_jnp
    from repro.kernels import ops
    from repro.models.transformer import _masked_decode

    gaps = {}

    def audited(name, kernel, reference):
        def record(y, r):
            gaps.setdefault(name, []).append(rel_gap(y, r))

        def call(*args, **kw):
            y = kernel(*args, **kw)
            jax.debug.callback(record, y, reference(*args, **kw))
            return y
        return call

    def quant_ref(x, q, scale, *, group, in_scale=None, layer=None, **_):
        if layer is not None:       # one layer of a stacked weight
            q, scale = q[layer], scale[layer]
            in_scale = None if in_scale is None else in_scale[layer]
        return _q_matmul_jnp(x, QTensor(q, scale, 8, group, q.shape,
                                        in_scale))

    def block_sparse_ref(x, w, idx, **_):
        return jnp.einsum("...i,io->...o", x, w.astype(x.dtype),
                          preferred_element_type=jnp.float32
                          ).astype(x.dtype)

    def paged_ref(q, k_pool, v_pool, tables, lengths, *, softcap=0.0,
                  window=0, **_):
        B, nblk = tables.shape
        _, bs, K, D = k_pool.shape
        gk = k_pool[tables].reshape(B, nblk * bs, K, D)
        gv = v_pool[tables].reshape(B, nblk * bs, K, D)
        kpos = jnp.arange(nblk * bs)[None, :]
        valid = kpos < lengths[:, None]
        if window:
            valid &= kpos >= lengths[:, None] - window
        return _masked_decode(q, gk, gv, valid, softcap)

    saved = {n: getattr(ops, n) for n in
             ("quant_matmul", "block_sparse_matmul", "paged_attention")}
    refs = {"quant_matmul": quant_ref, "block_sparse_matmul":
            block_sparse_ref, "paged_attention": paged_ref}
    for n, fn in saved.items():
        setattr(ops, n, audited(n, fn, refs[n]))
    try:
        yield gaps
    finally:
        jax.effects_barrier()
        for n, fn in saved.items():
            setattr(ops, n, fn)


def rel_gap(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def finite(*arrays) -> bool:
    import numpy as np
    return all(bool(np.isfinite(np.asarray(a)).all()) for a in arrays)


# ---------------------------------------------------------------------------
# one chip: gemma3-1b through the whole stack
# ---------------------------------------------------------------------------

def gemma3_1b():
    from repro.configs.gemma3_1b import CONFIG
    return CONFIG.replace(max_seq=MAX_LEN)


def pinned_recipes():
    """One int8 recipe (the compiled quant_matmul) and one that yields a
    BlockSparseTensor (the compiled block-sparse matmul); neither reads
    the calibration Hessian, so search time stays bounded."""
    from repro.core.pipeline import Recipe
    return [Recipe(name="w8-absmax", wbits=8, quant_method="absmax"),
            Recipe(name="bs128-d75", block_bs=128, block_density=0.75)]


def review_table(n: int, seed: int):
    from repro.olap.table import Table
    from repro.training.data import workload_rows
    return Table({"review": [r.text for r in
                             workload_rows("summarize", n, seed=seed)]})


def resident_engines(pool):
    return [e.engine for e in pool._entries.values()]


def clear_result_caches(pool) -> None:
    """Force the next pass to decode instead of answering from the
    engines' result caches (which would make row equality vacuous)."""
    for eng in resident_engines(pool):
        if eng.result_cache is not None:
            eng.result_cache.clear()


def one_chip(cfg, params, tok, phases, *, rows: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.olap.query import IOLMSession, Query
    from repro.serving.scheduler import Scheduler
    from repro.service import SemanticQueryService, ServiceClient, serve
    from repro.service.core import table_rows
    from repro.training.data import PROMPTS

    # off-TPU (a CPU rehearsal of this function) no kernel is compiled
    check_kernels = jax.default_backend() == "tpu"
    # The Acc variant ranks candidates by agreement with the base model,
    # a pure function of the decodes; the Perf variant ranks by host
    # wall clock around a decode that compiles inside the timed region,
    # so its pick (and the kernel the engine then runs) varies by run.
    sess = IOLMSession(params, cfg, tokenizer=tok, backend="pallas",
                       objective="acc", recipes=pinned_recipes(),
                       calib_rows=16, eval_rows=8,
                       engine_kw=dict(ENGINE_KW), pool_budget=POOL_BUDGET)
    table = review_table(rows, seed)

    def query():
        return Query(table, sess, optimize=True).llm_map(
            "review", max_new=MAX_NEW)

    with phases.phase("query1_calibrate_search_serve"):
        serial = query().run().columns["summary"]
    for line in sess.log:
        log(line)
    check(sess.recalibrations == 1, "query 1 did not optimize")
    check(not sess.dropped_recipes,
          f"pinned recipes dropped: {sess.dropped_recipes}")
    engines = resident_engines(sess.pool)
    check(len(engines) == 1, f"expected one compressed engine, got "
          f"{[e.version for e in engines]}")
    eng = engines[0]
    check(eng.version != "base", "no compressed instance was placed")
    picked = eng.version.rsplit(":", 1)[-1]
    check(eng.backend == "pallas" and eng.stats.backend == "pallas",
          f"compressed engine backend is {eng.backend!r}")
    check(eng._paged, "compressed engine is not on the paged KV layout")

    with phases.phase("query2_model_cache_hit"):
        again = query().run().columns["summary"]
    check(sess.model_cache.hits >= 1 and sess.recalibrations == 1,
          "query 2 missed the ModelCache")
    check(again == serial, "query 2 rows differ from query 1")

    if check_kernels:
        got, want = decode_kernels(eng), expected_kernels(eng.params)
        check(want <= got, f"decode step kernels {sorted(got)}, "
              f"expected {sorted(want)}")
        log(f"decode step kernels: {sorted(got)}")

    clear_result_caches(sess.pool)
    sched = Scheduler(sess.pool, share=8)
    with phases.phase("scheduler_run_queries"):
        batch = table_rows(sched.run_queries({"t1": query()})["t1"])
    check(sched.stats.degradations == 0,
          f"scheduler degradations: {sched.stats.events}")

    clear_result_caches(sess.pool)
    svc = SemanticQueryService(sess)
    server, _ = serve(svc, port=0, block=False)
    try:
        host, port = server.server_address[:2]
        client = ServiceClient(host, port, timeout=600.0, max_retries=0)
        with phases.phase("http_service"):
            check(client.healthz()["ok"], "service /healthz not ok")
            http = client.query("t1", query().to_spec())
            stats = client.stats()
    finally:
        server.shutdown()
        server.server_close()
        svc.stop()
    check(stats["scheduler"]["degradations"] == 0,
          f"service degradations: {stats['scheduler']['events']}")
    check(http == batch, "HTTP rows differ from Scheduler.run_queries")
    check(len(http) == rows, f"HTTP returned {len(http)} of {rows} rows")

    # the compressed instance, pallas vs reference, same params/prompts
    prompts = [PROMPTS["summarize"] + v for v in table.columns["review"][:8]]
    toks, lens = tok.pad_batch(
        [tok.encode(p, bos=True) + [tok.SEP] for p in prompts],
        seq_len=ENGINE_KW["buckets"][-1])
    toks, lens = jnp.asarray(toks), jnp.asarray(lens)
    nxt = jnp.full((len(prompts), 1), tok.SEP, jnp.int32)
    bs = eng._block_size
    with phases.phase("logits_pallas_reference_f32"):
        with kernel_audit() as calls:
            kp, (pre_p, dec_p) = step_logits_fn(
                eng.cfg, "pallas", max_len=MAX_LEN, block_size=bs)(
                eng.params, toks, lens, nxt)
        _, (pre_r, dec_r) = step_logits_fn(
            eng.cfg, "reference", max_len=MAX_LEN, block_size=bs)(
            eng.params, toks, lens, nxt)
        cfg32, params32 = f32_model(eng.cfg, eng.params)
        _, (pre_t, dec_t) = step_logits_fn(
            cfg32, "reference", max_len=MAX_LEN, block_size=bs,
            precision="highest")(params32, toks, lens, nxt)
        del params32
    want = expected_kernels(eng.params)
    if check_kernels:
        check(want <= kp, f"logit step kernels {sorted(kp)}, "
              f"expected {sorted(want)}")
    audit = {n: {"calls": len(g), "max_gap": max(g)}
             for n, g in calls.items()}
    log(f"kernel calls vs their reference formula (relative L2, "
        f"tolerance {KERNEL_RTOL}): {audit}")
    check(want <= set(audit), f"kernels audited {sorted(audit)}, "
          f"expected {sorted(want)}")
    check(all(a["max_gap"] <= KERNEL_RTOL for a in audit.values()),
          f"a kernel call left its reference formula: {audit}")
    gaps = {"prefill": truth_gate("pallas prefill", pre_p, pre_r, pre_t),
            "decode": truth_gate("pallas decode", dec_p, dec_r, dec_t)}
    first_agree = float((pre_p.argmax(-1) == pre_r.argmax(-1)).mean())
    log(f"first-token argmax agreement, pallas vs reference: "
        f"{first_agree}")
    return {"picked": picked, "rows": rows, "kernel_audit": audit,
            "logits": gaps, "first_token_agreement": first_agree,
            "serial_equals_scheduler": serial == [r["summary"]
                                                  for r in batch]}


# ---------------------------------------------------------------------------
# four chips: pool placement, and the tensor-parallel engine
# ---------------------------------------------------------------------------

def tenant_queries(sess, n_tenants: int, rows: int, seed: int):
    """One query per tenant over the same table, each with its own
    operator template (so its own compressed instance).  Equal-length
    templates keep the calibration and evaluation shapes, and so the
    compiled programs, shared across tenants."""
    from repro.olap.query import Query
    table = review_table(rows, seed)
    return {f"t{i}": Query(table, sess).llm_map(
                "review", prompt=f"tenant {i} summarize: ", max_new=MAX_NEW)
            for i in range(n_tenants)}


def four_chip_placement(cfg, params, tok, devices, phases, *, rows: int,
                        seed: int) -> dict:
    from repro.olap.query import IOLMSession
    from repro.serving.scheduler import Scheduler
    from repro.service.core import table_rows
    w8 = pinned_recipes()[:1]
    kw = dict(tokenizer=tok, backend="pallas", recipes=w8, calib_rows=4,
              eval_rows=2, engine_kw=dict(ENGINE_KW),
              pool_budget=POOL_BUDGET)
    n = len(devices)
    fleet = IOLMSession(params, cfg, devices=list(devices), **kw)
    with phases.phase("placement_fleet_run_queries"):
        sched = Scheduler(fleet.pool, share=8)
        got = sched.run_queries(tenant_queries(fleet, n, rows, seed))
    check(sched.stats.degradations == 0,
          f"fleet degradations: {sched.stats.events}")
    homes = sorted(fleet.pool.placement_of(v)
                   for v in fleet.pool.resident_versions)
    log(f"fleet placement (device indices per instance): {homes}")
    check(homes == [(i,) for i in range(n)],
          f"expected one compressed instance per chip, got {homes}")
    check(sched.stats.peak_concurrent_devices == n,
          f"decode overlapped {sched.stats.peak_concurrent_devices} "
          f"devices, expected {n}")

    single = IOLMSession(params, cfg, **kw)
    with phases.phase("placement_one_chip_run_queries"):
        ref = Scheduler(single.pool, share=8).run_queries(
            tenant_queries(single, n, rows, seed))
    same = all(table_rows(got[t]) == table_rows(ref[t]) for t in got)
    log(f"fleet rows byte-identical to a fresh one-chip session: {same}")
    check(same, "fleet rows differ from the one-chip session")
    return {"placement": homes, "byte_identical": same}


def four_chip_tp(full, devices, phases, *, seed: int,
                 cut_layers: int) -> dict:
    """``full`` is served at full depth over the mesh; its first
    ``cut_layers`` layers, which one chip holds, are then served by the
    TP engine and by a one-chip engine, and compared."""
    import jax
    import jax.numpy as jnp
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_mesh
    from repro.models import api
    from repro.models.transformer import pattern_unit
    from repro.serving.engine import Engine
    from repro.training.data import ByteTokenizer, workload_rows

    mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
    kw = dict(slots=2, max_len=MAX_LEN, buckets=(192,))
    texts = [f"summarize: {r.text}"
             for r in workload_rows("summarize", 2, seed=seed)]
    key = jax.random.PRNGKey(seed)

    def shardings(cfg):
        shapes = jax.eval_shape(lambda: api.init_params(key, cfg))
        return shapes, SH.param_shardings(cfg, shapes, mesh)

    def first_layers(params, cfg, n):
        """The first ``n`` layers of a scanned stack, in the cut
        config's shardings: the same weights, without a second init."""
        unit, _, _ = pattern_unit(cfg)
        cut = cfg.replace(n_layers=n)
        check(n % len(unit) == 0 and pattern_unit(cut)[2] == 0,
              f"cannot cut {cfg.name} to {n} layers")
        shapes, shard = shardings(cut)
        out = jax.jit(lambda p: {**p, "blocks": jax.tree_util.tree_map(
            lambda x: x[:n // len(unit)], p["blocks"])},
            out_shardings=shard)(params)
        check(jax.tree_util.tree_structure(out)
              == jax.tree_util.tree_structure(shapes)
              and all(a.shape == b.shape for a, b in zip(
                  jax.tree_util.tree_leaves(out),
                  jax.tree_util.tree_leaves(shapes))),
              "cut params do not match the cut config")
        return cut, out, shard

    tok = ByteTokenizer(full.vocab_size)
    with phases.phase("tp_full_depth_serve"):
        # built straight into their TP shardings: an eager init would
        # first materialize every weight on device 0
        _, shard = shardings(full)
        params = jax.jit(lambda k: api.init_params(k, full),
                         out_shardings=shard)(key)
        out_full = Engine(params, full, tokenizer=tok, mesh=mesh,
                          param_shardings=shard, **kw).generate(
            texts, max_new=8)
    log(f"{full.name} TP ({full.n_layers} layers) over "
        f"{len(devices)} chips: {len(out_full)} rows, peak bytes "
        f"{peak_bytes(devices)}")
    check(len(out_full) == len(texts), "TP engine lost rows")

    toks, _ = tok.pad_batch([tok.encode(t, bos=True) for t in texts],
                            seq_len=kw["buckets"][0])
    toks = jnp.asarray(toks)

    def last_logits(cfg, params, toks):
        return jax.jit(lambda p, t: api.prefill(
            p, cfg, {"tokens": t}, max_len=MAX_LEN,
            compact_local=False)[0][:, -1].astype(jnp.float32))(params, toks)

    with phases.phase("tp_vs_one_chip_cut_depth"):
        cut, params, shard = first_layers(params, full, cut_layers)
        tp_out = Engine(params, cut, tokenizer=tok, mesh=mesh,
                        param_shardings=shard, **kw).generate(
            texts, max_new=8)
        lg_tp = last_logits(cut, params, toks)
        # the f32 truth, sharded too: one chip cannot hold it
        cfg32, params32 = f32_model(cut, params, shard)
        with jax.default_matmul_precision("highest"):
            lg_truth = last_logits(cfg32, params32, toks)
        del params32
        one = jax.device_put(params, devices[0])
        del params
        one_out = Engine(one, cut, tokenizer=tok, device=devices[0],
                         **kw).generate(texts, max_new=8)
        lg_one = last_logits(cut, one, jax.device_put(toks, devices[0]))
    log(f"TP vs one chip at {cut_layers} layers: greedy tokens equal: "
        f"{tp_out == one_out}")
    gaps = truth_gate(f"TP ({cut_layers} layers)", lg_tp, lg_one, lg_truth)
    return {"tokens_equal": tp_out == one_out, "logits": gaps,
            "cut_layers": cut_layers}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip phase")
    ap.add_argument("--only", choices=("tp", "placement"),
                    help="with --chips 4: run only this part of it")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.only and args.chips != 4:
        ap.error("--only goes with --chips 4")

    try:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        log(f"FAIL: cannot import the program ({e}); run from the repo root")
        return 1
    cache = enable_compile_cache(ROOT)
    devices = jax.devices()
    d0 = devices[0]
    log(f"jax {jax.__version__} devices={devices} "
        f"kind={d0.device_kind!r} count={len(devices)} cache={cache}")
    if d0.platform != "tpu":
        log(f"FAIL: no TPU (platform {d0.platform!r})")
        return 1
    if len(devices) < args.chips:
        log(f"FAIL: --chips {args.chips} but {len(devices)} devices")
        return 1
    devices = devices[:args.chips]

    from repro.models import api
    from repro.training.data import ByteTokenizer
    phases = Phases()
    def gemma_params():
        cfg = gemma3_1b()
        with phases.phase("init_params"):
            # one jitted program: eager init compiles every op apart
            params = jax.jit(lambda k: api.init_params(k, cfg))(
                jax.random.PRNGKey(args.seed))
            jax.block_until_ready(params)
        return cfg, params, ByteTokenizer(cfg.vocab_size)

    try:
        res = {}
        if args.chips == 1:
            res = one_chip(*gemma_params(), phases, rows=ROWS,
                           seed=args.seed)
        if args.chips == 4 and args.only != "placement":
            from repro.configs.mistral_nemo_12b import CONFIG as NEMO
            res["tp"] = four_chip_tp(NEMO.replace(max_seq=MAX_LEN), devices,
                                     phases, seed=args.seed, cut_layers=8)
        if args.chips == 4 and args.only != "tp":
            res.update(four_chip_placement(*gemma_params(), devices,
                                           phases, rows=8, seed=args.seed))
    except SmokeFailure as e:
        log(f"FAIL: {e}")
        return 1
    log(f"result: {json.dumps(res)}")
    log(f"peak_bytes_in_use per device: {peak_bytes(devices)}")
    log("phases (name, wall s, compile s): " + json.dumps(
        phases.rows))
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
