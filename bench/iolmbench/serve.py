"""Set-up and the measured window, driven through the program's own
path: ``IOLMSession`` -> ``Query.llm_map`` -> ``ModelPool`` /
``Scheduler`` -> ``Engine`` (paged decode, Pallas kernels on a TPU).

Set-up optimizes the cell's query on its probe (calibration, recipe
search, compression) by serving the set-up rows through the query
planner and the scheduler, then warms every admission width (1 ..
slots rows) in every length bucket the column's rows fall in, and
clears the result caches.  The window submits the mix's requests as
they fall due, each as the planner submits one operator
(``Scheduler.submit`` under the query's signature and probe), and ticks
the scheduler between them.
"""
from __future__ import annotations

import contextlib
import gc
import math
import time
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax

from iolmbench import traffic, weights
from iolmbench.clock import CompileWatch, Timers, span, spanned
from iolmbench.trace import WINDOW_SPAN

# what the harness reads inside the program's engine, for want of a
# public counter or span (PERF.md lists them for the tracing work)
ENGINE_INTERNALS = ("_active", "_cur_pos", "_cur_tok", "_slot_state",
                    "_tables", "_block_size", "jit_targets", "step_begin",
                    "step_finish", "stats", "slots", "result_cache")


def require(obj, names) -> None:
    """Fail loudly where the program no longer has what the harness
    reads, rather than count something else."""
    missing = [n for n in names if not hasattr(obj, n)]
    if missing:
        raise RuntimeError(f"{type(obj).__name__} lacks {missing}, which "
                           f"the benchmark reads (bench/iolmbench/serve.py)")


def _block(tree) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.block_until_ready()


class EngineProbe:
    """Host spans around the engine's two tick halves, and, while
    ``recording``, what each tick admitted and decoded: the counts the
    kernels' operations and bytes are computed from.

    admits:  (rows, padded prefill tokens, [(real suffix tokens,
              prefix tokens) per row])
    decodes: live KV lengths of the slots one decode step served
    """

    def __init__(self, engine):
        require(engine, ENGINE_INTERNALS)
        self.engine = engine
        self.recording = False
        self.admits: List[Tuple[int, int, List[Tuple[int, int]]]] = []
        self.decodes: List[List[int]] = []
        self._begin, self._finish = engine.step_begin, engine.step_finish
        engine.step_begin = self.step_begin
        engine.step_finish = spanned("engine.step_finish", self._finish)

    def step_begin(self):
        e = self.engine
        before = set(e._active)
        tok0 = e.stats.prefill_tokens
        with span("engine.step_begin"):
            handle = self._begin()
        if self.recording:
            padded = e.stats.prefill_tokens - tok0
            if padded:
                new = [s for s in e._active if s not in before]
                rows = [(len(e._active[s].prompt_ids),
                         len(e._active[s].prefix_ids or ())) for s in new]
                self.admits.append((len(new) + len(handle.finished),
                                    padded, rows))
            if handle.nxt is not None:
                self.decodes.append([int(e._cur_pos[s]) + 1
                                     for s in e._active])
        return handle


@dataclass
class Served:
    """One finished row as the timed path produced it."""
    prompt: str
    out_ids: List[int]
    text: str


@dataclass
class Window:
    seconds: float                       # measured length, host clock
    ticks: int                           # scheduler ticks that had work
    rows: int                            # rows finished inside it
    attempted: int                       # rows the scheduler took up
    failed: int                          # of those, never finished
    served: List[Served]                 # every row finished, drain too
    latency: Dict[str, List[float]] = field(default_factory=dict)
    info: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Setup:
    cfg: Any
    kw: Dict[str, Any]                   # the configuration's sizes
    weights: Any
    column: List[str]
    session: Any
    engine: Any                          # the engine the window serves
    probe: EngineProbe
    op: Dict[str, Any]                   # qsig, probe, prefix, max_new
    picked: str                          # the served engine's recipe
    timers: Timers
    info: Dict[str, Any] = field(default_factory=dict)


@contextlib.contextmanager
def _timed_layers(timers: Timers, sess):
    """Host-clock spans around the instance-optimization layers, each
    ended by blocking on its result: ``calibrate_s`` around
    ``InstanceOptimizer.run_calibration``, ``search_s`` around
    ``policy.search`` (compression included), ``optimize_s`` around the
    session's whole optimization of a never-seen query."""
    from repro.core import policy as POL
    from repro.core.pipeline import InstanceOptimizer

    calib, search, optimize = (InstanceOptimizer.run_calibration,
                               POL.search, sess._optimize)

    def run_calibration(self, *a, **kw):
        with timers.time("calibrate_s"), span("iolm.calibrate"):
            return calib(self, *a, **kw)

    def timed_search(*a, **kw):
        with timers.time("search_s"), span("iolm.search"):
            out = search(*a, **kw)
            pick = out.acc if sess.objective != "perf" else out.perf
            if pick is not None:
                _block(pick.params)
            return out

    def timed_optimize(*a, **kw):
        with timers.time("optimize_s"), span("iolm.optimize"):
            m = optimize(*a, **kw)
            _block(m.params)
            return m

    InstanceOptimizer.run_calibration = run_calibration
    POL.search = timed_search
    sess._optimize = timed_optimize
    try:
        yield
    finally:
        InstanceOptimizer.run_calibration = calib
        POL.search = search
        del sess._optimize


def serves_base(cell) -> bool:
    target = cell.mix["session"].get("serve", "compressed")
    if target not in ("compressed", "base"):
        raise ValueError(f"unknown serving target {target!r}")
    return target == "base"


def shared_prefix(cell) -> bool:
    return bool(cell.mix.get("shared_prefix", True))


def build(cell, seed: int, *, watch: CompileWatch,
          sizes: Optional[Dict[str, Any]] = None) -> Setup:
    """Everything before the window.  ``sizes`` overrides configuration
    sizes (the CPU rehearsal's small model)."""
    from repro.configs.base import ModelConfig
    from repro.core.pipeline import Recipe
    from repro.olap.query import IOLMSession, Query
    from repro.olap.table import Table
    from repro.serving.scheduler import QueryDriver, Scheduler
    from repro.training.data import ByteTokenizer

    mix, s = cell.mix, cell.mix["session"]
    kw = dict(cell.model_kwargs(), **(sizes or {}))
    cfg = ModelConfig(**kw)
    timers = Timers()
    with timers.time("init_s"):
        w = weights.make(kw, seed, zlib.crc32(cell.config_name.encode()))
        _block(w)
    with timers.time("data_s"):
        column = traffic.review_column(mix, seed)
    eng_kw = dict(s["engine"])
    eng_kw["buckets"] = tuple(eng_kw["buckets"])
    sess = IOLMSession(
        w, cfg, tokenizer=ByteTokenizer(cfg.vocab_size),
        backend=s["backend"], objective=s["objective"],
        recipes=[Recipe(**{k: (tuple(v) if isinstance(v, list) else v)
                           for k, v in r.items()}) for r in s["recipes"]],
        calib_rows=s["calib_rows"], eval_rows=s["eval_rows"],
        engine_kw=eng_kw, pool_budget=int(s["pool_budget_bytes"]))
    instr, max_new = mix["instruction"], int(mix["max_new"])
    c0 = watch.seconds
    ops: List[Any] = []
    with timers.time("setup_query_s"), _timed_layers(timers, sess):
        q = Query(Table({"review": column[:int(mix["setup_rows"])]}),
                  sess).llm_map("review", prompt=instr, max_new=max_new)
        sched = Scheduler(sess.pool, share=int(s["share"]))
        driver = QueryDriver(sched, "setup", q,
                             on_op_done=lambda d, op, outs: ops.append(op))
        driver.start()
        while not driver.finished:
            sched.step()
            driver.poll()
        if driver.error is not None:
            raise driver.error
    if len(ops) != 1:
        raise RuntimeError(f"the set-up query ran {len(ops)} operators")
    op = ops[0]
    opd = {"qsig": op.qsig, "probe": list(op.probe),
           "prefix": op.spec.prefix, "max_new": op.spec.max_new,
           "optimize": not serves_base(cell)}
    eng = sess.pool.engine_for(opd["qsig"], opd["probe"],
                               optimize=opd["optimize"])
    probe = EngineProbe(eng)
    with timers.time("warmup_s"):
        warm_widths(eng, cell, column, instr)
    if eng.result_cache is not None:
        eng.result_cache.clear()
    info = {"setup_compile_s": watch.seconds - c0,
            "dropped_recipes": list(sess.dropped_recipes),
            "degradations": sched.stats.degradations,
            "truncated": eng.stats.truncated,
            "session_log": list(sess.log)}
    return Setup(cfg=cfg, kw=kw, weights=w, column=column, session=sess,
                 engine=eng, probe=probe, op=opd,
                 picked=eng.version.rsplit(":", 1)[-1], timers=timers,
                 info=info)


def warm_widths(eng, cell, column: List[str], instr: str) -> None:
    """Admit 1 .. slots rows at once, in every length bucket the
    column's rows fall in, each row as long as the bucket's longest,
    two new tokens each: compiles every admission width's prefill and
    slot insert, the host-side sampling ops and the decode step, before
    the window."""
    mix = cell.mix
    shared = shared_prefix(cell)
    e = mix["session"]["engine"]
    lengths = traffic.longest_per_bucket(column, instr, shared,
                                         e["buckets"], int(e["max_len"]))
    base = int(mix["table_rows"]) + 1
    for b, L in sorted(lengths.items()):
        for n in range(1, eng.slots + 1):
            for j in range(n):
                text = traffic.review_text(0, base + 1000 * n + j, L)
                eng.submit(instr + text, max_new=2,
                           prefix=instr if shared else None)
            while eng.has_work():
                eng.step()
        base += 1000 * (eng.slots + 1)


@dataclass
class _Open:
    due: float
    sub: Any
    submitted: float
    first: Optional[float] = None
    last: Optional[float] = None


def window(st: Setup, cell, seconds: float, seed: int) -> Window:
    """The mix's requests submitted as they fall due, the scheduler
    ticked between them.  The window closes at the first tick past the
    deadline that finishes a row (rows admitted together finish
    together, so a window cut between two such ticks would count part
    of a batch's time without its rows); a request's rows not yet taken
    up by then are dropped, and the rows in flight are waited for up to
    the mix's ``drain_s``: they are checked, and their latency counts
    the wait, but they do not count as rows of the window."""
    from repro.serving.scheduler import Scheduler

    mix, s, op = cell.mix, cell.mix["session"], st.op
    arrivals = traffic.schedule(mix, seed, seconds)
    drain_s = float(mix["arrivals"].get("drain_s", 60.0))
    sched = Scheduler(st.session.pool, share=int(s["share"]))
    instr = op["prefix"]
    prefix = instr if shared_prefix(cell) else None
    closed = False

    def prompts(rows):
        for i in rows:
            if closed:
                return
            yield instr + st.column[i]

    reqs: List[_Open] = []
    ticks, nxt, rows_in_window = 0, 0, 0
    win = span(WINDOW_SPAN)
    win.__enter__()
    st.probe.recording = True
    t0 = time.perf_counter()
    t_close = None
    while True:
        now = time.perf_counter() - t0
        while not closed and nxt < len(arrivals) \
                and arrivals[nxt].due_s <= now:
            a = arrivals[nxt]
            with span("bench.submit"):
                sub = sched.submit(
                    f"r{nxt}", prompts(a.rows), qsig=op["qsig"],
                    probe=op["probe"], max_new=op["max_new"],
                    prefix=prefix, optimize=op["optimize"])
            reqs.append(_Open(a.due_s, sub, time.perf_counter() - t0))
            nxt += 1
        busy = bool(sched.active or sched.pending)
        done0 = sched.stats.rows
        if busy:
            with span("bench.tick"):
                sched.step()
            ticks += not closed
        t = time.perf_counter() - t0
        for o in reqs:
            if o.first is None and any(r.done for r in o.sub.reqs):
                o.first = t
            if o.last is None and o.sub.done and o.sub.error is None:
                o.last = t
        if not closed and t >= seconds and (
                sched.stats.rows > done0 or not busy):
            closed, t_close = True, t
            rows_in_window = sched.stats.rows
            in_flight = sum(len(o.sub.inflight) for o in reqs)
            st.probe.recording = False
            win.__exit__(None, None, None)
            win = span("bench.drain")
            win.__enter__()
        if closed and (all(o.sub.done for o in reqs)
                       or t > t_close + drain_s):
            break
        if not busy and not closed:
            due = arrivals[nxt].due_s if nxt < len(arrivals) else seconds
            with span("bench.sleep"):
                time.sleep(max(0.0, min(due, seconds)
                               - (time.perf_counter() - t0)))
    end = time.perf_counter() - t0
    win.__exit__(None, None, None)
    taken = [r for o in reqs for r in o.sub.reqs]
    errors = sum(o.sub.error is not None for o in reqs)
    # a request that never finished counts at the drain's end: its
    # latency is at least that
    lat_last = [(o.last if o.last is not None else end) - o.due
                for o in reqs]
    lat_first = [(o.first if o.first is not None else end) - o.due
                 for o in reqs]
    late = sorted(o.submitted - o.due for o in reqs)
    return Window(seconds=t_close, ticks=ticks, rows=rows_in_window,
                  attempted=len(taken) + errors,
                  failed=sum(not r.done for r in taken) + errors,
                  served=[Served(r.src, list(r.out_ids), r.text)
                          for r in taken if r.done],
                  latency={"query_s": lat_last, "first_row_s": lat_first},
                  info={"requests": len(reqs),
                        "rows_in_flight_at_close": in_flight,
                        "drain_s": end - t_close,
                        "generator_late_p50_s": late[len(late) // 2],
                        "generator_late_max_s": late[-1],
                        "degradations": sched.stats.degradations})


def nearest_rank(values: List[float], p: float) -> float:
    """The ``p``-th percentile by nearest rank (the value below which
    ``p`` percent of the sample lies)."""
    v = sorted(values)
    return v[max(0, math.ceil(p / 100.0 * len(v)) - 1)]


def free(st: Setup) -> None:
    """Drop every reference the harness holds to the program's state
    (session, pool, engines, compressed instances); the weights stay,
    for the reference."""
    st.session = st.engine = st.probe = None
    gc.collect()
