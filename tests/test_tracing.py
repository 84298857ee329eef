"""The in-program span recorder (``repro.tracing``) and the spans and
counter the serving engine, the scheduler and the recipe search feed
it."""
import dataclasses
import importlib.util
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro import tracing as T
from repro.core.pipeline import Recipe
from repro.olap.query import IOLMSession
from repro.serving.engine import Engine
from repro.serving.scheduler import Scheduler


@pytest.fixture
def recorder():
    T.reset()
    T.enable()
    try:
        yield T
    finally:
        T.disable()
        T.reset()


def by_name(name):
    return [r for r in T.spans() if r.name == name]


def test_disabled_span_is_the_shared_noop_and_records_nothing():
    T.disable()
    T.reset()
    a, b = T.span("engine.pull"), T.span("iolm.eval", recipe="x")
    assert a is b is T._NOOP
    with a as rec:
        assert rec is None
        jax.jit(lambda x: x + 7)(jnp.ones(3)).block_until_ready()
    assert T.spans() == [] and T.snapshot() == {}


def test_nesting_and_parent_links(recorder):
    with T.span("outer", k=1) as o:
        with T.span("mid") as m:
            with T.span("inner") as i:
                pass
        with T.span("mid") as m2:
            pass
    assert [r.name for r in T.spans()] == ["inner", "mid", "mid", "outer"]
    assert i.parent is m and m.parent is o and m2.parent is o
    assert o.parent is None and o.attrs == {"k": 1}
    assert T.snapshot()["mid"]["count"] == 2


def test_self_time_is_duration_less_children(recorder):
    with T.span("outer") as o:
        time.sleep(0.01)
        with T.span("inner") as i:
            time.sleep(0.02)
        with T.span("inner") as i2:
            time.sleep(0.005)
    snap = T.snapshot()
    assert snap["outer"]["total_s"] == pytest.approx(o.seconds)
    assert snap["outer"]["self_s"] == pytest.approx(
        o.seconds - i.seconds - i2.seconds)
    assert snap["outer"]["self_s"] >= 0.01
    assert snap["inner"]["self_s"] == pytest.approx(snap["inner"]["total_s"])
    assert snap["inner"]["total_s"] == pytest.approx(i.seconds + i2.seconds)


def test_a_fresh_jit_adds_one_compile_to_the_innermost_span(recorder):
    x = jnp.arange(5.0)
    with T.span("outer"):
        with T.span("inner"):
            jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    snap = T.snapshot()
    assert snap["inner"]["compiles"] == 1
    assert snap["inner"]["compile_s"] > 0
    assert snap["outer"]["compiles"] == 0
    assert snap["outer"]["compile_s"] == 0


def test_reset_clears_everything(recorder):
    with T.span("a"):
        jax.jit(lambda v: v - 2.0)(jnp.ones(2)).block_until_ready()
    assert T.spans()
    T.reset()
    assert T.spans() == [] and T.snapshot() == {}


# -- the engine's spans and counter ---------------------------------------

PREFIX = "fix the typo: "


def _engine(tiny_dense, **kw):
    cfg, params = tiny_dense
    kw = dict(dict(slots=2, max_len=64, buckets=(16, 32)), **kw)
    return Engine(params, cfg, **kw)


def test_host_syncs_count_two_per_admission_and_decode(tiny_dense):
    eng = _engine(tiny_dense, use_prefix_cache=False)
    eng.generate([f"row {i} abc" for i in range(5)], max_new=4)
    st = eng.stats
    assert st.decode_steps and st.prefills >= 3
    assert st.host_syncs == 2 * st.decode_steps + 2 * st.prefills


def test_pipelined_share_reads_the_window_counters(tiny_dense):
    """``bench/metrics/pipelined_share.py`` on a context as the harness
    builds it (the window's integer ``EngineStats``): every decode step
    but the first was launched before the one before it was pulled; a
    program without the counter reads nothing."""
    path = os.path.join(os.path.dirname(__file__), os.pardir, "bench",
                        "metrics", "pipelined_share.py")
    spec = importlib.util.spec_from_file_location("pipelined_share", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    eng = _engine(tiny_dense, use_prefix_cache=False)
    eng.generate([f"row {i} abc" for i in range(5)], max_new=4)
    stats = {f.name: getattr(eng.stats, f.name)
             for f in dataclasses.fields(eng.stats) if f.type in (int, "int")}
    steps = stats["decode_steps"]
    assert steps > 1 and stats["pipelined_steps"] == steps - 1
    ctx = SimpleNamespace(engine_stats=stats)
    assert reader.read(ctx) == pytest.approx(100.0 * (steps - 1) / steps)
    del stats["pipelined_steps"]
    assert reader.read(ctx) is None
    assert reader.read(SimpleNamespace(engine_stats={
        "decode_steps": 0, "pipelined_steps": 0})) is None


def test_engine_spans_nest_and_carry_what_was_admitted(tiny_dense,
                                                       recorder):
    eng = _engine(tiny_dense)
    texts = [PREFIX + f"row {i} {'x' * i}" for i in range(5)]
    eng.generate(texts, max_new=3, prefix=PREFIX)
    admits, decodes = by_name("engine.admit"), by_name("engine.decode")
    assert len(decodes) == eng.stats.decode_steps
    assert eng.stats.host_syncs == 2 * len(decodes) + 2 * len(admits)
    assert sum(len(a.attrs["rids"]) for a in admits) == len(texts)
    for a in admits:
        assert a.attrs["bucket"] in eng.buckets
        assert set(a.attrs["prefix_lens"]) == {len(PREFIX) + 1}   # + BOS
        assert len(a.attrs["suffix_lens"]) == len(a.attrs["rids"])
    # the first admission builds the template's prefix entry
    assert admits[0].attrs["tokens"] == (
        len(admits[0].attrs["rids"]) * admits[0].attrs["bucket"]
        + len(PREFIX) + 1)
    assert sum(a.attrs["tokens"] for a in admits) == eng.stats.prefill_tokens
    for name in ("engine.prefill", "engine.first_token", "engine.insert"):
        rs = by_name(name)
        assert len(rs) == len(admits)
        assert all(r.parent in admits for r in rs)
    for d in decodes:
        assert 1 <= len(d.attrs["kv_lens"]) <= eng.slots
    assert len(by_name("engine.pull")) == len(decodes)
    assert sum(r.attrs["rows"] for r in by_name("engine.retire")) == len(texts)


def test_scheduler_tick_spans(tiny_dense, recorder):
    cfg, params = tiny_dense
    sess = IOLMSession(params, cfg, pool_budget=1 << 30,
                       engine_kw=dict(slots=2, max_len=64, buckets=(32,)))
    sched = Scheduler(sess.pool, share=2)
    sched.submit("t", [f"p{i}" for i in range(4)], qsig="q", max_new=2,
                 optimize=False)
    sched.run()
    ticks = by_name("engine.schedule")
    assert [t.attrs["tick"] for t in ticks] == list(
        range(1, sched.stats.ticks + 1))
    tops = by_name("engine.top_up")
    assert all(t.parent in ticks for t in tops)
    assert sum(t.attrs["rows"] for t in tops) == 4
    assert by_name("engine.decode")
    assert all(r.parent in ticks for r in by_name("engine.decode")
               + by_name("engine.admit") + by_name("engine.pull"))
    assert not hasattr(sched, "trace")


def test_search_spans_split_the_optimization(tiny_dense, recorder):
    cfg, params = tiny_dense
    recipes = [Recipe(name="w8", wbits=8, quant_method="absmax"),
               Recipe(name="bs16", block_bs=16, block_density=0.5)]
    sess = IOLMSession(params, cfg, recipes=recipes, calib_rows=4,
                       eval_rows=2,
                       engine_kw=dict(slots=2, max_len=64, buckets=(32,)))
    compiles = []

    def count(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        sess._optimize("qsig", [f"fix: categ{i}" for i in range(8)])
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    (opt,) = by_name("iolm.optimize")
    assert opt.attrs == {"qsig": "qsig"} and opt.parent is None
    (cal,) = by_name("iolm.calibrate")
    (search,) = by_name("iolm.search")
    assert cal.parent is opt and search.parent is opt
    evals, comps = by_name("iolm.eval"), by_name("iolm.compress")
    assert [e.attrs["recipe"] for e in evals] == ["baseline", "w8", "bs16"]
    assert [c.attrs["recipe"] for c in comps] == ["w8", "bs16"]
    assert all(r.parent is search for r in evals + comps)
    snap = T.snapshot()
    # the search is its evals and compressions, and little else
    assert snap["iolm.search"]["self_s"] < 0.05 * search.seconds
    # every program compiled in the optimization lands in one of its spans
    assert sum(snap[n]["compiles"] for n in snap) == len(compiles) > 0
    assert snap["iolm.eval"]["compiles"] > 0
    # a cache hit records no optimization
    T.reset()
    sess._optimize("qsig", [f"fix: categ{i}" for i in range(8)])
    assert by_name("iolm.optimize") == []
