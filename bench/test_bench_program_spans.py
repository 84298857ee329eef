"""The program's own spans and counter as the harness sees them: in a
trace recorded here on the CPU, against what ``serve.EngineProbe``
reads from the engine's internals, and through a rehearsal of the scan
cell with the program's recorder on."""
from types import SimpleNamespace

import jax
import pytest

from iolmbench import main as M
from iolmbench import serve, spec, traffic, weights
from iolmbench import trace as TR
from iolmbench.rehearsal import tiny_cell
from repro import tracing

SEED = 2 ** 31 + 11


@pytest.fixture
def recorder():
    tracing.reset()
    tracing.enable()
    try:
        yield tracing
    finally:
        tracing.disable()
        tracing.reset()


def tiny_engine():
    """The scan cell's engine at the rehearsal's sizes, with the
    harness's probe attached, and the rows it would serve."""
    from repro.configs.base import ModelConfig
    from repro.serving.engine import Engine
    from repro.training.data import ByteTokenizer

    cell, sizes = tiny_cell("nemo-iolm-scan")
    kw = dict(cell.model_kwargs(), **sizes)
    cfg = ModelConfig(**kw)
    e = dict(cell.mix["session"]["engine"])
    e["buckets"] = tuple(e["buckets"])
    eng = Engine(weights.make(kw, SEED, 1), cfg,
                 tokenizer=ByteTokenizer(cfg.vocab_size), backend="reference",
                 version="tiny", **e)
    probe = serve.EngineProbe(eng)
    rows = traffic.review_column(cell.mix, SEED)[:11]
    return eng, probe, cell.mix["instruction"], rows


def test_engine_records_equal_the_probe_row_for_row(recorder):
    eng, probe, instr, rows = tiny_engine()
    probe.recording = True
    for r in rows:
        eng.submit(instr + r, max_new=3, prefix=instr)
    eng.drain()
    admits = [r for r in tracing.spans() if r.name == "engine.admit"]
    decodes = [r for r in tracing.spans() if r.name == "engine.decode"]
    assert len(admits) >= 3 and len(decodes) == eng.stats.decode_steps
    assert probe.admits == [
        (len(a.attrs["rids"]), a.attrs["tokens"],
         list(zip(a.attrs["suffix_lens"], a.attrs["prefix_lens"])))
        for a in admits]
    assert probe.decodes == [d.attrs["kv_lens"] for d in decodes]
    assert eng.stats.host_syncs == 2 * len(probe.decodes) \
        + 2 * len(probe.admits)


def test_program_spans_in_a_recorded_cpu_trace(tmp_path):
    """With the recorder off, a running profiler still receives the
    program's spans; they nest inside the harness's, so an idle gap in
    the host's pull of the tokens is put on ``engine.pull``."""
    from iolmbench.clock import span
    from iolmbench.main import _profile_options

    eng, probe, instr, rows = tiny_engine()
    assert tracing.span("engine.pull") is tracing._NOOP    # recorder off
    for r in rows[:6]:
        eng.submit(instr + r, max_new=3, prefix=instr)
    eng.step()                        # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=_profile_options())
    ticks = 0
    with span(TR.WINDOW_SPAN):
        while eng.has_work():
            with span("bench.tick"):
                eng.step()
            ticks += 1
    jax.profiler.stop_trace()
    tr = TR.from_xspace(TR.find_xplane(str(tmp_path)))
    pulls = [s for s in tr.spans if s[0] == "engine.pull"]
    assert len(pulls) == ticks
    outer = {n: [s for s in tr.spans if s[0] == n]
             for n in ("engine.step_finish", "bench.tick")}
    for _, s, e in pulls:
        for n, ivs in outer.items():
            assert any(s0 <= s and e <= e0 for _, s0, e0 in ivs), n
    assert {"engine.admit", "engine.decode", "engine.retire"} <= {
        n for n, _, _ in tr.spans}
    # a device busy up to the first pull and again from its end
    _, p0, p1 = pulls[0]
    w0, w1 = tr.window
    dev = "/device:TPU:0"
    hand = TR.Trace(ops={dev: [("fusion.1", w0, p0), ("fusion.2", p1, w1)]},
                    modules={dev: []}, spans=tr.spans, window=tr.window)
    red = TR.reduce(hand, ())
    assert red.idle_by_span == pytest.approx({"engine.pull": (p1 - p0) / 1e9})
    read = spec.metric_reader("pull_idle_ms.scan")
    ctx = SimpleNamespace(trace=red, window=SimpleNamespace(ticks=ticks))
    assert read(ctx) == pytest.approx(1000.0 * (p1 - p0) / 1e9 / ticks)
    # a program without the engine's spans (the harness's alone): nothing
    harness = [s for s in tr.spans if s[0].startswith("bench.")
               or s[0] in ("engine.step_begin", "engine.step_finish")]
    red = TR.reduce(TR.Trace(ops=hand.ops, modules=hand.modules,
                             spans=harness, window=tr.window), ())
    assert "engine.step_finish" in red.idle_by_span
    assert read(SimpleNamespace(trace=red, window=ctx.window)) is None


def test_scan_rehearsal_with_the_recorder_on(recorder, monkeypatch):
    """The readings a harness with the recorder on takes: the search
    split into its evaluations and compressions, calibration, the
    programs compiled under the engine's spans in set-up, and the
    window's pulls and first-token syncs; none of them changes the
    result line."""
    monkeypatch.setattr(M, "enable_compile_cache", lambda: "off")
    window = serve.window
    marks = {}

    def marked(*a, **kw):
        marks["setup"] = tracing.snapshot()
        tracing.reset()
        w = window(*a, **kw)
        marks["window"] = tracing.snapshot()
        return w

    monkeypatch.setattr(serve, "window", marked)
    cell, sizes = tiny_cell("nemo-iolm-scan")
    out = M.run_cell(cell, SEED, 3.0, True, require_chip=False, sizes=sizes)
    assert out["correct"] is True
    m = out["metrics"]
    assert "pull_idle_ms.scan" not in m       # no chip, no device trace
    su, win = marks["setup"], marks["window"]
    n_recipes = len(cell.mix["session"]["recipes"])
    assert su["iolm.eval"]["count"] == 1 + n_recipes
    assert su["iolm.compress"]["count"] == n_recipes
    parts = su["iolm.eval"]["total_s"] + su["iolm.compress"]["total_s"]
    assert parts == pytest.approx(su["iolm.search"]["total_s"], rel=0.05)
    assert su["iolm.calibrate"]["total_s"] == pytest.approx(
        m["calibrate_s"]["value"], rel=0.02)
    assert sum(v["compiles"] for n, v in su.items()
               if n.startswith("engine.")) > 0
    assert sum(v["compile_s"] for n, v in su.items()
               if n.startswith("iolm.")) > 0
    steps = win["engine.decode"]["count"]
    admits = win["engine.admit"]["count"]
    assert steps and admits
    assert win["engine.pull"]["count"] == steps
    assert win["engine.first_token"]["count"] == admits
    assert sum(v["compiles"] for v in win.values()) == 0
