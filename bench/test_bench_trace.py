"""The trace reduction, on a hand-made trace with hand-checked numbers
and on a trace recorded here on the CPU."""
import time

import pytest

from iolmbench import trace as TR

US = 1000  # ns


def _hand_trace():
    dev = "/device:TPU:0"
    ops = [("quant_matmul.1", 2 * US, 3 * US),
           ("fusion.2", 2500, 4 * US),            # overlaps the first
           ("while.3", 1900, 4100),               # holds the two above
           ("paged_attention", 6 * US, 7 * US),
           ("vmap_quant_matmul_.7", 9 * US, 9500),
           ("copy.3", 500, 1500),                 # starts before the window
           ("quant_matmulx", 10 * US, 10500)]     # another op, not the kernel
    mods = [("jit_step", 1800, 4200), ("jit_row_prefill_from", 5800, 7100),
            ("jit_step", 8900, 10600)]
    spans = [("bench.window", 1 * US, 11 * US), ("bench.tick", 1200, 5 * US),
             ("engine.step_finish", 3900, 4900),
             ("engine.step_begin", 5 * US, 6500),
             ("bench.sleep", 7 * US, 9 * US)]
    return TR.Trace(ops={dev: ops}, modules={dev: mods}, spans=spans,
                    window=(1 * US, 11 * US))


def test_union_and_gaps():
    busy = TR.union([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert busy == [(1, 4), (5, 8)]
    assert TR.gaps(busy, 0, 10) == [(0, 1), (4, 5), (8, 10)]


def test_kernel_of_matches_name_and_instance_suffix():
    ks = ("quant_matmul", "paged_attention")
    assert TR.kernel_of("quant_matmul", ks) == "quant_matmul"
    assert TR.kernel_of("quant_matmul.12", ks) == "quant_matmul"
    assert TR.kernel_of("vmap_quant_matmul_.1", ks) == "quant_matmul"
    assert TR.kernel_of("quant_matmulx", ks) is None
    assert TR.kernel_of("fusion.3", ks) is None


def test_op_name_from_a_tpu_event():
    ev = ("%quant_matmul.64 = bf16[32,131072]{1,0:T(8,128)(2,1)S(1)} "
          "custom-call(bf16[32,5120]{1,0} %fusion.20)")
    assert TR.op_name(ev) == "quant_matmul.64"
    assert TR.op_name("fusion.2") == "fusion.2"


def test_reduce_hand_checked():
    r = TR.reduce(_hand_trace(), ("quant_matmul", "paged_attention"))
    # busy: [1000,1500] + [1900,4100] + [6000,7000] + [9000,9500]
    # + [10000,10500] = 4700 ns of a 10000 ns window
    assert r.window_s == pytest.approx(10e-6)
    assert r.busy_s == pytest.approx(4.7e-6)
    assert r.devices == 1
    assert r.kernel_s == pytest.approx({"quant_matmul": 1.5e-6,
                                        "paged_attention": 1e-6})
    assert r.kernel_calls == {"quant_matmul": 2, "paged_attention": 1}
    assert r.program_s == pytest.approx({"jit_step": 4.1e-6,
                                         "jit_row_prefill_from": 1.3e-6})
    # gaps and the innermost span at each midpoint: [1500,1900] 1700 ->
    # tick, [4100,6000] 5050 -> step_begin (the tick ends at 5000),
    # [7000,9000] 8000 -> sleep, 9750 and 10750 -> no span
    assert r.idle_by_span == pytest.approx({
        "bench.tick": 0.4e-6, "engine.step_begin": 1.9e-6,
        "bench.sleep": 2e-6, TR.NO_SPAN: 1e-6})
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s)
    assert [n for n, _ in r.longest_gaps[:2]] == ["bench.sleep",
                                                  "engine.step_begin"]
    # the while holds other operations: left out of the breakdown
    assert dict(r.top_ops) == pytest.approx({
        "jit_step/quant_matmul": 1e-6, "jit_step/fusion": 1.5e-6,
        "jit_step/vmap_quant_matmul_": 0.5e-6,
        "jit_row_prefill_from/paged_attention": 1e-6, "?/copy": 0.5e-6,
        "jit_step/quant_matmulx": 0.5e-6})


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded here: the harness's spans are found on the host
    plane, on the clock of the window; a CPU has no device plane, so
    nothing is reported as device time."""
    import jax
    import jax.numpy as jnp
    from iolmbench.clock import span
    from iolmbench.main import _profile_options

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=_profile_options())
    t0 = time.perf_counter()
    with span(TR.WINDOW_SPAN):
        for _ in range(3):
            with span("bench.tick"):
                f(x).block_until_ready()
        with span("bench.sleep"):
            time.sleep(0.02)
    host_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    tr = TR.from_xspace(TR.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in tr.spans]
    assert names.count("bench.tick") == 3 and "bench.sleep" in names
    w0, w1 = tr.window
    assert 0.02 <= (w1 - w0) / 1e9 <= host_s
    assert all(w0 <= s and e <= w1 for n, s, e in tr.spans
               if n != TR.WINDOW_SPAN)
    r = TR.reduce(tr, ("quant_matmul",))
    assert r.devices == 0 and r.busy_s == 0 and not r.kernel_s
