"""What the harness finds without an edit of its own files: the kernels
the trace reduction attributes (the files of ``bench/kernels/``), the
kernel that takes expert stacks (``TAKES = "experts"``), every counter
of the engine's ``EngineStats``, and the experts an expert call reached
from the program's count (``roofline.expert_share``)."""
import dataclasses
import os
import shutil
import types

import pytest

from iolmbench import flops, roofline, spec
from iolmbench import main as M
from iolmbench import trace as TR
from iolmbench.rehearsal import shrink, tiny_cell
from test_bench_rehearsal_moe import _cell as moe_cell
from test_bench_trace import _hand_trace

OLD_STATS = ("rows", "tokens_out", "prefills", "decode_steps", "truncated",
             "busy_slot_steps", "total_slot_steps", "prefix_hits",
             "prefill_tokens")


def _kernels_dir(tmp_path, **extra):
    """A copy of ``bench/kernels`` with extra counts files, name ->
    source."""
    d = tmp_path / "kernels"
    shutil.copytree(os.path.join(spec.BENCH_DIR, "kernels"), d,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, src in extra.items():
        (d / f"{name}.py").write_text(src)
    return str(tmp_path)


EXPERT_COUNTS = ('TAKES = "experts"\n'
                 "from iolmbench.flops import expert_bytes as bytes_moved\n"
                 "from iolmbench.flops import expert_ops as ops\n")


def test_kernels_are_the_files_of_bench_kernels():
    assert spec.kernels() == ["block_sparse_matmul", "paged_attention",
                              "quant_matmul"]
    assert spec.expert_kernel() is None


def test_reduce_with_the_found_kernels_is_hand_checked():
    found = TR.reduce(_hand_trace(), spec.kernels())
    assert found == TR.reduce(_hand_trace(),
                              ("quant_matmul", "paged_attention"))
    assert found.kernel_s == pytest.approx({"quant_matmul": 1.5e-6,
                                            "paged_attention": 1e-6})
    assert found.kernel_calls == {"quant_matmul": 2, "paged_attention": 1}


def test_a_new_counts_file_gets_its_kernel_attributed(tmp_path):
    d = _kernels_dir(tmp_path, grouped_expert_matmul=EXPERT_COUNTS)
    tr = _hand_trace()
    dev = next(iter(tr.ops))
    tr.ops[dev] += [("grouped_expert_matmul.4", 10600, 10800),
                    ("vmap_grouped_expert_matmul_.2", 10800, 10900)]
    today = TR.reduce(tr, spec.kernels())
    assert "grouped_expert_matmul" not in today.kernel_s
    r = TR.reduce(tr, spec.kernels(d))
    assert r.kernel_s["grouped_expert_matmul"] == pytest.approx(0.3e-6)
    assert r.kernel_calls["grouped_expert_matmul"] == 2
    assert r.kernel_s["quant_matmul"] == pytest.approx(1.5e-6)


@pytest.fixture(scope="module")
def moe_instance():
    """The int8 instance the MoE rehearsal serves: one ``LLLG`` period,
    8 experts of 2 a token."""
    from repro.configs.base import ModelConfig
    from repro.core.pipeline import InstanceOptimizer, Recipe

    from iolmbench import weights
    cell = moe_cell()
    kw = dict(cell.model_kwargs(), **shrink(cell))
    params, _, _ = InstanceOptimizer(weights.make(kw, 5, 1),
                                     ModelConfig(**kw)).apply(
        Recipe(name="w8", wbits=8, quant_method="absmax"))
    return flops.instance_matmuls(params, top_k=kw["top_k"])


def test_expert_stacks_expect_the_kernel_that_takes_them(moe_instance,
                                                         tmp_path):
    assert any("experts" in w for w in moe_instance)
    today = {"paged_attention", "quant_matmul"}
    assert M.expected_kernels(moe_instance) == today
    d = _kernels_dir(tmp_path, grouped_expert_matmul=EXPERT_COUNTS)
    assert spec.expert_kernel(d) == "grouped_expert_matmul"
    want = M.expected_kernels(moe_instance, d)
    assert want == today | {"grouped_expert_matmul"}
    # a decode step whose experts fell back to the plain kernel
    assert want - today == {"grouped_expert_matmul"}
    # a dense instance expects what it did
    dense = [w for w in moe_instance if "experts" not in w]
    assert M.expected_kernels(dense, d) == today


def test_two_kernels_taking_the_experts_is_an_error(tmp_path):
    d = _kernels_dir(tmp_path, a_experts=EXPERT_COUNTS,
                     b_experts=EXPERT_COUNTS)
    with pytest.raises(ValueError, match="more than one"):
        spec.expert_kernel(d)
    with pytest.raises(ValueError):
        M.expected_kernels([], d)


def test_stats_take_every_counter_of_the_engine():
    import jax
    from repro.configs.base import ModelConfig
    from repro.models import api
    from repro.serving.engine import Engine, EngineStats

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=260,
                      max_seq=256)
    eng = Engine(api.init_params(jax.random.PRNGKey(0), cfg), cfg, slots=2,
                 max_len=64, buckets=(32,))
    for t in ("alpha", "beta", "gamma"):
        eng.submit(t, max_new=3)
    eng.drain()
    got = M._stats(eng)
    counters = {f.name for f in dataclasses.fields(EngineStats)
                if f.type in (int, "int")} - set(M.HIGH_WATER)
    assert set(got) == counters
    assert set(got) >= set(OLD_STATS) | {"cache_hits", "host_syncs",
                                         "prefill_tokens_saved",
                                         "confidence_rows"}
    assert not set(got) & {"peak_inflight", "kv_blocks_in_use",
                           "kv_blocks_shared"}
    assert {k: got[k] for k in OLD_STATS} == {
        k: getattr(eng.stats, k) for k in OLD_STATS}
    assert got["decode_steps"] > 0
    assert got["host_syncs"] == 2 * (got["prefills"] + got["decode_steps"])

    # a counter the program adds arrives with no edit here
    @dataclasses.dataclass
    class Grown(EngineStats):
        moe_decode_experts: int = 0

    eng.stats = Grown(**dataclasses.asdict(eng.stats), moe_decode_experts=7)
    assert M._stats(eng) == dict(got, moe_decode_experts=7)


# an expert stack of 4 layers, 8 experts of 2 a token; a plain one
WI = {"path": "blocks.0.moe.wi", "kernel": "quant_matmul", "K": 64,
      "N": 32, "per_forward": 32, "group": 64, "kept": 2048, "experts": 8,
      "top_k": 2}
INSTANCE = [dict(WI),
            dict(WI, path="blocks.0.moe.wo", K=32, N=64, group=32),
            {"path": "blocks.0.attn.wq", "kernel": "quant_matmul", "K": 64,
             "N": 64, "per_forward": 4, "group": 64, "kept": 4096}]
COUNTS = types.SimpleNamespace(ops=flops.expert_ops,
                               bytes_moved=flops.expert_bytes)
# bandwidth-bound at these sizes
PEAK = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e9}


def _ctx(admits, decodes, stats, kernel_s=1.0):
    return types.SimpleNamespace(
        instance=INSTANCE, admits=admits, decodes=decodes, slots=4,
        engine_stats=stats, peak=PEAK,
        trace=types.SimpleNamespace(kernel_s={"experts_k": kernel_s}))


@pytest.fixture
def counts(monkeypatch):
    monkeypatch.setattr(spec, "kernel_counts", lambda k: COUNTS)


def _least(calls):
    return flops.least_seconds(COUNTS, calls, PEAK)


ADMITS = [(2, 32, [(10, 3), (12, 3)])] * 3          # three of M = 32
DECODES = [[5, 6, 7]] * 10                           # ten of M = 4


def test_expert_share_from_both_counters(counts):
    # 4 MoE layers: prefill reaches 6 experts a call, decode 3.5
    stats = {"moe_prefill_experts": 6 * 3 * 4,
             "moe_decode_experts": 35 * 4}
    ctx = _ctx(ADMITS, DECODES, stats)
    assert roofline.expert_reach(ctx) == {
        ("blocks.0.moe.wi", 32): 6, ("blocks.0.moe.wo", 32): 6,
        ("blocks.0.moe.wi", 4): 3.5, ("blocks.0.moe.wo", 4): 3.5}
    # by hand, wi at M = 4: rows 2*4*64 + 2*4*32 in and out, 3.5 experts
    # of 64*32 codes and 32 f32 scales
    wi4 = {"M": 4, "K": 64, "N": 32, "kept": 2048, "top_k": 2,
           "reached": 3.5, "weight_bytes": 2048 + 4 * 32}
    assert flops.expert_bytes(**wi4) == 768 + 3.5 * 2176
    calls = flops.expert_calls(INSTANCE, ADMITS, DECODES, 4,
                               reached=roofline.expert_reach(ctx))
    assert (wi4, 40) in calls
    assert roofline.expert_share(ctx, "experts_k") == pytest.approx(
        100.0 * _least(calls))


def test_expert_share_without_counters_is_the_top_k_fallback(counts):
    ctx = _ctx(ADMITS, DECODES, {"host_syncs": 3})
    assert set(roofline.expert_reach(ctx).values()) == {2}
    assert roofline.expert_share(ctx, "experts_k") == pytest.approx(
        100.0 * _least(flops.expert_calls(INSTANCE, ADMITS, DECODES, 4)))
    # one counter alone: the other phase keeps top_k
    ctx = _ctx(ADMITS, DECODES, {"moe_decode_experts": 5 * 10 * 4})
    assert roofline.expert_reach(ctx)[("blocks.0.moe.wi", 32)] == 2
    assert roofline.expert_reach(ctx)[("blocks.0.moe.wi", 4)] == 5
    # nothing to read: no trace of the kernel, no expert stack
    assert roofline.expert_share(_ctx(ADMITS, DECODES, {}, kernel_s=0.0),
                                 "experts_k") is None
    ctx.instance = INSTANCE[2:]
    assert roofline.expert_share(ctx, "experts_k") is None


def test_a_call_whose_rows_reach_every_expert(counts):
    ctx = _ctx([], DECODES, {"moe_decode_experts": 8 * 10 * 4})
    (s, n), (_, n_wo) = flops.expert_calls(
        INSTANCE, [], DECODES, 4, reached=roofline.expert_reach(ctx))
    assert (s["reached"], n, n_wo) == (8, 40, 40)
    # every expert's codes and scales once, the rows in and out once:
    # wi 64x32 with 32 scales, wo 32x64 with 64
    assert flops.expert_bytes(**s) == 8 * (64 * 32 + 4 * 32) + 768
    assert roofline.expert_share(ctx, "experts_k") == pytest.approx(
        100.0 * 40 * (8 * 2176 + 768 + 8 * 2304 + 768) / 1e9)


def test_widths_that_differ_in_a_phase_keep_top_k(counts):
    admits = [(2, 32, [(10, 3), (12, 3)]), (1, 16, [(10, 3)])]
    ctx = _ctx(admits, [], {"moe_prefill_experts": 7 * 2 * 4})
    assert set(roofline.expert_reach(ctx).values()) == {2}


def test_mean_reach_never_reads_above_each_calls_own(counts):
    """Two decode steps, one reaching 2 experts in each layer and the
    other 8: at the mean of 5 the least time is no more than theirs."""
    peak = {"bf16_flops_per_s": 3e9, "hbm_bytes_per_s": 1e9}
    shape = dict(M=4, K=64, N=32, kept=2048, top_k=2, weight_bytes=2176)
    # at 2 experts the call is bound by its operations, at 8 by its bytes
    assert (flops.expert_ops(reached=2, **shape) / 3e9
            > flops.expert_bytes(reached=2, **shape) / 1e9)
    assert (flops.expert_ops(reached=8, **shape) / 3e9
            < flops.expert_bytes(reached=8, **shape) / 1e9)
    own = flops.least_seconds(COUNTS, [(dict(shape, reached=2), 4),
                                       (dict(shape, reached=8), 4)], peak)
    ctx = _ctx([], [[5, 6]] * 2, {"moe_decode_experts": (2 + 8) * 4},
               kernel_s=own)
    ctx.instance, ctx.peak = INSTANCE[:1], peak
    mean = flops.least_seconds(COUNTS, flops.expert_calls(
        ctx.instance, [], ctx.decodes, 4,
        reached=roofline.expert_reach(ctx)), peak)
    assert mean < own
    assert roofline.expert_share(ctx, "experts_k") == pytest.approx(
        100.0 * mean / own)
    assert roofline.expert_share(ctx, "experts_k") < 100.0


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(M, "enable_compile_cache", lambda: "off")


def test_host_syncs_per_step_on_the_scan_rehearsal(no_cache, monkeypatch):
    seen = {}
    reader = spec.metric_reader

    def keep(name, *a, **kw):
        read = reader(name, *a, **kw)
        if name != "host_syncs_per_step.scan":
            return read

        def wrapped(ctx):
            seen["ctx"] = ctx
            return read(ctx)
        return wrapped

    monkeypatch.setattr(spec, "metric_reader", keep)
    cell, sizes = tiny_cell("nemo-iolm-scan")
    out = M.run_cell(cell, 2 ** 31 + 23, 2.0, True, require_chip=False,
                     sizes=sizes)
    ctx = seen["ctx"]
    st = ctx.engine_stats
    admissions, steps = len(ctx.admits), len(ctx.decodes)
    assert steps == st["decode_steps"] > 0
    assert admissions == st["prefills"] > 0
    m = out["metrics"]["host_syncs_per_step.scan"]
    assert m["unit"] == "syncs/step"
    assert m["value"] == 2 + 2 * admissions / steps
