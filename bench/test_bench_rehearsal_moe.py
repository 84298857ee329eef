"""CPU rehearsal of a mixture-of-experts configuration with window and
full layers, built here and not in BENCHMARK.json: the harness makes
its weights, serves the scan through the program, counts its expert
stacks and window layers, and reports to the result line.  Its
configuration names no reference, so the run is not correct."""
import pytest

from iolmbench import flops, spec
from iolmbench import main as M
from iolmbench.rehearsal import shrink

NAME = "rehearsal-moe-window-scan"
# Mellum2-12B-A2.5B's widths: every layer sparse, window layers LLLG
CONFIG = {"name": "moe-window", "family": "moe", "n_layers": 28,
          "d_model": 2304, "n_heads": 32, "n_kv_heads": 4, "head_dim": 128,
          "d_ff": 7168, "vocab_size": 98304, "attn_pattern": "LLLG" * 7,
          "window_size": 1024, "rope_theta": 500000.0, "n_experts": 64,
          "top_k": 8, "moe_d_ff": 896, "mlp_gated": True,
          "tie_embeddings": False, "max_seq": 288,
          "param_dtype": "bfloat16"}


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(M, "enable_compile_cache", lambda: "off")


def _cell():
    scan = spec.find_cell("nemo-iolm-scan")
    return spec.Cell(
        name=NAME, chips=1, config_name=CONFIG["name"], config=dict(CONFIG),
        mix_name=scan.mix_name, mix=scan.mix, end_to_end=scan.end_to_end,
        per_layer=[dict(m, workloads=[NAME]) if "workloads" in m else m
                   for m in scan.per_layer])


def test_shrink_keeps_the_family_and_one_period():
    cell = _cell()
    sizes = shrink(cell)
    assert (sizes["n_layers"], sizes["attn_pattern"]) == (4, "LLLG")
    assert (sizes["n_experts"], sizes["top_k"]) == (8, 2)
    # the longest prompt: 1 + 103 instruction bytes + 127 + 1
    assert sizes["window_size"] == 232
    assert sizes["window_size"] < cell.mix["session"]["engine"]["max_len"]


def test_moe_window_rehearsal_reports_and_is_not_correct(no_cache,
                                                         monkeypatch):
    seen = {}
    instance, attention = flops.instance_matmuls, flops.attention_calls

    def keep_instance(*a, **kw):
        seen["instance"] = instance(*a, **kw)
        return seen["instance"]

    def keep_attention(*a, **kw):
        seen["attention"] = attention(*a, **kw)
        return seen["attention"]

    monkeypatch.setattr(flops, "instance_matmuls", keep_instance)
    monkeypatch.setattr(flops, "attention_calls", keep_attention)
    cell = _cell()
    out = M.run_cell(cell, 2 ** 31 + 99, 3.0, True, require_chip=False,
                     sizes=shrink(cell))
    assert list(out)[-1] == "checks"
    m = out["metrics"]
    assert {"calibrate_s", "search_s", "slot_occupancy.scan",
            "tick_ms.scan"} <= set(m)
    assert 0 < m["slot_occupancy.scan"]["value"] <= 100
    assert m["tick_ms.scan"]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    # the served int8 instance's expert stacks, 8 experts of 2 a token
    experts = [w for w in seen["instance"] if "experts" in w]
    assert len(experts) == 3 * 4
    assert {(w["experts"], w["top_k"]) for w in experts} == {(8, 2)}
    # decode steps past the window: the three window layers' calls apart
    assert any(n == 3 for _, n in seen["attention"])
    c = out["checks"]
    assert c["served_logit_gap"] == {"value": None, "limit": None}
    assert c["truncated_prompts"]["value"] == 0
    assert out["correct"] is False
