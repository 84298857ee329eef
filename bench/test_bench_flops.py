"""Work counts of a mixture-of-experts instance with window and full
layers, worked out by hand: routed experts at top_k of E, the router,
keys capped on window layers, expert stacks kept apart from the plain
matmuls; and the dense configuration's counts pinned to the parent's."""
import os

import pytest

from iolmbench import flops, spec

M = dict(family="moe", n_layers=4, attn_pattern="LLLG", window_size=4,
         d_model=8, n_heads=2, n_kv_heads=1, head_dim=4, n_experts=4,
         top_k=2)
WI = {"path": "blocks.0.moe.wi", "kernel": "quant_matmul", "K": 8, "N": 4,
      "per_forward": 16, "group": 8, "kept": 32, "experts": 4, "top_k": 2}
INSTANCE = [
    {"path": "blocks.0.attn.wq", "kernel": "quant_matmul", "K": 8, "N": 8,
     "per_forward": 4, "group": 8, "kept": 64},
    WI,
    {"path": "unembed", "kernel": "quant_matmul", "K": 8, "N": 16,
     "per_forward": 1, "group": 8, "kept": 128},
]


def test_needed_ops_moe_window_by_hand():
    # per token: wq 2*64*4 = 512; experts 2*32 * 4 layers * 2 of 4 = 512
    # (not 1024: the two unrouted experts do not count); router
    # 2*8*4 * 4 layers = 256; head 2*128 = 256.  Attention 4*2*4 = 32
    # per (query, key) and layer: one full layer, three window layers
    # of 4 keys
    admits = [(1, 8, [(2, 3)])]        # 2 suffix tokens after 3 prefix
    # prefill: queries at 3 and 4 see 4 and 5 keys, 4 and 4 in a window:
    # 2*1280 + 32*9 + 96*8 + 256 = 3872
    # decode of two slots at lengths 6 and 2:
    # 2*(1280+256) + 32*8 + 96*(4+2) = 3904
    assert flops.needed_ops(M, INSTANCE, admits, [[6, 2]]) == 3872 + 3904


def test_window_longer_than_every_row_counts_every_key():
    m = dict(M, window_size=16)
    full = dict(M, attn_pattern=None)
    admits = [(1, 8, [(2, 3)])]
    assert (flops.needed_ops(m, INSTANCE, admits, [[6, 2]])
            == flops.needed_ops(full, INSTANCE, admits, [[6, 2]])
            == 3872 + 3904 + 96 * 1 + 96 * 2)


def test_matmul_calls_leave_expert_stacks_out():
    calls = flops.matmul_calls(INSTANCE, [(1, 8, [(2, 3)])], [[6, 2]],
                               slots=4)
    assert sorted((s["K"], s["N"], s["M"], n)
                  for s, n in calls["quant_matmul"]) == [
        (8, 8, 4, 4), (8, 8, 8, 4), (8, 16, 4, 1), (8, 16, 8, 1)]


def test_expert_calls_fall_back_to_top_k_experts():
    admits = [(1, 8, [(2, 3)])]
    calls = flops.expert_calls(INSTANCE, admits, [[6, 2], [7, 3]], slots=4)
    # one call per layer (4) per admission or decode step
    assert [(s["M"], s["reached"], n) for s, n in calls] == [
        (4, 2, 8), (8, 2, 4)]
    s = calls[0][0]
    # codes 8*4 + one f32 scale per column: 48 bytes an expert
    assert s["weight_bytes"] == 48
    assert flops.expert_ops(**s) == 2 * 4 * 2 * 32
    # rows in 2*4*8, out 2*4*4, two experts' weights
    assert flops.expert_bytes(**s) == 64 + 32 + 2 * 48
    counted = flops.expert_calls(INSTANCE, admits, [[6, 2]], slots=4,
                                 reached={("blocks.0.moe.wi", 8): 3.5})
    assert [(s["M"], s["reached"]) for s, _ in counted] == [(4, 2),
                                                            (8, 3.5)]
    assert flops.expert_calls(INSTANCE[:1], admits, [[6, 2]], 4) == []


def test_attention_calls_cap_window_layers():
    shape = {"H": 2, "Kh": 1, "D": 4, "block": 4}
    assert flops.attention_calls(M, [[6, 2], [3, 2]], block=4) == [
        (dict(lengths=[6, 2], **shape), 1),
        (dict(lengths=[4, 2], **shape), 3),
        (dict(lengths=[3, 2], **shape), 4)]


def test_instance_marks_expert_stacks():
    from repro.configs.base import ModelConfig
    from repro.core.pipeline import InstanceOptimizer, Recipe

    from iolmbench import weights
    m = dict(M, name="t", n_layers=4, d_model=64, n_kv_heads=2, head_dim=16,
             d_ff=96, vocab_size=260, moe_d_ff=32, n_experts=8,
             n_shared_experts=1)
    params, _, _ = InstanceOptimizer(weights.make(m, 3, 4),
                                     ModelConfig(**m)).apply(
        Recipe(name="w8", wbits=8, quant_method="absmax"))
    inst = {w["path"]: w for w in flops.instance_matmuls(params, top_k=2)}
    for u in range(4):
        for leaf, (K, N) in (("wi", (64, 32)), ("wg", (64, 32)),
                             ("wo", (32, 64))):
            w = inst[f"blocks.{u}.moe.{leaf}"]
            assert (w["K"], w["N"], w["per_forward"], w["experts"],
                    w["top_k"]) == (K, N, 8, 8, 2)
        assert "experts" not in inst[f"blocks.{u}.attn.wq"]
        assert "experts" not in inst[f"blocks.{u}.shared_mlp.wi"]
    assert "experts" not in inst["unembed"]
    with pytest.raises(ValueError):
        flops.instance_matmuls(params)          # top_k unknown


def test_dense_counts_are_the_parents():
    """mistral-nemo-12b's int8 instance at its widths, five 16-row
    admissions and forty decode steps: the counts the harness gave
    before expert stacks and window layers were counted apart."""
    m = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                    "mistral-nemo-12b.json"))
    inst = [{"path": f"blocks.0.{p}", "kernel": "quant_matmul", "K": K,
             "N": N, "per_forward": 6, "group": 128, "kept": K * N}
            for p, K, N in (("attn.wq", 5120, 4096), ("attn.wk", 5120, 1024),
                            ("attn.wv", 5120, 1024), ("attn.wo", 4096, 5120),
                            ("mlp.wi", 5120, 14336), ("mlp.wg", 5120, 14336),
                            ("mlp.wo", 14336, 5120))]
    inst.append({"path": "unembed", "kernel": "quant_matmul", "K": 5120,
                 "N": 131072, "per_forward": 1, "group": 128,
                 "kept": 5120 * 131072})
    admits = [(16, 2048, [(65 + (i * 7 + j) % 64, 104) for j in range(16)])
              for i in range(5)]
    decodes = [[105 + (i * 13 + j) % 146 for j in range(16)]
               for i in range(40)]
    peak = spec.peaks("TPU v5 lite")
    assert flops.needed_ops(m, inst, admits, decodes) == 25811201949696.0
    assert flops.least_seconds(
        spec.kernel_counts("quant_matmul"),
        flops.matmul_calls(inst, admits, decodes, 16)["quant_matmul"],
        peak) == 0.3570571311359278
    assert flops.least_seconds(
        spec.kernel_counts("paged_attention"),
        flops.attention_calls(m, decodes, 32), peak) == 0.003692101391941392
    assert flops.expert_calls(inst, admits, decodes, 16) == []
