"""Operations and bytes of each kernel and of the served instance, on
shapes worked out by hand at both configurations' widths, and the
peaks table."""
import pytest

from iolmbench import flops, spec

QM = spec.kernel_counts("quant_matmul")
BS = spec.kernel_counts("block_sparse_matmul")
PA = spec.kernel_counts("paged_attention")


def test_quant_matmul_nemo_decode_mlp():
    # 32 slots x (5120 -> 14336), groups of 128 rows: 40 scale rows
    kw = dict(M=32, K=5120, N=14336, group=128)
    assert QM.ops(**kw) == 4_697_620_480
    # x 327,680 + codes 73,400,320 + scales 2,293,760 + out 917,504
    assert QM.bytes_moved(**kw) == 76_939_264


def test_quant_matmul_granite_prefill_mlp():
    # 32 rows x 128 tokens through 6144 -> 24576
    kw = dict(M=4096, K=6144, N=24576, group=128)
    assert QM.ops(**kw) == 1_236_950_581_248
    # x 50,331,648 + codes 150,994,944 + scales 4,718,592
    # + out 201,326,592
    assert QM.bytes_moved(**kw) == 407_371_776


def test_block_sparse_matmul_nemo_decode_mlp():
    # 40 x 112 tiles of 128 x 128, three quarters kept
    kw = dict(M=32, K=5120, N=14336, bs=128, kept=3360)
    assert BS.ops(**kw) == 3_523_215_360
    # x 327,680 + tiles 110,100,480 + out 917,504
    assert BS.bytes_moved(**kw) == 111_345_664


def test_paged_attention_nemo_gqa():
    # two live slots of 250 and 100 positions: 8 + 4 blocks of 32
    kw = dict(lengths=[250, 100], H=32, Kh=8, D=128, block=32)
    assert PA.ops(**kw) == 5_734_400
    # K and V of 384 positions x 8 heads x 128 x 2 bytes, + q and out
    assert PA.bytes_moved(**kw) == 1_572_864 + 32_768


def test_paged_attention_granite_mqa():
    kw = dict(lengths=[288], H=48, Kh=1, D=128, block=32)
    assert PA.ops(**kw) == 7_077_888
    assert PA.bytes_moved(**kw) == 147_456 + 24_576


def test_least_seconds_takes_the_binding_bound():
    peak = spec.peaks("TPU v5 lite")
    decode = [(dict(M=32, K=5120, N=14336, group=128), 3)]
    assert flops.least_seconds(QM, decode, peak) == pytest.approx(
        3 * 76_939_264 / 819e9)                  # memory bound
    prefill = [(dict(M=4096, K=6144, N=24576, group=128), 1)]
    assert flops.least_seconds(QM, prefill, peak) == pytest.approx(
        1_236_950_581_248 / 197e12)              # compute bound


def test_peaks_table():
    p = spec.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        spec.peaks("cpu")


INSTANCE = [
    {"path": "blocks.0.attn.wq", "kernel": "quant_matmul", "K": 8, "N": 8,
     "per_forward": 2, "group": 8, "kept": 64},
    {"path": "unembed", "kernel": "quant_matmul", "K": 8, "N": 16,
     "per_forward": 1, "group": 8, "kept": 128},
]


def test_matmul_calls_per_admission_and_decode_step():
    admits = [(2, 256, [(100, 104), (90, 104)])]
    calls = flops.matmul_calls(INSTANCE, admits, [[5], [6], [7]], slots=4)
    wq = [(s["M"], n) for s, n in calls["quant_matmul"] if s["N"] == 8]
    assert sorted(wq) == [(4, 6), (256, 2)]


def test_needed_ops_by_hand():
    m = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4)
    # per token 2*64*2 = 256 in the layers, 2*128 = 256 in the head;
    # attention 4*2*4*2 = 64 per (query, key)
    admits = [(1, 8, [(2, 3)])]        # 2 suffix tokens after 3 prefix
    # prefill: 2*256 + 64*(4 + 5) + 256 = 1344
    # decode of two slots at lengths 6 and 7: 2*(256+256) + 64*13 = 1856
    assert flops.needed_ops(m, INSTANCE, admits, [[6, 7]]) == 1344 + 1856
