"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as Q
from repro.core import sparsify as S
from repro import tracing as T
from repro.kernels import ops, ref
from repro.kernels import quant_matmul as QMM

RNG = np.random.default_rng(7)


@pytest.fixture
def recorder():
    T.reset()
    T.enable()
    yield
    T.disable()
    T.reset()


def _rel(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return np.abs(got - want).max() / (np.abs(want).max() + 1e-9)


def _qmm_case(M, K, N, g, lead=()):
    w = RNG.normal(size=(K, N)).astype(np.float32)
    qt = Q.absmax_quantize(w, bits=8, group=g)
    x = jnp.asarray(RNG.normal(size=(*lead, M, K)), jnp.float32).astype(
        jnp.bfloat16)
    return qt, x


class TestQuantMatmul:
    @pytest.mark.parametrize("M,K,N,g", [
        (8, 128, 128, 128), (64, 256, 128, 64), (1, 512, 256, 128),
        (130, 256, 384, 32), (16, 1024, 128, 128),
        (17, 256, 256, 64),              # rows not a multiple of 8
        (600, 256, 256, 64),             # two row tiles, 8 rows of padding
        (16, 256, 200, 64),              # N not a multiple of 128
        (8, 512, 33000, 64),             # ... nor fitting VMEM whole:
                                         # the last column block is partial
        (16, 384, 256, 128),             # K not a multiple of the K tile
        (16, 1024, 2560, 128),           # bn 1280: N is 10 lanes wide
    ])
    def test_shapes(self, M, K, N, g):
        qt, x = _qmm_case(M, K, N, g)
        got = ops.quant_matmul(x, qt.q, qt.scale, group=qt.group,
                               interpret=True)
        want = ref.quant_matmul(x, qt.q, qt.scale, group=qt.group)
        assert got.shape == (M, N)
        assert _rel(got, want) < 2e-2

    @pytest.mark.parametrize("xdtype", [jnp.bfloat16, jnp.float32])
    def test_dtypes(self, xdtype):
        w = RNG.normal(size=(256, 128)).astype(np.float32)
        qt = Q.absmax_quantize(w, bits=8, group=128)
        x = jnp.asarray(RNG.normal(size=(32, 256))).astype(xdtype)
        got = ops.quant_matmul(x, qt.q, qt.scale, group=qt.group,
                               interpret=True)
        want = ref.quant_matmul(x, qt.q, qt.scale, group=qt.group)
        assert _rel(got, want) < 2e-2
        assert got.dtype == xdtype

    def test_batched_input_reshape(self):
        w = RNG.normal(size=(128, 64)).astype(np.float32)
        qt = Q.absmax_quantize(w, bits=8, group=64)
        x = jnp.asarray(RNG.normal(size=(2, 5, 128)), jnp.bfloat16)
        got = ops.quant_matmul(x, qt.q, qt.scale, group=qt.group,
                               interpret=True)
        assert got.shape == (2, 5, 64)

    def test_in_scale_smoothquant(self):
        w = RNG.normal(size=(256, 128)).astype(np.float32)
        amax = np.abs(RNG.normal(size=256)).astype(np.float32) + 0.5
        qt = Q.absmax_quantize(w, bits=8, group=128, amax_x=amax,
                               smooth_alpha=0.5)
        assert qt.in_scale is not None
        x = jnp.asarray(RNG.normal(size=(16, 256)), jnp.bfloat16)
        got = ops.quant_matmul(x, qt.q, qt.scale, group=qt.group,
                               in_scale=qt.in_scale, interpret=True)
        want = ref.quant_matmul(x, qt.q, qt.scale, group=qt.group,
                                in_scale=qt.in_scale)
        assert _rel(got, want) < 2e-2

    def test_in_scale_under_vmap(self, recorder):
        """``in_scale`` rescales x before the kernel, also when x is
        vmapped and folded into rows."""
        w = RNG.normal(size=(256, 128)).astype(np.float32)
        amax = np.abs(RNG.normal(size=256)).astype(np.float32) + 0.5
        qt = Q.absmax_quantize(w, bits=8, group=128, amax_x=amax,
                               smooth_alpha=0.5)
        x = jnp.asarray(RNG.normal(size=(3, 16, 256)), jnp.bfloat16)
        got = jax.vmap(lambda xr: ops.quant_matmul(
            xr, qt.q, qt.scale, group=qt.group, in_scale=qt.in_scale,
            interpret=True))(x)
        want = ref.quant_matmul(x, qt.q, qt.scale, group=qt.group,
                                in_scale=qt.in_scale)
        assert _rel(got, want) < 2e-2
        (a,) = [r.attrs for r in T.spans() if r.name == "kernel.quant_matmul"]
        assert a["folded"] and a["rows"] == 48 and a["weight_passes"] == 1

    @pytest.mark.parametrize("batch,M,under_scan", [
        (3, 5, False), (4, 16, True), (2, 300, False),
    ])
    def test_vmapped_x_folds_into_rows(self, recorder, batch, M,
                                       under_scan):
        """A vmapped x over one shared weight runs one kernel call over
        all batch x M rows: the numbers of the call per row (to within
        one bf16 rounding: the CPU's dot blocks its rows by M), and the
        span says it folded."""
        qt, x = _qmm_case(M, 256, 384, 64, lead=(batch,))
        def call(xr):
            return ops.quant_matmul(xr, qt.q, qt.scale, group=qt.group,
                                    interpret=True)
        def body(xr):
            if not under_scan:
                return call(xr)
            # as in the model: weights stacked over layers, scanned
            stack = (jnp.stack([qt.q] * 2), jnp.stack([qt.scale] * 2))
            return jax.lax.scan(
                lambda h, w: (ops.quant_matmul(
                    h, w[0], w[1], group=qt.group,
                    interpret=True)[..., :256], None), xr, stack)[0]
        got = jax.jit(jax.vmap(body))(x)
        want = jnp.stack([jax.jit(body)(x[i]) for i in range(batch)])
        assert _rel(got, want) < 1e-2
        rows = batch * M
        folded = [r.attrs for r in T.spans()
                  if r.name == "kernel.quant_matmul" and r.attrs["folded"]]
        assert folded and all(a["rows"] == rows for a in folded)
        assert folded[0]["weight_passes"] == -(-rows // 512)
        # the jaxpr holds one kernel call per traced site, over all rows
        text = str(jax.make_jaxpr(jax.vmap(call))(x))
        assert text.count("pallas_call") == 1

    def test_batched_weight_keeps_the_batched_grid(self, recorder):
        """Weights that differ per batch element (experts) are not
        folded: one weight pass per element."""
        qts = [_qmm_case(8, 256, 128, 64)[0] for _ in range(3)]
        q = jnp.stack([t.q for t in qts])
        s = jnp.stack([t.scale for t in qts])
        x = jnp.asarray(RNG.normal(size=(3, 8, 256)), jnp.bfloat16)
        got = jax.vmap(lambda xe, qe, se: ops.quant_matmul(
            xe, qe, se, group=64, interpret=True))(x, q, s)
        for e in range(3):
            want = ref.quant_matmul(x[e], q[e], s[e], group=64)
            assert _rel(got[e], want) < 2e-2
        (a,) = [r.attrs for r in T.spans() if r.name == "kernel.quant_matmul"]
        assert not a["folded"] and a["weight_passes"] == 3 and a["rows"] == 8


    @pytest.mark.parametrize("smooth", [False, True])
    def test_stacked_weight_reads_its_layer_in_place(self, smooth):
        """``layer=`` reads one layer of a ``[L, K, N]`` stack: the same
        numbers as the call on the slice, without slicing."""
        w = RNG.normal(size=(3, 256, 384)).astype(np.float32)
        amax = np.abs(RNG.normal(size=256)).astype(np.float32) + 0.5
        qts = [Q.absmax_quantize(w[i], bits=8, group=64,
                                 amax_x=amax if smooth else None,
                                 smooth_alpha=0.5 if smooth else None)
               for i in range(3)]
        q = jnp.stack([t.q for t in qts])
        s = jnp.stack([t.scale for t in qts])
        ins = jnp.stack([t.in_scale for t in qts]) if smooth else None
        x = jnp.asarray(RNG.normal(size=(16, 256)), jnp.bfloat16)
        for i in range(3):
            got = jax.jit(lambda x, i: ops.quant_matmul(
                x, q, s, group=64, in_scale=ins, layer=i,
                interpret=True))(x, jnp.int32(i))
            want = ops.quant_matmul(x, qts[i].q, qts[i].scale, group=64,
                                    in_scale=qts[i].in_scale,
                                    interpret=True)
            assert np.array_equal(np.asarray(got), np.asarray(want))


class TestQuantMatmulTiles:
    """The tile rule of ``quant_matmul`` (pure Python: nothing runs)."""

    @pytest.mark.parametrize("M", [1, 8, 16, 17, 128, 513, 600, 2048,
                                   4100])
    def test_row_padding_wastes_under_an_eighth(self, M):
        bm = QMM.row_tile(M)
        padded = -(-M // bm) * bm
        assert bm <= QMM.ROW_CAP
        assert (padded - M) * 8 < M
        if M <= QMM.ROW_CAP:
            assert bm == M                  # one row tile, no padding

    @staticmethod
    def _registry_shapes():
        from repro.configs.registry import all_configs
        from repro.core.pipeline import _is_target
        from repro.models import api
        shapes = set()
        for cfg in all_configs().values():
            tree = jax.eval_shape(
                lambda c=cfg: api.init_params(jax.random.PRNGKey(0), c))
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
                name = jax.tree_util.keystr(path, simple=True, separator=".")
                if _is_target(name, leaf):      # the weights quantized
                    shapes.add(tuple(leaf.shape[-2:]))
        # mistral-nemo-12b's local shards on a 4-way tensor-parallel mesh
        shapes |= {(5120, 1024), (5120, 256), (5120, 3584), (5120, 32768),
                   (1024, 5120), (3584, 5120)}
        return sorted(shapes)

    def test_every_projection_fits_the_vmem_budget(self):
        n = 0
        for K, N in self._registry_shapes():
            for g in (32, 64, 128):
                if K % g:
                    continue
                for M in (1, 16, 17, 128, 600, 2048, 4096):
                    for itemsize in (2, 4):
                        bm, bn, bk = QMM.tiles(M, K, N, g, itemsize)
                        assert QMM.vmem_bytes(bm, bn, bk, g, itemsize) \
                            <= QMM.VMEM_BUDGET, (M, K, N, g, bm, bn, bk)
                        assert K % bk == 0 and bk % g == 0
                        assert bn == N or (bn % 128 == 0 and bn <= QMM.BN_CAP
                                           and (N % bn == 0 or N % 128))
                        n += 1
        assert n > 500

    @pytest.mark.parametrize("M,K,N,want", [
        (16, 5120, 14336, (16, 2048, 1024)),      # nemo decode: one row tile
        (16, 14336, 5120, (16, 1280, 1024)),
        (16, 5120, 131072, (16, 2048, 1024)),
        (2048, 5120, 14336, (512, 2048, 1024)),   # nemo prefill, folded
        (16, 1152, 6912, (16, 1152, 1152)),       # gemma3-1b: bk is all K
    ])
    def test_mistral_nemo_tiles(self, M, K, N, want):
        assert QMM.tiles(M, K, N, 128, 2) == want


class TestBlockSparse:
    @pytest.mark.parametrize("K,N,bs,dens", [
        (256, 256, 64, 0.5), (512, 128, 128, 0.75), (128, 256, 32, 0.25),
    ])
    def test_skips_match_oracle(self, K, N, bs, dens):
        w = RNG.normal(size=(K, N)).astype(np.float32)
        m = S.block_sparse_mask(w, bs=bs, density=dens)
        bst = S.apply_block_mask(w, m, bs)
        x = jnp.asarray(RNG.normal(size=(16, K)), jnp.bfloat16)
        got = ops.block_sparse_matmul(x, bst.w, bst.idx, bs=bs,
                                      interpret=True)
        want = ref.block_sparse_matmul(x, bst.w, bst.mask, bs=bs)
        assert _rel(got, want) < 2e-2


class TestFlashAttention:
    @pytest.mark.parametrize("B,S_,T,H,Kh,D,win,cap", [
        (2, 64, 64, 4, 2, 64, 0, 0.0),      # GQA causal
        (1, 128, 128, 8, 1, 32, 32, 0.0),   # MQA sliding window
        (2, 64, 64, 4, 4, 64, 0, 30.0),     # MHA with softcap (gemma2)
        (1, 64, 192, 2, 2, 32, 0, 0.0),     # cross len (q_offset decode)
    ])
    def test_variants(self, B, S_, T, H, Kh, D, win, cap):
        q = jnp.asarray(RNG.normal(size=(B, S_, H, D)), jnp.bfloat16)
        k = jnp.asarray(RNG.normal(size=(B, T, Kh, D)), jnp.bfloat16)
        v = jnp.asarray(RNG.normal(size=(B, T, Kh, D)), jnp.bfloat16)
        off = T - S_
        got = ops.flash_attention(q, k, v, causal=True, window=win,
                                  softcap=cap, q_offset=off, interpret=True)
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, S_, D)
        kf = k.transpose(0, 2, 1, 3).reshape(B * Kh, T, D)
        vf = v.transpose(0, 2, 1, 3).reshape(B * Kh, T, D)
        want = ref.attention(qf, kf, vf, causal=True, window=win,
                             softcap=cap, q_offset=off)
        want = want.reshape(B, H, S_, D).transpose(0, 2, 1, 3)
        assert _rel(got, want) < 2e-2

    def test_matches_model_attention(self):
        """Kernel agrees with the model's own full_attention path."""
        from repro.models import layers as L
        B, S_, H, Kh, D = 2, 64, 4, 2, 32
        q = jnp.asarray(RNG.normal(size=(B, S_, H, D)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(B, S_, Kh, D)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(B, S_, Kh, D)), jnp.float32)
        got = ops.flash_attention(q.astype(jnp.bfloat16),
                                  k.astype(jnp.bfloat16),
                                  v.astype(jnp.bfloat16), causal=True,
                                  interpret=True)
        want = L.full_attention(q, k, v, causal=True)
        assert _rel(got, want) < 3e-2


class TestPagedAttention:
    @pytest.mark.parametrize("win,cap", [
        (0, 0.0), (24, 0.0), (0, 30.0), (24, 30.0),
    ])
    def test_matches_masked_decode(self, win, cap):
        """The paged kernel, gathering K/V through a scrambled block
        table, matches the model's contiguous decode attention."""
        from repro.models.transformer import _masked_decode
        S, T, H, Kh, D, bs = 3, 64, 4, 2, 32, 16
        nblk = T // bs
        q = jnp.asarray(RNG.normal(size=(S, 1, H, D)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(S, T, Kh, D)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(S, T, Kh, D)), jnp.float32)
        lengths = np.array([17, 40, 64], np.int32)
        # scatter each row's KV into a scrambled global pool (+1 spare
        # block that no table references)
        tables = np.asarray(RNG.permutation(S * nblk), np.int32) \
            .reshape(S, nblk)
        kp = np.zeros((S * nblk + 1, bs, Kh, D), np.float32)
        vp = np.zeros_like(kp)
        for s in range(S):
            for j in range(nblk):
                kp[tables[s, j]] = np.asarray(k[s, j * bs:(j + 1) * bs])
                vp[tables[s, j]] = np.asarray(v[s, j * bs:(j + 1) * bs])
        got = ops.paged_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                  tables, lengths, softcap=cap,
                                  window=win, interpret=True)
        kpos = np.arange(T)
        valid = kpos[None, :] < lengths[:, None]
        if win:
            valid &= kpos[None, :] >= (lengths[:, None] - win)
        want = _masked_decode(q, k, v, jnp.asarray(valid), cap)
        assert _rel(got, want) < 1e-5


class TestKernelDispatch:
    def test_pallas_backend_routes_qtensor(self, monkeypatch):
        from repro.core import compressed as C
        w = RNG.normal(size=(128, 64)).astype(np.float32)
        qt = Q.absmax_quantize(w, bits=8, group=64)
        x = jnp.asarray(RNG.normal(size=(4, 128)), jnp.bfloat16)
        base = C.matmul(x, qt)          # default backend: reference on CPU
        calls = {}
        import repro.kernels.ops as kops
        orig = kops.quant_matmul
        def spy(*a, **k):
            calls["hit"] = True
            return orig(*a, **k)
        monkeypatch.setattr(kops, "quant_matmul", spy)
        with C.kernel_backend("pallas"):
            out = C.matmul(x, qt)
        assert calls.get("hit")
        # the off-TPU fallback computes the reference formula verbatim:
        # dispatch through the pallas backend is BYTE-identical on CPU
        assert np.array_equal(np.asarray(out), np.asarray(base))

    def test_pallas_backend_routes_a_stacked_layer(self, monkeypatch):
        """A ``QLayer`` (one layer of a scan-stacked int8 weight) goes to
        the kernel with the whole stack and its index; off-TPU the
        result is byte-identical to the reference on the slice."""
        from repro.core import compressed as C
        qts = [Q.absmax_quantize(RNG.normal(size=(128, 64)).astype(
            np.float32), bits=8, group=64) for _ in range(2)]
        stack = jax.tree_util.tree_map(lambda *c: jnp.stack(c), *qts)
        x = jnp.asarray(RNG.normal(size=(4, 128)), jnp.bfloat16)
        seen = {}
        import repro.kernels.ops as kops
        orig = kops.quant_matmul
        def spy(*a, **k):
            seen.update(k)
            return orig(*a, **k)
        monkeypatch.setattr(kops, "quant_matmul", spy)
        base = C.matmul(x, qts[1])
        with C.kernel_backend("pallas"):
            out = C.matmul(x, C.QLayer(stack, 1))
        assert seen["layer"] == 1
        assert np.array_equal(np.asarray(out), np.asarray(base))
        assert np.array_equal(np.asarray(C.matmul(x, C.QLayer(stack, 1))),
                              np.asarray(base))

    def test_backend_scoping_restores_default(self):
        from repro.core import compressed as C
        from repro.kernels.backend import resolve_backend
        assert resolve_backend("auto") == "reference"   # CPU test platform
        with C.kernel_backend("pallas"):
            assert C.current_backend() == "pallas"
            with C.kernel_backend("reference"):
                assert C.current_backend() == "reference"
            assert C.current_backend() == "pallas"
        assert C.current_backend() == "reference"

    def test_backend_validation(self):
        from repro.kernels.backend import normalize_backend
        with pytest.raises(ValueError):
            normalize_backend("cuda")
        assert normalize_backend(None) == "auto"
        assert normalize_backend("PALLAS") == "pallas"

    def test_use_kernels_shim_warns_and_maps(self):
        from repro.core import compressed as C
        with pytest.warns(DeprecationWarning):
            C.use_kernels(True)
        try:
            with pytest.warns(DeprecationWarning):
                assert C.kernels_enabled()
        finally:
            with pytest.warns(DeprecationWarning):
                C.use_kernels(False)
        with pytest.warns(DeprecationWarning):
            assert not C.kernels_enabled()
