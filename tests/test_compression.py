"""Compression correctness: quantization, sparsification, pipeline e2e."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch
from repro.configs import registry
from repro.core import quantize as Q
from repro.core import sparsify as S
from repro.core.calibrate import calibrate
from repro.core.compressed import (BlockSparseTensor, QTensor, param_bytes,
                                   quantize_embed)
from repro.core.pipeline import InstanceOptimizer, Recipe
from repro.models import api

RNG = np.random.default_rng(0)


def _w(K=128, N=64):
    return RNG.normal(size=(K, N)).astype(np.float32)


def _H(K=128, rows=512):
    X = RNG.normal(size=(rows, K)).astype(np.float64)
    return X.T @ X


class TestQuantize:
    def test_absmax_error_bound(self):
        w = _w()
        qt = Q.absmax_quantize(w, bits=8, group=32)
        wd = np.asarray(qt.dequantize(), np.float32)
        # max error per element <= scale/2 (+ bf16 rounding slack)
        smax = np.asarray(qt.scale).repeat(32, 0)
        assert np.all(np.abs(w - wd) <= smax * 0.5 + 0.02 * np.abs(w) + 1e-3)

    def test_gptq_beats_absmax_in_hessian_norm(self):
        w, H = _w(), _H()
        g = Q.gptq_quantize(w, H, bits=4, group=32)
        a = Q.absmax_quantize(w, bits=4, group=32)
        eg = Q.quant_error(w, g, H)
        ea = Q.quant_error(w, a, H)
        assert eg < ea, (eg, ea)

    def test_int4_pack_roundtrip(self):
        codes = RNG.integers(-8, 8, size=(64, 32)).astype(np.int8)
        qt = QTensor(Q.pack_int4(jnp.asarray(codes)),
                     jnp.ones((2, 32), jnp.float32), 4, 32, (64, 32))
        got = np.asarray(qt.unpack())
        np.testing.assert_array_equal(got, codes)

    def test_smoothquant_flattens_activation_outliers(self):
        w = _w()
        amax = np.ones(128, np.float32)
        amax[7] = 100.0                      # an outlier channel
        s = Q.smooth_scales(amax, w, alpha=0.5)
        assert s[7] > np.median(s) * 3       # outlier migrated into weight
        qt = Q.absmax_quantize(w, bits=8, group=128, amax_x=amax,
                               smooth_alpha=0.5)
        # dequantize folds in_scale back: reconstruction still close to w
        wd = np.asarray(qt.dequantize(), np.float32)
        assert np.abs(w - wd).mean() < 0.02

    def test_qembed_roundtrip_and_logits(self):
        table = RNG.normal(size=(50, 16)).astype(np.float32)
        qe = quantize_embed(jnp.asarray(table))
        got = np.asarray(qe.lookup(jnp.arange(50)), np.float32)
        np.testing.assert_allclose(got, table, atol=2e-2, rtol=2e-2)
        x = RNG.normal(size=(3, 16)).astype(np.float32)
        lg = np.asarray(qe.logits(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)))
        np.testing.assert_allclose(lg, x @ table.T, atol=0.5, rtol=0.1)


class TestSparsify:
    def test_wanda_nm_structure(self):
        w = _w()
        mask = S.wanda_mask(w, np.ones(128, np.float32), n=2, m=4)
        g = mask.reshape(32, 4, 64).sum(1)
        assert (g == 2).all()

    def test_wanda_unstructured_sparsity(self):
        w = _w()
        mask = S.wanda_mask(w, np.ones(128, np.float32), sparsity=0.5)
        assert abs(mask.mean() - 0.5) < 0.02

    def test_sparsegpt_lower_error_than_wanda(self):
        """Error propagation must beat naive masking in ||E^T H E||."""
        w, H = _w(), _H()
        act = np.sqrt(np.diag(H)).astype(np.float32)
        wsg, msg = S.sparsegpt_prune(w, H, sparsity=0.5)
        mwd = S.wanda_mask(w, act, sparsity=0.5)
        wwd = np.where(mwd, w, 0.0)
        err = lambda wp: np.sqrt(np.einsum("io,ij,jo->", w - wp, H, w - wp))
        assert err(wsg) < err(wwd), (err(wsg), err(wwd))

    def test_block_mask_uniform_columns(self):
        w = _w(128, 128)
        m = S.block_sparse_mask(w, bs=32, density=0.5)
        assert (m.sum(0) == 2).all()
        bst = S.apply_block_mask(w, m, 32)
        assert bst.idx.shape == (4, 2)
        assert 0.49 < bst.density() < 0.51


class TestPipeline:
    @pytest.mark.parametrize("arch,recipe", [
        ("mistral-nemo-12b", Recipe(name="w8", wbits=8)),
        ("qwen2-moe-a2.7b", Recipe(name="m", wbits=8, experts_keep=4)),
        ("rwkv6-3b", Recipe(name="r", wbits=8, ffn_keep_frac=0.75)),
        ("zamba2-7b", Recipe(name="z", wbits=8, kv_keep_frac=0.5)),
        ("whisper-base", Recipe(name="w", wbits=8, drop_units=1)),
        ("gemma3-1b", Recipe(name="g", wbits=4, group=32,
                             quant_embed=True)),
    ])
    def test_e2e_compression(self, arch, recipe, reduced_models):
        cfg, params = reduced_models[arch]
        batch = make_batch(cfg)
        opt = InstanceOptimizer(params, cfg)
        opt.run_calibration(batch)
        p2, c2, rep = opt.apply(recipe)
        assert rep.bytes_after < rep.bytes_before
        logits, _ = api.forward(p2, c2, batch)
        assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))
        # decode path too
        cache = api.init_cache(c2, 2, 64)
        lg, _ = api.decode_step(p2, c2, cache, batch["tokens"][:, :1],
                                jnp.zeros((2,), jnp.int32), max_len=64)
        assert not bool(jnp.any(jnp.isnan(lg.astype(jnp.float32))))

    def test_stacked_block_sparse_keeps_gather_indices(self,
                                                       reduced_models):
        """Layer-stacked block-sparse weights carry their per-layer
        gather indices: without them the pallas backend cannot run the
        tile-skipping kernel."""
        cfg, params = reduced_models["mistral-nemo-12b"]
        p2, _, _ = InstanceOptimizer(params, cfg).apply(
            Recipe(name="bs", block_bs=16, block_density=0.5))
        leaves = [x for x in jax.tree_util.tree_leaves(
            p2, is_leaf=lambda x: isinstance(x, BlockSparseTensor))
            if isinstance(x, BlockSparseTensor)]
        assert leaves and all(x.idx is not None for x in leaves)
        stacked = [x for x in leaves if x.w.ndim == 3]
        assert stacked and all(x.idx.shape[0] == x.w.shape[0]
                               for x in stacked)

    def test_w8_preserves_argmax(self, reduced_models):
        cfg, params = reduced_models["mistral-nemo-12b"]
        batch = make_batch(cfg, B=4)
        opt = InstanceOptimizer(params, cfg)
        opt.run_calibration(batch)
        p2, c2, _ = opt.apply(Recipe(name="w8", wbits=8))
        l1, _ = api.forward(params, cfg, batch)
        l2, _ = api.forward(p2, c2, batch)
        agree = float(jnp.mean(jnp.argmax(l1[:, -1], -1)
                               == jnp.argmax(l2[:, -1], -1)))
        assert agree == 1.0

    def test_compression_ratio_reported(self, reduced_models):
        cfg, params = reduced_models["granite-20b"]
        opt = InstanceOptimizer(params, cfg)
        opt.run_calibration(make_batch(cfg))
        _, _, rep = opt.apply(Recipe(name="w4", wbits=4, group=32,
                                     quant_method="absmax"))
        assert rep.compression > 2.0   # int4 + f32 scales vs bf16

    def test_calibration_hessian_is_gram_matrix(self, reduced_models):
        cfg, params = reduced_models["mistral-nemo-12b"]
        batch = make_batch(cfg)
        stats = calibrate(params, cfg, batch, hessian=True)
        key = sorted(k for k in stats.weights if k.endswith("attn.wq"))[0]
        st = stats.weights[key]
        assert st.H is not None and st.H.shape[0] == st.H.shape[1]
        evs = np.linalg.eigvalsh(st.H)
        assert evs.min() > -1e-5          # PSD
        assert st.count > 0 and st.sqnorm.min() >= 0
