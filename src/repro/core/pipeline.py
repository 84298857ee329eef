"""The IOLM-DB instance-optimization pipeline.

``InstanceOptimizer`` turns a (params, config) pair plus a calibration
sample into a compressed, query-specialized model:

    opt = InstanceOptimizer(params, cfg)
    opt.run_calibration(sample_batch)
    new_params, new_cfg, report = opt.apply(Recipe(...))

Stages (paper §3.2), in order:
  1. structural pruning  — layer drop, KV-group prune, FFN-channel prune,
     expert prune (MoE), all driven by calibration statistics
  2. sparsification      — SparseGPT / Wanda masks (N:M or unstructured),
     or TPU block sparsity (whole MXU tiles skipped by the Pallas kernel)
  3. quantization        — GPTQ / absmax int8 or int4, group-wise scales,
     optional SmoothQuant activation-outlier migration; masks from stage
     2 are respected inside the GPTQ sweep (the SparseGPT+GPTQ
     composition the paper cites)

The result's weight matrices are ``QTensor`` / ``BlockSparseTensor``
containers that every model family consumes transparently through
``repro.core.compressed.matmul``.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import calibrate as C
from repro.core import prune as P
from repro.core import quantize as Q
from repro.core import sparsify as S
from repro.core.compressed import (BlockSparseTensor, QTensor, param_bytes,
                                   quantize_embed)


@dataclass(frozen=True)
class Recipe:
    """One point in the compression design space."""
    name: str = "recipe"
    # --- structural ---
    drop_units: int = 0                # scan repeats (pattern units) to drop
    kv_keep_frac: float = 1.0          # fraction of KV groups kept
    ffn_keep_frac: float = 1.0         # fraction of FFN hidden channels kept
    experts_keep: int = 0              # MoE: experts kept per layer (0 = all)
    # --- sparsity ---
    sparsity: float = 0.0              # unstructured fraction REMOVED
    nm: Tuple[int, int] = (0, 0)       # (n, m) structured: keep n of m
    sparse_method: str = "sparsegpt"   # sparsegpt | wanda
    block_bs: int = 0                  # TPU block-sparse tile (0 = off)
    block_density: float = 1.0         # fraction of tiles kept
    # --- quantization ---
    wbits: int = 16                    # 16 = none, 8, 4
    group: int = 128
    quant_method: str = "gptq"         # gptq | absmax
    smooth_alpha: float = 0.0          # SmoothQuant (0 = off)
    quant_embed: bool = False

    @property
    def needs_hessian(self) -> bool:
        """Whether compression reads the calibration Hessian (GPTQ
        quantization or SparseGPT pruning); the others need only
        channel norms and maxima."""
        return ((self.wbits < 16 and self.quant_method == "gptq")
                or (bool(self.sparsity or self.nm[1])
                    and self.sparse_method == "sparsegpt"))

    def describe(self) -> str:
        parts = []
        if self.drop_units:
            parts.append(f"drop{self.drop_units}u")
        if self.kv_keep_frac < 1:
            parts.append(f"kv{self.kv_keep_frac:.2f}")
        if self.ffn_keep_frac < 1:
            parts.append(f"ffn{self.ffn_keep_frac:.2f}")
        if self.experts_keep:
            parts.append(f"E{self.experts_keep}")
        if self.nm[1]:
            parts.append(f"{self.nm[0]}:{self.nm[1]}")
        elif self.sparsity:
            parts.append(f"sp{self.sparsity:.2f}")
        if self.block_bs:
            parts.append(f"bs{self.block_bs}@{self.block_density:.2f}")
        if self.wbits < 16:
            parts.append(f"w{self.wbits}g{self.group}:{self.quant_method}")
        if self.smooth_alpha:
            parts.append(f"sq{self.smooth_alpha}")
        return "+".join(parts) or "identity"


# weights eligible for quantization/sparsification, by leaf name
_COMPRESS_NAMES = frozenset({
    "wq", "wk", "wv", "wo", "wi", "wg", "wr", "unembed",
    "in_proj", "out_proj",
})
_SKIP_SUBTREES = ("gn",)   # rwkv groupnorm has a "w" that is 1D anyway


def _leaf_name(path: str) -> str:
    return path.rsplit(".", 1)[-1]


def _is_target(path: str, leaf) -> bool:
    if isinstance(leaf, (QTensor, BlockSparseTensor)):
        return False
    name = _leaf_name(path)
    if name not in _COMPRESS_NAMES:
        return False
    return getattr(leaf, "ndim", 0) >= 2


def _stack_depth(cfg, path: str) -> int:
    """Leading stacked-layer axes of a param subtree (cf. calibrate paths)."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm", "rwkv"):
        return 1 if path.startswith("blocks.") else 0
    if fam == "hybrid":
        if path.startswith("mamba_groups."):
            return 2
        if path.startswith("mamba_tail."):
            return 1
        return 0
    return 0   # encdec: unrolled lists, indices already in the tree path


def _stats_key(cfg, path: str, idx: Tuple[int, ...]) -> str:
    """Map a tree path + stack indices to the calibration stats key."""
    parts = path.split(".")
    fam = cfg.family
    if fam in ("dense", "moe", "vlm", "rwkv") and parts[0] == "blocks":
        return ".".join(parts[:2] + [str(idx[0])] + parts[2:])
    if fam == "hybrid" and parts[0] == "mamba_groups":
        return ".".join([parts[0], str(idx[0]), str(idx[1])] + parts[1:])
    if fam == "hybrid" and parts[0] == "mamba_tail":
        return ".".join([parts[0], str(idx[0])] + parts[1:])
    return path


@dataclass
class Report:
    recipe: Recipe
    bytes_before: int
    bytes_after: int
    params_before: int
    params_after: int
    seconds: float
    per_weight: List[Dict[str, Any]]
    cfg_before: Any = None
    cfg_after: Any = None

    @property
    def compression(self) -> float:
        return self.bytes_before / max(self.bytes_after, 1)

    def summary(self) -> str:
        return (f"[{self.recipe.name}] {self.recipe.describe()}: "
                f"{self.bytes_before / 1e6:.1f} MB -> "
                f"{self.bytes_after / 1e6:.1f} MB "
                f"({self.compression:.2f}x) in {self.seconds:.1f}s")


def _param_count(tree) -> int:
    n = 0
    for leaf in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, (QTensor, BlockSparseTensor))):
        if isinstance(leaf, QTensor):
            n += int(np.prod(leaf.q.shape)) * (2 if leaf.bits == 4 else 1)
        elif isinstance(leaf, BlockSparseTensor):
            n += int(leaf.w.size * leaf.density())
        else:
            n += leaf.size
    return n


class InstanceOptimizer:
    """Generates a query-specialized compressed model (the paper's core)."""

    def __init__(self, params, cfg):
        self.params = params
        self.cfg = cfg
        self.stats: Optional[C.CalibStats] = None

    # -- stage 0: calibration ------------------------------------------------
    def run_calibration(self, batch: Dict[str, Any], *, hessian: bool = True):
        with tracing.span("iolm.calibrate"):
            self.stats = C.calibrate(self.params, self.cfg, batch,
                                     hessian=hessian)
        return self.stats

    # -- full pipeline -------------------------------------------------------
    def apply(self, recipe: Recipe):
        t0 = time.time()
        if self.stats is None:
            self.stats = C.CalibStats({}, {}, 0)
        params, cfg, stats = self.params, self.cfg, self.stats
        bytes_before = param_bytes(params)
        n_before = _param_count(params)

        # 1. structural
        if recipe.drop_units:
            params, cfg, stats = P.drop_layers(params, cfg, stats,
                                               recipe.drop_units)
        if recipe.kv_keep_frac < 1.0 and cfg.family != "rwkv":
            keep = max(1, int(round(recipe.kv_keep_frac * cfg.n_kv_heads)))
            params, cfg, stats = P.prune_kv_groups(params, cfg, stats, keep)
        if recipe.ffn_keep_frac < 1.0:
            params, cfg, stats = P.prune_ffn(params, cfg, stats,
                                             recipe.ffn_keep_frac)
        if recipe.experts_keep and cfg.family == "moe":
            params, cfg, stats = P.prune_experts(params, cfg, stats,
                                                 recipe.experts_keep)

        # 2+3. sparsify + quantize, per weight
        per_weight: List[Dict[str, Any]] = []
        if (recipe.wbits < 16 or recipe.sparsity or recipe.nm[1]
                or recipe.block_bs):
            params = self._compress_weights(params, cfg, stats, recipe,
                                            per_weight)
        if recipe.quant_embed:
            params = dict(params)
            params["embed"] = quantize_embed(params["embed"])

        report = Report(recipe=recipe, bytes_before=bytes_before,
                        bytes_after=param_bytes(params),
                        params_before=n_before,
                        params_after=_param_count(params),
                        seconds=time.time() - t0, per_weight=per_weight,
                        cfg_before=self.cfg, cfg_after=cfg)
        return params, cfg, report

    # -- weight-level compression ---------------------------------------------
    def _compress_weights(self, params, cfg, stats, recipe, per_weight):
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            params, is_leaf=lambda x: isinstance(x, (QTensor,
                                                     BlockSparseTensor)))
        out_leaves = []
        for path_t, leaf in flat:
            path = C._path_str(path_t)
            if not _is_target(path, leaf):
                out_leaves.append(leaf)
                continue
            depth = _stack_depth(cfg, path)
            is_expert = ".moe." in f".{path}." and _leaf_name(path) in (
                "wi", "wg", "wo")
            out_leaves.append(self._compress_one(
                leaf, cfg, stats, recipe, path, depth, is_expert, per_weight))
        return jax.tree_util.tree_unflatten(treedef, out_leaves)

    def _compress_one(self, leaf, cfg, stats, recipe, path, depth,
                      is_expert, per_weight):
        """Compress one (possibly layer-stacked, possibly expert-stacked)
        weight; returns a stacked QTensor/BlockSparseTensor/array."""
        w_np = np.asarray(jax.device_get(leaf), np.float32)
        shape = w_np.shape
        # enumerate layer indices
        if depth == 0:
            idxs = [()]
        elif depth == 1:
            idxs = [(r,) for r in range(shape[0])]
        else:
            idxs = [(g, k) for g in range(shape[0]) for k in range(shape[1])]

        results = []
        for idx in idxs:
            w = w_np[idx] if idx else w_np
            st = stats.get(_stats_key(cfg, path, idx))
            if is_expert:
                sub = [self._one_matrix(w[e], recipe, _expert_stats(st, e),
                                        path, per_weight, log=e == 0
                                        and idx in ((), (0,), (0, 0)))
                       for e in range(w.shape[0])]
                results.append(_stack_q(sub))
            else:
                results.append(self._one_matrix(
                    w, recipe, st, path, per_weight,
                    log=idx in ((), (0,), (0, 0))))
        out = _stack_q(results) if depth else results[0]
        if depth == 2:
            # regroup flat (g*k) stacking into [G, K, ...]
            G, K = shape[0], shape[1]
            out = jax.tree.map(lambda a: a.reshape(G, K, *a.shape[1:]), out)
        return out

    def _one_matrix(self, w, recipe, st, path, per_weight, log=False):
        """Sparsify+quantize a single [d_in, d_out] matrix."""
        d_in, d_out = w.shape
        H = st.H if st is not None else None
        act_norm = (np.sqrt(st.sqnorm / max(st.count, 1))
                    if st is not None and st.sqnorm is not None
                    else np.ones(d_in, np.float32))
        amax = st.amax if st is not None and st.amax is not None else None
        mask = None
        entry = {"path": path, "shape": (d_in, d_out)}

        # --- TPU block sparsity: container-level, kernel skips tiles ---
        if recipe.block_bs and recipe.block_density < 1.0 \
                and d_in % recipe.block_bs == 0 and d_out % recipe.block_bs == 0:
            bmask = S.block_sparse_mask(w, bs=recipe.block_bs,
                                        density=recipe.block_density,
                                        act_norm=act_norm)
            if recipe.wbits >= 16:
                if log:
                    entry["kind"] = f"block_sparse@{recipe.block_density}"
                    per_weight.append(entry)
                return S.apply_block_mask(w, bmask, recipe.block_bs)
            # compose: zero the tiles, then quantize below
            big = np.kron(bmask.astype(np.float32),
                          np.ones((recipe.block_bs, recipe.block_bs),
                                  np.float32))
            mask = big > 0
            w = w * big

        # --- fine-grained sparsity (size reduction; composes with quant) ---
        n, m = recipe.nm
        if (m or recipe.sparsity) and mask is None:
            if recipe.sparse_method == "sparsegpt" and H is not None:
                w, mask = S.sparsegpt_prune(w, H, sparsity=recipe.sparsity,
                                            n=n, m=m)
            else:
                mask = S.wanda_mask(w, act_norm, sparsity=recipe.sparsity,
                                    n=n, m=m)
                w = np.where(mask, w, 0.0)

        # --- quantization ---
        if recipe.wbits < 16:
            alpha = recipe.smooth_alpha
            if recipe.quant_method == "gptq" and H is not None:
                qt = Q.gptq_quantize(w, H, bits=recipe.wbits,
                                     group=recipe.group, amax_x=amax,
                                     smooth_alpha=alpha, mask=mask)
            else:
                qt = Q.absmax_quantize(w, bits=recipe.wbits,
                                       group=recipe.group, amax_x=amax,
                                       smooth_alpha=alpha)
                if mask is not None:
                    codes = np.asarray(jax.device_get(qt.unpack()))
                    codes = np.where(mask, codes, 0).astype(np.int8)
                    from repro.core.compressed import pack_int4
                    q = (pack_int4(jnp.asarray(codes)) if recipe.wbits == 4
                         else jnp.asarray(codes))
                    qt = QTensor(q, qt.scale, qt.bits, qt.group, qt.shape,
                                 qt.in_scale)
            if log:
                entry["kind"] = f"quant w{recipe.wbits}"
                per_weight.append(entry)
            return qt
        if mask is not None:
            if log:
                entry["kind"] = "sparse (dense container)"
                per_weight.append(entry)
            return jnp.asarray(w.astype(np.float32), dtype=jnp.bfloat16)
        return jnp.asarray(w.astype(np.float32), dtype=jnp.bfloat16)


def _expert_stats(st, e):
    if st is None or st.sqnorm is None:
        return None
    # per-expert row count, NOT the global sum over experts: the Wanda
    # act_norm divides sqnorm[e] by this, and the global count deflates
    # lightly-routed experts' norms by their routing share
    count = int(st.count_e[e]) if st.count_e is not None else st.count
    return C.WeightStats(shape=tuple(st.shape[1:]), count=count,
                         H=None if st.H is None else st.H[e],
                         sqnorm=st.sqnorm[e], amax=st.amax[e])


def _stack_q(items):
    """Stack per-layer compression results along a new axis 0."""
    first = items[0]
    if isinstance(first, QTensor):
        q = jnp.stack([it.q for it in items])
        s = jnp.stack([it.scale for it in items])
        ins = (None if first.in_scale is None
               else jnp.stack([it.in_scale for it in items]))
        return QTensor(q, s, first.bits, first.group, first.shape[-2:], ins)
    if isinstance(first, BlockSparseTensor):
        return BlockSparseTensor(jnp.stack([it.w for it in items]),
                                 jnp.stack([it.mask for it in items]),
                                 first.bs,
                                 jnp.stack([it.idx for it in items]))
    return jnp.stack(items)
