"""Compressed-parameter containers and the universal matmul dispatch.

The IOLM-DB pipeline rewrites selected weight matrices of a model's param
pytree into ``QTensor`` (quantized, optionally group-wise, optionally with
SmoothQuant input scales) or ``BlockSparseTensor`` (TPU block-sparse, the
hardware adaptation of the paper's 2:4 sparsity).
Every linear layer in ``repro.models`` calls :func:`matmul`, which
dispatches on the container type, so compression is transparent to all
architecture families.

The jnp paths here are the portable fallback (and the oracle for the
Pallas kernels in ``repro.kernels``); the fused kernels take over when
the active :mod:`repro.kernels.backend` resolves to ``"pallas"`` —
scoped per call site via :func:`kernel_backend`, threaded explicitly
from ``IOLMSession(backend=…)`` down through pool and engine rather
than flipped through a process-wide flag.

Calibration: ``set_record_hook`` installs an eager-mode observer that the
matmul dispatch (and the MoE block) feeds with (weight, activation)
pairs; ``repro.core.calibrate`` uses it to gather Hessians / channel
norms / routing statistics without any model-code changes.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.backend import normalize_backend, resolve_backend

# Process default (mutated only by the deprecated use_kernels() shim) and
# the scoped override.  A ContextVar — not a module global — so engines
# running under the fan-out scheduler, threads, or nested traces each see
# their own backend.
_BACKEND_DEFAULT = "auto"
_BACKEND: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_backend", default=None)

_RECORD_HOOK: Optional[Callable] = None
_ROUTE_HOOK: Optional[Callable] = None


@contextlib.contextmanager
def kernel_backend(backend):
    """Scope a KernelBackend over a block of (trace-time) compute.

    Engines wrap their jit call sites in this, so the dispatch below picks
    the engine's backend while tracing — no global state survives the
    ``with`` block.
    """
    token = _BACKEND.set(normalize_backend(backend))
    try:
        yield
    finally:
        _BACKEND.reset(token)


def current_backend() -> str:
    """The resolved backend in effect: ``"reference"`` or ``"pallas"``."""
    b = _BACKEND.get()
    return resolve_backend(b if b is not None else _BACKEND_DEFAULT)


def use_kernels(flag: bool) -> None:
    """Deprecated: set the process-default backend.

    Use ``IOLMSession(backend=…)`` / ``Engine(backend=…)`` or the scoped
    :func:`kernel_backend` context manager instead.
    """
    warnings.warn(
        "use_kernels() is deprecated; pass backend='pallas'/'reference' to "
        "IOLMSession/Engine or use repro.core.compressed.kernel_backend()",
        DeprecationWarning, stacklevel=2)
    global _BACKEND_DEFAULT
    _BACKEND_DEFAULT = "pallas" if flag else "reference"


def kernels_enabled() -> bool:
    """Deprecated: query whether the current backend resolves to pallas."""
    warnings.warn(
        "kernels_enabled() is deprecated; use "
        "repro.core.compressed.current_backend() == 'pallas'",
        DeprecationWarning, stacklevel=2)
    return current_backend() == "pallas"


def set_record_hook(fn: Optional[Callable]) -> None:
    """fn(w, x) observes eager matmuls; x is [..., d_in] (or [E, C, d_in]
    together with a per-expert valid-count for stacked expert weights)."""
    global _RECORD_HOOK
    _RECORD_HOOK = fn


def set_route_hook(fn: Optional[Callable]) -> None:
    """fn(router_w, counts, probs_mean) observes MoE routing statistics."""
    global _ROUTE_HOOK
    _ROUTE_HOOK = fn


def record(w, x, valid=None) -> None:
    """Explicit calibration record (used by MoE expert einsums)."""
    if _RECORD_HOOK is not None and not isinstance(x, jax.core.Tracer):
        _RECORD_HOOK(w, x, valid)


def record_routing(router_w, counts, probs_mean) -> None:
    if _ROUTE_HOOK is not None and not isinstance(counts, jax.core.Tracer):
        _ROUTE_HOOK(router_w, counts, probs_mean)


@jax.tree_util.register_pytree_node_class
class QTensor:
    """Group-wise quantized weight matrix ``[d_in, d_out]``.

    q         int8 codes ``[d_in, d_out]`` (int4: packed two-per-byte along
              d_in -> ``[d_in // 2, d_out]`` uint8)
    scale     f32 per-(group, out-channel) scales ``[d_in // group, d_out]``
    in_scale  optional f32 ``[d_in]`` SmoothQuant per-channel input scale
              (x is multiplied by it before the quantized matmul; the
              inverse was folded into the stored codes at quantization)
    bits      4 or 8 (static)

    Children may carry an extra leading layer axis when stacked for
    ``lax.scan`` — methods are only invoked on per-layer slices.
    """

    def __init__(self, q, scale, bits: int, group: int, shape, in_scale=None):
        self.q = q
        self.scale = scale
        self.in_scale = in_scale
        self.bits = int(bits)
        self.group = int(group)
        self.shape = tuple(shape)

    # --- pytree protocol ---
    def tree_flatten(self):
        return (self.q, self.scale, self.in_scale), (self.bits, self.group,
                                                     self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale, in_scale = children
        return cls(q, scale, aux[0], aux[1], aux[2], in_scale)

    @property
    def dtype(self):
        return jnp.bfloat16

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def nbytes(self) -> int:
        """Stored bytes, computed from the actual children (stacked-safe)."""
        b = self.q.size * self.q.dtype.itemsize
        b += self.scale.size * self.scale.dtype.itemsize
        if self.in_scale is not None:
            b += self.in_scale.size * self.in_scale.dtype.itemsize
        return int(b)

    def unpack(self) -> jax.Array:
        """int8 logical codes [d_in, d_out] (unpacks int4)."""
        if self.bits == 8:
            return self.q
        u = self.q  # uint8 [d_in//2, d_out]
        lo = (u & 0xF).astype(jnp.int8)
        hi = (u >> 4).astype(jnp.int8)
        lo = jnp.where(lo >= 8, lo - 16, lo)
        hi = jnp.where(hi >= 8, hi - 16, hi)
        d_in = self.shape[-2]
        out = jnp.zeros((d_in, self.shape[-1]), jnp.int8)
        out = out.at[0::2].set(lo).at[1::2].set(hi)
        return out

    def dequantize(self) -> jax.Array:
        """Dense bf16 reconstruction (folds in_scale back into the weight)."""
        w = self.unpack().astype(jnp.float32)
        g = self.group
        d_in, d_out = self.shape[-2], self.shape[-1]
        w = w.reshape(d_in // g, g, d_out) * self.scale[:, None, :]
        w = w.reshape(d_in, d_out)
        if self.in_scale is not None:
            w = w * self.in_scale[:, None]
        return w.astype(jnp.bfloat16)


def pack_int4(codes: jax.Array) -> jax.Array:
    """int8 codes in [-8, 7], even first dim -> packed uint8 pairs."""
    lo = codes[0::2].astype(jnp.uint8) & 0xF
    hi = codes[1::2].astype(jnp.uint8) & 0xF
    return lo | (hi << 4)


@jax.tree_util.register_pytree_node_class
class QEmbed:
    """Int8 embedding table with per-row (per-vocab-entry) scales.

    Supports the two operations embeddings need: row gather (lookup) and
    tied-unembedding logits  x @ W^T = (x @ q^T) * s  — the per-row scale
    factors out of the reduction, so the matmul runs on int8 codes.
    """

    def __init__(self, q, scale):
        self.q = q            # int8 [V, d]
        self.scale = scale    # f32 [V]

    def tree_flatten(self):
        return (self.q, self.scale), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def dtype(self):
        return jnp.bfloat16

    @property
    def nbytes(self) -> int:
        return int(self.q.size + self.scale.size * 4)

    def lookup(self, tokens):
        return (self.q[tokens].astype(jnp.float32)
                * self.scale[tokens][..., None]).astype(jnp.bfloat16)

    def logits(self, x):
        y = jnp.einsum("...d,vd->...v", x.astype(jnp.bfloat16),
                       self.q.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return y * self.scale


def quantize_embed(table, bits: int = 8) -> QEmbed:
    """Per-row absmax int8 quantization of an embedding table."""
    assert bits == 8, "embedding tables are int8 only"
    w = np.asarray(jax.device_get(table), np.float32)
    s = np.abs(w).max(1) / 127.0 + 1e-12
    q = np.clip(np.rint(w / s[:, None]), -127, 127).astype(np.int8)
    return QEmbed(jnp.asarray(q), jnp.asarray(s.astype(np.float32)))


@jax.tree_util.register_pytree_node_class
class BlockSparseTensor:
    """Block-sparse weight ``[d_in, d_out]`` with ``bs x bs`` zero blocks.

    TPU adaptation of the paper's 2:4 sparsity: whole 128-aligned blocks
    are pruned so the MXU can skip them (gather-based Pallas kernel);
    storage keeps only nonzero blocks + a bitmap.  ``w`` here is the
    dense zero-filled array (portable fallback / oracle); ``mask`` is the
    static block bitmap [d_in/bs, d_out/bs] (f32 0/1 so it scans cleanly);
    ``idx`` [d_out/bs, keep] int32 lists the kept input-block rows per
    output block column (uniform ``keep`` — the Pallas kernel's static
    gather length).
    """

    def __init__(self, w, mask, bs: int, idx=None):
        self.w = w
        self.mask = mask
        self.bs = int(bs)
        self.idx = idx

    def tree_flatten(self):
        return (self.w, self.mask, self.idx), (self.bs,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], children[2])

    @property
    def shape(self):
        return self.w.shape

    @property
    def ndim(self) -> int:
        return self.w.ndim

    @property
    def dtype(self):
        return self.w.dtype

    @property
    def nbytes(self) -> int:
        nnz = float(jax.device_get(self.mask.sum()))
        return int(nnz * self.bs * self.bs * self.w.dtype.itemsize
                   + self.mask.size / 8 + 1)

    def density(self) -> float:
        return float(jax.device_get(self.mask.mean()))


def param_bytes(tree) -> int:
    """Total stored bytes of a (possibly compressed) param pytree."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, (QTensor, BlockSparseTensor))):
        if isinstance(leaf, (QTensor, BlockSparseTensor)):
            total += leaf.nbytes
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total


def _q_matmul_jnp(x: jax.Array, w: QTensor) -> jax.Array:
    """Dequantize-then-dot: the same schedule the Pallas kernel uses
    (int8 codes scaled to bf16 right before the MXU contraction); XLA
    fuses the dequant into the matmul so codes stream from HBM as int8."""
    if w.in_scale is not None:
        x = (x.astype(jnp.float32) * w.in_scale).astype(x.dtype)
    y = jnp.einsum("...i,io->...o", x, w.dequantize(),
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def matmul(x: jax.Array, w) -> jax.Array:
    """Universal ``x @ w`` over raw / quantized / block-sparse weights."""
    if isinstance(w, QTensor):
        if w.bits == 8 and current_backend() == "pallas":
            from repro.kernels import ops as kops
            return kops.quant_matmul(x, w.q, w.scale, group=w.group,
                                     in_scale=w.in_scale)
        return _q_matmul_jnp(x, w)
    if isinstance(w, BlockSparseTensor):
        if current_backend() == "pallas":
            from repro.kernels import ops as kops
            return kops.block_sparse_matmul(x, w.w, w.idx, bs=w.bs)
        return jnp.einsum("...i,io->...o", x, w.w.astype(x.dtype),
                          preferred_element_type=jnp.float32).astype(x.dtype)
    if _RECORD_HOOK is not None and not isinstance(x, jax.core.Tracer):
        _RECORD_HOOK(w, x, None)
    return jnp.einsum("...i,io->...o", x, w.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def expert_matmul(x: jax.Array, w) -> jax.Array:
    """Batched per-expert matmul ``[E, C, d_in] @ [E, d_in, d_out]`` over
    raw or quantized expert stacks (MoE layers call this)."""
    if isinstance(w, QTensor):
        def one(xe, qe, se, ise):
            wq = QTensor(qe, se, w.bits, w.group, w.shape[-2:], ise)
            return matmul(xe, wq)
        if w.in_scale is None:
            return jax.vmap(lambda xe, qe, se: one(xe, qe, se, None))(
                x, w.q, w.scale)
        return jax.vmap(one)(x, w.q, w.scale, w.in_scale)
    return jnp.einsum("ecd,edf->ecf", x, w.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def is_weight_leaf(x) -> bool:
    return isinstance(x, (QTensor, BlockSparseTensor)) or hasattr(x, "shape")
