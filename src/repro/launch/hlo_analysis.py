"""Roofline-term extraction from compiled XLA artifacts.

``compiled.cost_analysis()`` on an SPMD-partitioned module reports
**per-device** FLOPs/bytes (calibrated: a [2048x2048]^2 matmul sharded
over 16 devices reports 1/16 of 2N^3), and the optimized HLO text is the
per-device program, so its collective operand shapes are per-device
shard payloads.  The roofline terms therefore divide by per-chip peaks
directly:

    compute term    = device_FLOPs / peak FLOP/s          (197e12 bf16)
    memory term     = device_bytes / HBM bandwidth        (819e9 B/s)
    collective term = device_collective_bytes / ICI link  (50e9 B/s)

Equivalently: global_FLOPs / (chips x peak) when compute shards
perfectly — deviations between the two ARE the parallelization loss, and
``useful_compute_frac`` = MODEL_FLOPS / (device_FLOPs x chips) makes the
redundancy (remat, replication) visible.  Collective bytes are parsed
from HLO (all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute result shapes).  Hardware constants: TPU v5e.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, Optional

# --- TPU v5e constants (per chip) ---
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

# result shape may be a tuple, including one-level-nested tuples as
# emitted for async pairs: `(bf16[8], (bf16[8], u32[]))` — the inner
# alternative admits one nesting depth
_COLL_RE = re.compile(
    r"^\s*(?:%?[\w.\-]+\s*=\s*)?"
    r"((?:\((?:[^()]|\([^()]*\))*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(",
    re.M)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> float:
    """bytes of 'bf16[128,1024]{1,0}' or tuple '(f32[2,4], u32[])'.

    Sub-byte dtypes (s4/u4) are packed two-per-byte but a shape's
    buffer is still whole bytes — ceil per array, so 'u4[3]' is 2
    bytes, not 1.5."""
    total = 0.0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += math.ceil(n * _DTYPE_BYTES[dt])
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum of result-shape bytes per collective kind.

    Async collectives appear as a '-start'/'-done' pair whose result
    shapes both carry the payload; only the '-done' (or a synchronous
    op with no suffix) is counted, so a pair contributes once."""
    out: Dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, kind, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-start":
            continue
        out[kind] = out.get(kind, 0.0) + _shape_bytes(shape_str)
    return out


@dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    coll_bytes: float
    chips: int
    coll_detail: Dict[str, float] = field(default_factory=dict)
    peak_flops: float = PEAK_FLOPS
    hbm_bw: float = HBM_BW
    ici_bw: float = ICI_BW

    @property
    def t_compute(self) -> float:
        return self.flops / self.peak_flops          # flops are per-device

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / self.hbm_bw     # bytes are per-device

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.ici_bw         # HLO is per-device

    @property
    def bound(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def to_dict(self) -> Dict:
        return {
            "flops": self.flops, "bytes": self.bytes_accessed,
            "coll_bytes": self.coll_bytes, "chips": self.chips,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "bound": self.bound,
            "coll_detail": self.coll_detail,
        }


def analyze(compiled, chips: int, hlo_text: Optional[str] = None) -> Roofline:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))
    txt = hlo_text if hlo_text is not None else compiled.as_text()
    detail = collective_bytes(txt)
    return Roofline(flops=flops, bytes_accessed=nbytes,
                    coll_bytes=sum(detail.values()), chips=chips,
                    coll_detail=detail)


def memory_per_device(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    return out


def model_flops(cfg, shape_spec) -> float:
    """MODEL_FLOPS = 6 N D (dense train) / 2 N D (inference fwd), with
    N = active params; D = processed tokens."""
    n = cfg.active_param_count()
    if shape_spec.kind == "train":
        per_tok = 6 * n
        toks = shape_spec.global_batch * shape_spec.seq_len
    elif shape_spec.kind == "prefill":
        per_tok = 2 * n
        toks = shape_spec.global_batch * shape_spec.seq_len
    else:  # decode: one token per row
        per_tok = 2 * n
        toks = shape_spec.global_batch
    return float(per_tok) * toks
