"""Reduction of a profiler trace to device numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  A device plane (``/device:TPU:<n>``)
holds a line of operations (``XLA Ops``) and a line of program runs
(``XLA Modules``); the host plane holds the harness's spans
(``jax.profiler.TraceAnnotation``).  All times are nanoseconds on one
clock.

Output, over the traced window ``[w0, w1]`` (the harness's
``bench.window`` span):

* busy: the union of the device's operation intervals; idle share is
  1 - busy / window;
* device time per kernel, summed over the kernel's operations (a
  Pallas kernel is one operation named after its ``pallas_call``);
* device time per program, summed over that program's runs;
* each idle gap attributed to the innermost harness span that covers
  its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "engine.", "iolm.")
NO_SPAN = "(no harness span)"


@dataclass
class Trace:
    ops: Dict[str, List[Interval]]       # device plane -> operations
    modules: Dict[str, List[Interval]]   # device plane -> program runs
    spans: List[Interval]                # harness host spans
    window: Tuple[int, int]


@dataclass
class Reduced:
    window_s: float
    busy_s: float                        # mean over devices
    kernel_s: Dict[str, float]           # summed over devices
    program_s: Dict[str, float]
    kernel_calls: Dict[str, int]
    idle_by_span: Dict[str, float]
    longest_gaps: List[Tuple[str, float]]
    top_ops: List[Tuple[str, float]]
    devices: int


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def op_name(event_name: str) -> str:
    """An operation's name from its trace event, which on a TPU is the
    whole HLO instruction (``%quant_matmul.64 = bf16[32,131072] ...``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda n: n) -> List[Interval]:
    return [(name(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def from_xspace(path: str, *, device_prefix: str = "/device:TPU:",
                ops_line: str = OPS_LINE,
                modules_line: str = MODULES_LINE) -> Trace:
    """Read the device lines and the harness spans of one trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, mods, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name == ops_line:
                    ops[plane.name] = _events(line, op_name)
                elif line.name == modules_line:
                    mods[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [iv for iv in _events(line)
                          if iv[0].startswith(SPAN_PREFIXES)]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, got {len(win)}")
    return Trace(ops=ops, modules=mods, spans=spans,
                 window=(win[0][1], win[0][2]))


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(ivs: Sequence[Interval], w0: int, w1: int) -> List[Interval]:
    return [(n, max(s, w0), min(e, w1)) for n, s, e in ivs
            if e > w0 and s < w1]


def gaps(busy: Sequence[Tuple[int, int]], w0: int,
         w1: int) -> List[Tuple[int, int]]:
    out, t = [], w0
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out


class SpanIndex:
    """The harness spans as arrays, for finding the innermost span at
    a time."""

    def __init__(self, spans: Sequence[Interval]):
        keep = [iv for iv in spans if iv[0] != WINDOW_SPAN]
        self.names = [n for n, _, _ in keep]
        self.start = np.array([s for _, s, _ in keep], np.int64)
        self.end = np.array([e for _, _, e in keep], np.int64)

    def at(self, t: int) -> str:
        hit = np.nonzero((self.start <= t) & (t < self.end))[0]
        if not len(hit):
            return NO_SPAN
        return self.names[hit[np.argmin(self.end[hit] - self.start[hit])]]


def attribute(gap: Tuple[int, int], spans: SpanIndex) -> str:
    """The innermost harness span covering the gap's midpoint."""
    return spans.at((gap[0] + gap[1]) // 2)


def kernel_of(op: str, kernels: Sequence[str]) -> Optional[str]:
    """The kernel an operation runs: the ``pallas_call``'s name, with an
    instance suffix (``quant_matmul.3``) and, for a call under
    ``jax.vmap``, the batching rule's ``vmap_<name>_``."""
    base = op.split(".", 1)[0]
    while base.startswith("vmap_") and base.endswith("_"):
        base = base[len("vmap_"):-1]
    return base if base in kernels else None


# operations that hold other operations: their time is their body's
CONTAINERS = ("while", "conditional", "call")


def reduce(tr: Trace, kernels: Sequence[str], *, top: int = 10) -> Reduced:
    w0, w1 = tr.window
    devices = sorted(tr.ops)
    busy_total = 0
    kernel_s: Dict[str, float] = collections.defaultdict(float)
    calls: Dict[str, int] = collections.defaultdict(int)
    program_s: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    op_s: Dict[str, float] = collections.defaultdict(float)
    all_gaps: List[Tuple[str, float]] = []
    spans = SpanIndex(clip(tr.spans, w0, w1))
    for d in devices:
        ops = clip(tr.ops[d], w0, w1)
        mods = clip(tr.modules.get(d, []), w0, w1)
        busy = union((s, e) for _, s, e in ops)
        busy_total += sum(e - s for s, e in busy)
        for n, s, e in mods:
            program_s[n] += (e - s) / 1e9
        mods.sort(key=lambda iv: iv[1])
        starts = [s for _, s, _ in mods]
        for n, s, e in ops:
            k = kernel_of(n, kernels)
            if k is not None:
                kernel_s[k] += (e - s) / 1e9
                calls[k] += 1
            if n.split(".", 1)[0] not in CONTAINERS:
                op_s[_op_label(n, s, mods, starts)] += (e - s) / 1e9
        for g in gaps(busy, w0, w1):
            who = attribute(g, spans)
            idle[who] += (g[1] - g[0]) / 1e9
            all_gaps.append((who, (g[1] - g[0]) / 1e9))
    n = max(1, len(devices))
    return Reduced(
        window_s=(w1 - w0) / 1e9, busy_s=busy_total / n / 1e9,
        kernel_s=dict(kernel_s), program_s=dict(program_s),
        kernel_calls=dict(calls), idle_by_span=dict(idle),
        longest_gaps=sorted(all_gaps, key=lambda x: -x[1])[:top],
        top_ops=sorted(op_s.items(), key=lambda x: -x[1])[:top],
        devices=len(devices))


def _op_label(op: str, start: int, mods: Sequence[Interval],
              starts: Sequence[int]) -> str:
    """``program/operation`` for the breakdown (the program run that
    holds the operation's start; the operation's instance suffix
    dropped)."""
    i = bisect.bisect_right(starts, start) - 1
    prog = mods[i][0] if i >= 0 and start < mods[i][2] else "?"
    return f"{prog}/{op.split('.', 1)[0]}"
