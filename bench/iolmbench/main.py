"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the result line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Earlier lines (standard error) record the set-up's parts, the window's
counts, the picked recipe and the kernels in the served decode step;
the last lines of standard error give each number compared beside its
limit, and the last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the whole window.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from iolmbench import spec

# weight bits one step below each served precision (the control)
LOWER_BITS = {16: 8, 8: 4}


class NoChip(RuntimeError):
    pass


def log(msg: str, **fields) -> None:
    line = f"[bench] {msg}"
    if fields:
        line += " " + json.dumps(fields, default=str)
    print(line, file=sys.stderr, flush=True)


@dataclass
class Context:
    """What a per-layer metric's reader may read."""
    sizes: Dict[str, Any]
    timers: Dict[str, float]
    window: Any                  # serve.Window
    engine_stats: Dict[str, int]  # EngineStats deltas over the window
    admits: List
    decodes: List
    instance: List[Dict[str, Any]]
    slots: int
    block: int
    trace: Any                   # trace.Reduced, or None untraced/off-chip
    peak: Optional[Dict[str, float]]


def enable_compile_cache() -> str:
    """JAX's persistent cache at one fixed path inside the checkout
    (``$JAX_COMPILATION_CACHE_DIR`` when set), holding every program,
    however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        spec.ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(cell, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                     f"JAX found {len(devs)} {devs[0].platform} device(s)")
    return devs[:cell.chips]


def decode_kernels(engine) -> set:
    """Pallas kernels compiled into the engine's own paged decode step,
    lowered under its backend with its live state."""
    import jax.numpy as jnp
    from repro.core.compressed import kernel_backend
    with kernel_backend(engine.backend):
        low = engine.jit_targets()["_decode"].lower(
            engine.params, engine._slot_state, engine._tables(),
            jnp.asarray(engine._cur_tok), jnp.asarray(engine._cur_pos),
            jnp.int32(0))
    return set(re.findall(r'kernel_name = "(\w+)"', low.as_text()))


def expected_kernels(instance, bench_dir: str = spec.BENCH_DIR) -> set:
    """Pallas kernels the served decode step has to hold: attention,
    each projection's kernel, and for expert stacks the kernel whose
    counts file takes them (``spec.expert_kernel``), where one does."""
    experts = spec.expert_kernel(bench_dir)
    return {"paged_attention"} | {
        experts if experts and "experts" in w else w["kernel"]
        for w in instance}


def limits_for(cell_name: str) -> Dict[str, Any]:
    return spec.load_json(os.path.join(spec.BENCH_DIR, "limits",
                                       cell_name + ".json"))


# EngineStats fields that hold a high-water mark, not a count: their
# difference over the window means nothing
HIGH_WATER = ("peak_inflight", "kv_blocks_in_use", "kv_blocks_shared")


def _stats(eng) -> Dict[str, int]:
    """Every counter of the engine's ``EngineStats``: its integer fields
    but the high-water marks, so that a counter the program adds
    reaches the readers (``Context.engine_stats``) as it is."""
    s = eng.stats
    return {f.name: int(getattr(s, f.name)) for f in dataclasses.fields(s)
            if f.type in (int, "int") and f.name not in HIGH_WATER}


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, sizes: Optional[Dict] = None,
             control: bool = False, t_start: Optional[float] = None
             ) -> Dict[str, Any]:
    """One run; returns the result (the last line's object).  ``sizes``
    and ``require_chip=False`` serve the CPU rehearsal; ``control``
    also reads the control's gap at one precision step below."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    import repro  # noqa: F401  the system under test, before any chip
    from iolmbench import check, flops, serve
    from iolmbench import trace as TR
    from iolmbench.clock import CompileWatch

    cache = enable_compile_cache()
    devs = devices_for(cell, require_chip)
    d0 = devs[0]
    on_chip = d0.platform == "tpu"
    log("devices", platform=d0.platform, kind=d0.device_kind,
        count=len(devs), compile_cache=cache)
    watch = CompileWatch()
    st = serve.build(cell, seed, watch=watch, sizes=sizes)
    setup_s = time.perf_counter() - t_start
    eng, probe = st.engine, st.probe
    instance = flops.instance_matmuls(eng.params,
                                      top_k=st.kw.get("top_k", 0))
    log("setup", setup_s=setup_s, parts=dict(st.timers.s),
        compile_s=watch.seconds, programs=watch.programs(),
        picked=st.picked, dropped=st.info["dropped_recipes"],
        truncated=st.info["truncated"])
    for line in st.info["session_log"]:
        log("session " + line)

    stats0 = _stats(eng)
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir, profiler_options=_profile_options())
    p0 = watch.programs()
    win = serve.window(st, cell, seconds, seed)
    compiles = watch.programs() - p0
    if trace:
        jax.profiler.stop_trace()
    stats1 = _stats(eng)
    delta = {k: stats1[k] - stats0[k] for k in stats0}
    log("window", seconds=win.seconds, ticks=win.ticks, rows=win.rows,
        attempted=win.attempted, failed=win.failed,
        compiles_in_window=compiles, engine=delta,
        admissions=len(probe.admits), decode_steps=len(probe.decodes),
        **win.info)

    found = decode_kernels(eng) if on_chip else set()
    want = expected_kernels(instance)
    if on_chip:
        log("decode step kernels", found=sorted(found), expected=sorted(want))
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peak_mem = max((m for m in mem if m is not None), default=None)

    # the program's state goes before the reference runs
    slots, block = eng.slots, eng._block_size
    admits, decodes = probe.admits, probe.decodes
    picked = st.picked
    serve.free(st)
    eng = probe = None

    checks = correctness(cell, st, win, seed, picked, control=control)
    checks["truncated_prompts"] = {"value": delta["truncated"], "limit": 0}
    checks["answers_differing"] = {"value": check.differing(win.served),
                                   "limit": 0}
    checks["degraded_submissions"] = {
        "value": win.info.get("degradations", 0), "limit": 0}
    if on_chip:
        checks["kernels_missing"] = {"value": len(want - found), "limit": 0}

    red = None
    if trace:
        red = TR.reduce(TR.from_xspace(TR.find_xplane(tdir)), spec.kernels())
        shutil.rmtree(tdir, ignore_errors=True)
        log("trace", window_s=red.window_s, busy_s=red.busy_s,
            kernel_s=red.kernel_s, kernel_calls=red.kernel_calls,
            program_s=red.program_s, idle_by_span=red.idle_by_span,
            longest_gaps=red.longest_gaps)
    peak = spec.peaks(d0.device_kind) if on_chip else None
    ctx = dict(sizes=st.kw, timers=dict(st.timers.s), window=win,
               engine_stats=delta, admits=admits, decodes=decodes,
               instance=instance, slots=slots, block=block,
               trace=red if (red is not None and red.devices) else None,
               peak=peak)
    e2e = {"setup_s": setup_s,
           "optimize_s": st.timers.s.get("optimize_s"),
           "rows_per_s": win.rows / win.seconds}
    if "query_s" in win.latency:
        e2e["query_p95_s"] = serve.nearest_rank(win.latency["query_s"], 95)
        e2e["first_row_p95_s"] = serve.nearest_rank(
            win.latency["first_row_s"], 95)
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        if trace:
            v = spec.metric_reader(m["name"])(Context(**ctx))
        else:
            v = e2e.get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_mem}
    if ctx["trace"] is not None:
        device.update(busy_s=red.busy_s, window_s=red.window_s)
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values()) and win.rows > 0
    out = {"correct": bool(ok), "attempted": win.attempted,
           "failed": win.failed, "metrics": metrics, "device": device}
    if ctx["trace"] is not None:
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in red.top_ops],
            "idle_gaps": sorted(([n, s] for n, s in
                                 red.idle_by_span.items()),
                                key=lambda x: -x[1])[:10]}
    out["checks"] = checks
    return out


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def correctness(cell, st, win, seed: int, picked: str, *,
                control: bool) -> Dict[str, Dict[str, float]]:
    """The served tokens of a sample of the window's rows against the
    plain reference (``check.compare``).  A configuration that names no
    reference reads nothing, and its run is not correct."""
    from iolmbench import check
    if "reference" not in cell.config:
        log("no reference reading: the configuration names no reference")
        return {"served_logit_gap": {"value": None, "limit": None}}
    lim = limits_for(cell.name)
    recipes = {r["name"]: r for r in cell.mix["session"]["recipes"]}
    recipes["base"] = {"name": "base"}          # the bf16 model as given
    bits = check.recipe_bits(recipes[picked]) if picked in recipes else None
    rows = check.sample(check.distinct(win.served), seed,
                        int(lim["sample_rows"]))
    if bits is None or not rows:
        log("no reference reading", picked=picked, rows=len(rows))
        return {"served_logit_gap": {"value": None,
                                     "limit": lim["served_logit_gap"]}}
    ref = spec.reference_module(cell.config)
    res = check.compare(
        ref, st.weights, st.kw, rows, bits=bits,
        length=int(cell.mix["session"]["engine"]["max_len"]),
        batch=int(lim["sample_rows"]),
        control_bits=LOWER_BITS.get(bits) if control else None)
    log("reference", bits=bits, **res)
    out = {"served_logit_gap": {"value": res["gap_max"],
                                "limit": lim["served_logit_gap"]}}
    if control:
        out["control_logit_gap"] = {"value": res["control_gap_max"],
                                    "limit": lim["served_logit_gap"]}
    return out


def report(out: Dict[str, Any]) -> None:
    """The numbers compared beside their limits, as the last lines of
    standard error; the result as the last line of standard output."""
    for name, c in out["checks"].items():
        print(f"[bench] check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start)
    except NoChip as e:
        log(f"FAIL: {e}")
        return 3
    report(out)
    return 0
