"""Render benchmark artifacts into the committed docs.

Two targets:

  --readme   regenerate the README.md §Results table from the
             ``results/BENCH_*.json`` artifacts written by
             ``benchmarks/{serving,multi_tenant,device_parallel}.py
             --json`` (each spliced between RESULTS_BEGIN/END markers)
  (default)  render results/dryrun.json into EXPERIMENTS.md §Dry-run +
             §Roofline — skipped with a message when either file is
             absent (the dry-run artifact is not part of the tree)
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ROOT = os.path.join(os.path.dirname(__file__), "..")
RESULTS = os.path.join(ROOT, "results", "dryrun.json")
EXP = os.path.join(ROOT, "EXPERIMENTS.md")
README = os.path.join(ROOT, "README.md")
HBM = 16e9

# rwkv/zamba inner sequence recurrences stay as rolled scans even in the
# unrolled analysis build -> their HLO compute term undercounts
RECURRENT = ("rwkv6-3b", "zamba2-7b")


def fmt_t(v):
    if v >= 1:
        return f"{v:.2f}s"
    if v >= 1e-3:
        return f"{v * 1e3:.1f}ms"
    return f"{v * 1e6:.0f}us"


def dryrun_table(data):
    lines = ["| arch | shape | single-pod (256) | multi-pod (512) | "
             "GB/dev (s/m) |", "|---|---|---|---|---|"]
    archs, shapes = [], []
    for k in data:
        a, s, m = k.split(":")
        if a not in archs:
            archs.append(a)
        if s not in shapes:
            shapes.append(s)
    for a in archs:
        for s in shapes:
            rs = data.get(f"{a}:{s}:single")
            rm = data.get(f"{a}:{s}:multi")
            if rs is None and rm is None:
                continue
            if rs and rs["status"] == "skipped":
                lines.append(f"| {a} | {s} | skip | skip | — |")
                continue

            def st(r):
                if r is None:
                    return "—", ""
                if r["status"] != "ok":
                    return r["status"].upper(), ""
                gb = r["bytes_per_device"] / 1e9
                tag = "ok" if r["bytes_per_device"] <= HBM else "ok†"
                return tag, f"{gb:.1f}"
            s1, g1 = st(rs)
            s2, g2 = st(rm)
            lines.append(f"| {a} | {s} | {s1} | {s2} | {g1}/{g2} |")
    n_ok = sum(1 for v in data.values() if v["status"] == "ok")
    n_skip = sum(1 for v in data.values() if v["status"] == "skipped")
    lines.append("")
    lines.append(f"**{n_ok} / {len(data)} cells compile** "
                 f"({n_skip} documented long_500k skips, "
                 f"{len(data) - n_ok - n_skip} failures).  "
                 "† = exceeds the 16 GB/device HBM budget in the "
                 "paper-faithful BASELINE lowering — each is brought under "
                 "budget by the §Perf optimizations (A2/B1/C2), kept "
                 "baseline here per the reproduce-then-optimize protocol.")
    return "\n".join(lines)


def roofline_table(data):
    lines = ["| arch | shape | t_comp | t_mem | t_coll | bound | useful | "
             "GB/dev |", "|---|---|---|---|---|---|---|---|"]
    for k in sorted(data):
        if not k.endswith(":single"):
            continue
        r = data[k]
        if r["status"] != "ok":
            continue
        a, s, _ = k.split(":")
        ro = r["roofline"]
        useful = r["useful_compute_frac"]
        note = "‡" if a in RECURRENT and s in ("train_4k", "prefill_32k") \
            else ""
        lines.append(
            f"| {a} | {s} | {fmt_t(ro['t_compute'])} | "
            f"{fmt_t(ro['t_memory'])} | {fmt_t(ro['t_collective'])} | "
            f"{ro['bound']} | {useful:.2f}{note} | "
            f"{r['bytes_per_device'] / 1e9:.1f} |")
    lines.append("")
    lines.append(
        "‡ recurrent archs: the WKV/SSD chunk scans stay rolled even in "
        "the unrolled analysis build, so the HLO compute term undercounts "
        "the recurrence — MODEL_FLOPS (the `useful` numerator) is the "
        "reference for those cells.  Dominant-term one-liners: train cells "
        "are memory-bound (remat re-reads + FSDP gathers — cut by larger "
        "microbatches or 2.5-D sharding); prefill cells memory-bound "
        "(flash tiles already minimal — next lever is int8 weights); "
        "decode cells collective-bound in the baseline (cache re-gather — "
        "fixed in §Perf by sequence-sharded caches); MoE cells "
        "dispatch-bound (fixed in §Perf by sharded dispatch buffers).")
    return "\n".join(lines)


def _splice(text, begin, end, body):
    i, j = text.index(begin) + len(begin), text.index(end)
    return text[:i] + "\n" + body + "\n" + text[j:]


# ---------------------------------------------------------------------------
# README §Results from results/BENCH_*.json
# ---------------------------------------------------------------------------

def _latest(pattern):
    """Newest artifact matching results/BENCH_<pattern>*.json, parsed."""
    hits = sorted(glob.glob(os.path.join(ROOT, "results",
                                         f"BENCH_{pattern}*.json")),
                  key=os.path.getmtime)
    if not hits:
        return None
    with open(hits[-1]) as f:
        return json.load(f)


def readme_results_table() -> str:
    lines = ["| benchmark | cell | CPU rows/s | v5e rows/s: roofline "
             "projection, not measured | notes |",
             "|---|---|---|---|---|"]
    n = 0
    serving = _latest("serving")
    if serving:
        for mname, p in serving.get("prefix", {}).items():
            lines.append(
                f"| serving (prefix cache) | {mname} off→on | "
                f"{p['rows_per_s_off']:.1f} → {p['rows_per_s_on']:.1f} | "
                f"— | {p['prefill_token_reduction'] * 100:.0f}% prefill "
                f"tokens saved, outputs identical="
                f"{p['outputs_identical']} |")
            n += 1
    mt = _latest("multitenant")
    mt_cells = (mt or {}).get("cells") or []
    if mt_cells:
        nmax = max(c["tenants"] for c in mt_cells)
        for c in mt_cells:
            if c["tenants"] != nmax:
                continue
            lines.append(
                f"| multi-tenant | {c['fleet']} x{c['tenants']} tenants | "
                f"{c['rows_per_s']:.1f} | {c['v5e_rows_per_s']:.0f} | "
                f"{c['resident']} resident models |")
            n += 1
    casc = _latest("cascade")
    if casc:
        lines.append(
            f"| cascade | budget {casc['budget']:g} | "
            f"{casc['rows'] / max(casc['wall_s_cascade'], 1e-9):.1f} | — | "
            f"{casc['ratio']:g}x fewer full-model rows "
            f"({casc['full_rows_base']} → {casc['full_rows_cascade']}), "
            f"acc {casc['acc_base']:.2f} → {casc['acc_cascade']:.2f}, "
            f"escalation {casc['escalation_rate'] * 100:.0f}% |")
        n += 1
    dp = _latest("device_parallel")
    for c in (dp or {}).get("cells") or []:
        lines.append(
            f"| device-parallel | {c['cell']} | "
            f"{c['rows_per_s']:.1f} | {c['v5e_rows_per_s']:.0f} | "
            f"{c['resident']} resident, "
            f"{c['concurrent_devices']} devices in flight |")
        n += 1
    if n == 0:
        return ("_No `results/BENCH_*.json` artifacts found — run the "
                "benchmarks with `--json` first (see below)._")
    lines.append("")
    lines.append("_`CPU rows/s` is host wall clock of the `--smoke` runs on "
                 "a CPU, not a device metric.  The v5e column is a "
                 "roofline projection from peak constants (aggregate over "
                 "resident engines); no chip measured it.  Regenerate: run "
                 "the four "
                 "benchmarks with `--json results/BENCH_<name>.json`, "
                 "then `python benchmarks/render_experiments.py "
                 "--readme`._")
    return "\n".join(lines)


def render_readme() -> None:
    with open(README) as f:
        text = f.read()
    text = _splice(text, "<!-- RESULTS_BEGIN -->", "<!-- RESULTS_END -->",
                   readme_results_table())
    with open(README, "w") as f:
        f.write(text)
    print(f"rendered results/BENCH_*.json into {README}")


def main(readme: bool = False):
    if readme:
        render_readme()
        return
    if not (os.path.exists(RESULTS) and os.path.exists(EXP)):
        print(f"skipping EXPERIMENTS render: needs {RESULTS} and {EXP} "
              "(run the dry-run first); use --readme for the README "
              "results table")
        return
    with open(RESULTS) as f:
        data = json.load(f)
    with open(EXP) as f:
        text = f.read()
    text = _splice(text, "<!-- DRYRUN_BEGIN -->", "<!-- DRYRUN_END -->",
                   dryrun_table(data))
    text = _splice(text, "<!-- ROOFLINE_BEGIN -->", "<!-- ROOFLINE_END -->",
                   roofline_table(data))
    with open(EXP, "w") as f:
        f.write(text)
    print(f"rendered {RESULTS} into {EXP}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--readme", action="store_true",
                    help="regenerate README.md §Results from "
                         "results/BENCH_*.json")
    args = ap.parse_args()
    main(readme=args.readme)
