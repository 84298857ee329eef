"""Finding a cell's parts by name.

``BENCHMARK.json`` at the root of the checkout lists the cells; a cell
names a configuration file and a traffic mix file, and the per-layer
metrics name their readers.  Nothing here knows a particular cell.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

# keys of a configuration file that describe it rather than size it
CONFIG_META = ("source", "reduced", "published", "assumed", "deployment",
               "reference")


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # the configuration file, as written
    mix_name: str
    mix: Dict[str, Any]             # the traffic mix file, as written
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)

    def model_kwargs(self) -> Dict[str, Any]:
        """The configuration's sizes, as the program's ModelConfig
        takes them."""
        return {k: v for k, v in self.config.items()
                if k not in CONFIG_META}


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _for_cell(entries, cell: str) -> List[Dict[str, Any]]:
    """Metrics that a cell reports: those without a ``workloads`` key,
    and those that list the cell."""
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def find_cell(name: str, root: str = ROOT) -> Cell:
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfgs = {c["name"]: c for c in bm["configs"]}
    c = cfgs[w["config"]]
    config = load_json(os.path.join(root, c["file"]))
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config_name=c["name"],
                config=config, mix_name=w["traffic"], mix=mix,
                end_to_end=_for_cell(bm["end_to_end"], name),
                per_layer=_for_cell(bm["per_layer"], name))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``bench/metrics/<name>.py``, else the file of the name's first
    dotted part (``tick_ms.scan`` -> ``tick_ms.py``): a quantity split
    by the end-to-end metric it moves keeps one reader."""
    d = os.path.join(bench_dir, "metrics")
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(d, stem + ".py")
        if os.path.exists(path):
            return load_module(path, "bench_metric_" + stem.replace(
                ".", "_").replace("-", "_")).read
    raise FileNotFoundError(f"no reader for metric {name!r} under {d}")


def kernel_counts(kernel: str, bench_dir: str = BENCH_DIR):
    """``bench/kernels/<kernel>.py``: its ``ops(**shape)`` and
    ``bytes_moved(**shape)``."""
    return load_module(os.path.join(bench_dir, "kernels", kernel + ".py"),
                       "bench_kernel_" + kernel)


def kernels(bench_dir: str = BENCH_DIR) -> List[str]:
    """The kernels the trace reduction attributes: the stems of
    ``bench/kernels/*.py``, one file for each kernel."""
    d = os.path.join(bench_dir, "kernels")
    return sorted(f[:-len(".py")] for f in os.listdir(d)
                  if f.endswith(".py"))


def expert_kernel(bench_dir: str = BENCH_DIR) -> Optional[str]:
    """The kernel whose counts file declares ``TAKES = "experts"``: the
    one that runs an instance's expert stacks.  None where no file
    declares it; two that do are an error."""
    named = [k for k in kernels(bench_dir)
             if getattr(kernel_counts(k, bench_dir), "TAKES", None)
             == "experts"]
    if len(named) > 1:
        raise ValueError(f"more than one kernel takes the expert stacks: "
                         f"{named}")
    return named[0] if named else None


def reference_module(config: Dict[str, Any], bench_dir: str = BENCH_DIR):
    """The plain reference the configuration names
    (``bench/reference/<name>.py``)."""
    return load_module(os.path.join(bench_dir, "reference",
                                    config["reference"] + ".py"),
                       "bench_reference_" + config["reference"])


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
