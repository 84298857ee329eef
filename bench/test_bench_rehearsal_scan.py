"""CPU rehearsal of the backlog cell: the traffic, the window loop, the
reference check with its control, the metric arithmetic and the result
line, at a small size; and the command's refusal without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from iolmbench import main as M
from iolmbench.rehearsal import tiny_cell

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
DEVICE_METRICS = ("prefill_share", "quant_matmul_roofline",
                  "paged_attention_roofline", "idle_share", "mfu")


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(M, "enable_compile_cache", lambda: "off")


def test_scan_rehearsal_traced_with_control(no_cache):
    cell, sizes = tiny_cell("nemo-iolm-scan")
    out = M.run_cell(cell, 2 ** 31 + 7, 3.0, True, require_chip=False,
                     sizes=sizes, control=True)
    assert list(out)[-1] == "checks"
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == 1
    assert "busy_s" not in out["device"]         # no chip, no device time
    m = out["metrics"]
    assert not [k for k in m if k.split(".")[0] in DEVICE_METRICS]
    assert {"calibrate_s", "search_s", "slot_occupancy.scan",
            "tick_ms.scan"} <= set(m)
    assert 0 < m["slot_occupancy.scan"]["value"] <= 100
    assert m["slot_occupancy.scan"]["unit"] == "%"
    assert m["tick_ms.scan"]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    c = out["checks"]
    # the program's served tokens pass, the int4 control fails
    assert c["served_logit_gap"]["value"] <= c["served_logit_gap"]["limit"]
    assert c["control_logit_gap"]["value"] > c["control_logit_gap"]["limit"]
    assert c["truncated_prompts"]["value"] == 0
    assert out["correct"] is False          # the control came out wrong


def _run_cli(cwd, env):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         "nemo-iolm-scan", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _no_result(p):
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_command_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run_cli(ROOT, env)
    assert p.returncode != 0
    _no_result(p)
    assert "TPU" in p.stderr


def test_command_fails_with_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run_cli(str(tmp_path), env)
    assert p.returncode != 0
    _no_result(p)
    assert "No module named 'repro'" in p.stderr
