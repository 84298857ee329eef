"""Shared benchmark harness: the trained OLAP model + helpers."""
from __future__ import annotations

import os
import sys
import time
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serving.engine import Engine
from repro.training import checkpoint as CK
from repro.training import data as D
from repro.training import optimizer as OPT
from repro.training import train_loop as TL

enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))

CKPT_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                        "tiny_olap_ckpt")

MODEL_CFG = ModelConfig(name="tiny-olap", family="dense", n_layers=4,
                        d_model=128, n_heads=4, n_kv_heads=2, d_ff=384,
                        vocab_size=260, rope_theta=10000.0, max_seq=512)


def load_model(min_steps: int = 300) -> Tuple[ModelConfig, dict,
                                              D.ByteTokenizer]:
    """The benchmark LLM: trained on the three OLAP tasks (train if no
    checkpoint exists yet)."""
    tok = D.ByteTokenizer(MODEL_CFG.vocab_size)
    step = CK.latest_step(CKPT_DIR)
    if step is None or step < min_steps:
        out = TL.train(MODEL_CFG,
                       TL.TrainConfig(steps=max(min_steps, 300), batch=16,
                                      seq_len=96, log_every=100,
                                      ckpt_dir=CKPT_DIR, ckpt_every=300),
                       OPT.adamw(lr=2e-3, warmup=30,
                                 total_steps=max(min_steps, 300)))
        return MODEL_CFG, out["params"], tok
    params0 = jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), MODEL_CFG))
    opt = OPT.adamw()
    opt0 = jax.eval_shape(opt.init, params0)
    (params, _), _, _ = CK.restore(CKPT_DIR, (params0, opt0))
    return MODEL_CFG, params, tok


def make_engine(params, cfg, tok, **kw) -> Engine:
    kw.setdefault("slots", 8)
    kw.setdefault("max_len", 160)
    kw.setdefault("buckets", (48, 96, 128))
    return Engine(params, cfg, tokenizer=tok, **kw)


def slot_bytes(cfg, max_len: int = 160) -> int:
    """Per-decode-slot state bytes (KV cache / recurrent state, batch=1);
    single source of truth lives next to the ModelPool that budgets it."""
    from repro.serving.scheduler import slot_state_bytes
    return slot_state_bytes(cfg, max_len)


def slots_for_budget(params, cfg, mem_budget: int, *, max_len: int = 160,
                     max_slots: int = 32) -> int:
    """The paper's parallelism dividend: a fixed accelerator memory budget
    holds the model + N decode slots; compressing the model converts the
    freed bytes directly into more concurrent rows."""
    from repro.core.compressed import param_bytes
    free = mem_budget - param_bytes(params)
    return int(max(1, min(max_slots, free // max(slot_bytes(cfg, max_len),
                                                 1))))


def budget_engine(params, cfg, tok, mem_budget: int, **kw) -> Engine:
    s = slots_for_budget(params, cfg, mem_budget,
                         max_len=kw.get("max_len", 160))
    kw["slots"] = s
    return make_engine(params, cfg, tok, **kw)


def v5e_decode_rows_per_s(params, cfg, slots: int, avg_new: int,
                          *, max_len: int = 160, ndev: int = 1) -> float:
    """Roofline-predicted serving throughput on the TPU v5e target.

    One decode step streams the (compressed) weights + every live slot's
    cache from HBM and spends 2·N_active FLOPs per row; rows/s =
    slots / (step_time · tokens_per_row).  This is the number the CPU
    container cannot measure (serial core, no HBM) but the compiled
    artifact sizes determine: int8 weights halve the memory term, freed
    budget raises ``slots`` — the paper's two throughput mechanisms.

    ``ndev > 1`` models a tensor-parallel engine: each device streams
    and computes 1/ndev of the weights AND 1/ndev of the per-slot
    cache (cache_shardings shards KV over the model axis) per step,
    but the result is still ONE model's decode stream (the
    device-parallel benchmark compares it against ndev independent
    compressed replicas).
    """
    from repro.core.compressed import param_bytes
    from repro.launch.hlo_analysis import HBM_BW, PEAK_FLOPS
    n = max(ndev, 1)
    wb = param_bytes(params) / n
    kv = slot_bytes(cfg, max_len) / n
    flops = 2.0 * cfg.active_param_count() * slots / n
    t_step = max((wb + slots * kv) / HBM_BW, flops / PEAK_FLOPS)
    return slots / (t_step * avg_new)


def tenant_workload(i: int, n_rows: int, *, seed0: int = 100):
    """Distinct prompt template per tenant -> distinct qsig -> distinct
    compressed instance; unique row suffixes keep the result cache out
    of fleet measurements.  Shared by the multi-tenant and
    device-parallel benchmarks (different ``seed0`` per benchmark)."""
    tmpl = (f"tenant-{i} data cleaning: reply with only the canonical "
            f"category for value: ")
    rows = D.workload_rows("correct", n_rows, seed=seed0 + i)
    prompts = [f"{tmpl}{r.text}#{j}" for j, r in enumerate(rows)]
    return tmpl, prompts


def reset_pool_steady_state(pool) -> None:
    """Clear per-engine result caches + stats after a fleet benchmark's
    warmup pass, so the timed pass measures the warm pool (resident
    engines, built jit executables) rather than compilation."""
    from repro.serving.engine import EngineStats
    for entry in pool._entries.values():
        if entry.engine.result_cache is not None:
            entry.engine.result_cache.clear()
        entry.engine.stats = EngineStats()


def task_accuracy(outs: List[str], rows) -> float:
    return float(np.mean([o.strip().startswith(r.target)
                          for o, r in zip(outs, rows)]))


def timed_rows(engine: Engine, prompts: List[str], max_new: int = 20):
    t0 = time.time()
    outs = engine.generate(prompts, max_new=max_new)
    return outs, len(prompts) / (time.time() - t0)


class Csv:
    """name,us_per_call,derived accumulator (the run.py contract)."""

    def __init__(self):
        self.lines: List[str] = []

    def add(self, name: str, us_per_call: float, derived: str = ""):
        line = f"{name},{us_per_call:.1f},{derived}"
        self.lines.append(line)
        print(line, flush=True)
