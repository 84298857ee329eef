"""A cell shrunk for a CPU rehearsal: the same traffic, window loop,
check and metric arithmetic, on a two-layer model of the same family
(grouped or multi-query attention, gated or plain MLP, as configured)
with four slots, the reference kernels' formulas, and the int8 recipe
alone (at these sizes the search could pick either recipe)."""
from __future__ import annotations

from typing import Any, Dict, Tuple

from iolmbench import spec


def tiny_cell(name: str) -> Tuple[spec.Cell, Dict[str, Any]]:
    cell = spec.find_cell(name)
    s = cell.mix["session"]
    s["engine"]["slots"] = 4
    s["share"] = 4
    s["backend"] = "reference"
    s["recipes"] = [r for r in s["recipes"] if r["name"] == "w8-absmax"]
    cell.mix["table_rows"] = 1000
    kv = 1 if cell.config["n_kv_heads"] == 1 else 2
    sizes = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=kv,
                 head_dim=32, d_ff=256, vocab_size=512)
    return cell, sizes
