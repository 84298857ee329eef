"""A cell shrunk for a CPU rehearsal: the same traffic, window loop,
check and metric arithmetic, on a small model of the same family
(grouped or multi-query attention, gated or plain MLP, as configured;
one period of the layer pattern, its window the longest prompt, so
that it masks the decode steps past it; a mixture of experts cut to 8
experts, 2 a token) with four slots, the reference kernels' formulas,
and the int8 recipe alone (at these sizes the search could pick either
recipe)."""
from __future__ import annotations

from typing import Any, Dict, Tuple

from iolmbench import check, spec, weights


def shrink(cell: spec.Cell) -> Dict[str, Any]:
    """Cut ``cell``'s session to the rehearsal's in place, and return
    the sizes of its small model."""
    s = cell.mix["session"]
    s["engine"]["slots"] = 4
    s["share"] = 4
    s["backend"] = "reference"
    s["recipes"] = [r for r in s["recipes"] if r["name"] == "w8-absmax"]
    cell.mix["table_rows"] = 1000
    c = cell.config
    kv = 1 if c["n_kv_heads"] == 1 else 2
    sizes = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=kv,
                 head_dim=32, d_ff=256, vocab_size=512)
    if c.get("attn_pattern"):
        # the window is the longest prompt: every whole-sequence forward
        # of set-up fits in it (the program's blocked window attention
        # takes a longer sequence only in whole windows), and the decode
        # steps past it mask
        longest = len(check.encode(cell.mix["instruction"] + "x" * int(
            cell.mix["text_bytes"][1])))
        unit = weights.pattern_unit(c)[0]
        sizes.update(n_layers=len(unit), attn_pattern=unit,
                     window_size=min(int(c.get("window_size", 4096)),
                                     longest))
    if c["family"] == "moe":
        sizes.update(n_experts=8, top_k=2, moe_d_ff=64)
    return sizes


def tiny_cell(name: str) -> Tuple[spec.Cell, Dict[str, Any]]:
    cell = spec.find_cell(name)
    return cell, shrink(cell)
