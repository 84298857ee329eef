"""What decides ``correct``: the served tokens against the plain
reference.

Once the window has closed and the program's state is freed, a sample
of the rows the window finished, drawn from the seed and holding the
longest of them, is run through the configuration's plain reference
(float32, full-precision matmuls) with its served tokens.  At every
served token the number read is the gap by which that token's
reference logit lies below the reference's best logit at that position
(0 where the served token is the reference's own greedy choice).  The
number compared is the widest such gap of the sample.

The control puts the reference in the program's place one precision
step below the served instance (int4 weights where the instance holds
int8): at each position of the same rows it takes the token the
lower precision puts first and reads its gap the same way.
"""
from __future__ import annotations

import random
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from iolmbench.serve import Served
from iolmbench.traffic import seed_words

# the program's byte tokenizer: ids 0..3 are PAD, BOS, EOS, SEP, then
# the 256 bytes
BOS, SEP, OFFSET = 1, 3, 4


def encode(prompt: str) -> List[int]:
    """A prompt as the engine admits it: BOS, its bytes, SEP."""
    return [BOS] + [b + OFFSET for b in prompt.encode("utf-8")] + [SEP]


def distinct(served: Sequence[Served]) -> List[Served]:
    """The first finished row of each prompt.  Rows are in submission
    order, so that is the row a slot decoded; a later row of the same
    prompt was answered by the result cache or rode on it in flight,
    its tokens re-encoded from the text (``differing`` checks those)."""
    seen, out = set(), []
    for r in served:
        if r.prompt not in seen:
            seen.add(r.prompt)
            out.append(r)
    return out


def differing(served: Sequence[Served]) -> int:
    """Rows whose answer differs from the first answer to the same
    prompt: dedup and the result cache must answer exactly."""
    first: Dict[str, str] = {}
    return sum(first.setdefault(r.prompt, r.text) != r.text for r in served)


def sample(served: Sequence[Served], seed: int, n: int) -> List[Served]:
    """``n`` finished rows drawn from the seed, the longest first."""
    if not served:
        return []
    longest = max(range(len(served)), key=lambda i: (
        len(served[i].prompt) + len(served[i].out_ids)))
    rest = [i for i in range(len(served)) if i != longest]
    r = random.Random(int(seed_words(seed, 5, n=2).view(np.uint64)[0]))
    pick = [longest] + r.sample(rest, min(n - 1, len(rest)))
    return [served[i] for i in pick]


def recipe_bits(recipe: Dict[str, Any]) -> Optional[int]:
    """The weight bits of a recipe the dense reference can state, or
    None: the model as given (16), or plain absmax rounding of every
    projection, nothing pruned."""
    plain = {"name", "wbits", "quant_method", "group"}
    bits = int(recipe.get("wbits", 16))
    if set(recipe) - plain or (bits < 16 and
                               recipe.get("quant_method") != "absmax"):
        return None
    return bits


def _batch(rows: Sequence[Served], length: int, batch: int):
    """Token rows (prompt + served tokens but the last), right-padded to
    ``length``, and the positions whose logits pick each served token.
    Short samples are filled with copies of the first row (dropped from
    the readings by ``mask``)."""
    toks = np.zeros((batch, length), np.int32)
    pos = np.zeros((batch, max(len(r.out_ids) for r in rows)), np.int32)
    served = np.zeros_like(pos)
    mask = np.zeros(pos.shape, bool)
    for b in range(batch):
        r = rows[b] if b < len(rows) else rows[0]
        p = encode(r.prompt)
        seq = p + r.out_ids[:-1]
        if len(seq) > length:
            raise ValueError(f"row of {len(seq)} tokens exceeds the "
                             f"reference length {length}")
        toks[b, :len(seq)] = seq
        n = len(r.out_ids)
        pos[b, :n] = np.arange(len(p) - 1, len(p) - 1 + n)
        pos[b, n:] = len(p) - 1
        served[b, :n] = r.out_ids
        mask[b, :n] = b < len(rows)
    return toks, pos, served, mask


def gaps(ref_logits: np.ndarray, chosen: np.ndarray,
         mask: np.ndarray) -> np.ndarray:
    """Per position: best reference logit minus the chosen token's."""
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, chosen[..., None], -1)[..., 0]
    return (best - got)[mask]


def compare(ref, weights, m: Dict[str, Any], rows: Sequence[Served], *,
            bits: int, length: int, batch: int,
            control_bits: Optional[int] = None) -> Dict[str, Any]:
    """The widest served-token gap of ``rows`` under the reference at
    ``bits``; with ``control_bits``, also the control's widest gap."""
    toks, pos, served, mask = _batch(rows, length, batch)
    ref_logits = ref.logits_at(weights, m, toks, pos, bits=bits)
    g = gaps(ref_logits, served, mask)
    out = {"rows": len(rows), "tokens": int(mask.sum()),
           "gap_max": float(g.max()), "gap_mean": float(g.mean()),
           "greedy_agree": float((g == 0).mean())}
    if control_bits is not None:
        ctrl = ref.logits_at(weights, m, toks, pos, bits=control_bits)
        gc_ = gaps(ref_logits, ctrl.argmax(-1), mask)
        out.update(control_gap_max=float(gc_.max()),
                   control_gap_mean=float(gc_.mean()),
                   control_greedy_agree=float((gc_ == 0).mean()))
    return out
