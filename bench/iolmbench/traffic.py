"""The one traffic generator: a mix file's parameters -> table and
arrivals.

A mix (``bench/traffic/<mix>.json``) is data only:

``table_rows``        rows in the review column
``text_bytes``        [lo, hi]: each value's text length, log-uniform
``length_seed``       fixes the lengths (and the duplicate pattern), so
                      every ``--seed`` draws the same sizes: the seed
                      changes the text, and the order of the arrivals
``values``            optional ``{"distinct": n, "zipf": s}``: the
                      column holds ``n`` distinct values, each row one
                      of them drawn with probability ~ 1/rank**s (the
                      duplicates that dedup and the result cache
                      answer); without it every row is distinct
``instruction``       the operator's prompt template
``shared_prefix``     optional, default true: rows are submitted with the
                      template as their shared prefix (the prefix cache
                      prefills it once); false submits whole prompts
``max_new``           tokens per row
``setup_rows``        rows of the set-up query (its probe is the first
                      rows of the column, as the window's is)
``arrivals``          ``{"kind": "backlog"}``: one request for the whole
                      column, due at the start (a closed backlog);
                      ``{"kind": "open_loop", "rate_per_s",
                      "rows_per_request": [lo, hi], "schedule_seed"[,
                      "gap_cv"]}``: requests of lo..hi rows arriving
                      open-loop, the gaps between them of mean
                      1/rate and coefficient of variation ``gap_cv``
                      (1, the default, is a Poisson process; above 1,
                      bursts); either may give ``drain_s``, how long
                      requests still open at the window's close are
                      waited for (default 60)
``session``           the IOLMSession, engine and scheduler settings;
                      ``serve`` (default ``compressed``) says whether the
                      window is served by the query's instance-optimized
                      model or by the base model

The review sentences are copied from ``repro/training/data.py``
(``gen_review``) so that a program change cannot move them.  Each value
starts with its id, so no two distinct values are equal.
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

_PRODUCTS = ["headphones", "keyboard", "monitor", "webcam", "microphone",
             "laptop stand", "usb hub", "desk lamp", "office chair",
             "mouse pad", "router", "speaker", "charger", "tablet",
             "smartwatch", "printer"]
_ADJ_POS = ["great", "excellent", "fantastic", "solid", "amazing",
            "reliable", "superb", "crisp"]
_ADJ_NEG = ["terrible", "awful", "flimsy", "noisy", "laggy",
            "disappointing", "cheap", "broken"]
_FILLER = ["I bought this last month.", "Shipping was fast.",
           "The packaging was fine.", "My friend recommended it.",
           "I use it every day.", "Setup took five minutes.",
           "Color matches the photos.", "Works with my setup."]


def seed_words(seed: int, *salt: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words from any whole-number seed (and salts), so
    seeds past 32 bits stay distinct."""
    return np.random.SeedSequence([int(seed) % (1 << 64), *salt]) \
        .generate_state(n)


def _rng(seed: int, *salt: int) -> random.Random:
    return random.Random(int(seed_words(seed, *salt, n=2).view(np.uint64)[0]))


def text_lengths(mix: Dict, n: int) -> List[int]:
    """Value text lengths in bytes, log-uniform over ``text_bytes``,
    from the mix's own ``length_seed`` (the same for every run seed)."""
    lo, hi = mix["text_bytes"]
    r = _rng(mix["length_seed"], 1)
    return [int(math.floor(math.exp(r.uniform(math.log(lo),
                                              math.log(hi + 1)))))
            for _ in range(n)]


def review_text(seed: int, i: int, length: int) -> str:
    """Value ``i``: ``#<id> `` then review sentences, cut to ``length``
    ASCII bytes."""
    r = _rng(seed, 2, i)
    prod = r.choice(_PRODUCTS)
    pos = r.random() < 0.5
    adj = r.choice(_ADJ_POS if pos else _ADJ_NEG)
    fill = r.sample(_FILLER, len(_FILLER))
    pieces = [f"#{i:05d}"] + fill[:2] + [f"The {prod} is {adj}."] + fill[2:]
    text = " ".join(pieces)
    while len(text) < length:
        text += " " + r.choice(_FILLER)
    return text[:length]


def value_ids(mix: Dict) -> List[int]:
    """Which distinct value each row holds: the row's own under no
    ``values`` key, else a Zipf draw from ``length_seed`` (the same
    duplicate pattern for every run seed)."""
    n = int(mix["table_rows"])
    vals = mix.get("values")
    if not vals:
        return list(range(n))
    k, s = int(vals["distinct"]), float(vals["zipf"])
    p = 1.0 / np.arange(1, k + 1, dtype=np.float64) ** s
    g = np.random.default_rng(seed_words(mix["length_seed"], 6, n=4))
    return g.choice(k, size=n, p=p / p.sum()).tolist()


def review_column(mix: Dict, seed: int) -> List[str]:
    ids = value_ids(mix)
    k = int(mix["values"]["distinct"]) if mix.get("values") else len(ids)
    texts = [review_text(seed, i, L)
             for i, L in enumerate(text_lengths(mix, k))]
    return [texts[i] for i in ids]


def admitted_tokens(text: str, template: str, shared: bool,
                    buckets: Sequence[int], max_len: int) -> int:
    """Tokens of one row as the engine prefills it: the value's bytes
    and SEP after a shared template (prefilled once, apart), or the
    whole prompt where the template is not shared or the split would
    not fit the top bucket below ``max_len``."""
    n_t = len(template.encode("utf-8")) + 1            # BOS + template
    n_v = len(text.encode("utf-8")) + 1                # value + SEP
    if shared and text and n_t + n_v <= buckets[-1] \
            and n_t + bucket_of(n_v, buckets) <= max_len - 1:
        return n_v
    return n_t + n_v


def bucket_of(n: int, buckets: Sequence[int]) -> int:
    return next((b for b in sorted(buckets) if b >= n), max(buckets))


def longest_per_bucket(column: Sequence[str], template: str, shared: bool,
                       buckets: Sequence[int], max_len: int
                       ) -> Dict[int, int]:
    """For each length bucket the column's rows fall in, the longest
    value's length in bytes: the shapes set-up has to warm."""
    out: Dict[int, int] = {}
    for L in sorted({len(t.encode("utf-8")) for t in column}):
        b = bucket_of(admitted_tokens("x" * L, template, shared, buckets,
                                      max_len), buckets)
        out[b] = L
    return out


@dataclass(frozen=True)
class Arrival:
    due_s: float                 # offset from the window's start
    rows: Tuple[int, ...]        # row indices into the column


def schedule(mix: Dict, seed: int, seconds: float) -> List[Arrival]:
    """The window's requests.  A backlog is one request for the whole
    column at time 0.  An open-loop mix draws its gaps and request
    sizes once from ``schedule_seed`` (the same multiset for every run
    seed, so every run offers the same work) and orders them by
    ``seed``; rows are taken in column order after the set-up query's."""
    arr = mix["arrivals"]
    if arr["kind"] == "backlog":
        return [Arrival(0.0, tuple(range(int(mix["table_rows"]))))]
    if arr["kind"] != "open_loop":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    n = max(1, int(round(float(arr["rate_per_s"]) * seconds)))
    r = _rng(arr["schedule_seed"], 3)
    cv = float(arr.get("gap_cv", 1.0))
    gaps = [r.gammavariate(1.0 / cv ** 2, cv ** 2) for _ in range(n + 1)]
    lo, hi = arr["rows_per_request"]
    sizes = [r.randint(lo, hi) for _ in range(n)]
    order = _rng(seed, 4)
    order.shuffle(gaps)
    order.shuffle(sizes)
    scale = seconds / sum(gaps)          # n arrivals inside (0, seconds)
    t, nxt, out = 0.0, int(mix["setup_rows"]), []
    for g, k in zip(gaps, sizes):
        t += g * scale
        if nxt + k > int(mix["table_rows"]):
            raise ValueError("the mix's table is too small for its "
                             "arrivals at this length")
        out.append(Arrival(t, tuple(range(nxt, nxt + k))))
        nxt += k
    return out
