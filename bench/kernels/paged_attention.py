"""``paged_attention``: one decode query per slot against its KV blocks.

q [S, H, D] bf16; each slot ``s`` attends to ``lengths[s]`` positions
of K and V [*, Kh, D] bf16 stored in blocks of ``block`` positions.

Operations: the score and the weighted sum, one multiply-add each per
(slot, query head, position, D).  Bytes: the K and V blocks that hold
each slot's live positions, once, plus q and the output.  ``lengths``
lists the slots that held a request; idle slots need nothing.
"""
from typing import Sequence


def _blocks(n: int, block: int) -> int:
    return -(-n // block)


def ops(lengths: Sequence[int], H: int, Kh: int, D: int,
        block: int) -> float:
    return 4.0 * H * D * sum(lengths)


def bytes_moved(lengths: Sequence[int], H: int, Kh: int, D: int,
                block: int) -> float:
    kv = sum(_blocks(n, block) * block for n in lengths) * Kh * D * 2 * 2
    return kv + 2.0 * 2 * len(lengths) * H * D
