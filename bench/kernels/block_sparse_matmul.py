"""``block_sparse_matmul``: x [M, K] bf16 times a bf16 weight [K, N]
stored in ``bs`` x ``bs`` tiles of which ``kept`` are nonzero, out
[M, N] bf16.

Operations: one multiply-add per (m, k, n) of the kept tiles.  Bytes:
x once, the kept tiles once, the output once.
"""


def ops(M: int, K: int, N: int, bs: int, kept: int) -> float:
    return 2.0 * M * kept * bs * bs


def bytes_moved(M: int, K: int, N: int, bs: int, kept: int) -> float:
    return 2.0 * M * K + 2.0 * kept * bs * bs + 2.0 * M * N
