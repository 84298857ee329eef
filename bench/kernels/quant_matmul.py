"""``quant_matmul``: x [M, K] bf16 times int8 codes [K, N] with one f32
scale per (group of ``group`` rows of K, column), out [M, N] bf16.

Operations: one multiply-add per (m, k, n).  Bytes: the least the
call must move through HBM — x once, the codes once, the scales once,
the output once.  (The kernel's own tiling may read more; that is what
its roofline share shows.)
"""


def ops(M: int, K: int, N: int, group: int) -> float:
    return 2.0 * M * K * N


def bytes_moved(M: int, K: int, N: int, group: int) -> float:
    return 2.0 * M * K + 1.0 * K * N + 4.0 * (K // group) * N + 2.0 * M * N
