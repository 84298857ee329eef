"""``quant_matmul``'s share of its roofline in the window, in %: the
least time its calls could take (``bench/kernels/quant_matmul.py``)
over the device time of its operations in the trace."""
from iolmbench.roofline import matmul_share


def read(ctx):
    return matmul_share(ctx, "quant_matmul")
