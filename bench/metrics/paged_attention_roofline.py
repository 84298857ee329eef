"""``paged_attention``'s share of its roofline in the window, in %:
the least time its calls could take for the KV blocks of the live
lengths the engine held (``bench/kernels/paged_attention.py``) over the
device time of its operations in the trace."""
from iolmbench.roofline import attention_share


def read(ctx):
    return attention_share(ctx, "paged_attention")
