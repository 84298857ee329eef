"""AOT compiles of the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which ships with the installed jaxlib,
compiles each kernel at real model widths for a chip that is described,
not attached.  That catches what interpret mode cannot — block shapes
the TPU lowering refuses, unaligned slices, VMEM overuse — at no chip
time.  Widths: gemma3-1b decode (4 q / 1 kv head of 256, d_model 1152,
d_ff 6912, 512 window), and gemma2-2b's 4 kv heads for the paged
kernel's multi-head block.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

SLOTS = 8           # decode slots
BLOCK = 32          # paged KV block size
MAX_LEN = 1024      # per-slot context: 32 blocks, past the 512 window


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text      # the kernel, not a fallback
    return text


@pytest.mark.parametrize("heads,kv_heads,window", [
    (4, 1, 512),          # gemma3-1b: MQA, local layers
    (8, 4, 0),            # gemma2-2b widths: Kh=4 K/V blocks
])
def test_paged_attention_compiles(chip, heads, kv_heads, window):
    nblk = MAX_LEN // BLOCK
    nb = SLOTS * nblk + 1
    pool = ((nb, BLOCK, kv_heads, 256), jnp.bfloat16)
    _compile(lambda q, k, v, t, n: ops.paged_attention(
                 q, k, v, t, n, window=window, interpret=False),
             chip, ((SLOTS, 1, heads, 256), jnp.bfloat16), pool, pool,
             ((SLOTS, nblk), jnp.int32), ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("k,n", [(1152, 6912), (6912, 1152), (1024, 1152)])
def test_quant_matmul_compiles(chip, k, n):
    group = 128
    _compile(lambda x, q, s: ops.quant_matmul(x, q, s, group=group,
                                              interpret=False),
             chip, ((SLOTS, k), jnp.bfloat16), ((k, n), jnp.int8),
             ((k // group, n), jnp.float32))


def test_block_sparse_matmul_compiles(chip):
    k, n, bs = 1152, 6912, 128
    keep = int(round(0.75 * (k // bs)))
    _compile(lambda x, w, idx: ops.block_sparse_matmul(x, w, idx, bs=bs,
                                                       interpret=False),
             chip, ((SLOTS, k), jnp.bfloat16), ((k, n), jnp.bfloat16),
             ((n // bs, keep), jnp.int32))
