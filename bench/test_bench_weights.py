"""The weight maker's layouts against the program's own, and the dense
configuration's arrays pinned to the values they had before the
mixture-of-experts and window layouts were added."""
import hashlib
import math
import zlib

import jax
import numpy as np
import pytest

from iolmbench import weights
from iolmbench.rehearsal import tiny_cell

DENSE = dict(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
             n_kv_heads=2, head_dim=16, d_ff=96, vocab_size=260)
MOE = dict(DENSE, family="moe", n_experts=8, top_k=2, moe_d_ff=32)
LAYOUTS = {
    "dense": (DENSE, ("G", 2, 0)),
    "dense_lllg": (dict(DENSE, n_layers=8, attn_pattern="LLLG" * 2),
                   ("LLLG", 2, 0)),
    "dense_tail": (dict(DENSE, n_layers=9, attn_pattern="LLLG" * 2 + "L"),
                   ("LLLG", 2, 1)),
    "moe": (MOE, ("G", 2, 0)),
    "moe_shared": (dict(MOE, n_shared_experts=2), ("G", 2, 0)),
    "moe_lllg": (dict(MOE, n_layers=4, attn_pattern="LLLG"),
                 ("LLLG", 1, 0)),
}
# sha256 of the mistral-nemo-12b tree at the rehearsal's sizes, seed
# 2**31 + 7 (the dense layout's arrays before the others were added)
NEMO_DIGEST = "12b9af3088b3b55825312db5e7daf293f98180f687dfb2c3a2e71360ad3535da"


def _leaves(tree):
    return [(jax.tree_util.keystr(p), s.shape, s.dtype)
            for p, s in jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_shapes_are_the_programs_layout(name):
    from repro.configs.base import ModelConfig
    from repro.models import api
    from repro.models.transformer import pattern_unit
    m, split = LAYOUTS[name]
    cfg = ModelConfig(**m)
    prog = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    mine = weights.shapes(m)
    assert weights.pattern_unit(m) == pattern_unit(cfg) == split
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(prog))
    assert _leaves(mine) == _leaves(prog)


def test_moe_weights_types_and_scales():
    m = dict(MOE, n_layers=4, attn_pattern="LLLG", d_model=256,
             moe_d_ff=128, n_shared_experts=1)
    w = weights.make(m, 2 ** 33 + 5, 9)
    moe = w["blocks"][3]["moe"]
    assert moe["router"].dtype == np.float32
    assert {a.dtype for a in jax.tree_util.tree_leaves(w)} == {
        np.dtype("float32"), np.dtype(jax.numpy.bfloat16)}
    std = lambda a: float(np.asarray(a, np.float32).std())  # noqa: E731
    d, ffe, L = 256, 128, 4
    assert std(moe["router"]) == pytest.approx(1 / math.sqrt(d), rel=0.05)
    assert std(moe["wi"]) == pytest.approx(1 / math.sqrt(d), rel=0.05)
    assert std(moe["wo"]) == pytest.approx(
        1 / math.sqrt(ffe) / math.sqrt(2 * L), rel=0.05)
    assert std(w["blocks"][0]["shared_mlp"]["wo"]) == pytest.approx(
        1 / math.sqrt(ffe) / math.sqrt(2 * L), rel=0.1)
    assert float(np.asarray(w["blocks"][1]["ln2"]["w"], np.float32).min()) \
        == 1.0
    # every leaf draws from a key of its own
    assert std(moe["wi"][0, 0] - moe["wg"][0, 0]) > 0


def test_dense_weights_pinned():
    cell, sizes = tiny_cell("nemo-iolm-scan")
    kw = dict(cell.model_kwargs(), **sizes)
    w = weights.make(kw, 2 ** 31 + 7, zlib.crc32(cell.config_name.encode()))
    h = hashlib.sha256()
    for p, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(p).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.view(np.uint16).tobytes())
    assert h.hexdigest() == NEMO_DIGEST


def test_layouts_the_maker_does_not_take_are_refused():
    with pytest.raises(ValueError):
        weights.shapes(dict(DENSE, post_norms=True))
    with pytest.raises(ValueError):
        weights.shapes(dict(DENSE, family="rwkv"))
    with pytest.raises(ValueError):
        weights.shapes(dict(DENSE, attn_pattern="LG" * 2))
