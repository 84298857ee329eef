"""CPU rehearsal of an open-loop mix, and the output check against a
served token altered where the engine produces it."""
import jax.numpy as jnp
import pytest

from iolmbench import main as M
from iolmbench import serve
from iolmbench.rehearsal import tiny_cell


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(M, "enable_compile_cache", lambda: "off")


def test_stream_rehearsal_end_to_end(no_cache, monkeypatch):
    """Bursty open-loop requests over a column of Zipf-repeated values,
    whole prompts (no shared template), served by the base model: every
    one a parameter of the mix file, none a line of code."""
    built = []
    build = serve.build

    def keep(*a, **kw):
        st = build(*a, **kw)
        built.append((st.picked, st.engine))
        return st

    monkeypatch.setattr(serve, "build", keep)
    cell, sizes = tiny_cell("nemo-iolm-scan")
    cell.mix.update(
        arrivals={"kind": "open_loop", "rate_per_s": 2.0,
                  "rows_per_request": [1, 8], "schedule_seed": 21,
                  "gap_cv": 2.0, "drain_s": 60},
        values={"distinct": 40, "zipf": 1.1}, shared_prefix=False)
    cell.mix["session"]["serve"] = "base"
    cell.end_to_end = [{"name": n, "unit": u} for n, u in (
        ("rows_per_s", "rows/s"), ("query_p95_s", "s"),
        ("first_row_p95_s", "s"), ("optimize_s", "s"), ("setup_s", "s"))]
    out = M.run_cell(cell, 5, 3.0, False, require_chip=False, sizes=sizes)
    m = out["metrics"]
    assert set(m) == {"rows_per_s", "query_p95_s", "first_row_p95_s",
                      "optimize_s", "setup_s"}
    assert m["first_row_p95_s"]["value"] <= m["query_p95_s"]["value"]
    assert m["optimize_s"]["value"] < m["setup_s"]["value"]
    assert m["rows_per_s"]["unit"] == "rows/s"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["answers_differing"]["value"] == 0
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    picked, eng = built[0]
    assert picked == "base" and eng.version == "base"
    assert eng.stats.cache_hits > 0        # repeated values were answered
    assert eng.stats.prefix_hits == 0      # no shared template


def test_altered_token_fails_the_check(no_cache, monkeypatch):
    """Every token the engine samples is moved to the next id: the run
    must come out not correct."""
    import repro.serving.engine as E

    def next_token(logits, key, **kw):
        return ((jnp.argmax(logits, -1) + 1) % logits.shape[-1]
                ).astype(jnp.int32)

    monkeypatch.setattr(E, "sample", next_token)
    cell, sizes = tiny_cell("nemo-iolm-scan")
    out = M.run_cell(cell, 11, 2.0, False, require_chip=False, sizes=sizes)
    c = out["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]
    assert out["correct"] is False
