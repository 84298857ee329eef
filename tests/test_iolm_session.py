"""End-to-end coverage of the instance-optimization workflow:
``IOLMSession._optimize`` recipe search, identity-model fallback, and
the ``ModelCache`` hit/miss/eviction + data-signature paths."""
import numpy as np
import pytest

from repro.core import policy as POL
from repro.core.pipeline import InstanceOptimizer, Recipe
from repro.models import api
from repro.olap.query import IOLMSession, ModelCache, OptimizedModel


W8 = Recipe(name="w8", wbits=8, quant_method="absmax")


@pytest.fixture(scope="module")
def tiny(tiny_dense):
    return tiny_dense


def make_session(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("recipes", [W8])
    kw.setdefault("calib_rows", 4)
    kw.setdefault("eval_rows", 2)
    kw.setdefault("engine_kw", dict(slots=2, max_len=64, buckets=(32,)))
    return IOLMSession(params, cfg, **kw)


PROMPTS = [f"fix: categ{i}" for i in range(8)]


class TestOptimizeWorkflow:
    def test_optimize_runs_search_and_versions_model(self, tiny):
        sess = make_session(tiny)
        m = sess._optimize("qsig1", PROMPTS)
        assert isinstance(m, OptimizedModel)
        # version ties the model to (query, DATA, recipe): compression
        # is calibration-dependent, so the data signature is part of
        # the identity
        dsig = sess.model_cache.data_signature(PROMPTS)
        assert m.version == f"qsig1:{dsig}:w8"
        assert m.recipe.name == "w8"
        assert m.report is not None and m.report.compression > 1.0
        assert any("picked w8" in line for line in sess.log)
        # the compressed params actually run
        logits, _ = api.forward(m.params, m.cfg,
                                {"tokens": np.ones((1, 8), np.int32)})
        assert logits.shape[-1] == m.cfg.vocab_size

    def test_model_cache_hit_skips_reoptimization(self, tiny):
        sess = make_session(tiny)
        m1 = sess._optimize("qsig1", PROMPTS)
        n_log = len(sess.log)
        m2 = sess._optimize("qsig1", PROMPTS)
        assert m2 is m1                          # memoized, not re-searched
        assert sess.model_cache.hits == 1
        assert any("model cache hit" in line for line in sess.log[n_log:])

    def test_distinct_data_resolves_to_distinct_models(self, tiny):
        sess = make_session(tiny)
        m1 = sess._optimize("qsig1", PROMPTS)
        m2 = sess._optimize("qsig1", [p + "x" for p in PROMPTS])
        assert sess.model_cache.hits == 0
        assert len(sess.model_cache) == 2
        # same query over different data must NOT share a model version:
        # a pool keyed on version would otherwise serve tenant B through
        # tenant A's data-calibrated params
        assert m1.version != m2.version

    def test_identity_fallback_when_no_recipe_survives(self, tiny,
                                                       monkeypatch):
        """Empty search outcome (every recipe inapplicable / below the
        acc floor with no candidates at all) -> the session falls back
        to the uncompressed identity model instead of failing."""
        cfg, params = tiny
        sess = make_session(tiny)

        def empty_search(opt, eval_fn, recipes, *, acc_floor, keep_params):
            base = eval_fn(opt.params, opt.cfg)
            return POL.SearchOutcome(baseline=base, candidates=[],
                                     perf=None, acc=None)

        monkeypatch.setattr("repro.olap.query.POL.search", empty_search)
        m = sess._optimize("qsig1", PROMPTS)
        assert m.version == "base"
        assert m.recipe.name == "identity"
        assert m.params is params                # the base model, unchanged
        # the fallback is cached like any other outcome
        assert sess._optimize("qsig1", PROMPTS) is m

    def test_dropped_recipe_is_recorded(self, tiny, monkeypatch):
        """A recipe that raises is reported with its exception — in the
        search outcome and on the session — never dropped silently."""
        apply = InstanceOptimizer.apply

        def flaky(self, recipe):
            if recipe.name == "boom":
                raise ValueError("no such layer")
            return apply(self, recipe)

        monkeypatch.setattr(InstanceOptimizer, "apply", flaky)
        sess = make_session(tiny, recipes=[W8, Recipe(name="boom", wbits=8)])
        assert sess._optimize("qsig1", PROMPTS).recipe.name == "w8"
        assert sess.dropped_recipes == [
            ("qsig1", "boom", "ValueError: no such layer")]

    @pytest.mark.parametrize("recipe,hessian", [
        (W8, False), (Recipe(name="w8-gptq", wbits=8), True)])
    def test_hessian_gathered_only_for_recipes_that_read_it(
            self, tiny, monkeypatch, recipe, hessian):
        seen = []
        run = InstanceOptimizer.run_calibration

        def spy(self, batch, *, hessian=True):
            seen.append(hessian)
            return run(self, batch, hessian=hessian)

        monkeypatch.setattr(InstanceOptimizer, "run_calibration", spy)
        make_session(tiny, recipes=[recipe])._optimize("qsig1", PROMPTS)
        assert seen == [hessian]

    def test_acc_objective_picks_acc_variant(self, tiny):
        sess = make_session(tiny, objective="acc",
                            recipes=[W8, Recipe(name="w4", wbits=4,
                                                group=32)])
        m = sess._optimize("qsig1", PROMPTS)
        assert m.version.startswith("qsig1:")
        # acc objective maximizes agreement; w8 dominates w4 here
        assert m.recipe.name == "w8"


class TestModelCache:
    def _m(self, tag):
        return OptimizedModel(None, None, None, Recipe(name=tag), tag)

    def test_signature_sees_past_first_64_values(self):
        head = [f"v{i}" for i in range(64)]
        a = head + ["tail-a"]
        b = head + ["tail-b"]
        assert ModelCache.data_signature(a) != ModelCache.data_signature(b)

    def test_signature_sees_value_count(self):
        vals = [f"v{i}" for i in range(70)]
        assert (ModelCache.data_signature(vals)
                != ModelCache.data_signature(vals + [vals[-1]]))

    def test_signature_separates_long_values_with_common_prefix(self):
        base = "x" * 300
        assert (ModelCache.data_signature([base + "a"])
                != ModelCache.data_signature([base + "ab"]))

    def test_signature_deterministic(self):
        vals = [f"row{i}" for i in range(100)]
        assert (ModelCache.data_signature(vals)
                == ModelCache.data_signature(list(vals)))

    def test_capacity_cap_evicts_lru(self):
        mc = ModelCache(capacity=2)
        mc.put("q1", "d", self._m("m1"))
        mc.put("q2", "d", self._m("m2"))
        assert mc.get("q1", "d") is not None     # refresh q1
        mc.put("q3", "d", self._m("m3"))         # evicts q2, not q1
        assert len(mc) == 2 and mc.evictions == 1
        assert mc.get("q2", "d") is None
        assert mc.get("q1", "d") is not None

    def test_unbounded_tenant_stream_stays_capped(self):
        mc = ModelCache(capacity=8)
        for i in range(100):
            mc.put(f"q{i}", "d", self._m(f"m{i}"))
        assert len(mc) == 8 and mc.evictions == 92

    def test_eviction_follows_recency_order_exactly(self):
        mc = ModelCache(capacity=3)
        for tag in ("a", "b", "c"):
            mc.put(tag, "d", self._m(tag))
        assert mc.get("a", "d") is not None      # order now b, c, a
        mc.put("x", "d", self._m("x"))           # evicts b
        mc.put("y", "d", self._m("y"))           # evicts c
        assert mc.get("b", "d") is None and mc.get("c", "d") is None
        assert all(mc.get(t, "d") is not None for t in ("a", "x", "y"))

    def test_capacity_one_keeps_only_latest(self):
        mc = ModelCache(capacity=1)
        mc.put("q1", "d", self._m("m1"))
        assert len(mc) == 1 and mc.evictions == 0
        mc.put("q2", "d", self._m("m2"))
        assert len(mc) == 1 and mc.evictions == 1
        assert mc.get("q1", "d") is None
        assert mc.get("q2", "d").version == "m2"

    def test_put_existing_key_replaces_without_eviction(self):
        mc = ModelCache(capacity=2)
        mc.put("q1", "d", self._m("old"))
        mc.put("q2", "d", self._m("m2"))
        mc.put("q1", "d", self._m("new"))        # replace, at capacity
        assert len(mc) == 2 and mc.evictions == 0
        assert mc.get("q1", "d").version == "new"
        mc.put("q3", "d", self._m("m3"))         # now q2 is LRU
        assert mc.get("q2", "d") is None and mc.evictions == 1

    def test_hit_and_eviction_accounting_on_repeated_get_put(self):
        mc = ModelCache(capacity=2)
        assert mc.get("q1", "d") is None         # miss: no hit counted
        assert mc.hits == 0
        mc.put("q1", "d", self._m("m1"))
        for _ in range(3):
            assert mc.get("q1", "d") is not None
        assert mc.hits == 3
        # distinct data signature is a distinct entry, not a hit
        assert mc.get("q1", "other-dsig") is None
        assert mc.hits == 3
        for i in range(4):
            mc.put(f"q{i + 2}", "d", self._m(f"m{i}"))
        assert mc.evictions == 3 and len(mc) == 2
        # evicted entries miss; counters are monotone
        assert mc.get("q1", "d") is None and mc.hits == 3
