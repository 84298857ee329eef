"""Jitted hot-path auditor (analysis/jit_audit.py).

The load-bearing pair: the audit runs CLEAN on the real engine (the CI
gate), and FIRES when a regression is deliberately injected — a host
sync inside the jitted decode step, a call site that leaks a donated
buffer, a value-driven retrace.  A checker that can't fail proves
nothing, so every code the clean run relies on has an injection test.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import jit_audit as JA
from repro.serving.engine import Engine


@pytest.fixture(scope="module")
def audited(tiny_dense):
    """One full audit of a clean engine, shared by the assertions."""
    cfg, params = tiny_dense
    engine = Engine(params, cfg)
    report = JA.audit_engine(engine)
    return engine, report


class TestCleanEngine:
    def test_zero_diagnostics(self, audited):
        _, report = audited
        assert report.diagnostics == [], [d.to_dict()
                                          for d in report.diagnostics]

    def test_one_compile_per_bucket_shape(self, audited):
        """The retrace invariant, stated positively: every jitted
        target compiled exactly once per distinct input signature the
        scripted workload produced."""
        _, report = audited
        assert report.cache_stats           # workload hit every target
        for name, s in report.cache_stats.items():
            assert s["compiles"] == s["signatures"], (name, s)
            assert s["calls"] >= s["signatures"]

    def test_workload_covers_prefill_ladder(self, audited):
        engine, report = audited
        prefills = [n for n in report.cache_stats if "_prefill[" in n]
        assert len(prefills) >= 2           # short + long bucket
        assert any("_prefill_from[" in n for n in report.cache_stats)
        assert "_decode" in report.cache_stats
        assert "_insert" in report.cache_stats
        assert "_next_tok" in report.cache_stats

    def test_budget_extracted(self, audited):
        _, report = audited
        assert report.budget is not None
        assert report.budget["flops"] > 0
        assert report.budget["coll_bytes"] == 0   # single-device engine

    def test_audit_restores_engine_targets(self, audited):
        """The proxies must not outlive the audit: the engine's jitted
        attributes are the original callables again."""
        engine, _ = audited
        for name, fn in engine.jit_targets().items():
            assert not isinstance(fn, JA.JitCallRecorder), name

    def test_confidence_emission_is_callback_free(self, audited):
        """The cascade confidence (serving/sampler.token_confidence) is
        computed inside the jitted decode/prefill steps from arrays
        already live there; emitting it must introduce no host callback
        (JIT001) and keep the donation rebinding intact (JIT003)."""
        engine, report = audited
        assert not any(d.code in ("JIT001", "JIT003")
                       for d in report.diagnostics)
        # and the signal actually reaches the finished requests
        reqs = engine.generate_stream(["confidence probe"], max_new=4,
                                      return_requests=True)
        assert 0.0 < reqs[0].confidence <= 1.0


class TestInjectedRegressions:
    def test_host_sync_in_decode_fires_JIT001(self, tiny_dense):
        """A debug print (= host callback) smuggled into the jitted
        decode step must be flagged."""
        cfg, params = tiny_dense
        engine = Engine(params, cfg)
        orig = engine._decode

        # *rest keeps the wrapper layout-agnostic: the paged decode
        # signature carries block tables between state and tokens
        def synced(params, state, *rest):
            jax.debug.print("tick {}", rest[-1])  # the injected host sync
            return orig(params, state, *rest)

        engine._decode = jax.jit(synced, donate_argnums=(1,))
        report = JA.audit_engine(engine, prompts=["a", "b", "c"])
        hits = [d for d in report.diagnostics if d.code == "JIT001"]
        assert hits and hits[0].location == "engine._decode"

    def test_donated_arg_not_rebound_fires_JIT003(self):
        src = ("leaked = self._decode(self.params, self._slot_state,"
               " toks, pos, ctr)\n"
               "self._slot_state = leaked[1]\n")
        diags = JA.audit_donation_sites(src, JA.ENGINE_DONATIONS, "x.py")
        assert [d.code for d in diags] == ["JIT003"]
        assert "self._slot_state" in diags[0].message

    def test_rebinding_call_sites_pass(self):
        src = ("self._slot_state = self._insert(self._slot_state, rows,"
               " idxs)\n"
               "nxt, self._slot_state = self._decode(self.params,"
               " self._slot_state, toks, pos, ctr)\n")
        assert JA.audit_donation_sites(src, JA.ENGINE_DONATIONS,
                                       "x.py") == []

    def test_value_driven_retrace_fires_JIT006(self):
        """A static argnum that changes per call compiles per VALUE
        while the shape signature stays constant — exactly the hazard
        JIT006 exists for."""
        f = jax.jit(lambda x, n: x + n, static_argnums=(1,))
        rec = JA.JitCallRecorder("f", f)
        rec(jnp.ones(3), 1)
        rec(jnp.ones(3), 2)
        diags = JA.audit_retrace(rec)
        assert [d.code for d in diags] == ["JIT006"]

    def test_shape_driven_recompile_is_not_a_retrace(self):
        f = jax.jit(lambda x: x * 2)
        rec = JA.JitCallRecorder("f", f)
        rec(jnp.ones(3))
        rec(jnp.ones(5))           # legit: new shape, new compile
        assert JA.audit_retrace(rec) == []

    def test_weak_scalar_arg_flagged_JIT004(self):
        f = jax.jit(lambda x, s: x * s)
        closed = jax.make_jaxpr(f)(jnp.ones(3), 0.5)
        diags = JA.audit_weak_args("f", closed)
        assert [d.code for d in diags] == ["JIT004"]
        assert diags[0].severity == "warning"   # float: promotion-active

    def test_committed_dtype_args_pass(self):
        f = jax.jit(lambda x, s: x * s)
        closed = jax.make_jaxpr(f)(jnp.ones(3), jnp.float32(0.5))
        assert JA.audit_weak_args("f", closed) == []


class TestJitTargets:
    def test_names_cover_the_hot_path(self, tiny_dense):
        cfg, params = tiny_dense
        engine = Engine(params, cfg)
        names = set(engine.jit_targets())
        assert {"_insert", "_decode", "_next_tok"} <= names
        assert {n for n in names if n.startswith("_prefill[")} == {
            f"_prefill[{b}]" for b in engine.buckets}
        # prefix cache enabled by default -> the seeded ladder exists
        assert any(n.startswith("_prefill_from[") for n in names)
