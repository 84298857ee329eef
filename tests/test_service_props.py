"""Property-based tests (hypothesis) for the service layer invariants:
the per-tenant in-flight SLO cap under arbitrary admission/release
interleavings, and the reservoir percentile estimator against exact
``statistics.quantiles``.  Deterministic spot-check versions of both
run unconditionally in tests/test_service.py; these push the same
invariants through randomized schedules."""
import statistics

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r "
           "requirements-dev.txt); skipping, not aborting collection")
from hypothesis import given, settings, strategies as st

from repro.serving.metrics import Reservoir
from repro.service.slo import AdmissionController, TenantSLO

SETTINGS = dict(max_examples=50, deadline=None)

lat = st.floats(min_value=0.0, max_value=1e6,
                allow_nan=False, allow_infinity=False)


@given(data=st.lists(lat, min_size=2, max_size=400))
@settings(**SETTINGS)
def test_reservoir_exact_below_capacity(data):
    """While the stream fits the reservoir, every reported percentile
    IS the exact inclusive-method quantile."""
    r = Reservoir(capacity=512)
    for x in data:
        r.add(x)
    assert r.quantile(0.5) == pytest.approx(
        statistics.quantiles(data, n=2, method="inclusive")[0])
    assert r.quantile(0.95) == pytest.approx(
        statistics.quantiles(data, n=20, method="inclusive")[18])
    assert r.quantile(0.99) == pytest.approx(
        statistics.quantiles(data, n=100, method="inclusive")[98])


def _stream(n, seed, dist):
    rng = np.random.default_rng(seed)
    draw = {"uniform": lambda: rng.uniform(0.0, 1e6, n),
            "exponential": lambda: rng.exponential(1e3, n),
            "lognormal": lambda: rng.lognormal(3.0, 2.0, n),
            # tie-heavy: few distinct values, zeros and the range's ends
            "levels": lambda: np.round(rng.uniform(0.0, 1e6, n), -5),
            "ties": lambda: rng.choice([0.0, 0.5, 1e3, 1e6], n)}[dist]
    return [float(x) for x in draw()]


# Streams past the reservoir's capacity (1500+ values) exceed what
# hypothesis may draw value by value, so the stream is generated from
# a drawn length, seed and latency distribution (continuous, or with
# many repeated values).
overflow_streams = st.builds(_stream, st.integers(1500, 2500),
                             st.integers(0, 2 ** 32 - 1),
                             st.sampled_from(["uniform", "exponential",
                                              "lognormal", "levels",
                                              "ties"]))


@given(data=overflow_streams, seed=st.integers(0, 2 ** 16))
@settings(max_examples=15, deadline=None)
def test_reservoir_overflow_estimate_rank_tolerance(data, seed):
    """Beyond capacity the estimate is a sample quantile: assert rank
    tolerance (the p50 estimate lands between the exact p30 and p70),
    a ±6-sigma band for a 256-element uniform sample."""
    r = Reservoir(capacity=256, seed=seed)
    for x in data:
        r.add(x)
    exact = statistics.quantiles(data, n=10, method="inclusive")
    assert exact[2] <= r.quantile(0.5) <= exact[6]


@given(ops=st.lists(st.tuples(st.booleans(), st.integers(1, 8)),
                    max_size=300),
       cap=st.integers(1, 32))
@settings(**SETTINGS)
def test_inflight_rows_never_exceed_cap(ops, cap):
    """Any admit/release interleaving: in-flight rows <= the SLO cap,
    and the controller's ledger matches an independent replay."""
    ac = AdmissionController(
        {"t": TenantSLO(max_inflight_rows=cap, max_queries=10 ** 6)})
    live = []
    for is_release, rows in ops:
        if is_release and live:
            ac.release("t", live.pop(0))
        else:
            if ac.try_admit("t", rows, 0.0) is None:
                live.append(rows)
        cur = ac.inflight_rows("t")
        assert cur == sum(live)
        assert cur <= cap
    # single-row queries can always make progress once drained
    for rows in live:
        ac.release("t", rows)
    assert ac.try_admit("t", min(1, cap), 0.0) is None
