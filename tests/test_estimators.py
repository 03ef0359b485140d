"""Statistical correctness of the paper's estimators (Lemmas 1–2, §4.3).

Property tests (hypothesis) + Monte-Carlo checks:
  * full-scan exactness: estimate == exact result, variance == 0
  * unbiasedness of the sampling estimator over random prefixes
  * CI coverage ≈ the nominal confidence level
  * merge associativity/commutativity (the GLA contract)
  * the corrected Alg. 1 (count = scanned items) — the paper erratum
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import engine, estimators as E, gla, randomize
from repro.data import tpch

jax.config.update("jax_platform_name", "cpu")


def _shards(rows=40_000, parts=4, chunk=256, seed=3):
    cols = tpch.generate_lineitem(rows, seed=seed)
    parts_ = randomize.randomize_global(
        {k: jnp.asarray(v) for k, v in cols.items()}, jax.random.key(seed),
        parts)
    return cols, randomize.pack_partitions(parts_, chunk_len=chunk)


def test_full_scan_exact_and_zero_width():
    rows = 40_000
    cols, shards = _shards(rows)
    g = gla.make_sum_gla(tpch.q6_func, tpch.q6_cond(tpch.Q6_LOW_WINDOW),
                         d_total=float(rows))
    res = engine.run_query(g, shards, rounds=8)
    exact = tpch.exact_answer(cols, tpch.q6_func,
                              tpch.q6_cond(tpch.Q6_LOW_WINDOW))[0]
    est = res.estimates
    # last round = full scan: collapse on the exact answer (paper §4.3.1)
    np.testing.assert_allclose(float(res.final), exact, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(est.estimate)[-1], exact, rtol=2e-4)
    assert float(np.asarray(est.upper)[-1] - np.asarray(est.lower)[-1]) < 1e-3
    # widths shrink monotonically in expectation; check endpoints
    widths = np.asarray(est.upper) - np.asarray(est.lower)
    assert widths[0] > widths[-1]


def test_unbiasedness_monte_carlo():
    """E[X] over random data orders ≈ exact aggregate (Lemma 1).

    Uses the Q1 predicate (~3.6% selectivity) so the exact answer is
    non-zero at this scale; the Q6 needle-in-haystack case is covered by
    the convergence benchmark at 1M rows.
    """
    rows, prefix = 4_000, 800
    cols = tpch.generate_lineitem(rows, seed=1)
    chunk = {k: jnp.asarray(v) for k, v in cols.items()}
    chunk["_mask"] = jnp.ones(rows, jnp.float32)
    func = np.asarray(tpch.q6_func(chunk), np.float64)
    condv = np.asarray(tpch.q1_cond(chunk), np.float64)
    g = func * condv
    exact = g.sum()
    rng = np.random.default_rng(0)
    ests = []
    for _ in range(300):
        perm = rng.permutation(rows)[:prefix]
        ests.append(rows / prefix * g[perm].sum())
    err = abs(np.mean(ests) - exact) / abs(exact)
    # MC standard error of the mean
    se = np.std(ests) / np.sqrt(len(ests)) / abs(exact)
    assert err < 4 * se + 0.01


def test_ci_coverage():
    """95% CI covers the truth ~95% of the time (normal-approx tolerance)."""
    rows, prefix = 5_000, 1_000
    rng = np.random.default_rng(42)
    vals = rng.lognormal(0.0, 1.0, rows)
    exact = vals.sum()
    hits = 0
    trials = 200
    for t in range(trials):
        perm = rng.permutation(rows)[:prefix]
        s, sq = vals[perm].sum(), (vals[perm] ** 2).sum()
        est = E.horvitz_estimate(jnp.asarray(s), jnp.asarray(float(prefix)),
                                 float(rows))
        var = E.variance_estimate(jnp.asarray(s), jnp.asarray(sq),
                                  jnp.asarray(float(prefix)), float(rows))
        lo, hi = E.normal_bounds(est, var, 0.95)
        hits += float(lo) <= exact <= float(hi)
    assert 0.88 <= hits / trials <= 1.0


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(-100, 100), min_size=3, max_size=3),
       st.lists(st.floats(-100, 100), min_size=3, max_size=3),
       st.lists(st.floats(-100, 100), min_size=3, max_size=3))
def test_merge_associative_commutative(a, b, c):
    def mk(v):
        return E.SumState(jnp.float32(v[0]), jnp.float32(abs(v[1])),
                          jnp.float32(abs(v[2])), jnp.float32(1.0))

    s1, s2, s3 = mk(a), mk(b), mk(c)
    m = E.sum_state_merge
    left = m(m(s1, s2), s3)
    right = m(s1, m(s2, s3))
    # Each grouping of a 3-term f32 sum is within γ_2·Σ|x| of the exact
    # sum (tpch.f32_sum_tolerance), so the two are within 2·γ_2·Σ|x| of
    # each other.  Relative to the result this is unbounded when the terms
    # cancel (1.23 + 3.75 - 5.0), so the bound is on Σ|x|.
    mag = jax.tree.map(lambda p, q, r: abs(p) + abs(q) + abs(r), s1, s2, s3)
    tol = 2 * tpch.f32_sum_tolerance(2)
    for x, y, s in zip(jax.tree.leaves(left), jax.tree.leaves(right),
                       jax.tree.leaves(mag)):
        assert abs(float(x) - float(y)) <= tol * float(s)
    ab, ba = m(s1, s2), m(s2, s1)
    for x, y in zip(jax.tree.leaves(ab), jax.tree.leaves(ba)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


def test_erratum_count_scanned_not_matched():
    """count must track scanned items (|S|), not predicate matches.

    With the paper-as-printed in-branch count, the variance factor
    (|D|-count) would not vanish at full scan for selective predicates.
    """
    rows = 10_000
    cols, shards = _shards(rows, seed=9)
    g = gla.make_sum_gla(tpch.q6_func, tpch.q6_cond(tpch.Q6_HIGH_WINDOW),
                         d_total=float(rows))
    res = engine.run_query(g, shards, rounds=4)
    st_ = res.snapshots
    scanned = float(np.asarray(st_.scanned)[-1])
    matched = float(np.asarray(st_.matched)[-1])
    assert scanned == pytest.approx(rows)
    assert matched < scanned  # selective predicate
    width = float(np.asarray(res.estimates.upper)[-1]
                  - np.asarray(res.estimates.lower)[-1])
    assert width < 1e-3


def test_variance_estimate_degenerate_sample_sizes():
    """|S| in {0, 1} leaves the variance undefined: the estimator must
    clamp to +inf (infinite-width bounds), never NaN (Eq. 4 divides by
    |S|^2(|S|-1) and multiplies by (|D|-|S|))."""
    d_total = 1000.0
    for s in (0.0, 1.0):
        var = E.variance_estimate(jnp.float32(0.0), jnp.float32(0.0),
                                  jnp.float32(s), d_total)
        assert np.isposinf(float(var)), (s, float(var))
        lo, hi = E.normal_bounds(jnp.float32(0.0), var, 0.95)
        assert not np.isnan(float(lo)) and not np.isnan(float(hi))
    # |S| = 2 is the smallest defined sample: finite and non-negative
    var2 = E.variance_estimate(jnp.float32(3.0), jnp.float32(5.0),
                               jnp.float32(2.0), d_total)
    assert np.isfinite(float(var2)) and float(var2) >= 0.0


def test_variance_estimate_fp_negative_clamps_to_zero():
    """A constant sample makes |S|*sumsq - sum^2 cancel to ~0; float error
    can drive it slightly negative.  The estimator clamps at 0 — bounds
    collapse instead of going NaN through sqrt(negative)."""
    s = 3.0
    c = 0.1  # 0.1 is inexact in binary: s*sumsq - sum^2 != 0 exactly
    var = E.variance_estimate(jnp.float32(s * c), jnp.float32(s * c * c),
                              jnp.float32(s), 10.0)
    assert float(var) >= 0.0
    lo, hi = E.normal_bounds(jnp.float32(s * c), var, 0.95)
    assert not np.isnan(float(lo)) and not np.isnan(float(hi))


def test_mult_estimate_zero_scanned_tuples():
    """Stratified estimator before any tuple arrives: estimate 0 with
    infinite (not NaN) bounds, per-partition EstimatorTerminate included."""
    st = E.mult_estimator_terminate(E.mult_state_zero(), d_local=250.0)
    assert float(st.est) == 0.0
    assert np.isposinf(float(st.estvar))
    est = E.mult_estimate(st, 0.95)
    assert not np.isnan(float(est.estimate))
    assert np.isneginf(float(est.lower)) and np.isposinf(float(est.upper))
    # an empty stratum (d_local == 0) must not generate NaN either
    st0 = E.mult_estimator_terminate(E.mult_state_zero(), d_local=0.0)
    e0 = E.mult_estimate(st0, 0.95)
    assert not np.isnan(float(e0.estimate))


def test_single_round_schedule_end_to_end():
    """rounds=1 (one snapshot at full scan) is a legal schedule on every
    emission path and for both estimator models: bounds collapse on the
    exact answer, never NaN."""
    rows = 8_000
    cols, shards = _shards(rows, seed=21)
    exact = tpch.exact_answer(cols, tpch.q6_func,
                              tpch.q6_cond(tpch.Q6_LOW_WINDOW))[0]
    for estimator in ("single", "multiple"):
        g = gla.make_sum_gla(tpch.q6_func, tpch.q6_cond(tpch.Q6_LOW_WINDOW),
                             d_total=float(rows), estimator=estimator)
        for emit in ("chunk", "round"):
            res = engine.run_query(g, shards, rounds=1, emit=emit)
            est = np.asarray(res.estimates.estimate)
            assert est.shape[0] == 1
            assert not np.any(np.isnan(est))
            np.testing.assert_allclose(est[-1], exact, rtol=2e-4)
            width = float(np.asarray(res.estimates.upper)[-1]
                          - np.asarray(res.estimates.lower)[-1])
            assert width < 1e-3


def test_multiple_estimator_empty_partition_no_nan():
    """A partition with zero live tuples (all-padding shard) contributes
    est=0 and var=inf to the stratified sum: bounds blow up to infinite
    width — honest, and never NaN."""
    rows = 6_000
    _, shards = _shards(rows, parts=4, seed=13)
    # kill partition 3: zero mask = no live tuples, d_local = 0
    shards = dict(shards)
    shards["_mask"] = shards["_mask"].at[3].set(0.0)
    g = gla.make_sum_gla(tpch.q6_func, tpch.q6_cond(tpch.Q6_LOW_WINDOW),
                         d_total=float(rows), estimator="multiple")
    res = engine.run_query(g, shards, rounds=3, emit="round")
    est = np.asarray(res.estimates.estimate)
    lo = np.asarray(res.estimates.lower)
    hi = np.asarray(res.estimates.upper)
    assert not np.any(np.isnan(est))
    assert not np.any(np.isnan(lo)) and not np.any(np.isnan(hi))
    assert np.all(np.isposinf(hi - lo))  # dead stratum: unbounded interval


def test_single_vs_multiple_equal_at_uniform_progress():
    """With equal partition sizes and uniform progress the two models agree
    (paper Fig. 1 single-node observation generalized)."""
    rows = 20_000
    _, shards = _shards(rows, seed=5)
    g1 = gla.make_sum_gla(tpch.q6_func, tpch.q6_cond(tpch.Q6_LOW_WINDOW),
                          d_total=float(rows), estimator="single")
    g2 = gla.make_sum_gla(tpch.q6_func, tpch.q6_cond(tpch.Q6_LOW_WINDOW),
                          d_total=float(rows), estimator="multiple")
    r1 = engine.run_query(g1, shards, rounds=5)
    r2 = engine.run_query(g2, shards, rounds=5)
    np.testing.assert_allclose(np.asarray(r1.estimates.estimate),
                               np.asarray(r2.estimates.estimate), rtol=1e-4)
