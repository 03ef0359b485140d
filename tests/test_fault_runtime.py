"""Chaos lane: §4.6 failure semantics enforced on LIVE sessions.

tests/test_fault.py covers the fused post-processing path
(``dist.fault.run_with_failures``); this file kills partitions while a
session is actually running — injected through ``FaultPolicy.fail_at`` or
detected from a dying streaming source (``fault.FailingSource``) — and
checks the runtime enforces exactly what ``dist/fault.py`` documents:
``single`` survives with finite variance-floored bounds, ``multiple`` is
poisoned to (-inf, +inf) from the failure round, ``synchronized`` freezes
at the last pre-failure round, and no NaN ever reaches a QueryResult.

The kill-at-round matrix sweeps {scan, group-kernel, bundle} x estimator
x {first, mid, last} fail rounds on the vmapped engine, plus sharded
variants on an 8-device mesh.  The property section pins the estimator
invariants the chaos assertions rely on (hypothesis, or the fixed-seed
shim from conftest.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import estimators as E
from repro.core import gla, randomize
from repro.core import session as S
from repro.core.uda import Estimate
from repro.data import tpch
from repro.dist import fault

ROWS = 8192
PARTS = 4
ROUNDS = 4  # C=8 chunks/partition at chunk_len=256 -> 2 chunks per round
FAIL_ROUNDS = (0, 2, 3)  # first, mid, last


def _sum(estimator, window=(0, 1460)):
    def func(c):
        return c["quantity"]

    def cond(c):
        sd = c["shipdate"]
        return ((sd >= window[0]) & (sd < window[1])).astype(jnp.float32)

    return gla.make_sum_gla(func, cond, d_total=float(ROWS),
                            estimator=estimator)


def _group(estimator):
    return gla.make_groupby_gla(
        tpch.q1_func, tpch.q1_cond, tpch.q1_group_small, num_groups=4,
        d_total=float(ROWS), num_aggs=4, estimator=estimator)


def _bundle(estimator):
    return gla.GLABundle([_sum(estimator), _sum(estimator, window=(0, 400))])


# built once at module scope: the session step jits statically on the GLA
# object, so every (path, estimator) cell compiles exactly once across the
# whole kill-at-round matrix.  The "multiple" model publishes no kernel
# contract (MultState), so the kernel paths cover {single, synchronized} —
# exactly the families whose state is SumState-shaped.
_GLAS = {("scan", e): _sum(e)
         for e in ("single", "multiple", "synchronized")}
_GLAS.update({("kernel_group", e): _group(e)
              for e in ("single", "synchronized")})
_GLAS.update({("kernel_bundle", e): _bundle(e)
              for e in ("single", "synchronized")})
CASES = sorted(_GLAS)


@pytest.fixture(scope="module")
def shards():
    cols = tpch.generate_lineitem(ROWS, seed=21)
    parts = randomize.randomize_global(
        {k: jnp.asarray(v) for k, v in cols.items()}, jax.random.key(4),
        PARTS)
    return randomize.pack_partitions(parts, chunk_len=256)


def _drive(sess):
    while not sess.done:
        sess.step()
    return sess.result()


@pytest.fixture(scope="module")
def baselines(shards):
    """No-failure incremental runs, one per matrix cell.  Pre-failure
    rounds of a chaos run must match these bitwise: before the first
    failure the session executes the identical all-alive program."""
    out = {}
    for (path, est), g in _GLAS.items():
        emit = "chunk" if path == "scan" else "kernel"
        out[(path, est)] = _drive(S.Session(g, shards, rounds=ROUNDS,
                                            emit=emit))
    return out


def _members(est):
    if isinstance(est, Estimate):
        return (est,)
    return tuple(e for e in est if e is not None)


def _rows(est):
    return (np.asarray(est.estimate, np.float64),
            np.asarray(est.lower, np.float64),
            np.asarray(est.upper, np.float64))


def _assert_no_nan(res):
    for part in (res.final, res.snapshots, res.estimates):
        for leaf in jax.tree.leaves(part):
            assert not np.any(np.isnan(np.asarray(leaf)))


def _check_single(em, eb, fr):
    x, lo, hi = _rows(em)
    xb, lob, hib = _rows(eb)
    # survives: finite variance-floored bounds at every round
    assert np.all(np.isfinite(lo)) and np.all(np.isfinite(hi))
    assert np.all(np.isfinite(x))
    # pre-failure rounds ran the identical all-alive program
    np.testing.assert_array_equal(lo[:fr], lob[:fr])
    np.testing.assert_array_equal(hi[:fr], hib[:fr])
    # the variance floor: |S| is capped below |D|, so the final round's
    # interval is strictly wider than the uninterrupted run's
    assert np.max(hi[-1] - lo[-1]) > np.max(hib[-1] - lob[-1])


def _check_multiple(em, eb, fr):
    x, lo, hi = _rows(em)
    _, lob, hib = _rows(eb)
    # poisoned from the failure round on, untouched before it
    assert np.all(np.isneginf(lo[fr:])) and np.all(np.isposinf(hi[fr:]))
    np.testing.assert_array_equal(lo[:fr], lob[:fr])
    np.testing.assert_array_equal(hi[:fr], hib[:fr])
    assert np.all(np.isfinite(x[:fr] if fr else x))


def _check_sync(em, eb, fr):
    x, lo, hi = _rows(em)
    xb, lob, hib = _rows(eb)
    if fr == 0:
        # nothing preceded the failure: no snapshot ever clears the
        # barrier, bounds are infinite from the start
        assert np.all(np.isneginf(lo)) and np.all(np.isposinf(hi))
        return
    np.testing.assert_array_equal(x[:fr], xb[:fr])
    np.testing.assert_array_equal(lo[:fr], lob[:fr])
    np.testing.assert_array_equal(hi[:fr], hib[:fr])
    for r in range(fr, x.shape[0]):  # frozen at the last pre-failure round
        np.testing.assert_array_equal(x[r], x[fr - 1])
        np.testing.assert_array_equal(lo[r], lo[fr - 1])
        np.testing.assert_array_equal(hi[r], hi[fr - 1])


_CHECKS = {"single": _check_single, "multiple": _check_multiple,
           "synchronized": _check_sync}


@pytest.mark.parametrize("fail_round", FAIL_ROUNDS)
@pytest.mark.parametrize("path,estimator", CASES)
def test_kill_at_round(shards, baselines, path, estimator, fail_round):
    emit = "chunk" if path == "scan" else "kernel"
    sess = S.Session(
        _GLAS[(path, estimator)], shards, rounds=ROUNDS, emit=emit,
        fault=S.FaultPolicy(estimator, fail_at={2: fail_round}))
    res = _drive(sess)
    _assert_no_nan(res)
    base = baselines[(path, estimator)]
    got = _members(res.estimates)
    want = _members(base.estimates)
    assert len(got) == len(want) > 0
    for em, eb in zip(got, want):
        _CHECKS[estimator](em, eb, fail_round)


def test_final_covers_surviving_data_only(shards):
    """The partial final equals the fused engine's: the dead partition's
    data (including what it scanned before dying) is excluded."""
    g = _GLAS[("scan", "single")]
    sess = S.Session(g, shards, rounds=ROUNDS,
                     fault=S.FaultPolicy("single", fail_at={2: 2}))
    res = _drive(sess)
    ref = fault.run_with_failures(g, shards, rounds=ROUNDS,
                                  fail_at={2: 2}, estimator="single")
    np.testing.assert_allclose(np.asarray(res.final), np.asarray(ref.final),
                               rtol=1e-6)


def test_fused_policy_matches_run_with_failures(shards):
    """run() with no stopping rule executes the fused program; an attached
    FaultPolicy ships the same [R, P] schedule run_with_failures builds and
    post-processes identically."""
    for est in ("single", "multiple", "synchronized"):
        g = _GLAS[("scan", est)]
        sess = S.Session(g, shards, rounds=ROUNDS,
                         fault=S.FaultPolicy(est, fail_at={1: 2}))
        a = sess.run()
        b = fault.run_with_failures(g, shards, rounds=ROUNDS,
                                    fail_at={1: 2}, estimator=est)
        np.testing.assert_array_equal(np.asarray(a.estimates.lower),
                                      np.asarray(b.estimates.lower))
        np.testing.assert_array_equal(np.asarray(a.estimates.upper),
                                      np.asarray(b.estimates.upper))
        np.testing.assert_allclose(np.asarray(a.final),
                                   np.asarray(b.final), rtol=1e-6)


# ---------------------------------------------------------------------------
# detection: the streaming path loses a partition for real
# ---------------------------------------------------------------------------

def test_streaming_loss_detected_and_survived(shards):
    """A FailingSource raises PartitionLostError from the prefetcher's
    worker thread mid-scan; the session records the failure round, retries
    against the survivors, and finishes with finite single-model bounds
    and the same final as an injected failure at that round."""
    g = _GLAS[("scan", "single")]
    src = fault.FailingSource(shards, fail_chunk={2: 4})  # dies in round 2
    sess = S.Session(g, src, rounds=ROUNDS, fault=S.FaultPolicy("single"))
    res = _drive(sess)
    assert sess._fail_at == {2: 2}
    _assert_no_nan(res)
    lo = np.asarray(res.estimates.lower)
    hi = np.asarray(res.estimates.upper)
    assert np.all(np.isfinite(lo)) and np.all(np.isfinite(hi))
    inj = _drive(S.Session(g, shards, rounds=ROUNDS,
                           fault=S.FaultPolicy("single", fail_at={2: 2})))
    np.testing.assert_allclose(np.asarray(res.final),
                               np.asarray(inj.final), rtol=1e-6)


def test_streaming_loss_without_policy_is_fatal(shards):
    src = fault.FailingSource(shards, fail_chunk={1: 0})
    sess = S.Session(_GLAS[("scan", "single")], src, rounds=ROUNDS)
    with pytest.raises(fault.PartitionLostError, match=r"\[1\]"):
        sess.step()


def test_policy_api_validation(shards):
    g = _GLAS[("scan", "single")]
    with pytest.raises(ValueError, match="unknown estimator model"):
        S.FaultPolicy("stratified")
    with pytest.raises(ValueError, match=">= 0"):
        S.FaultPolicy("single", fail_at={0: -1})
    with pytest.raises(ValueError, match="P=4"):
        S.Session(g, shards, rounds=ROUNDS,
                  fault=S.FaultPolicy("single", fail_at={7: 1}))
    with pytest.raises(ValueError, match="not both"):
        S.Session(g, shards, rounds=ROUNDS, alive=np.ones(PARTS, bool),
                  fault=S.FaultPolicy("single"))
    with pytest.raises(ValueError, match="P="):
        fault.FailingSource(shards, fail_chunk={9: 0})


# ---------------------------------------------------------------------------
# sharded engine: same semantics when partitions are devices
# ---------------------------------------------------------------------------

needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs 8 devices (fake-device CI lane)")


@pytest.fixture(scope="module")
def shards8():
    cols = tpch.generate_lineitem(ROWS, seed=21)
    parts = randomize.randomize_global(
        {k: jnp.asarray(v) for k, v in cols.items()}, jax.random.key(4), 8)
    return randomize.pack_partitions(parts, chunk_len=256)


@needs8
def test_sharded_kill_mid_scan_single(shards8):
    """ISSUE acceptance: killing a shard mid-scan on the 8-device lane
    under `single` yields finite variance-floored bounds and a final over
    surviving data — no crash, no NaN — matching the vmapped engine."""
    mesh = jax.make_mesh((8,), ("data",))
    g = _GLAS[("scan", "single")]
    sh = S.Session(g, shards8, rounds=ROUNDS, mesh=mesh,
                   fault=S.FaultPolicy("single", fail_at={3: 2}))
    res = _drive(sh)
    _assert_no_nan(res)
    lo = np.asarray(res.estimates.lower)
    hi = np.asarray(res.estimates.upper)
    assert np.all(np.isfinite(lo)) and np.all(np.isfinite(hi))
    assert np.all(hi[-1] > lo[-1])
    vm = _drive(S.Session(g, shards8, rounds=ROUNDS,
                          fault=S.FaultPolicy("single", fail_at={3: 2})))
    np.testing.assert_allclose(np.asarray(res.final), np.asarray(vm.final),
                               rtol=1e-5)


@needs8
def test_sharded_kill_poisons_multiple(shards8):
    mesh = jax.make_mesh((8,), ("data",))
    g = _GLAS[("scan", "multiple")]
    sess = S.Session(g, shards8, rounds=ROUNDS, mesh=mesh,
                     fault=S.FaultPolicy("multiple", fail_at={5: 2}))
    res = _drive(sess)
    _assert_no_nan(res)
    lo = np.asarray(res.estimates.lower)
    hi = np.asarray(res.estimates.upper)
    assert np.all(np.isneginf(lo[2:])) and np.all(np.isposinf(hi[2:]))
    assert np.all(np.isfinite(lo[:2]))


# ---------------------------------------------------------------------------
# property tests: the estimator invariants the chaos assertions rely on
# ---------------------------------------------------------------------------

def _run_once(test, *args):
    """Call a property test's body once, outside hypothesis."""
    inner = getattr(getattr(test, "hypothesis", None), "inner_test", None)
    if inner is not None:  # absent under the conftest fallback shim
        inner(*args)


@pytest.fixture(scope="module")
def properties_compiled():
    """Each property's first call compiles its eager ops (the tiny
    Session's too); run them once here so that hypothesis times examples,
    not compilation, against its deadline."""
    _run_once(test_half_width_nonincreasing_in_scanned, 1.0, 1.0)
    _run_once(test_variance_clamp_small_sample_never_nan, 0.0, 0)
    _run_once(test_alive_mask_renormalization_unbiased,
              [0.0] * (_TINY_P * _TINY_L))


@pytest.mark.usefixtures("properties_compiled")
@settings(max_examples=20)
@given(st.floats(min_value=0.1, max_value=10.0),
       st.floats(min_value=0.5, max_value=10.0))
def test_half_width_nonincreasing_in_scanned(mu, sigma):
    """Single-model Eq. (4): for fixed population moments the variance
    estimate (hence the CI half-width) is non-increasing in |S| — more
    scanned tuples can only tighten the interval."""
    d = 4096.0
    s = np.arange(2.0, d, 57.0)
    var = np.asarray(E.variance_estimate(
        jnp.asarray(s * mu, jnp.float32),
        jnp.asarray(s * (sigma ** 2 + mu ** 2), jnp.float32),
        jnp.asarray(s, jnp.float32), jnp.asarray(d, jnp.float32)),
        np.float64)
    assert np.all(np.isfinite(var)) and np.all(var >= 0.0)
    # f32 slack: the s*sumsq - sum^2 cancellation leaves ~1e-4 relative
    assert np.all(np.diff(var) <= var[:-1] * 1e-3 + 1e-6)


@pytest.mark.usefixtures("properties_compiled")
@settings(max_examples=20)
@given(st.floats(min_value=0.0, max_value=1e6),
       st.integers(min_value=0, max_value=1))
def test_variance_clamp_small_sample_never_nan(val, s):
    """|S| <= 1 leaves the sample variance undefined: the clamp must emit
    +inf (undefined can never certify convergence), never NaN — and the
    bounds built from it stay NaN-free (finite - inf = -inf)."""
    sf = jnp.asarray(float(s), jnp.float32)
    sum_ = jnp.asarray(val * s, jnp.float32)
    var = E.variance_estimate(sum_, jnp.asarray(val ** 2 * s, jnp.float32),
                              sf, jnp.asarray(100.0, jnp.float32))
    assert np.isposinf(np.asarray(var))
    est = E.horvitz_estimate(sum_, sf, jnp.asarray(100.0, jnp.float32))
    lo, hi = E.normal_bounds(est, var, 0.95)
    assert not np.isnan(np.asarray(est))
    assert np.isneginf(np.asarray(lo)) and np.isposinf(np.asarray(hi))


_TINY_P, _TINY_L = 4, 8
_TINY_GLA = gla.make_sum_gla(
    lambda c: c["v"], lambda c: jnp.ones_like(c["v"]),
    d_total=float(_TINY_P * _TINY_L), estimator="single")


@pytest.mark.usefixtures("properties_compiled")
@settings(max_examples=5)
@given(st.lists(st.floats(min_value=-100.0, max_value=100.0),
                min_size=_TINY_P * _TINY_L, max_size=_TINY_P * _TINY_L))
def test_alive_mask_renormalization_unbiased(vals):
    """Kill partition p at round 0 and run the REAL policy path to a full
    scan of the survivors: averaging the estimate over every choice of p
    equals the exact total (the alive-mask-weighted Horvitz-Thompson
    estimator is unbiased under partition-uniform sampling)."""
    v = np.asarray(vals, np.float32).reshape(_TINY_P, 1, _TINY_L)
    shards = {"v": jnp.asarray(v),
              "_mask": jnp.ones((_TINY_P, 1, _TINY_L), jnp.float32)}
    total = float(np.sum(np.asarray(v, np.float64)))
    ests = []
    for p in range(_TINY_P):
        sess = S.Session(_TINY_GLA, shards, rounds=1,
                         fault=S.FaultPolicy("single", fail_at={p: 0}))
        sess.step()
        ests.append(float(np.asarray(sess.result().estimates.estimate)[-1]))
    np.testing.assert_allclose(np.mean(ests), total, rtol=1e-4, atol=1.0)
