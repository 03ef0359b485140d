"""Engine semantics: zero-overhead snapshots, emit-path consistency,
straggler schedules, sync truncation, lane merge-order independence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, gla, randomize
from repro.data import tpch

ROWS = 30_000


@pytest.fixture(scope="module")
def shards():
    cols = tpch.generate_lineitem(ROWS, seed=11)
    parts = randomize.randomize_global(
        {k: jnp.asarray(v) for k, v in cols.items()}, jax.random.key(2), 4)
    return randomize.pack_partitions(parts, chunk_len=256)


@pytest.fixture(scope="module")
def q6(shards):
    return gla.make_sum_gla(tpch.q6_func, tpch.q6_cond(tpch.Q6_LOW_WINDOW),
                            d_total=float(ROWS))


def test_emit_paths_agree(shards, q6):
    """chunk-prefix, round, and masked-round paths give identical snapshots
    under a uniform schedule."""
    C = shards["_mask"].shape[1]
    rounds = 4
    while C % rounds:
        rounds -= 1
    a = engine.run_query(q6, shards, rounds=rounds, emit="chunk")
    b = engine.run_query(q6, shards, rounds=rounds, emit="round")
    c = engine.run_query(q6, shards, rounds=rounds, emit="round_masked")
    for x, y in ((a, b), (a, c)):
        np.testing.assert_allclose(np.asarray(x.estimates.estimate),
                                   np.asarray(y.estimates.estimate),
                                   rtol=2e-4)
    np.testing.assert_allclose(float(a.final), float(b.final), rtol=1e-5)


def test_snapshots_do_not_change_final(shards, q6):
    """Interactive mode returns the same final answer as non-interactive —
    the zero-overhead design invariant (timing measured in benchmarks)."""
    on = engine.run_query(q6, shards, rounds=7, snapshots=True)
    off = engine.run_query(q6, shards, rounds=7, snapshots=False)
    np.testing.assert_allclose(float(on.final), float(off.final), rtol=1e-6)
    assert off.estimates is None and on.estimates is not None


def test_straggler_async_final_exact(shards, q6):
    sched = engine.straggler_schedule(4, shards["_mask"].shape[1], 6,
                                      speeds=[1, 1, 2, 4], seed=7)
    res = engine.run_query(q6, shards, schedule=sched, mode="async")
    uni = engine.run_query(q6, shards, rounds=6)
    np.testing.assert_allclose(float(res.final), float(uni.final), rtol=1e-6)
    # async snapshots differ across schedules, but the last one is complete
    np.testing.assert_allclose(np.asarray(res.estimates.estimate)[-1],
                               float(uni.final), rtol=2e-4)


def test_sync_truncates_to_min_progress(shards, q6):
    sched = engine.straggler_schedule(4, shards["_mask"].shape[1], 6,
                                      speeds=[1, 1, 2, 4], seed=7)
    res = engine.run_query(q6, shards, schedule=sched, mode="sync")
    # scanned counts at each snapshot must equal P * min-progress * chunk
    mins = np.min(sched[:, 1:], axis=0)
    scanned = np.asarray(res.snapshots.scanned)
    L = shards["_mask"].shape[2]
    # partitions have ragged tails; allow the padded-chunk tolerance
    expected = 4 * mins * L
    assert np.all(scanned <= expected + 1e-6)
    assert np.all(scanned >= expected * 0.95 - L)


def test_lanes_merge_order_independent(shards, q6):
    """DataPath work-unit analogue: more lanes, same result."""
    r1 = engine.run_query(q6, shards, rounds=4, lanes=1)
    r4 = engine.run_query(q6, shards, rounds=4, lanes=4)
    np.testing.assert_allclose(float(r1.final), float(r4.final), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r1.estimates.estimate),
                               np.asarray(r4.estimates.estimate), rtol=1e-4)


def test_groupby_large_state_round_path(shards):
    gq = gla.make_groupby_gla(tpch.q1_func, tpch.q1_cond, tpch.q1_group_large,
                              num_groups=1000, d_total=float(ROWS), num_aggs=4)
    C = shards["_mask"].shape[1]
    rounds = 4
    while C % rounds:
        rounds -= 1
    res = engine.run_query(gq, shards, rounds=rounds, emit="round")
    cols = tpch.generate_lineitem(ROWS, seed=11)
    exact = tpch.exact_answer(cols, tpch.q1_func, tpch.q1_cond,
                              tpch.q1_group_large, 1000)
    np.testing.assert_allclose(np.asarray(res.final), exact, rtol=2e-3,
                               atol=1e-2)


@pytest.mark.parametrize("emit,rounds", [("chunk", 1), ("chunk", 2),
                                         ("round", 2)])
def test_groupby_adds_chunk_partials_not_rows(emit, rounds):
    """Each chunk's group partial is summed alone, then added to the carry:
    chunk 1's 1024 ones sum to 1024 exactly and land on 2^24 exactly.  Were
    the rows added straight into the carry, each +1 on 2^24 would round
    away (f32 ulp 2 there), leaving 2^24."""
    x = np.ones((1, 2, 1024), np.float32)
    x[0, 0] = 0.0
    x[0, 0, 0] = 2.0 ** 24
    shards = {"x": jnp.asarray(x), "_mask": jnp.ones_like(jnp.asarray(x))}
    g = gla.make_groupby_gla(
        lambda c: c["x"], lambda c: jnp.ones_like(c["x"]),
        lambda c: jnp.zeros(c["x"].shape, jnp.int32), num_groups=4,
        d_total=2048.0)
    res = engine.run_query(g, shards, rounds=rounds, emit=emit)
    assert float(np.asarray(res.final)[0, 0]) == 2.0 ** 24 + 1024
