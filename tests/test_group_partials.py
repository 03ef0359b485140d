"""A group-by chunk's partials (DESIGN.md §3): a one-hot contraction up to
``gla.ONEHOT_MAX_GROUPS`` static groups, a ``segment_sum`` scatter above,
and either way the float64 sums of the rows whose ids are in range."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gla

L = 1024


def _primitives(jaxpr):
    """Every primitive name in ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def _chunk(G, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": jnp.asarray(rng.normal(size=L).astype(np.float32)),
            "g": jnp.asarray(rng.integers(0, G, L).astype(np.int32)),
            "_mask": jnp.ones(L, jnp.float32)}


@pytest.mark.parametrize("G,bucket_bits", [
    (1, None), (4, None), (gla.ONEHOT_MAX_GROUPS, None),
    (gla.ONEHOT_MAX_GROUPS + 1, None), (100_000, 13)])
def test_chunk_step_scatters_only_above_cutoff(G, bucket_bits):
    g = gla.make_groupby_gla(
        lambda c: c["x"], lambda c: c["x"] > 0, lambda c: c["g"],
        num_groups=G, d_total=1.0, bucket_bits=bucket_bits)
    table = G if bucket_bits is None else 1 << bucket_bits
    prims = list(_primitives(jax.make_jaxpr(g.accumulate)(
        g.init(), _chunk(G)).jaxpr))
    if table <= gla.ONEHOT_MAX_GROUPS:
        assert gla.group_partials_path(table) == "onehot"
        assert "scatter-add" not in prims
        assert prims.count("dot_general") == 3
    else:
        assert gla.group_partials_path(table) == "scatter"
        assert prims.count("scatter-add") == 3
        assert "dot_general" not in prims


@pytest.mark.parametrize("G", [4, gla.ONEHOT_MAX_GROUPS])
def test_onehot_partials_match_float64_and_drop_stray_ids(G):
    rng = np.random.default_rng(G)
    A = 3
    vals = rng.normal(scale=100.0, size=(L, A)).astype(np.float32)
    w = (rng.random(L) < 0.7).astype(np.float32)
    gids = rng.integers(-3, G + 3, L).astype(np.int32)   # some out of range
    assert (gids < 0).any() and (gids >= G).any()
    d_s, d_q, d_m = jax.jit(gla.group_partials, static_argnums=3)(
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(gids), G)

    keep = (gids >= 0) & (gids < G)
    v64, w64, g = vals[keep].astype(np.float64), w[keep].astype(np.float64), \
        gids[keep]
    ref = [np.zeros((G, A)), np.zeros((G, A)), np.zeros(G)]
    absref = [np.zeros((G, A)), np.zeros((G, A))]
    np.add.at(ref[0], g, v64 * w64[:, None])
    np.add.at(ref[1], g, v64 * v64 * w64[:, None])
    np.add.at(ref[2], g, w64)
    np.add.at(absref[0], g, np.abs(v64) * w64[:, None])
    np.add.at(absref[1], g, v64 * v64 * w64[:, None])
    # f32 rounding of an n-term sum: at most n·eps·Σ|x| (n = rows a group
    # holds), and one more eps·|x| for the f32 product feeding each term
    n = np.bincount(g, minlength=G)[:, None] + 1
    eps = np.finfo(np.float32).eps
    for got, want, mag in zip((d_s, d_q), ref[:2], absref):
        assert np.all(np.abs(np.asarray(got, np.float64) - want)
                      <= n * eps * mag)
    np.testing.assert_array_equal(np.asarray(d_m), ref[2])  # integer counts
