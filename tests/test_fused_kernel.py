"""Fused selection→bucket→aggregate kernel (kernels/fused_agg.py,
DESIGN.md §12), run in interpret mode: bitwise equivalence against the
segment-sum scan path across {scalar, group, bundle} × {plain, dict,
bit-packed} on both engines, the dense-predicate regression that broke
the legacy "bitwise" claim, MXU padding discipline, blocked and ragged
chunk grids, lowering refusals, and single-dispatch accounting.  (A
compiled TPU kernel's group members take the one-hot contraction and
are held to the float64-oracle tolerance instead — docs/KERNELS.md
rule 2.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, gla, randomize
from repro.core import scan as SC
from repro.core import session as S
from repro.core.spec import QuerySpec
from repro.data import encodings as ENC
from repro.data import tpch
from repro.data.source import EncodedSource
from repro.kernels import fused_agg as FK

ROWS = 12_000
PARTS = 4
ROUNDS = 4

needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs 8 (fake) devices: run under "
                            "XLA_FLAGS=--xla_force_host_platform_device_"
                            "count=8")


def _tb(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


@pytest.fixture(scope="module")
def raw():
    return tpch.generate_lineitem(ROWS, seed=17)


@pytest.fixture(scope="module")
def shards(raw):
    parts = randomize.randomize_global(
        {k: jnp.asarray(v) for k, v in raw.items()}, jax.random.key(4),
        PARTS)
    n_chunks = -(-ROWS // PARTS // 256)
    return randomize.pack_partitions(
        parts, chunk_len=256, min_chunks=-(-n_chunks // ROUNDS) * ROUNDS)


def _dense_cond(c):
    # >80% selectivity: the trap that made the legacy group-kernel
    # "bitwise" tests vacuous (they only ever saw sparse q1 predicates)
    return (c["shipdate"] < 1460).astype(jnp.float32)


def _glas():
    d = float(ROWS)
    scalar = gla.make_sum_gla(tpch.q6_func, _dense_cond, d_total=d)
    scalar4 = gla.make_sum_gla(tpch.q1_func, _dense_cond, d_total=d,
                               num_aggs=4)
    group = gla.make_groupby_gla(tpch.q1_func, _dense_cond,
                                 tpch.q1_group_small, num_groups=4,
                                 d_total=d, num_aggs=4)
    bundle = gla.GLABundle([scalar, group, scalar4])
    return {"scalar": scalar, "scalar4": scalar4, "group": group,
            "bundle": bundle}


def _encodings(raw):
    return ENC.normalize_encodings(
        {"discount": ENC.dict_encoding_for(np.asarray(raw["discount"])),
         "shipdate": ENC.BitPackedEncoding(bits=16),
         "rfls": ENC.BitPackedEncoding(bits=2)})


def _flat_cols(shards, ragged=True):
    """One partition's [C, L] column dict with a ragged final chunk."""
    cols = {k: v[0] for k, v in shards.items()}
    if ragged:
        mask = np.asarray(cols["_mask"]).copy()
        mask[-1, -37:] = 0.0
        cols = dict(cols, _mask=jnp.asarray(mask))
    return cols


def _encode_cols(cols, encs):
    enc = dict(encs)
    out = dict(cols)
    for name, e in enc.items():
        out[name] = jnp.asarray(
            ENC.encode_array(np.asarray(cols[name]), e))
    return out


def _fold_scan(g, cols, rounds=ROUNDS):
    st = SC.stack_init(g, 1)
    views = []
    C = cols["_mask"].shape[0]
    per = C // rounds
    for r in range(rounds):
        st, v = SC.scan_round_step(
            g, st, {k: x[r * per:(r + 1) * per] for k, x in cols.items()}, 1)
        views.append(v)
    return st, views


# ---------------------------------------------------------------------------
# kernel-level bitwise sweep (ragged tails + dense predicate throughout)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["scalar", "scalar4", "group", "bundle"])
@pytest.mark.parametrize("encoding", ["plain", "encoded"])
@pytest.mark.parametrize("rounds", [ROUNDS, 1])
def test_fused_round_step_bitwise_vs_scan(shards, raw, name, encoding,
                                          rounds):
    """Carry-in fused steps == scan fold, every round boundary, every
    member, bit for bit — including in-kernel dict + bit-packed decode.
    One round covers the whole 12-chunk shard: a full 8-chunk block plus
    a partial one."""
    g = _glas()[name]
    cols = _flat_cols(shards)
    encs = _encodings(raw) if encoding == "encoded" else ()
    feed = _encode_cols(cols, encs) if encs else cols

    ref_st, ref_views = _fold_scan(g, cols, rounds)
    st = g.init()
    C = cols["_mask"].shape[0]
    per = C // rounds
    for r in range(rounds):
        st = SC.fused_round_step(
            g, st, {k: x[r * per:(r + 1) * per] for k, x in feed.items()},
            encs)
        assert _tb(st) == _tb(ref_views[r]), (name, encoding, r)
    assert _tb(st) == _tb(ref_st)


@pytest.mark.parametrize("name", ["scalar", "scalar4"])
def test_fused_prefix_states_bitwise(shards, raw, name):
    """The one-dispatch scalar prefix family == scan_prefix: final AND all
    C+1 per-chunk running states (what round snapshots index)."""
    g = _glas()[name]
    cols = _flat_cols(shards)
    sf, sp = SC.scan_prefix(g, cols, 1)
    ff, fp = SC.fused_prefix_states(g, cols)
    assert _tb(sf) == _tb(ff)
    assert _tb(sp) == _tb(fp)
    encs = _encodings(raw)
    ff_e, fp_e = SC.fused_prefix_states(g, _encode_cols(cols, encs), encs)
    assert _tb(sf) == _tb(ff_e)
    assert _tb(sp) == _tb(fp_e)


def test_fused_prefix_rejects_group_and_bundle(shards):
    cols = _flat_cols(shards)
    for g in (_glas()["group"], _glas()["bundle"]):
        with pytest.raises(ValueError, match="solo scalar"):
            SC.fused_prefix_states(g, cols)


# ---------------------------------------------------------------------------
# engine + session: vmapped
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["scalar", "group", "bundle"])
def test_engine_fused_kernel_bitwise_vs_chunk(shards, name):
    """emit='kernel' (now the fused path) == emit='chunk' (segment-sum
    scan): finals byte-for-byte on the vmapped engine — the scalar path's
    old interchangeable-not-bitwise carve-out is gone."""
    g = _glas()[name]
    a = engine.run_query(QuerySpec(g, rounds=ROUNDS, emit="chunk"), shards)
    b = engine.run_query(QuerySpec(g, rounds=ROUNDS, emit="kernel"), shards)
    assert _tb(a.final) == _tb(b.final)
    assert _tb(a.snapshots) == _tb(b.snapshots)


@pytest.mark.parametrize("name", ["scalar", "group", "bundle"])
def test_session_fused_encoded_bitwise(shards, raw, name):
    """Incrementally stepped sessions over an EncodedSource (decode
    in-kernel) == the plain resident fused program, byte for byte."""
    g = _glas()[name]
    ref = engine.run_query(QuerySpec(g, rounds=ROUNDS, emit="kernel"),
                           shards)
    esrc = EncodedSource.from_shards(
        {k: np.asarray(v) for k, v in shards.items()},
        dict(_encodings(raw)))
    sess = S.Session(QuerySpec(g, rounds=ROUNDS, emit="kernel"), esrc)
    assert sess._path == "kernel_fused"
    while not sess.done:
        sess.step()
    inc = sess.result()
    assert _tb(inc.final) == _tb(ref.final)
    assert _tb(inc.snapshots) == _tb(ref.snapshots)


def test_scalar_session_kernel_bitwise(shards):
    """The formerly non-bitwise scalar kernel session: fused carry-in steps
    now reproduce the fused program exactly (replaces the old
    'interchangeable' contract)."""
    g = _glas()["scalar"]
    ref = engine.run_query(QuerySpec(g, rounds=ROUNDS, emit="kernel"),
                           shards)
    sess = S.Session(QuerySpec(g, rounds=ROUNDS, emit="kernel"), shards)
    assert sess._path == "kernel_fused"
    inc = sess.run()
    assert _tb(inc.final) == _tb(ref.final)
    assert _tb(inc.estimates) == _tb(ref.estimates)


# ---------------------------------------------------------------------------
# engine + session: sharded (8 fake devices — CI tier1-multidevice lane)
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("name", ["scalar", "group", "bundle"])
def test_sharded_fused_kernel_bitwise(name, raw):
    g = _glas()[name]
    parts = randomize.randomize_global(
        {k: jnp.asarray(v) for k, v in raw.items()}, jax.random.key(4), 8)
    n_chunks = -(-ROWS // 8 // 128)
    shards8 = randomize.pack_partitions(
        parts, chunk_len=128, min_chunks=-(-n_chunks // ROUNDS) * ROUNDS)
    mesh = jax.make_mesh((8,), ("data",))
    a = engine.run_query(QuerySpec(g, rounds=ROUNDS, emit="chunk"),
                         shards8, mesh=mesh)
    b = engine.run_query(QuerySpec(g, rounds=ROUNDS, emit="kernel"),
                         shards8, mesh=mesh)
    assert _tb(a.final) == _tb(b.final)
    assert _tb(a.snapshots) == _tb(b.snapshots)
    esrc = EncodedSource.from_shards(
        {k: np.asarray(v) for k, v in shards8.items()},
        dict(_encodings(raw)))
    sess = S.Session(QuerySpec(g, rounds=ROUNDS, emit="kernel"), esrc,
                     mesh=mesh)
    assert sess._path == "kernel_fused"
    while not sess.done:
        sess.step()
    inc = sess.result()
    assert _tb(inc.final) == _tb(b.final)
    assert _tb(inc.snapshots) == _tb(b.snapshots)


# ---------------------------------------------------------------------------
# padding discipline + dispatch accounting
# ---------------------------------------------------------------------------

def test_fused_mxu_padding_spy(shards):
    """The kernel's accumulator layout pads G→×128 and A→×8 (MXU tiling,
    docs/KERNELS.md), reductions run over UNPADDED [L, A] values, and
    padding never leaks into results."""
    from unittest import mock

    g = _glas()["bundle"]
    cols = _flat_cols(shards)
    seen = []
    orig = FK._chunk_contrib

    def spy(fs, meta_row, chunk, msk, L, use_mxu):
        seen.append(meta_row)
        out = orig(fs, meta_row, chunk, msk, L, use_mxu)
        # contributions arrive already padded to the accumulator layout
        assert all(d.shape[1] % 8 == 0 or d.shape[1] == 1 for d in out)
        return out

    ref, _ = _fold_scan(g, cols)
    with mock.patch.object(FK, "_chunk_contrib", side_effect=spy):
        st = SC.fused_round_step(g, g.init(), cols)
    for kind, A, A_pad, G, G_pad in seen:
        assert A_pad % 8 == 0 and A_pad >= A
        if kind == "group":
            assert G_pad % 128 == 0 and G_pad >= G
    assert {m[0] for m in seen} == {"scalar", "group"}
    assert _tb(st) == _tb(ref)  # padding leaked nowhere


@pytest.mark.parametrize("name", ["group", "bundle"])
def test_fused_mxu_one_hot_parity(shards, name):
    """use_mxu=True (one-hot matmul bucket accumulation) vs the default
    gather lowering, under interpret mode: the matmul re-associates the
    per-bucket sums, so the contract is allclose — not bitwise — against
    both the default kernel and the scan fold."""
    g = _glas()[name]
    cols = _flat_cols(shards)
    ref, _ = _fold_scan(g, cols)
    base = SC.fused_round_step(g, g.init(), cols)
    try:
        mxu = SC.fused_round_step(g, g.init(), cols, use_mxu=True)
    except Exception as e:  # pragma: no cover - backend-dependent pads
        pytest.skip(f"one-hot pad shapes infeasible in interpret mode: {e}")
    for got, want in ((mxu, base), (mxu, ref)):
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=1e-3)


def test_fused_single_dispatch_accounting(shards, raw):
    """One pallas_call per round-slice for a whole bundle — counted
    structurally under eval_shape, plain and encoded alike."""
    g = _glas()["bundle"]
    cols = _flat_cols(shards)
    encs = _encodings(raw)
    feed = _encode_cols(cols, encs)
    with FK.count_dispatches() as box:
        jax.eval_shape(lambda s, c: SC.fused_round_step(g, s, c, encs),
                       g.init(), feed)
    assert box[0] == 1
    with FK.count_dispatches() as box:
        jax.eval_shape(lambda c: SC.fused_prefix_states(_glas()["scalar"], c),
                       cols)
    assert box[0] == 1


def test_fused_lowering_error_names_plan(shards, raw):
    """A compiled (non-interpret) kernel refuses, by name, the plans the
    TPU compiler cannot lower — join probe gathers and bit-packed unpacks
    — before any pallas_call is built; interpret mode runs them."""
    cols = _flat_cols(shards)
    join_cols, join_g, _ = tpch.q3_scenario(ROWS, num_orders=64)
    del join_cols
    join_feed = dict(cols, orderkey=jnp.zeros_like(cols["rfls"]))
    with pytest.raises(FK.FusedLoweringError, match="probe"):
        FK.fused_round_step(join_g, join_g.init(), join_feed,
                            interpret=False)
    g = _glas()["scalar"]
    encs = _encodings(raw)
    feed = _encode_cols(cols, encs)
    with pytest.raises(FK.FusedLoweringError, match="bit-packed"):
        FK.fused_prefix_states(g, feed, encs, interpret=False)
    FK.fused_prefix_states(g, feed, encs, interpret=True)


def test_fused_available_gates():
    d = 100.0
    fused_ok = gla.make_sum_gla(lambda c: c["x"], lambda c: c["x"] * 0 + 1,
                                d_total=d)
    assert SC.fused_available(fused_ok)
    multiple = gla.make_sum_gla(tpch.q1_func, tpch.q1_cond, d_total=d,
                                num_aggs=4, estimator="multiple")
    assert not SC.fused_available(multiple)
    from repro.data.source import ColumnSpec
    trailing = (ColumnSpec(name="x", dtype="float32", trailing=(3,)),)
    assert not SC.fused_available(fused_ok, trailing)
