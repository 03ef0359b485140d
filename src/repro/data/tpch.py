"""Synthetic TPC-H lineitem-like data — the paper's evaluation workload.

The paper runs over an 8 TB TPC-H instance (48e9 lineitem rows over 8 nodes).
This container is CPU-only, so the generator reproduces the *distributions*
TPC-H dbgen uses for the columns the paper's queries touch, scaled by
``rows``.  Selectivity regimes match the paper:

  * Q6 low-selectivity  — one-year shipdate window  (~2.8e-4 match rate)
  * Q6 high-selectivity — single-day shipdate       (~7.3e-7 in the paper;
    here a single day out of 2,526 ⇒ needle-in-haystack at our scale)
  * Q1 group-by small   — 4 populated (returnflag, linestatus) groups
  * Q1 group-by large   — group by suppkey (paper: 1M groups; scaled)
  * join group-by       — lineitem ⋈ supplier ⋈ nation (25 nations),
    supplier/nation replicated + pre-joined (paper §5.4 strategy)

Column encodings (all numeric, columnar):
  shipdate  int32  days in [0, 2526)   (1992-01-02 .. 1998-12-01)
  discount  float32 in {0.00 .. 0.10}  (dbgen: uniform 11 values)
  quantity  float32 in {1 .. 50}
  extendedprice float32
  tax       float32 in {0.00 .. 0.08}
  rfls      int32 in [0, 4)   returnflag×linestatus combined group
  suppkey   int32 in [0, num_suppliers)
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

DAYS = 2526  # dbgen shipdate span
Q6_LOW_WINDOW = (420, 785)   # ~1 year starting '1993-02-26'
Q6_HIGH_WINDOW = (420, 421)  # the single day '1993-02-26'
Q1_WINDOW = (2434, 2526)     # ['1998-09-01','1998-12-01']
NUM_NATIONS = 25


def generate_lineitem(rows: int, *, num_suppliers: int = 1000, seed: int = 7,
                      dtype=np.float32) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    cols = {
        "shipdate": rng.integers(0, DAYS, rows, dtype=np.int32),
        "discount": (rng.integers(0, 11, rows) / 100.0).astype(dtype),
        "quantity": rng.integers(1, 51, rows).astype(dtype),
        "extendedprice": (rng.uniform(900.0, 105000.0, rows) / 1000.0).astype(dtype),
        "tax": (rng.integers(0, 9, rows) / 100.0).astype(dtype),
        "rfls": rng.integers(0, 4, rows, dtype=np.int32),
        "suppkey": rng.integers(0, num_suppliers, rows, dtype=np.int32),
    }
    return cols


def supplier_nation_table(num_suppliers: int = 1000, seed: int = 11):
    """Replicated dimension side: suppkey -> nationkey, plus validity.

    Mirrors the paper's strategy: supplier ⋈ nation pre-joined in memory,
    hashed on suppkey (paper §5.4).
    """
    rng = np.random.default_rng(seed)
    supp_nation = rng.integers(0, NUM_NATIONS, num_suppliers).astype(np.int32)
    valid = np.ones(num_suppliers, np.float32)
    return supp_nation, valid


# --- query pieces -----------------------------------------------------------

def q6_func(chunk):
    return chunk["extendedprice"] * chunk["discount"]


def q6_cond(window):
    lo, hi = window

    def cond(chunk):
        sd = chunk["shipdate"]
        return (
            (sd >= lo) & (sd < hi)
            & (chunk["discount"] >= 0.02 - 1e-6) & (chunk["discount"] <= 0.03 + 1e-6)
            & (chunk["quantity"] == 1.0)
        ).astype(jnp.float32)

    return cond


def q1_func(chunk):
    """The four Q1 SUM aggregates, stacked [n, 4]."""
    ep, dc, tx = chunk["extendedprice"], chunk["discount"], chunk["tax"]
    return jnp.stack(
        [chunk["quantity"], ep, ep * (1 - dc), ep * (1 - dc) * (1 + tx)], axis=-1
    )


def q1_cond(chunk):
    sd = chunk["shipdate"]
    return ((sd >= Q1_WINDOW[0]) & (sd < Q1_WINDOW[1])).astype(jnp.float32)


def q1_group_small(chunk):
    return chunk["rfls"]


def q1_group_large(chunk):
    return chunk["suppkey"]


# Scaled large-domain Q1 (paper §5.3: 1M groups): suppkey spans >= 100k raw
# ids, folded into 2**bucket_bits hash buckets (repro/core/gla.hash_bucket)
# so the dense composite state stays TPU/VMEM-feasible.
Q1_LARGE_SUPPLIERS = 100_000
Q1_LARGE_BUCKET_BITS = 13


def q1_large_scenario(rows: int, *, num_suppliers: int = Q1_LARGE_SUPPLIERS,
                      bucket_bits: int = Q1_LARGE_BUCKET_BITS, seed: int = 7,
                      estimator: str = "single"):
    """Large-domain Q1 group-by: columns + a hash-bucketed group-by GLA.

    The GLA publishes the group-by kernel projection, so it runs through
    ``engine.run_query(emit="kernel")`` (one ``ops.group_agg`` dispatch per
    round-slice) as well as the segment_sum paths.  Returns ``(cols, gla)``.
    """
    from repro.core import gla as _gla  # local: data must not require core

    cols = generate_lineitem(rows, num_suppliers=num_suppliers, seed=seed)
    g = _gla.make_groupby_gla(
        q1_func, q1_cond, q1_group_large, num_groups=num_suppliers,
        bucket_bits=bucket_bits, d_total=float(rows), estimator=estimator,
        num_aggs=4)
    return cols, g


# --- two-table Q3/Q10-class join scenarios ---------------------------------
#
# lineitem ⋈ orders on orderkey, group by an orders-side attribute with an
# orders-side date predicate — the TPC-H Q3/Q10 family shape.  The orders
# dimension is replicated + pre-joined in memory (paper §5.4), so it rides
# the fused kernel as ProbeTable operands (DESIGN.md §13).

NUM_SEGMENTS = 5        # dbgen c_mktsegment / o_orderpriority-scale domain
Q3_DATE_CUTOFFS = (430, 2100)   # orders-side o_orderdate window

def generate_orders_fk(rows: int, *, num_orders: int | None = None,
                       seed: int = 7) -> np.ndarray:
    """The lineitem-side foreign key l_orderkey, int32 [rows].

    Generated separately so :func:`generate_lineitem` stays byte-stable
    for every existing scenario; callers add it as ``cols["orderkey"]``.
    """
    num_orders = num_orders or max(1, rows // 4)
    rng = np.random.default_rng(seed + 101)
    return rng.integers(0, num_orders, rows, dtype=np.int32)


def orders_table(num_orders: int, seed: int = 13, *,
                 date_window=Q3_DATE_CUTOFFS):
    """Replicated orders dimension: orderkey -> (segment group, validity).

    ``segment`` plays c_mktsegment (Q3) / n_name (Q10); ``valid`` is the
    orders-side date predicate cond_M(M.sAtts), evaluated once at build
    time exactly like supplier ⋈ nation pre-joining (paper §5.4).
    """
    rng = np.random.default_rng(seed)
    segment = rng.integers(0, NUM_SEGMENTS, num_orders).astype(np.int32)
    orderdate = rng.integers(0, DAYS, num_orders).astype(np.int32)
    lo, hi = date_window
    valid = ((orderdate >= lo) & (orderdate < hi)).astype(np.float32)
    return segment, valid


def q3_scenario(rows: int, *, num_orders: int | None = None, seed: int = 7,
                estimator: str = "single"):
    """Q3-class join: SUM(revenue) per order segment, orders date-windowed.

    Returns ``(cols, gla, dim)`` with ``dim = (segment, valid)``; the GLA
    publishes a fused projection whose probe tables are the dim arrays, so
    it runs the one-dispatch fused kernel on both engines.
    """
    from repro.core import gla as _gla  # local: data must not require core

    cols = generate_lineitem(rows, seed=seed)
    cols["orderkey"] = generate_orders_fk(rows, num_orders=num_orders,
                                          seed=seed)
    n_orders = num_orders or max(1, rows // 4)
    segment, valid = orders_table(n_orders, seed=seed + 7)
    g = _gla.make_join_groupby_gla(
        q6_func, q1_cond, lambda c: c["orderkey"], segment, valid,
        num_groups=NUM_SEGMENTS, d_total=float(rows), estimator=estimator)
    return cols, g, (segment, valid)


def q10_scenario(rows: int, *, num_orders: int | None = None, seed: int = 7,
                 estimator: str = "single"):
    """Q10-class join: the four Q1 SUM aggregates per order segment.

    Same two-table shape as Q3 with a wider aggregate block ([G, 4]
    states) — exercises the fused kernel's A-axis padding under join
    probes.  Returns ``(cols, gla, dim)``.
    """
    from repro.core import gla as _gla

    cols = generate_lineitem(rows, seed=seed)
    cols["orderkey"] = generate_orders_fk(rows, num_orders=num_orders,
                                          seed=seed)
    n_orders = num_orders or max(1, rows // 4)
    segment, valid = orders_table(n_orders, seed=seed + 7)
    g = _gla.make_join_groupby_gla(
        q1_func, q1_cond, lambda c: c["orderkey"], segment, valid,
        num_groups=NUM_SEGMENTS, d_total=float(rows), estimator=estimator,
        num_aggs=4)
    return cols, g, (segment, valid)


def _exact_batches(cols, batch_rows: int):
    """Yield bounded row-batch chunk dicts (with ``_mask``) from either a
    flat columnar dict or a ``repro.data.source.ChunkSource``.

    Streaming sources are read one chunk-slice group at a time and
    flattened to rows with their real mask, so the reference never holds
    more than O(batch) rows on host or device — the same out-of-core
    discipline as the engine (DESIGN.md §8).
    """
    from repro.data import source as _source  # local: optional coupling

    if isinstance(cols, _source.ChunkSource):
        from repro.data import encodings as _encodings

        P, C, L = cols.spec.P, cols.spec.C, cols.spec.L
        step = max(1, batch_rows // max(1, P * L))
        for lo in range(0, C, step):
            sl = cols.slice_cols(lo, min(C, lo + step))
            if cols.encodings:  # physical codes/words -> logical values
                sl = _encodings.decode_cols(sl, cols.encodings)
            chunk = {}
            for k, v in sl.items():
                a = np.asarray(v)  # one host materialization per column
                chunk[k] = jnp.asarray(a.reshape((-1, *a.shape[3:])))
            yield chunk
        return
    n = next(iter(cols.values())).shape[0]
    for lo in range(0, n, batch_rows):
        chunk = {k: jnp.asarray(v[lo:lo + batch_rows]) for k, v in cols.items()}
        if "_mask" not in chunk:
            first = next(iter(chunk.values()))
            chunk["_mask"] = jnp.ones(first.shape[:1], jnp.float32)
        yield chunk


F32_UNIT_ROUNDOFF = 2.0 ** -24


def f32_sum_tolerance(n_terms: int) -> float:
    """Relative error bound of a float32 sum over at most ``n_terms``
    sequential additions, against the float64 oracle.

    Any summation order lands within γ_n·Σ|x| of the exact sum, with
    γ_n = n·u / (1 − n·u) and u = 2^-24 (Higham, *Accuracy and Stability
    of Numerical Algorithms*, 2nd ed., §4.2).  Multiply by Σ|x| — the
    oracle itself when every term is nonnegative — for the absolute
    tolerance.  Two f32 computations of one sum that associate
    differently (f32 carries, ROADMAP Reach 2.1) may each sit anywhere
    inside it.
    """
    nu = n_terms * F32_UNIT_ROUNDOFF
    if nu >= 1:
        raise ValueError(f"{n_terms} additions exceed float32's error bound")
    return nu / (1 - nu)


def exact_answer(cols, func, cond, group=None,
                 num_groups: int | None = None, *,
                 batch_rows: int = 1 << 18,
                 join_key=None, dim_group=None, dim_valid=None):
    """Ground truth in float64 (the oracle for all correctness tests).

    ``cols`` is a flat columnar dict (host rows) OR any
    ``repro.data.source.ChunkSource``.  The reference is accumulated over
    bounded host batches in float64 rather than materializing the entire
    dataset as one device chunk — which OOMed exactly at the out-of-core
    scales the source layer unlocks.  Padded rows contribute nothing: the
    batch's ``_mask`` folds into the predicate weight.

    Two-table joins (Q3/Q10 class): pass ``join_key`` (chunk -> fact-side
    foreign keys) with the replicated ``dim_group``/``dim_valid`` arrays.
    Each bounded batch gathers its own keys' dimension rows on host —
    only O(batch + |dim|) resident, never the whole fact table — with the
    dimension predicate folded into the weight and the group read through
    the join, mirroring ``gla.make_join_groupby_gla``.
    """
    if join_key is not None and (dim_group is None or dim_valid is None):
        raise ValueError("join oracle needs dim_group and dim_valid")
    dim_group = None if dim_group is None else np.asarray(dim_group)
    dim_valid = None if dim_valid is None else np.asarray(dim_valid, np.float64)
    acc = None
    out = None
    grouped = group is not None or (join_key is not None
                                    and dim_group is not None)
    for chunk in _exact_batches(cols, batch_rows):
        vals = np.asarray(func(chunk), np.float64)
        w = (np.asarray(cond(chunk), np.float64)
             * np.asarray(chunk["_mask"], np.float64))
        if join_key is not None:
            keys = np.asarray(join_key(chunk), np.int64)
            w = w * dim_valid[keys]
            gid = dim_group[keys]
        elif group is not None:
            gid = np.asarray(group(chunk))
        if vals.ndim == 1:
            vals = vals[:, None]
        contrib = vals * w[:, None]
        if not grouped:
            s = contrib.sum(axis=0)
            acc = s if acc is None else acc + s
        else:
            if out is None:
                out = np.zeros((num_groups, vals.shape[1]))
            np.add.at(out, gid, contrib)
    return out if grouped else acc
