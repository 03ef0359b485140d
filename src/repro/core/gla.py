"""Concrete GLAs — paper Algorithms 1–4.

Constructors return :class:`repro.core.uda.GLA` bundles for the three
aggregation problems of paper §4, each in the three estimation models:

  * :func:`make_sum_gla`          — §4.3  single-table SUM/COUNT (Algs. 1, 2)
  * :func:`make_groupby_gla`      — §4.4  group-by aggregation (Alg. 3)
  * :func:`make_join_groupby_gla` — §4.5  join group-by with replicated
                                    dimension table (Alg. 4)

Queries are expressed as ``func(chunk) -> [n] or [n, A]`` (A simultaneous
aggregates, like TPC-H Q1's four SUMs) and ``cond(chunk) -> [n] in {0,1}``.
Group-by adds ``group(chunk) -> [n] int ids in [0, num_groups)``.

TPU adaptation (DESIGN.md §3): a chunk's per-group partials are a one-hot
contraction at ``Precision.HIGHEST`` when the static group count is at most
:data:`ONEHOT_MAX_GROUPS`, and a ``jax.ops.segment_sum`` scatter above it
(:func:`group_partials`); the Pallas hot-path kernels in ``repro/kernels``
implement the same contraction with explicit VMEM tiling.  Group-by GLAs
publish the ``(vals, weight, gids)`` kernel projection so
``engine.run_query(emit="kernel")`` reaches that kernel directly (one
dispatch per round-slice); large raw-id domains fold through
:func:`hash_bucket` into a 2**bucket_bits dense bucket table.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimators as E
from repro.core.uda import GLA, Chunk, Estimate, FusedSpec, ProbeTable


def _as_2d(vals: jnp.ndarray) -> jnp.ndarray:
    """[n] -> [n, 1]; [n, A] stays."""
    return vals[:, None] if vals.ndim == 1 else vals


# ---------------------------------------------------------------------------
# Multi-query bundles (paper §3: "any number of concurrent estimation
# models" driven alongside one execution).  A bundle is itself a GLA whose
# state is the tuple of member states, so every engine scan path runs N
# queries over a single pass of the chunk stream.  Each member sees the
# exact same chunks in the exact same order as it would alone, so finals
# and snapshot states are bitwise-identical to solo runs
# (tests/test_multiquery.py).
# ---------------------------------------------------------------------------


def GLABundle(glas: Sequence[GLA], *, name: Optional[str] = None) -> GLA:
    """Stack heterogeneous GLAs into one fused GLA over a shared scan.

    The fused state is ``tuple(member states)``; accumulate/merge/terminate
    and the estimator extensions apply member-wise over the same chunk.
    ``estimate`` returns a tuple with one :class:`Estimate` per member
    (``None`` for members without an estimation model), preserving
    per-query round-emission views.  ``merge_is_additive`` holds iff it
    holds for every member — the engines' psum/tensordot merges then apply
    leaf-wise across the whole tuple.

    The bundle publishes no ``kernel_cols`` of its own; the engines'
    ``emit="kernel"`` path instead batches every member's kernel projection
    into one ``ops.group_agg`` dispatch per round-slice
    (``repro.core.scan.bundle_kernel_rounds_states``) when all members
    publish one.  Use :func:`repro.core.engine.run_queries` to execute a
    bundle and get per-query results back.

    Bundling the same member GLAs again returns the *same* bundle object
    (memoized): the engines' jit caches key on the GLA statically, so a
    repeated interactive workload must not pay an XLA recompile per
    ``run_queries`` call just because the combinator rebuilt its closures.
    """
    members = tuple(glas)
    if not members:
        raise ValueError("GLABundle needs at least one member GLA")
    if any(m.members for m in members):
        raise ValueError("GLABundle members must not themselves be bundles")
    return _bundle_cached(members, name)


@lru_cache(maxsize=256)
def _bundle_cached(members: tuple, name: Optional[str]) -> GLA:
    return _combine_members(members, name)


def _combine_members(members: tuple, name: Optional[str]) -> GLA:
    """The tuple-of-states combinator behind :func:`GLABundle`.

    Exposed separately (uncached) for the serving slot families, whose
    members close over *traced* per-slot parameters: those closures are
    rebuilt on every trace by design and must never enter the bundle
    memo — the jit cache of the serving step keys on the family object
    instead (repro/serving/service.py).
    """
    def init():
        return tuple(m.init() for m in members)

    def accumulate(state, chunk):
        return tuple(
            m.accumulate(s, chunk) for m, s in zip(members, state))

    def merge(a, b):
        return tuple(m.merge(x, y) for m, x, y in zip(members, a, b))

    def terminate(state):
        return tuple(m.terminate(s) for m, s in zip(members, state))

    def estimator_terminate(state, ctx=None):
        return tuple(
            m.estimator_terminate(s, ctx) for m, s in zip(members, state))

    def estimator_merge(a, b):
        return tuple(
            m.estimator_merge(x, y) for m, x, y in zip(members, a, b))

    def estimate(state, confidence, ctx=None):
        return tuple(
            m.estimate(s, confidence, ctx) if m.estimate is not None else None
            for m, s in zip(members, state))

    any_estimate = any(m.estimate is not None for m in members)
    return GLA(
        init=init, accumulate=accumulate, merge=merge, terminate=terminate,
        estimator_terminate=estimator_terminate,
        estimator_merge=estimator_merge,
        estimate=estimate if any_estimate else None,
        merge_is_additive=all(m.merge_is_additive for m in members),
        members=members,
        name=name or "bundle[" + "+".join(m.name for m in members) + "]",
    )


# ---------------------------------------------------------------------------
# Hash-bucketed group tables (paper §4.4 large-domain group-by, e.g. the
# 1M-group Q1).  The dense [G, A] composite state cannot scale with the raw
# id domain, so raw ids are folded into 2**bucket_bits buckets by a
# multiplicative hash.  The multiplier is odd, hence invertible mod 2**b:
# g -> (g * MULT) mod 2**b is a *bijection* on [0, 2**b), so any raw domain
# with num_groups <= 2**bucket_bits maps injectively and de-bucketing is
# exact (tests/test_groupby_kernel.py::
# test_kernel_final_matches_exact_debucketed).
# Larger domains fold ~num_groups / 2**b raw ids per bucket — the bucket
# then estimates the folded groups' combined aggregate.
# ---------------------------------------------------------------------------

_BUCKET_MULT = 2654435761  # 2**32 / golden ratio (Knuth), odd


def hash_bucket(gids: jnp.ndarray, bucket_bits: int) -> jnp.ndarray:
    """Raw group ids -> int32 bucket ids in [0, 2**bucket_bits)."""
    h = jnp.asarray(gids).astype(jnp.uint32) * jnp.uint32(_BUCKET_MULT)
    return (h & jnp.uint32((1 << bucket_bits) - 1)).astype(jnp.int32)


def debucket(bucket_vals: jnp.ndarray, raw_ids, bucket_bits: int):
    """Gather per-raw-id rows from a bucketed group table [2**b, ...].

    Exact whenever the active raw-id set maps injectively into buckets —
    guaranteed for num_groups <= 2**bucket_bits by the hash bijectivity.
    """
    idx = hash_bucket(jnp.asarray(raw_ids), bucket_bits)
    return jnp.take(bucket_vals, idx, axis=0)


# ---------------------------------------------------------------------------
# Paper Alg. 1 / Alg. 2 — GLASum, single / multiple / synchronized
# ---------------------------------------------------------------------------

def make_sum_gla(
    func: Callable[[Chunk], jnp.ndarray],
    cond: Callable[[Chunk], jnp.ndarray],
    *,
    d_total: float,
    estimator: str = "single",
    dtype=jnp.float32,
    num_aggs: int = 1,
) -> GLA:
    """SUM(func(d)) WHERE cond(d) — paper query (1).

    ``estimator``: "single" (Alg. 1), "multiple" (Alg. 2), "synchronized"
    (Wu et al.; same state as single — the barrier lives in the engine), or
    "none" (plain aggregate, the no-estimation overhead baseline).
    """
    A = num_aggs

    def zero_sum():
        z = jnp.zeros((A,), dtype)
        s = jnp.zeros((), dtype)
        return E.SumState(sum=z, sumsq=z, scanned=s, matched=s)

    def acc_sum(state: E.SumState, chunk: Chunk) -> E.SumState:
        vals = _as_2d(func(chunk)).astype(dtype)              # [n, A]
        w = (cond(chunk) * chunk["_mask"]).astype(dtype)      # [n]
        m = chunk["_mask"].astype(dtype)
        # multiply-then-reduce, NOT vals.T @ w: XLA:CPU fuses a matvec into
        # the surrounding scan carry (GEMM accumulator), changing the
        # reduction order between contexts.  The elementwise product + axis
        # reduction is context-stable, so the fused Pallas kernel
        # (kernels/fused_agg.py) reproduces these states bitwise —
        # the scalar-kernel path is exact, not just statistically
        # interchangeable (DESIGN.md §12, docs/KERNELS.md).
        return E.SumState(
            sum=state.sum + (vals * w[:, None]).sum(axis=0),
            sumsq=state.sumsq + ((vals * vals) * w[:, None]).sum(axis=0),
            scanned=state.scanned + jnp.sum(m),
            matched=state.matched + jnp.sum(w),
        )

    def merge(a, b):
        return jax.tree.map(jnp.add, a, b)

    def terminate(state):
        s = state.sum if A > 1 else state.sum[0]
        return s

    if estimator in ("single", "synchronized", "none"):

        def estimate(state: E.SumState, confidence, ctx=None) -> Estimate:
            est = E.horvitz_estimate(state.sum, state.scanned, d_total)
            var = E.variance_estimate(state.sum, state.sumsq, state.scanned, d_total)
            lo, hi = E.normal_bounds(est, var, confidence)
            sq = (lambda x: x) if A > 1 else (lambda x: x[0])
            return Estimate(sq(est), sq(lo), sq(hi),
                            info={"var": sq(var), "frac": state.scanned / d_total})

        # Per-shard fused-kernel dispatch (engine emit="kernel"): the Pallas
        # kernel reproduces acc_sum's state from (func, cond) projections —
        # only for the plain f32 single-aggregate SumState layout.
        if A == 1 and dtype == jnp.float32:
            def kernel_cols(chunk):
                return func(chunk), cond(chunk)
        else:
            kernel_cols = None

        # Fused in-kernel contract: any f32 SumState qualifies (A > 1 too —
        # the fused kernel pads A to a multiple of 8 itself).
        fused = (FusedSpec(func=func, cond=cond, group=None, num_aggs=A)
                 if dtype == jnp.float32 else None)

        return GLA(
            init=zero_sum, accumulate=acc_sum, merge=merge, terminate=terminate,
            estimate=None if estimator == "none" else estimate,
            merge_is_additive=True, kernel_cols=kernel_cols, fused=fused,
            name=f"sum-{estimator}",
        )

    if estimator == "multiple":

        def zero_mult():
            z = jnp.zeros((A,), dtype)
            return E.MultState(base=zero_sum(), est=z, estvar=z)

        def acc_mult(state: E.MultState, chunk: Chunk) -> E.MultState:
            return E.MultState(acc_sum(state.base, chunk), state.est, state.estvar)

        def merge_mult(a: E.MultState, b: E.MultState) -> E.MultState:
            # Merging *local* (pre-EstimatorTerminate) states: base adds,
            # est/estvar are not yet meaningful — keep additive for engine
            # uniformity (they are zero until estimator_terminate).
            return jax.tree.map(jnp.add, a, b)

        def est_term(state: E.MultState, ctx) -> E.MultState:
            """Alg. 2 EstimatorTerminate — needs |D_i| from the engine ctx."""
            b = state.base
            d_local = ctx["d_local"]
            est = E.horvitz_estimate(b.sum, b.scanned, d_local)
            var = E.variance_estimate(b.sum, b.sumsq, b.scanned, d_local)
            return E.MultState(b, est, var)

        def estimate(state: E.MultState, confidence, ctx=None) -> Estimate:
            lo, hi = E.normal_bounds(state.est, state.estvar, confidence)
            sq = (lambda x: x) if A > 1 else (lambda x: x[0])
            return Estimate(sq(state.est), sq(lo), sq(hi),
                            info={"var": sq(state.estvar)})

        return GLA(
            init=zero_mult, accumulate=acc_mult, merge=merge_mult,
            terminate=lambda s: terminate(s.base),
            estimator_terminate=est_term, estimator_merge=merge_mult,
            estimate=estimate, merge_is_additive=True, name="sum-multiple",
        )

    raise ValueError(f"unknown estimator model: {estimator!r}")


# ---------------------------------------------------------------------------
# Paper Alg. 3 — GLAGroupBy (composite GLA: a GLASum per group)
# ---------------------------------------------------------------------------

# Largest static group count whose chunk partials take the one-hot
# contraction: the MXU's lane width, where a 1024-row chunk's [L, G] f32
# one-hot is 512 KiB.  A v5e runs segment_sum as a serialized scatter
# (~9 ns a row per partial); larger domains, such as 2**13 hash buckets,
# whose one-hot would be 32 MiB a chunk, keep it.
ONEHOT_MAX_GROUPS = 128


def group_partials_path(num_groups: int) -> str:
    """How a chunk's group partials lower for a static group count:
    ``"onehot"`` (a contraction) or ``"scatter"`` (``segment_sum``)."""
    return "onehot" if num_groups <= ONEHOT_MAX_GROUPS else "scatter"


def group_partials(vals, w, gids, num_groups: int):
    """One chunk's per-group (sum, sumsq, matched) partials.

    ``vals`` [n, A], ``w`` [n] weights, ``gids`` [n] int32.  Ids outside
    ``[0, num_groups)`` are dropped on both paths: ``segment_sum`` drops
    them, and they equal no column of the one-hot's iota.  The one-hot
    dots run at ``Precision.HIGHEST`` — at default precision a TPU rounds
    the f32 operands to bfloat16.  The barrier keeps each chunk's [G, A]
    partial a value of its own: without it XLA folds
    ``carry + scatter_add(0, …)`` into ``scatter_add(carry, …)``, which adds
    every row straight into the running carry — f32 error then grows with
    the rows scanned, not with the chunks (4.7e-4 relative at 2^25 rows per
    partition).
    """
    vw = vals * w[:, None]
    if group_partials_path(num_groups) == "onehot":
        onehot = (jax.lax.broadcasted_iota(
            jnp.int32, (gids.shape[0], num_groups), 1)
            == gids[:, None]).astype(vals.dtype)                # [n, G]
        dot = partial(jnp.dot, preferred_element_type=vals.dtype,
                      precision=jax.lax.Precision.HIGHEST)
        parts = (dot(onehot.T, vw), dot(onehot.T, vals * vw),
                 dot(onehot.T, w[:, None])[:, 0])
    else:
        parts = (jax.ops.segment_sum(vw, gids, num_segments=num_groups),
                 jax.ops.segment_sum(vals * vw, gids, num_segments=num_groups),
                 jax.ops.segment_sum(w, gids, num_segments=num_groups))
    return jax.lax.optimization_barrier(parts)


def make_groupby_gla(
    func: Callable[[Chunk], jnp.ndarray],
    cond: Callable[[Chunk], jnp.ndarray],
    group: Callable[[Chunk], jnp.ndarray],
    *,
    num_groups: int,
    d_total: float,
    estimator: str = "single",
    dtype=jnp.float32,
    num_aggs: int = 1,
    bucket_bits: Optional[int] = None,
) -> GLA:
    """GROUP BY gAtts SUM(func(d)) WHERE cond(d) — paper query (5).

    State is the dense composite of per-group GLASum states ("GLA
    composition", paper §4.4): sums/sumsqs/matched are [G, A]/[G]; ``scanned``
    is global (each group's predicate is cond ∧ group==g over the same scan).
    A chunk's per-group partials come from :func:`group_partials`: a
    one-hot contraction up to :data:`ONEHOT_MAX_GROUPS` groups, a
    ``segment_sum`` scatter above.

    ``bucket_bits`` enables the large-domain hash-bucketed group table
    (paper's 1M-group Q1): raw ids from ``group`` are folded through
    :func:`hash_bucket` and the dense state covers the 2**bucket_bits
    buckets instead of the raw domain.  Recover per-raw-id rows with
    :func:`debucket` (exact for num_groups <= 2**bucket_bits).

    Under the single/synchronized/none estimation models, float32 states
    publish the group-by ``kernel_cols`` contract
    ``chunk -> (vals, weight, gids)`` plus ``kernel_num_groups``, so
    ``engine.run_query(emit="kernel")`` dispatches the Pallas one-hot MXU
    kernel (``repro/kernels/group_agg.py``) once per round-slice
    (DESIGN.md §3).  The "multiple" estimator keeps its MultState wrapper
    on the scan paths only.
    """
    A = num_aggs
    if bucket_bits is not None:
        raw_group = group

        def group(chunk):  # noqa: F811 — bucketed view of the raw ids
            return hash_bucket(raw_group(chunk), bucket_bits)

        G = 1 << bucket_bits
    else:
        G = num_groups

    def zero():
        return E.SumState(
            sum=jnp.zeros((G, A), dtype), sumsq=jnp.zeros((G, A), dtype),
            scanned=jnp.zeros((), dtype), matched=jnp.zeros((G,), dtype),
        )

    def acc(state: E.SumState, chunk: Chunk) -> E.SumState:
        vals = _as_2d(func(chunk)).astype(dtype)             # [n, A]
        w = (cond(chunk) * chunk["_mask"]).astype(dtype)     # [n]
        gids = group(chunk).astype(jnp.int32)                # [n]
        d_s, d_q, d_m = group_partials(vals, w, gids, G)
        return E.SumState(
            sum=state.sum + d_s,
            sumsq=state.sumsq + d_q,
            scanned=state.scanned + jnp.sum(chunk["_mask"].astype(dtype)),
            matched=state.matched + d_m,
        )

    def merge(a, b):
        return jax.tree.map(jnp.add, a, b)

    suffix = f"-b{bucket_bits}" if bucket_bits is not None else ""

    if estimator in ("single", "synchronized", "none"):

        def estimate(state: E.SumState, confidence, ctx=None) -> Estimate:
            est = E.horvitz_estimate(state.sum, state.scanned, d_total)   # [G, A]
            var = E.variance_estimate(state.sum, state.sumsq, state.scanned, d_total)
            lo, hi = E.normal_bounds(est, var, confidence)
            return Estimate(est, lo, hi, info={"var": var, "matched": state.matched})

        # Group-by fused-kernel dispatch (engine emit="kernel"): ops.group_agg
        # reproduces acc's state from the (func, cond, group) projections —
        # one one-hot MXU dispatch per round-slice (scan.kernel_rounds_states).
        if dtype == jnp.float32:
            def kernel_cols(chunk):
                return func(chunk), cond(chunk), group(chunk)
            kernel_G = G
            # ``group`` here is already the bucketed view when bucket_bits
            # is set, so the kernel hash-buckets in-register too.
            fused = FusedSpec(func=func, cond=cond, group=group, num_aggs=A,
                              num_groups=G)
        else:
            kernel_cols = None
            kernel_G = None
            fused = None

        return GLA(
            init=zero, accumulate=acc, merge=merge,
            terminate=lambda s: s.sum,
            estimate=None if estimator == "none" else estimate,
            merge_is_additive=True, kernel_cols=kernel_cols,
            kernel_num_groups=kernel_G, fused=fused,
            name=f"groupby-{estimator}{suffix}",
        )

    if estimator == "multiple":

        def zero_mult():
            z = jnp.zeros((G, A), dtype)
            return E.MultState(base=zero(), est=z, estvar=z)

        def acc_mult(state, chunk):
            return E.MultState(acc(state.base, chunk), state.est, state.estvar)

        def est_term(state: E.MultState, ctx) -> E.MultState:
            b = state.base
            d_local = ctx["d_local"]
            est = E.horvitz_estimate(b.sum, b.scanned, d_local)
            var = E.variance_estimate(b.sum, b.sumsq, b.scanned, d_local)
            return E.MultState(b, est, var)

        def estimate(state: E.MultState, confidence, ctx=None) -> Estimate:
            lo, hi = E.normal_bounds(state.est, state.estvar, confidence)
            return Estimate(state.est, lo, hi, info={"var": state.estvar})

        return GLA(
            init=zero_mult, accumulate=acc_mult, merge=merge,
            terminate=lambda s: s.base.sum,
            estimator_terminate=est_term, estimator_merge=merge,
            estimate=estimate, merge_is_additive=True,
            name=f"groupby-multiple{suffix}",
        )

    raise ValueError(f"unknown estimator model: {estimator!r}")


# ---------------------------------------------------------------------------
# Paper Alg. 4 — GLAJoin (replicated in-memory dimension table)
# ---------------------------------------------------------------------------

def make_join_groupby_gla(
    func: Callable[[Chunk], jnp.ndarray],
    cond: Callable[[Chunk], jnp.ndarray],
    join_key: Callable[[Chunk], jnp.ndarray],
    dim_group: jnp.ndarray,
    dim_valid: jnp.ndarray,
    *,
    num_groups: int,
    d_total: float,
    estimator: str = "single",
    dtype=jnp.float32,
    num_aggs: int = 1,
    bucket_bits: Optional[int] = None,
    d_dim: Optional[float] = None,
    s_dim: Optional[float] = None,
) -> GLA:
    """Join group-by — paper query (6), M replicated and hashed in memory.

    ``dim_group[k]`` is the group id the dimension row with key ``k`` maps to
    (e.g. supplier -> nation), ``dim_valid[k]`` its cond_M(M.sAtts) predicate.
    Per the paper, H is built by the user application during Init (query
    setup) and shipped with the query — here it is a replicated closure
    constant.  Accumulate = hash-probe (gather) + GLAGroupBy accumulate.

    Fused path: the probe arrays additionally ride as
    ``FusedSpec.probe_tables`` (:class:`repro.core.uda.ProbeTable`) — extra
    ``pallas_call`` operands the kernel injects into the in-kernel chunk
    dict — so Q3/Q10-class two-table queries run the one-dispatch fused
    kernel with the gather *inside* the VMEM residency, bitwise-identical
    to this scan path (the kernel closures repeat the gather expression
    trees below verbatim against the same arrays).  Oversized dimension
    tables fail the kernel's VMEM probe budget and fall back to the legacy
    ``kernel_cols`` path automatically (``fused_agg.fused_available``).

    §3.3 multiplicative join estimator: pass ``d_dim`` (dimension-table
    cardinality) and ``s_dim`` (rows of it sampled so far) to scale the
    estimate by the dimension-side inverse sampling fraction
    (``estimators.join_scale``).  With the replicated table fully resident
    — the default, ``d_dim=None`` — the factor is exactly 1 and the
    estimate is the unchanged single-table Horvitz–Thompson formula.
    """
    dim_group = jnp.asarray(dim_group, jnp.int32)
    dim_valid = jnp.asarray(dim_valid)

    def joined_group(chunk: Chunk) -> jnp.ndarray:
        keys = join_key(chunk).astype(jnp.int32)
        return dim_group[keys]

    def joined_cond(chunk: Chunk) -> jnp.ndarray:
        keys = join_key(chunk).astype(jnp.int32)
        return cond(chunk) * dim_valid[keys].astype(cond(chunk).dtype)

    inner = make_groupby_gla(
        func, joined_cond, joined_group,
        num_groups=num_groups, d_total=d_total, estimator=estimator,
        dtype=dtype, num_aggs=num_aggs, bucket_bits=bucket_bits,
    )

    fused = None
    if inner.fused is not None:
        pt_group = ProbeTable("dim_group", dim_group)
        pt_valid = ProbeTable("dim_valid", dim_valid)

        def fused_group(chunk: Chunk) -> jnp.ndarray:
            keys = join_key(chunk).astype(jnp.int32)
            gids = chunk[pt_group.key][keys]
            if bucket_bits is not None:
                gids = hash_bucket(gids, bucket_bits)
            return gids

        def fused_cond(chunk: Chunk) -> jnp.ndarray:
            keys = join_key(chunk).astype(jnp.int32)
            return cond(chunk) * chunk[pt_valid.key][keys].astype(
                cond(chunk).dtype)

        fused = inner.fused._replace(
            cond=fused_cond, group=fused_group,
            probe_tables=(pt_group, pt_valid))

    est_fn = inner.estimate
    if est_fn is not None and d_dim is not None:
        sd = float(d_dim if s_dim is None else s_dim)
        scale = jnp.asarray(
            float(d_dim), dtype) / jnp.maximum(jnp.asarray(sd, dtype), 1.0)
        inner_estimate = est_fn

        def est_fn(state, confidence, ctx=None):  # noqa: F811
            e = inner_estimate(state, confidence, ctx)
            var = e.info["var"] * (scale * scale)
            est = e.estimate * scale
            lo, hi = E.normal_bounds(est, var, confidence)
            return Estimate(est, lo, hi,
                            info={**e.info, "var": var, "dim_scale": scale})

    return inner.with_(name=f"join-{estimator}", fused=fused,
                       estimate=est_fn)


# ---------------------------------------------------------------------------
# Deep OLA composition — an outer estimator consuming inner OLA estimates
# (PAPERS.md 2303.04103; DESIGN.md §13)
# ---------------------------------------------------------------------------

def compose(inner: GLA, outer_estimate: Callable[[Estimate, float], Estimate],
            *, name: Optional[str] = None) -> GLA:
    """Nest an outer estimator over the inner GLA's *estimate*.

    Execution scaffolding — init/accumulate/merge/terminate, the estimator
    extensions, kernel contracts, additivity — is the inner GLA's
    **verbatim**: a composed plan rides every engine path, fused kernel,
    session, checkpoint envelope, and fault policy exactly as the inner
    plan does, with bitwise-identical states.  Only ``estimate`` differs:
    the inner estimate is computed first, then
    ``outer_estimate(inner_est, confidence)`` maps it to the outer
    :class:`Estimate` — the Deep OLA pattern where each refinement round
    re-derives the whole nested answer from the current inner bounds,
    variance propagated through the nesting
    (``estimators.nested_group_estimate``).
    """
    if inner.estimate is None:
        raise ValueError(
            f"compose() needs an inner GLA with an estimation model, "
            f"got {inner.name!r}")
    if inner.members:
        raise ValueError("compose() nests a single GLA, not a bundle — "
                         "bundle the composed GLAs instead")
    inner_estimate = inner.estimate

    def estimate(state, confidence, ctx=None) -> Estimate:
        return outer_estimate(inner_estimate(state, confidence, ctx),
                              confidence)

    return inner.with_(estimate=estimate,
                       name=name or f"compose[{inner.name}]")


def make_having_gla(inner: GLA, threshold, *, mode: str = ">=",
                    agg: int = 0, name: Optional[str] = None) -> GLA:
    """GROUP BY + HAVING over *estimated* aggregates (Deep OLA query shape).

    Sums the inner group-by's per-group estimates over the groups whose
    inner point estimate (aggregate column ``agg``) passes
    ``estimate <mode> threshold``, with the outer variance propagated as
    the sum of passing groups' inner variances — a group at |S| <= 1
    (+inf inner variance) that passes HAVING poisons the outer bound to
    ±inf, never NaN (estimators.nested_group_estimate).  ``threshold``
    may be a traced value (the serving layer passes per-slot thresholds
    as dynamic jit inputs).  Per-round bounds can widen transiently when
    the predicate flips a group; apply ``estimators.monotone_envelope``
    post-hoc for a monotone UI envelope.
    """
    cmps = {">=": lambda v, t: v >= t, ">": lambda v, t: v > t,
            "<=": lambda v, t: v <= t, "<": lambda v, t: v < t}
    if mode not in cmps:
        raise ValueError(f"unknown HAVING mode {mode!r}")
    cmp = cmps[mode]

    def having(est_g):
        v = est_g[:, agg] if est_g.ndim == 2 else est_g
        return cmp(v, threshold)

    def outer(inner_est: Estimate, confidence) -> Estimate:
        return E.nested_group_estimate(inner_est, having, confidence)

    return compose(inner, outer,
                   name=name or f"having[{inner.name}{mode}{threshold!r}]")


# ---------------------------------------------------------------------------
# Padded-slot query families — the serving layer's dynamic bundle
# (repro/serving/service.py, DESIGN.md §11).
#
# A GLABundle fixes its membership at trace time: every attach/detach of a
# query would build a new bundle object, and the engines' jit caches key on
# the GLA statically — a recompile per arrival.  A SlotFamily instead fixes
# the *query family* statically (a basis of value expressions, a set of
# range-predicate columns, optional group keys) and makes the per-slot
# query parameters DYNAMIC jit inputs (:class:`SlotParams`): which basis
# expression a slot aggregates, its half-open predicate ranges, and whether
# the slot was freshly (re)claimed this round.  The serving step then
# compiles once per (family, bank, slot capacity) and serves any
# arrival/departure pattern from the same executable; capacity grows in
# powers of two, so compile count under churn is bounded by capacity
# doublings, never per-arrival (audit: ``bounded_compiles_under_churn``).
#
# Bitwise discipline: each slot's program is built from the SAME
# constructors as a solo query (``make_sum_gla`` / ``make_groupby_gla``)
# with value selection by row-gather from the stacked basis and predicate
# weights from identical half-open comparisons, combined by the SAME
# tuple combinator as :func:`GLABundle` — so a slot's states, estimates
# and bounds are bitwise-identical to a fresh solo Session over the rounds
# the slot witnessed (tests/test_service.py).  Slot reclaim resets state
# via ``jnp.where(fresh, zeros, state)`` — never by multiplying with a
# 0/1 mask, which would turn negative carries into -0.0 and break bitwise
# identity with a fresh +0.0 init.
# ---------------------------------------------------------------------------

_INACTIVE_LO = np.float32(np.inf)    # empty half-open range: weight exactly 0
_INACTIVE_HI = np.float32(-np.inf)


class SlotQuery(NamedTuple):
    """One query expressible in a :class:`SlotFamily`.

    ``SUM(exprs[expr](d)) WHERE AND_j lo_j <= pred_col_j(d) < hi_j
    [GROUP BY group [HAVING est >= having]]`` — ``ranges`` maps predicate
    column -> (lo, hi) half-open; columns not named are unconstrained.
    ``group`` names one of the family's group keys (None = scalar
    aggregate).  ``having`` (requires ``group``) nests the Deep OLA
    HAVING estimator over the group estimates: the slot reports the SUM
    over groups whose estimated aggregate is >= the threshold
    (``gla.make_having_gla``); the threshold is a *dynamic* slot
    parameter, so arrivals with different thresholds share one compiled
    step.
    """

    expr: str
    ranges: Mapping[str, Tuple[float, float]] = {}
    group: Optional[str] = None
    having: Optional[float] = None


class SlotParams(NamedTuple):
    """Dynamic per-slot parameters of one bank — jit INPUTS, never
    statics.  Leaves are [K] / [K, n_pred] with K the bank's power-of-two
    slot capacity; inactive slots carry the empty range (lo=+inf,
    hi=-inf), so their predicate weight is exactly 0 on every tuple.
    ``hv`` is the per-slot HAVING threshold (having banks only; +inf on
    inactive slots, so no group passes and the nested estimate is an
    exact 0 ± 0)."""

    expr: jnp.ndarray   # int32 [K] — row into the family's expression basis
    lo: jnp.ndarray     # float32 [K, n_pred]
    hi: jnp.ndarray     # float32 [K, n_pred]
    fresh: jnp.ndarray  # bool [K] — reclaim: reset the slot's carry first
    hv: Optional[jnp.ndarray] = None  # float32 [K] — HAVING thresholds


def _range_cond(pred_cols: Tuple[str, ...], lo, hi):
    """Predicate closure over (possibly traced) per-column bounds.

    Shared verbatim between a slot's in-bundle program (traced bounds)
    and its solo comparison GLA (host float32 bounds), so the 0/1 weights
    are bitwise-identical.  Unconstrained columns carry (-inf, +inf) and
    compare all-True for finite data either way.
    """
    def cond(chunk):
        w = None
        for j, col in enumerate(pred_cols):
            c = (chunk[col] >= lo[j]) & (chunk[col] < hi[j])
            w = c if w is None else w & c
        return w.astype(jnp.float32)

    return cond


class SlotFamily:
    """A parametric family of slot queries over a fixed expression basis.

    Args:
      exprs: ordered mapping name -> (chunk -> [n] float32) value
        expressions — the basis a slot selects from by index.
      pred_cols: the columns range predicates may constrain.
      groups: optional mapping name -> (group_fn, num_groups) for group-by
        slots; each group key gets its own bank (its own dense [G, A]
        states and its own jitted step).

    Instances hash by identity — the serving layer builds ONE family per
    service and uses it as the static jit key of its per-round step; two
    equal-looking families are different compile keys on purpose.
    """

    def __init__(self, exprs: Mapping[str, Callable[[Chunk], jnp.ndarray]],
                 pred_cols: Sequence[str],
                 groups: Optional[Mapping[str, Tuple[Callable, int]]] = None):
        self.expr_names: Tuple[str, ...] = tuple(exprs)
        self._expr_fns = tuple(exprs[n] for n in self.expr_names)
        if not self._expr_fns:
            raise ValueError("SlotFamily needs at least one basis expression")
        self.pred_cols: Tuple[str, ...] = tuple(pred_cols)
        self.groups = dict(groups or {})

    # -- host-side parameter rows -------------------------------------------

    def bank_of(self, q: SlotQuery) -> str:
        """The bank a query lands in: its group key, "scalar", or — for
        nested HAVING queries — ``"<group>:having"`` (tree-shaped members
        need their own compiled step: same states, different estimate)."""
        if q.group is not None and q.group not in self.groups:
            raise KeyError(f"unknown group key {q.group!r}; family has "
                           f"{sorted(self.groups)}")
        if q.having is not None:
            if q.group is None:
                raise ValueError(
                    "SlotQuery.having needs a group key — HAVING nests "
                    "over per-group estimates")
            return f"{q.group}:having"
        return q.group if q.group is not None else "scalar"

    def slot_row(self, q: SlotQuery):
        """Host (expr_idx, lo[n_pred], hi[n_pred]) float32 row for ``q``."""
        if q.expr not in self.expr_names:
            raise KeyError(f"unknown expression {q.expr!r}; family basis is "
                           f"{list(self.expr_names)}")
        unknown = sorted(set(q.ranges) - set(self.pred_cols))
        if unknown:
            raise KeyError(f"query constrains {unknown}, not in the "
                           f"family's pred_cols {list(self.pred_cols)}")
        lo = np.full(len(self.pred_cols), -np.inf, np.float32)
        hi = np.full(len(self.pred_cols), np.inf, np.float32)
        for j, col in enumerate(self.pred_cols):
            if col in q.ranges:
                lo[j], hi[j] = (np.float32(q.ranges[col][0]),
                                np.float32(q.ranges[col][1]))
        return self.expr_names.index(q.expr), lo, hi

    def inactive_row(self):
        """(expr_idx, lo, hi) of a parked slot: the empty range."""
        n = len(self.pred_cols)
        return (0, np.full(n, _INACTIVE_LO, np.float32),
                np.full(n, _INACTIVE_HI, np.float32))

    # -- per-slot GLA programs ----------------------------------------------

    def _select_func(self, expr_idx):
        """Value expression by (possibly traced) basis index: the stacked
        basis is computed once per chunk (CSE'd across slots) and the
        slot's row gathered — the gathered row is bitwise the expression's
        own output, so it matches the solo GLA's direct call."""
        fns = self._expr_fns
        if len(fns) == 1:
            return fns[0]

        def func(chunk):
            return jnp.stack([f(chunk) for f in fns])[expr_idx]

        return func

    def _member_gla(self, bank: str, func, cond, d_total, hv=None) -> GLA:
        if bank == "scalar":
            return make_sum_gla(func, cond, d_total=d_total)
        base, _, nested = bank.partition(":")
        gfn, G = self.groups[base]
        inner = make_groupby_gla(func, cond, gfn, num_groups=G,
                                 d_total=d_total)
        if nested != "having":
            return inner
        # tree-shaped member: the slot's state IS the group-by state; only
        # the estimate nests (gla.compose), so carries, reclaim, and the
        # psum merge are the group bank's unchanged.  The (possibly
        # traced) threshold stays out of the static name.
        return make_having_gla(inner, hv, name=f"having[{base}]")

    def solo_gla(self, q: SlotQuery, *, d_total: float) -> GLA:
        """The stand-alone GLA of one slot query — what a fresh Session
        would run.  Built from the same constructors, the same predicate
        closure and the same d_total as the in-bundle slot program, so it
        is the bitwise reference for late-join tests."""
        expr_idx, lo, hi = self.slot_row(q)
        cond = _range_cond(self.pred_cols, lo, hi)
        hv = None if q.having is None else jnp.float32(q.having)
        return self._member_gla(self.bank_of(q), self._expr_fns[expr_idx],
                                cond, d_total, hv)

    def bind(self, bank: str, params: SlotParams, d_total) -> GLA:
        """The K-slot bundle GLA of one bank, closed over (traced) params.

        Called INSIDE the serving step's jit region: the returned GLA's
        member closures capture the traced per-slot parameters, so the
        step function — whose statics are only (family, bank, K) — serves
        every arrival/departure pattern from one executable.  Never
        memoized (see :func:`_combine_members`).
        """
        K = int(params.expr.shape[0])
        members = []
        for k in range(K):
            func = self._select_func(params.expr[k])
            cond = _range_cond(self.pred_cols, params.lo[k], params.hi[k])
            hv = None if params.hv is None else params.hv[k]
            members.append(self._member_gla(bank, func, cond, d_total, hv))
        return _combine_members(tuple(members), f"slots-{bank}x{K}")

    def partials_path(self, bank: str) -> str:
        """How the bank's chunk partials lower: ``"none"`` for the scalar
        bank, else :func:`group_partials_path` of its group count."""
        if bank == "scalar":
            return "none"
        return group_partials_path(self.groups[bank.partition(":")[0]][1])

    def zero_slot_state(self, bank: str):
        """One slot's init state (the reclaim target of a fresh slot)."""
        if bank == "scalar":
            z = jnp.zeros((1,), jnp.float32)
            s = jnp.zeros((), jnp.float32)
            return E.SumState(sum=z, sumsq=z, scanned=s, matched=s)
        _, G = self.groups[bank.partition(":")[0]]
        return E.SumState(
            sum=jnp.zeros((G, 1), jnp.float32),
            sumsq=jnp.zeros((G, 1), jnp.float32),
            scanned=jnp.zeros((), jnp.float32),
            matched=jnp.zeros((G,), jnp.float32))


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (and >= 1) — slot-capacity discipline."""
    return 1 << max(0, int(n - 1).bit_length())
