"""Shared scan/merge core — the one implementation both execution paths run.

The engine has two physical execution paths (DESIGN.md §2, §4):

  * vmapped   — partitions are a leading array axis on one device
                (``repro.core.engine._run_vmapped``), and
  * sharded   — partitions are devices along the ``data`` mesh axis under
                ``jax.shard_map`` (``repro.dist.shard_engine.run_sharded``).

Both consume the per-partition scan primitives in this module, so the GLA
math is written exactly once; the paths differ only in how per-partition
states are merged (tensordot over the partition axis vs. ``lax.psum``).

Scan variants (selected by the engine's ``emit`` argument):

  ``scan_prefix``        every prefix state [C+1, ...]; small-state GLAs,
                         arbitrary snapshot schedules.
  ``scan_rounds``        state only at round boundaries; large-state GLAs,
                         uniform schedules (C % R == 0).
  ``scan_rounds_masked`` per-round O(R·C) masked re-scan; large-state GLAs,
                         arbitrary schedules.
  ``kernel_prefix_states`` one fused Pallas dispatch for the whole shard
                         (per-chunk partials + prefix-sum); SumState GLAs
                         that publish ``kernel_cols`` (DESIGN.md §3).
  ``kernel_rounds_states`` one ``ops.group_agg`` Pallas dispatch per
                         round-slice; group-by GLAs that publish
                         ``kernel_cols`` + ``kernel_num_groups`` — dense
                         [G, A] states follow the round emission discipline
                         (DESIGN.md §3).
  ``fused_rounds_states`` / ``fused_prefix_states`` — the fused
                         selection→bucket→aggregate kernel (DESIGN.md §12,
                         kernels/fused_agg.py): predicate, hash-bucketing,
                         in-kernel column decode and f32 accumulation in
                         ONE carry-in dispatch per round-slice, bitwise-
                         identical to the scan paths (scalar included).
                         Preferred by both engines whenever the GLA
                         publishes a ``FusedSpec`` (``gla.fused``); the
                         kernel_* paths above remain for GLAs that only
                         publish the legacy ``kernel_cols`` projection.

The per-round-slice primitives those variants fold over all rounds —
``scan_round_step``, ``kernel_round_delta``, ``bundle_round_deltas``,
``kernel_scalar_round_delta``, ``fused_round_step`` — are also jitted
standalone by the incremental session driver (repro/core/session.py,
DESIGN.md §7), which advances one round at a time so stopping rules can
terminate the scan early.  One implementation, two execution disciplines.

``round_weights`` centralizes partition-liveness accounting: the engine and
the fault model (repro/dist/fault.py) express node failure as an ``alive``
mask of shape [P] (static) or [R, P] (failure-injection schedule), and every
merge weights partition states by it.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.uda import GLA

Pytree = Any


# ---------------------------------------------------------------------------
# lane (work-unit) handling
# ---------------------------------------------------------------------------

def stack_init(gla: GLA, lanes: int) -> Pytree:
    """Initial state, broadcast to ``lanes`` parallel GLA states."""
    s = gla.init()
    if lanes == 1:
        return s
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (lanes, *x.shape)), s)


def fold_merge(merge, states: Pytree, n: int) -> Pytree:
    """Left-fold ``merge`` over a leading axis of length ``n``."""
    acc = jax.tree.map(lambda x: x[0], states)
    for i in range(1, n):
        acc = merge(acc, jax.tree.map(lambda x, i=i: x[i], states))
    return acc


def accumulate_chunk(gla: GLA, states: Pytree, chunk: dict, lanes: int):
    """Advance lane states by one chunk; return (states, lane-merged view)."""
    if lanes == 1:
        st = gla.accumulate(states, chunk)
        return st, st
    lc = {k: v.reshape(lanes, -1) for k, v in chunk.items()}
    st = jax.vmap(gla.accumulate)(states, lc)
    return st, fold_merge(gla.merge, st, lanes)


# ---------------------------------------------------------------------------
# per-partition scans
# ---------------------------------------------------------------------------

def scan_prefix(gla: GLA, cols: dict, lanes: int):
    """Scan chunks emitting every prefix state (init prepended): [C+1, ...].

    Used when snapshots at *arbitrary* per-partition progress are needed
    (straggler schedules, sync truncation).  State must be small — the
    emission cost is O(C · |state|) HBM traffic, nothing else.
    """
    init = stack_init(gla, lanes)
    init_view = fold_merge(gla.merge, init, lanes) if lanes > 1 else init

    def body(st, chunk):
        st, view = accumulate_chunk(gla, st, chunk, lanes)
        return st, view

    last, prefixes = lax.scan(body, init, cols)
    prefixes = jax.tree.map(
        lambda i, p: jnp.concatenate([i[None], p], axis=0), init_view, prefixes
    )
    final_view = jax.tree.map(lambda p: p[-1], prefixes)
    return final_view, prefixes


def scan_round_step(gla: GLA, states: Pytree, round_cols: dict, lanes: int):
    """Advance laned per-partition states by ONE round-slice of chunks.

    The per-round-slice primitive both execution disciplines share: the
    monolithic :func:`scan_rounds` folds it over all rounds inside one
    program, and the incremental session driver (repro/core/session.py)
    jits it standalone and advances round by round, evaluating stopping
    rules in between.  Identical chunk-sequential accumulation order either
    way, so round-boundary states are bitwise-identical across disciplines
    (tests/test_session.py).

    Returns (new laned states, lane-merged round-boundary view).
    """
    def chunk_body(s, chunk):
        s, _ = accumulate_chunk(gla, s, chunk, lanes)
        return s, None

    states, _ = lax.scan(chunk_body, states, round_cols)
    view = fold_merge(gla.merge, states, lanes) if lanes > 1 else states
    return states, view


def scan_rounds(gla: GLA, cols: dict, lanes: int, rounds: int):
    """Uniform-schedule fast path: emit state only at round boundaries.

    O(|state|·R) emission — usable for large-state GLAs (1M-group group-by).
    Requires C % rounds == 0.
    """
    C = cols["_mask"].shape[0]
    assert C % rounds == 0, f"uniform rounds path needs C%R==0, got {C}%{rounds}"
    per = C // rounds
    rcols = {k: v.reshape((rounds, per, *v.shape[1:])) for k, v in cols.items()}
    init = stack_init(gla, lanes)

    def round_body(st, round_cols):
        return scan_round_step(gla, st, round_cols, lanes)

    last, views = lax.scan(round_body, init, rcols)
    final_view = fold_merge(gla.merge, last, lanes) if lanes > 1 else last
    return final_view, views


def scan_rounds_masked(gla: GLA, cols: dict, sched: jnp.ndarray, lanes: int):
    """Arbitrary-schedule path for large-state GLAs: O(R·C) masked scan.

    Round r re-scans all chunks with liveness mask (lo <= c < hi); correctness
    from the uda mask contract.  Emission is per-round.
    """
    C = cols["_mask"].shape[0]
    R = sched.shape[0] - 1
    init = stack_init(gla, lanes)

    def round_body(st, r):
        lo, hi = sched[r], sched[r + 1]

        def chunk_body(carry, xs):
            s = carry
            c, chunk = xs
            live = ((c >= lo) & (c < hi)).astype(chunk["_mask"].dtype)
            chunk = dict(chunk)
            chunk["_mask"] = chunk["_mask"] * live
            s, _ = accumulate_chunk(gla, s, chunk, lanes)
            return s, None

        st, _ = lax.scan(chunk_body, st, (jnp.arange(C), cols))
        view = fold_merge(gla.merge, st, lanes) if lanes > 1 else st
        return st, view

    last, views = lax.scan(round_body, init, jnp.arange(R))
    final_view = fold_merge(gla.merge, last, lanes) if lanes > 1 else last
    return final_view, views


# ---------------------------------------------------------------------------
# fused-kernel shard path (per-shard kernel dispatch, DESIGN.md §3)
# ---------------------------------------------------------------------------

def kernel_prefix_states(gla: GLA, cols: dict):
    """One Pallas dispatch for a whole [C, L] shard -> SumState prefixes.

    Valid for GLAs that publish ``kernel_cols`` (additive SumState layout):
    the kernel emits per-chunk (sum, sumsq, scanned, matched) partials in a
    single launch; additivity turns the prefix states into a cumsum, so the
    result is interchangeable with :func:`scan_prefix` at lanes == 1.
    """
    from repro.core import estimators as E
    from repro.kernels import ops

    assert gla.kernel_cols is not None, "GLA does not publish kernel_cols"
    C, L = cols["_mask"].shape
    flat = {k: v.reshape(C * L) for k, v in cols.items()}
    vals, weight = gla.kernel_cols(flat)
    partials = ops.shard_chunk_partials(
        vals.reshape(C, L), weight.reshape(C, L), cols["_mask"]
    )  # [C, 4]
    cum = jnp.concatenate(
        [jnp.zeros((1, 4), partials.dtype), jnp.cumsum(partials, axis=0)], 0
    )  # [C+1, 4]
    prefixes = E.SumState(
        sum=cum[:, 0:1], sumsq=cum[:, 1:2], scanned=cum[:, 2], matched=cum[:, 3]
    )
    final_view = jax.tree.map(lambda p: p[-1], prefixes)
    return final_view, prefixes


def _unroll_partitions(fn, shards: dict):
    """Run a per-shard (final, views) function on every partition, stacked.

    P is small and static, so an unrolled loop keeps the Pallas calls out of
    scan/vmap transforms (interpret mode on CPU stays supported).
    """
    P = shards["_mask"].shape[0]
    outs = [fn(jax.tree.map(lambda x, p=p: x[p], shards)) for p in range(P)]
    finals = jax.tree.map(lambda *xs: jnp.stack(xs), *[o[0] for o in outs])
    views = jax.tree.map(lambda *xs: jnp.stack(xs), *[o[1] for o in outs])
    return finals, views


def _fold_running_sum(deltas):
    """Fold per-round additive deltas into round-boundary states.

    Sequential association order on purpose — it matches the scan paths'
    chunk-by-chunk accumulation, which is what keeps kernel-path states
    bitwise-identical to the scan states.  Returns (final, views stacked
    [R, ...]).
    """
    acc, views = deltas[0], [deltas[0]]
    for d in deltas[1:]:
        acc = jax.tree.map(jnp.add, acc, d)
        views.append(acc)
    return acc, jax.tree.map(lambda *xs: jnp.stack(xs), *views)


def kernel_prefix_states_batched(gla: GLA, shards: dict):
    """Vmapped-path wrapper: one kernel dispatch per partition, stacked."""
    return _unroll_partitions(lambda c: kernel_prefix_states(gla, c), shards)


def kernel_scalar_round_delta(gla: GLA, slice_cols: dict):
    """Scalar-contract SumState delta for ONE round-slice of a shard.

    One ``shard_chunk_partials`` dispatch over the slice; the within-slice
    prefix keeps the chunk-sequential association, so the delta is the
    slice's chunk-ordered total.  Adding deltas round by round re-associates
    float adds against the whole-shard cumsum of
    :func:`kernel_prefix_states`, so this legacy path is interchangeable —
    not bitwise-identical — with the scan path.  Sessions prefer
    :func:`fused_round_step` (carry-in accumulation, bitwise-identical to
    the scan path) whenever the GLA publishes ``gla.fused``; this primitive
    remains for kernel_cols-only GLAs.
    """
    from repro.core import estimators as E
    from repro.kernels import ops

    assert gla.kernel_cols is not None, "GLA does not publish kernel_cols"
    C, L = slice_cols["_mask"].shape
    flat = {k: v.reshape(C * L) for k, v in slice_cols.items()}
    vals, weight = gla.kernel_cols(flat)
    partials = ops.shard_chunk_partials(
        vals.reshape(C, L), weight.reshape(C, L), slice_cols["_mask"]
    )  # [C, 4]
    tot = jnp.cumsum(partials, axis=0)[-1]
    return E.SumState(sum=tot[0:1], sumsq=tot[1:2], scanned=tot[2],
                      matched=tot[3])


def kernel_round_delta(gla: GLA, slice_cols: dict):
    """Group-by SumState delta for ONE round-slice: a single ``group_agg``
    dispatch with ``block_rows`` pinned to the chunk length (chunk-sequential
    association inside the kernel).  The per-round-slice primitive shared by
    the monolithic :func:`kernel_rounds_states` loop and the incremental
    session driver — both fold deltas with the same sequential running sum,
    so round-boundary states are bitwise-identical across disciplines."""
    from repro.core import estimators as E
    from repro.kernels import ops

    assert gla.kernel_cols is not None, "GLA does not publish kernel_cols"
    assert gla.kernel_num_groups is not None, (
        "GLA publishes the scalar kernel contract, not the group-by one")
    per, L = slice_cols["_mask"].shape
    sl = {k: v.reshape(per * L) for k, v in slice_cols.items()}
    vals, weight, gids = gla.kernel_cols(sl)
    w = (weight * sl["_mask"]).astype(jnp.float32)
    sums, sumsqs, matched = ops.group_agg(
        vals, w, gids.astype(jnp.int32), num_groups=gla.kernel_num_groups,
        block_rows=L)
    return E.SumState(
        sum=sums, sumsq=sumsqs,
        scanned=jnp.sum(sl["_mask"].astype(jnp.float32)),
        matched=matched,
    )


def kernel_rounds_states(gla: GLA, cols: dict, rounds: int):
    """One ``ops.group_agg`` dispatch per round-slice -> group SumState views.

    Valid for group-by GLAs publishing the ``(vals, weight, gids)`` kernel
    projection plus ``kernel_num_groups`` (core/gla.make_groupby_gla).  The
    dense [G, A] state makes per-chunk prefix emission memory-infeasible, so
    this path composes with the ``emit="round"`` discipline instead: the
    kernel aggregates each round-slice of the shard in a single launch and
    additivity turns the round-boundary states into a running sum of the
    per-round deltas — interchangeable with :func:`scan_rounds` at lanes==1.

    ``block_rows`` is pinned to the chunk length, so the kernel accumulates
    chunk-by-chunk in the same association order as the scan path; the
    running sum over rounds is folded sequentially for the same reason
    (see tests/test_groupby_kernel.py for the bitwise-equality check).
    """
    C, L = cols["_mask"].shape
    assert C % rounds == 0, (
        f"group-by kernel path needs C % rounds == 0, got {C} % {rounds}")
    per = C // rounds
    deltas = [
        kernel_round_delta(
            gla, {k: v[r * per:(r + 1) * per] for k, v in cols.items()})
        for r in range(rounds)
    ]
    return _fold_running_sum(deltas)


def kernel_rounds_states_batched(gla: GLA, shards: dict, rounds: int):
    """Vmapped-path wrapper for :func:`kernel_rounds_states`: unrolled over
    partitions (same rationale as :func:`_unroll_partitions`)."""
    return _unroll_partitions(
        lambda c: kernel_rounds_states(gla, c, rounds), shards)


# ---------------------------------------------------------------------------
# multi-query bundles: batched kernel dispatch (DESIGN.md §6)
# ---------------------------------------------------------------------------

def _bundle_member_projection(member: GLA, sl: dict):
    """Normalize a member's kernel projection to (vals [n, A], weight, G).

    Scalar-contract members (``kernel_num_groups is None``) are folded in as
    a 1-group table: their ``(vals, weight)`` projection becomes a group-by
    projection with every item in group 0, so a single ``ops.group_agg``
    dispatch serves scalar and group-by members alike.
    """
    assert member.kernel_cols is not None, (
        f"bundle member {member.name!r} does not publish kernel_cols")
    if member.kernel_num_groups is None:
        vals, weight = member.kernel_cols(sl)
        gids = jnp.zeros(vals.shape[0], jnp.int32)
        G = 1
    else:
        vals, weight, gids = member.kernel_cols(sl)
        G = member.kernel_num_groups
    if vals.ndim == 1:
        vals = vals[:, None]
    return vals, weight, gids.astype(jnp.int32), G


def bundle_round_deltas(gla: GLA, slice_cols: dict):
    """Per-member SumState deltas for ONE round-slice of a bundle: every
    member's kernel projection stacked row-wise into a single ``group_agg``
    dispatch (gid offsets into one concatenated group table, vals zero-padded
    to the widest member — see :func:`bundle_kernel_rounds_states` for why
    members stay value-isolated).  The per-round-slice primitive shared by
    the monolithic loop and the incremental session driver.  Returns a tuple
    of one delta per member, matching the bundle's tuple-state layout."""
    from repro.core import estimators as E
    from repro.kernels import ops

    members = gla.members
    assert members, "bundle kernel path needs a GLABundle"
    per, L = slice_cols["_mask"].shape
    sl = {k: v.reshape(per * L) for k, v in slice_cols.items()}
    mask = sl["_mask"].astype(jnp.float32)
    scanned = jnp.sum(mask)
    projs = [_bundle_member_projection(m, sl) for m in members]
    A_max = max(v.shape[1] for v, _, _, _ in projs)
    offs = []
    vals_cat, w_cat, gids_cat = [], [], []
    off = 0
    for vals, weight, gids, G in projs:
        offs.append(off)
        if vals.shape[1] < A_max:
            vals = jnp.concatenate(
                [vals, jnp.zeros((vals.shape[0], A_max - vals.shape[1]),
                                 vals.dtype)], axis=1)
        vals_cat.append(vals)
        w_cat.append((weight * sl["_mask"]).astype(jnp.float32))
        gids_cat.append(gids + jnp.int32(off))
        off += G
    sums, sumsqs, matched = ops.group_agg(
        jnp.concatenate(vals_cat, axis=0),
        jnp.concatenate(w_cat, axis=0),
        jnp.concatenate(gids_cat, axis=0),
        num_groups=off, block_rows=L)
    deltas = []
    for i, (vals, _, _, G) in enumerate(projs):
        o, A = offs[i], vals.shape[1]
        if members[i].kernel_num_groups is None:
            deltas.append(E.SumState(
                sum=sums[o, :1], sumsq=sumsqs[o, :1],
                scanned=scanned, matched=matched[o]))
        else:
            deltas.append(E.SumState(
                sum=sums[o:o + G, :A], sumsq=sumsqs[o:o + G, :A],
                scanned=scanned, matched=matched[o:o + G]))
    return tuple(deltas)


def bundle_kernel_rounds_states(gla: GLA, cols: dict, rounds: int):
    """ONE ``ops.group_agg`` dispatch per round-slice for a whole bundle.

    Every member's kernel projection of the same round-slice is stacked
    row-wise into a single dispatch: member m's group ids are offset into
    the disjoint range [off_m, off_m + G_m) of one concatenated group table,
    and its vals are zero-padded to the widest member's aggregate count.
    Because each member's rows are a multiple of ``block_rows`` (pinned to
    the chunk length L), members occupy disjoint kernel blocks, so member
    m's table rows receive exact-zero partials from every other member's
    blocks — group-by members' states stay bitwise-identical to their solo
    :func:`kernel_rounds_states` dispatch, while scalar members fold through
    the one-hot contraction and are interchangeable-not-bitwise with the
    scan path.  Engines prefer :func:`fused_rounds_states` (bitwise for
    every member, scalar included) whenever all members publish
    ``gla.fused``; this legacy path remains for kernel_cols-only bundles.
    Returns (tuple of member finals, tuple of member [R] views)
    matching the bundle's tuple-state layout.
    """
    members = gla.members
    assert members, "bundle kernel path needs a GLABundle"
    C, L = cols["_mask"].shape
    assert C % rounds == 0, (
        f"bundle kernel path needs C % rounds == 0, got {C} % {rounds}")
    per = C // rounds

    deltas = [[] for _ in members]  # [member][round] -> SumState delta
    for r in range(rounds):
        per_member = bundle_round_deltas(
            gla, {k: v[r * per:(r + 1) * per] for k, v in cols.items()})
        for i, d in enumerate(per_member):
            deltas[i].append(d)

    folded = [_fold_running_sum(member_deltas) for member_deltas in deltas]
    return (tuple(f for f, _ in folded), tuple(v for _, v in folded))


def bundle_kernel_rounds_states_batched(gla: GLA, shards: dict, rounds: int):
    """Vmapped-path wrapper for :func:`bundle_kernel_rounds_states`:
    unrolled over partitions (same rationale as
    :func:`_unroll_partitions`)."""
    return _unroll_partitions(
        lambda c: bundle_kernel_rounds_states(gla, c, rounds), shards)


# ---------------------------------------------------------------------------
# fused selection→bucket→aggregate kernel path (DESIGN.md §12)
# ---------------------------------------------------------------------------
# Thin drivers over repro.kernels.fused_agg: ONE carry-in Pallas dispatch per
# round-slice fusing predicate evaluation, hash-bucket group ids, in-kernel
# column decode (repro.data.encodings) and f32 accumulation.  Because the
# running state enters the kernel as an input ref, round-boundary states keep
# the exact scan-carry association — the fused paths are bitwise-identical to
# the scan paths for scalar, group-by and bundle GLAs alike
# (tests/test_fused_kernel.py, docs/KERNELS.md).

def fused_available(gla: GLA, columns=None) -> bool:
    """True when ``gla`` (and every bundle member) publishes ``gla.fused``
    and every source column is kernel-decodable (no trailing dims)."""
    from repro.kernels import fused_agg

    return fused_agg.fused_available(gla, columns)


def fused_round_step(gla: GLA, state, slice_cols: dict, encodings=(), *,
                     use_mxu=None):
    """Carry-in fused step for ONE round-slice: (state, slice) -> state.

    The per-round-slice primitive behind the ``kernel_fused`` session path.
    Carry-style rather than delta-style (no first/add split): the incoming
    state rides into the kernel as an input ref and every chunk accumulates
    on top, so starting from ``gla.init()`` reproduces the scan-carry
    association exactly from round 0.  ``encodings`` is the source's static
    (name, Encoding) tuple; encoded columns arrive physical and are decoded
    inside the kernel body.  Join GLAs additionally ship their replicated
    probe tables as extra kernel operands (``FusedSpec.probe_tables``);
    ``use_mxu`` picks the group accumulation and defaults to the compile
    target: the one-hot matmul in a compiled TPU kernel (re-associates, so
    oracle tolerance rather than bitwise), segment_sum under interpret.
    """
    from repro.kernels import fused_agg

    return fused_agg.fused_round_step(
        gla, state, slice_cols, encodings=encodings, use_mxu=use_mxu)


def fused_rounds_states(gla: GLA, cols: dict, rounds: int, encodings=()):
    """Fused analogue of :func:`kernel_rounds_states` /
    :func:`bundle_kernel_rounds_states`: one fused dispatch per round-slice
    with the carry threaded through, round-boundary views stacked [R, ...].
    Bitwise-identical to the :func:`scan_rounds` views at lanes == 1 for
    scalar, group-by and bundle states alike.  Requires C % rounds == 0.
    """
    C = cols["_mask"].shape[0]
    assert C % rounds == 0, (
        f"fused kernel path needs C % rounds == 0, got {C} % {rounds}")
    per = C // rounds
    st = gla.init()
    views = []
    for r in range(rounds):
        st = fused_round_step(
            gla, st,
            {k: v[r * per:(r + 1) * per] for k, v in cols.items()},
            encodings)
        views.append(st)
    return st, jax.tree.map(lambda *xs: jnp.stack(xs), *views)


def fused_rounds_states_batched(gla: GLA, shards: dict, rounds: int,
                                encodings=()):
    """Vmapped-path wrapper for :func:`fused_rounds_states`: unrolled over
    partitions (same rationale as :func:`_unroll_partitions`)."""
    return _unroll_partitions(
        lambda c: fused_rounds_states(gla, c, rounds, encodings), shards)


def fused_prefix_states(gla: GLA, cols: dict, encodings=()):
    """Fused analogue of :func:`kernel_prefix_states` for solo scalar GLAs:
    ONE fused dispatch per shard emitting the running per-chunk prefix rows
    alongside the final accumulators.  The running state lives in the
    kernel's output refs, so prefixes keep the exact chunk-sequential
    association — bitwise-identical to :func:`scan_prefix` at lanes == 1."""
    from repro.kernels import fused_agg

    return fused_agg.fused_prefix_states(gla, cols, encodings=encodings)


def fused_prefix_states_batched(gla: GLA, shards: dict, encodings=()):
    """Vmapped-path wrapper: one fused prefix dispatch per partition."""
    return _unroll_partitions(
        lambda c: fused_prefix_states(gla, c, encodings), shards)


# The session drivers' path-name -> per-round-slice primitive table, kept
# here next to the primitives so the vmapped and sharded steps cannot
# diverge (repro/core/session.py, repro/dist/shard_engine.py).  These are
# delta-style (first-round states ARE the first deltas); the carry-style
# "kernel_fused" path dispatches :func:`fused_round_step` directly instead.
ROUND_DELTA_FNS = {
    "kernel_scalar": kernel_scalar_round_delta,
    "kernel_group": kernel_round_delta,
    "kernel_bundle": bundle_round_deltas,
}


# ---------------------------------------------------------------------------
# liveness accounting (node failure, DESIGN.md §4)
# ---------------------------------------------------------------------------

def round_weights(alive: jnp.ndarray, rounds: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Normalize an alive mask to ([P, R] merge weights, [P] final weights).

    ``alive`` is [P] (partition dead for the whole query) or [R, P]
    (failure-injection schedule: row r gives liveness during round r).  The
    final result merges with the last round's liveness — a partition that
    died mid-query never reports its final state.
    """
    alive = jnp.asarray(alive)
    if alive.ndim == 1:
        w = jnp.broadcast_to(alive[:, None], (alive.shape[0], rounds))
        return w.astype(jnp.float32), alive.astype(jnp.float32)
    w = alive.T.astype(jnp.float32)  # [P, R]
    return w, w[:, -1]


# ---------------------------------------------------------------------------
# elastic carry algebra (resume on a different partition count, DESIGN.md §9)
# ---------------------------------------------------------------------------

def merge_carries(states: Pytree, group: int) -> Pytree:
    """Fold a [P, ...] carry pytree to [P/group, ...] partitions.

    New partition i is the left-fold Merge (additive add, the same
    association order as :func:`fold_merge`) of old partitions
    [i*group, (i+1)*group).  Valid for additive merges only — exactly the
    contract the engines' weighted liveness merges already require.
    ``merge_carries(split_carries(x, k), k)`` is the identity on the carry
    pytree (x + 0 is exact), property-tested in tests/test_elastic.py.
    """
    def m(x):
        assert x.shape[0] % group == 0, (x.shape, group)
        g = x.reshape((x.shape[0] // group, group, *x.shape[1:]))
        acc = g[:, 0]
        for j in range(1, group):
            acc = acc + g[:, j]
        return acc

    return jax.tree.map(m, states)


def split_carries(states: Pytree, group: int) -> Pytree:
    """Expand a [P, ...] carry pytree to [P*group, ...] partitions.

    Child p*group inherits parent p's whole carry; the other children
    start from the additive identity (zeros).  A carry cannot be unsummed
    into the sub-streams that produced it, but to an additive merge *where*
    a carry lives is unobservable — any weighted sum over the children
    equals the parent's contribution exactly, so merged snapshots, finals
    and estimates are preserved.  Inverse of :func:`merge_carries`.
    """
    def s(x):
        z = jnp.zeros_like(x)
        cols = [x, *[z] * (group - 1)]
        return jnp.stack(cols, axis=1).reshape(
            (x.shape[0] * group, *x.shape[1:]))

    return jax.tree.map(s, states)
