"""Sharded execution: partitions = devices under ``jax.shard_map``.

This is the deployment path of the engine (DESIGN.md §2, §4): the vmapped
path simulates partitions as an array axis on one device; here each
partition is a device along the ``data`` axis of a mesh built by
repro/launch/mesh.py.  Both paths call the *same* per-partition scan core
(repro/core/scan.py) — this module only owns what is genuinely distributed:

  * cross-partition merging.  GLA states must be additive (all shipped GLAs
    are), so Merge/EstimatorMerge lower to a single ``lax.psum`` — the ring
    all-reduce that plays the role of the paper's aggregation tree.
  * asynchronous snapshots.  Each partition contributes the prefix state at
    its *own* scheduled progress; the psum merges unequal-progress states,
    which is exactly what the paper's single estimator makes legal.
  * the synchronized barrier.  ``mode="sync"`` truncates every partition to
    the global minimum progress via ``lax.pmin`` and, with
    ``sync_cost_model=True``, additionally pays one coordination ``psum`` per
    chunk — the per-item serialization that makes the Wu et al. estimator
    slow, visible in wall time and in the HLO collective count
    (benchmarks/overhead.py).
  * node failure.  ``alive`` weights ([P] or [R, P], repro/dist/fault.py)
    zero dead partitions out of every psum.
  * replicated join sides.  Two-table plans (DESIGN.md §13) close their
    probe tables over the worker function — under ``shard_map`` the
    dimension arrays are trace-time constants replicated to every device
    (the paper §5.4 strategy), so the fused kernel's probe operands need
    no mesh annotations and the psum'd states stay bitwise-identical to
    the vmapped engine's.  Non-additive sketch GLAs (HLL max-merge,
    ``merge_is_additive=False``) are rejected by the additivity gate
    below: they run vmapped only.

Equivalence with the vmapped path is asserted in
tests/test_sharding.py::test_sharded_engine_matches_vmapped_subprocess.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import scan as SC
from repro.core.uda import GLA


def device_put_slice(cols: dict, *, mesh, axis_name: str = "data"):
    """Place one streaming round-slice on the mesh (DESIGN.md §8).

    ``cols`` is a host-side [P, width, L] columnar batch from a
    ``repro.data.source.ChunkSource``; each partition's block lands on its
    own device along ``axis_name``, so the per-host/per-device footprint
    is O(slice / P).  Called from the session prefetcher's worker thread —
    the transfer of slice r+1 overlaps round r's compute, and the fetched
    arrays feed :func:`session_step_sharded` without a re-layout.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    sh = NamedSharding(mesh, PartitionSpec(axis_name))
    return {k: jax.device_put(np.asarray(v), sh) for k, v in cols.items()}


def device_put_carry(states, *, mesh, axis_name: str = "data"):
    """Place a [P, ...] session carry pytree on the mesh (DESIGN.md §9).

    Resumed carries arrive host-backed from the checkpoint — possibly
    merged/split to a new partition count by the elastic carry algebra
    (``repro.core.scan.merge_carries``/``split_carries``) — and placing
    them explicitly along ``axis_name`` keeps the first resumed step free
    of implicit host→device resharding; the sibling of
    :func:`device_put_slice` for carries instead of data slices.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    sh = NamedSharding(mesh, PartitionSpec(axis_name))
    return jax.tree.map(
        lambda x: jax.device_put(jnp.asarray(x), sh), states)


def _shard_map(worker, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (the scan carry
    starts replicated from gla.init and becomes device-varying after the
    first accumulate, which the static checker rejects)."""
    return jax.shard_map(worker, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.partial(
    jax.jit,
    static_argnames=("gla", "mesh", "axis_name", "mode", "emit", "lanes",
                     "snapshots", "sync_cost_model"),
)
def _run_sharded_jit(gla: GLA, shards: dict, sched: jnp.ndarray,
                     alive2d: jnp.ndarray, *, mesh, axis_name: str, mode: str,
                     emit: str, lanes: int, snapshots: bool,
                     sync_cost_model: bool):
    P = shards["_mask"].shape[0]
    R = sched.shape[1] - 1
    # fused dispatch blocks [BLOCK_CHUNKS, L] rows per column — trailing
    # dims fall back to the legacy kernels (resident shards are always
    # plain/decoded)
    fused_ok = SC.fused_available(gla) and all(
        v.ndim == 3 for v in shards.values())

    def worker(cols, sched_p, alive_p):
        cols = jax.tree.map(lambda x: x[0], cols)      # [1, C, L] -> [C, L]
        sched_p = sched_p[0]
        alive_r = alive_p[0].astype(jnp.float32)       # [R] liveness per round
        d_local = jnp.sum(cols["_mask"])
        d_total = lax.psum(d_local, axis_name)

        if mode == "sync" and sync_cost_model:
            # Per-chunk progress coordination: the barrier the paper's
            # synchronized competitor needs.  The psum'd counter feeds the
            # next iteration's carry so it cannot be DCE'd.
            def body(carry, chunk):
                st, prog = carry
                st, view = SC.accumulate_chunk(gla, st, chunk, lanes)
                prog = lax.psum(prog + 1.0, axis_name) / P
                return (st, prog), view
            init = (SC.stack_init(gla, lanes), jnp.zeros(()))
            (last, _), prefixes = lax.scan(body, init, cols)
            init_view = SC.stack_init(gla, lanes)
            if lanes > 1:
                init_view = SC.fold_merge(gla.merge, init_view, lanes)
                last = SC.fold_merge(gla.merge, last, lanes)
            prefixes = jax.tree.map(
                lambda i, p: jnp.concatenate([i[None], p], 0), init_view, prefixes)
            final_view = last
        elif emit == "kernel":
            assert lanes == 1, "emit='kernel' runs single-lane"
            if fused_ok and (gla.members or gla.kernel_num_groups is not None):
                # ONE fused selection→bucket→aggregate dispatch per
                # round-slice covers every member (DESIGN.md §12),
                # bitwise-identical to the scan path except for compiled
                # TPU group members, which match within the float64-oracle
                # tolerance (docs/KERNELS.md rule 2).
                final_view, round_states = SC.fused_rounds_states(
                    gla, cols, R if snapshots else 1)
                prefixes = None
            elif fused_ok:
                # fused per-shard dispatch with in-kernel running prefixes —
                # bitwise-identical to the scan path, scalar contract too.
                final_view, prefixes = SC.fused_prefix_states(gla, cols)
            elif gla.members:
                # bundled kernel dispatch: ONE group_agg launch per
                # round-slice covers every member (DESIGN.md §6).
                final_view, round_states = SC.bundle_kernel_rounds_states(
                    gla, cols, R if snapshots else 1)
                prefixes = None
            elif gla.kernel_num_groups is not None:
                # group-by kernel dispatch: round emission discipline, no
                # per-chunk prefixes (DESIGN.md §3).  Snapshots off: one
                # whole-shard dispatch, nothing else is consumed.
                final_view, round_states = SC.kernel_rounds_states(
                    gla, cols, R if snapshots else 1)
                prefixes = None
            else:
                final_view, prefixes = SC.kernel_prefix_states(gla, cols)
        elif emit == "chunk":
            final_view, prefixes = SC.scan_prefix(gla, cols, lanes)
        elif emit == "round":
            final_view, round_states = SC.scan_rounds(gla, cols, lanes, R)
            prefixes = None
        else:
            raise ValueError(emit)

        if prefixes is not None:
            if mode == "sync":
                gmin = lax.pmin(sched_p[1:], axis_name)
                idx = gmin
            else:
                idx = sched_p[1:]
            round_states = jax.tree.map(lambda x: x[idx], prefixes)

        # weight by aliveness, then psum == EstimatorMerge over the tree.
        # Final states merge with the last round's liveness — a partition
        # that died mid-query never reports its final state.
        def w_final(x):
            return x * alive_r[-1].astype(x.dtype)

        def w_rounds(x):
            w = alive_r.reshape((R, *(1,) * (x.ndim - 1)))
            return x * w.astype(x.dtype)

        merged_final = lax.psum(jax.tree.map(w_final, final_view), axis_name)
        if snapshots:
            term = jax.vmap(
                lambda s: gla.estimator_terminate(s, {"d_local": d_local})
            )(round_states)
            merged_rounds = lax.psum(jax.tree.map(w_rounds, term), axis_name)
        else:
            merged_rounds = None
        return merged_final, merged_rounds, d_total, d_local[None]

    from jax.sharding import PartitionSpec as PS
    pspec = PS(axis_name)
    out_specs = (PS(), PS(), PS(), PS(axis_name))
    fn = _shard_map(worker, mesh, (pspec, pspec, pspec), out_specs)
    return fn(shards, sched, alive2d)


# ---------------------------------------------------------------------------
# incremental session steps (repro/core/session.py, DESIGN.md §7): the same
# per-round-slice primitives the fused program folds over all rounds, jitted
# standalone with partitions on the mesh axis.  One psum per step merges the
# round's estimator states; the scan carry stays sharded between steps.
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("gla", "mesh", "axis_name", "path", "lanes",
                              "confidence", "first", "encodings"),
)
def session_step_sharded(gla: GLA, states, slice_shards: dict,
                         w_r: jnp.ndarray, d_local: jnp.ndarray,
                         d_total: jnp.ndarray, *, mesh, axis_name: str,
                         path: str, lanes: int, confidence: float,
                         first: bool, encodings: tuple = ()):
    """Advance one round-slice with partitions on ``axis_name``.

    Same contract as ``session._step_vmapped``: returns (new per-partition
    states, per-partition round views, merged round state, round
    Estimate-or-None).  ``first`` starts the legacy kernel paths' running
    sum from the first delta, matching ``scan._fold_running_sum``
    bit-for-bit; the carry-style ``"kernel_fused"`` path needs no first
    split (zero-init carries are exact).  ``encodings`` is the source's
    static (name, Encoding) tuple: the fused path decodes in-kernel, every
    other path decodes generically before accumulating.
    """
    def worker(st, cols, w_p, dl):
        st = jax.tree.map(lambda x: x[0], st)
        cols = jax.tree.map(lambda x: x[0], cols)
        w = w_p[0]
        dl = dl[0]
        if encodings and path != "kernel_fused":
            from repro.data import encodings as ENC  # local: core stays data-free
            cols = ENC.decode_cols(cols, encodings)
        if path == "scan":
            new_st, view = SC.scan_round_step(gla, st, cols, lanes)
        elif path == "kernel_fused":
            new_st = SC.fused_round_step(gla, st, cols, encodings)
            view = new_st
        else:
            delta = SC.ROUND_DELTA_FNS[path](gla, cols)
            new_st = delta if first else jax.tree.map(jnp.add, st, delta)
            view = new_st
        term = gla.estimator_terminate(view, {"d_local": dl})
        merged = lax.psum(
            jax.tree.map(lambda x: x * w.astype(x.dtype), term), axis_name)
        return (jax.tree.map(lambda x: x[None], new_st),
                jax.tree.map(lambda x: x[None], view), merged)

    from jax.sharding import PartitionSpec as PS
    pspec = PS(axis_name)
    fn = _shard_map(worker, mesh, (pspec, pspec, pspec, pspec),
                    (pspec, pspec, PS()))
    new_states, views, merged = fn(states, slice_shards, w_r, d_local)
    est = None
    if gla.estimate is not None:
        est = gla.estimate(merged, confidence, {"d_total": d_total})
    return new_states, views, merged, est


@functools.partial(jax.jit, static_argnames=("gla", "mesh", "axis_name"))
def session_final_sharded(gla: GLA, views, w_final: jnp.ndarray, *, mesh,
                          axis_name: str):
    """Merge the current per-partition round views into the session final —
    the same weighted psum the fused program ends with."""
    def worker(v, w_p):
        v = jax.tree.map(lambda x: x[0], v)
        merged = lax.psum(
            jax.tree.map(lambda x: x * w_p[0].astype(x.dtype), v), axis_name)
        return merged

    from jax.sharding import PartitionSpec as PS
    fn = _shard_map(worker, mesh, (PS(axis_name), PS(axis_name)), PS())
    return gla.terminate(fn(views, w_final))


@functools.partial(jax.jit, static_argnames=("gla", "confidence"))
def _estimates_jit(gla: GLA, merged_rounds, d_total, confidence: float):
    return jax.vmap(
        lambda s: gla.estimate(s, confidence, {"d_total": d_total})
    )(merged_rounds)


def run_sharded(gla: GLA, shards: dict, sched: jnp.ndarray, alive: jnp.ndarray,
                *, mesh, axis_name: str, mode: str, emit: str, lanes: int,
                snapshots: bool, confidence: float, sync_cost_model: bool = True):
    """Same math as engine._run_vmapped with partitions on ``axis_name``."""
    from repro.core.engine import QueryResult

    assert gla.merge_is_additive, "sharded path requires additive merges"
    if emit == "kernel" and mode == "sync":
        # No silent downgrade: with sync_cost_model the per-chunk
        # coordination scan replaces the scan entirely (the kernel dispatch
        # would never run), and the group-by kernel contract has no prefix
        # states for the pmin truncation even without it.
        if sync_cost_model:
            raise ValueError(
                "emit='kernel' is incompatible with mode='sync' + "
                "sync_cost_model=True: the per-chunk coordination scan "
                "bypasses the kernel dispatch — use emit='chunk', or pass "
                "sync_cost_model=False (scalar-SumState GLAs only)")
        if gla.kernel_num_groups is not None or gla.members:
            raise ValueError(
                "group-by/bundled emit='kernel' emits round states only; "
                "mode='sync' needs prefix states for the min-progress "
                "truncation — use emit='chunk' or mode='async'")
    if emit == "round" and mode == "sync" and not sync_cost_model:
        # Same silent-downgrade class: scan_rounds has no prefix states, so
        # the pmin truncation would be skipped and async round states would
        # come back labeled as synchronized estimates.
        raise ValueError(
            "emit='round' emits round states only; mode='sync' needs prefix "
            "states for the min-progress truncation — use emit='chunk'")
    P = shards["_mask"].shape[0]
    R = sched.shape[1] - 1
    # alive arrives [P] or [R, P]; ship it as [P, R] so the partition axis
    # leads and shards like everything else.
    alive2d = jnp.broadcast_to(alive, (R, P)).T if alive.ndim == 1 else alive.T
    merged_final, merged_rounds, d_total, d_local = _run_sharded_jit(
        gla, shards, jnp.asarray(sched), alive2d, mesh=mesh,
        axis_name=axis_name, mode=mode, emit=emit, lanes=lanes,
        snapshots=snapshots, sync_cost_model=sync_cost_model)
    final = gla.terminate(merged_final)
    estimates = None
    if snapshots and gla.estimate is not None:
        estimates = _estimates_jit(gla, merged_rounds, d_total, confidence)
    return QueryResult(final, merged_rounds, estimates, d_total, d_local)
