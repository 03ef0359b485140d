"""The PF-OLA execution engine — paper §3.2–§3.4, adapted to SPMD JAX.

Execution model (DESIGN.md §2):

  * a *partition* is the unit of data locality (a GLADE worker node).  In the
    vmapped path partitions are a leading array axis (used by tests/benchmarks
    on 1 CPU device); in the sharded path partitions are devices along the
    ``data`` mesh axis under ``jax.shard_map``
    (repro/dist/shard_engine.py, used by the dry-run and real deployments).
    Both paths run the *same* GLA and the same math: the per-partition scans
    live in repro/core/scan.py and are shared verbatim — the paths differ
    only in the merge mechanism (tensordot over the partition axis here,
    ``lax.psum`` there).
  * within a partition, chunks are consumed by ``lax.scan`` — the analogue of
    DataPath work-units pulling chunks.  ``lanes > 1`` keeps several GLA
    states per partition (the paper's "list of GLA states bounded by the
    number of work units") and merges them on demand, which makes the
    associative-decomposability contract *observable* and testable.
  * a *snapshot* (partial-result request, paper §3.4) is the scan carry
    emitted at a round boundary.  The state already exists — emission adds no
    recompute and no extra data pass; this is the zero-overhead property,
    verified by benchmarks/overhead.py (wall time) and HLO cost analysis.
  * *stragglers / asynchrony*: a ``schedule`` gives each partition its own
    cumulative chunk-progress curve.  Async snapshots take each partition at
    its own progress (valid for the single estimator under global
    randomization); ``mode="sync"`` truncates every partition to the global
    minimum progress — the Wu et al. barrier — and, in the sharded path,
    pays a per-chunk collective, reproducing that estimator's overhead
    mechanistically.
  * node failure: ``alive`` masks partitions out of merging — [P] for a
    partition dead throughout, [R, P] for a failure-injection schedule; see
    repro/dist/fault.py for the estimator-level consequences (paper §4.6,
    DESIGN.md §4).
  * *plan trees* (DESIGN.md §13): ``QuerySpec`` lowers ``PlanNode`` trees
    (scan → filter/join → aggregate/sketch → having) to GLAs before they
    reach this engine, so every path here — including the fused kernel,
    whose join probe tables ride as extra Pallas operands — executes
    composed Deep OLA plans with the same machinery as flat ones.  Classic
    flat plans lower to one-node trees with bitwise-identical programs.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scan as SC
from repro.core import spec as QS
from repro.core.uda import GLA, Estimate

Pytree = Any


class QueryResult(NamedTuple):
    final: Any                    # gla.terminate(fully merged state)
    snapshots: Optional[Pytree]   # merged per-round states, leaves [R, ...]
    estimates: Optional[Estimate]  # per-round Estimate, leaves [R, ...]
    d_total: jnp.ndarray
    d_local: jnp.ndarray          # [P]


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

def uniform_schedule(num_partitions: int, num_chunks: int, rounds: int) -> np.ndarray:
    """Cumulative chunk boundaries [P, R+1]; round r covers [b[r], b[r+1])."""
    b = np.round(np.linspace(0, num_chunks, rounds + 1)).astype(np.int32)
    return np.broadcast_to(b, (num_partitions, rounds + 1)).copy()


def straggler_schedule(
    num_partitions: int, num_chunks: int, rounds: int, speeds, seed: int = 0
) -> np.ndarray:
    """Per-partition progress curves under heterogeneous speeds.

    ``speeds[p]`` is partition p's relative throughput; progress accrues
    proportionally with small multiplicative jitter, capped at num_chunks.
    Every partition eventually finishes (last round = full scan) so the query
    completes — stragglers only delay, as in the paper's asynchronous model.
    """
    rng = np.random.default_rng(seed)
    speeds = np.asarray(speeds, np.float64)
    base = num_chunks / speeds.max()
    sched = np.zeros((num_partitions, rounds + 1), np.int32)
    for p in range(num_partitions):
        jitter = rng.uniform(0.85, 1.15, rounds)
        inc = speeds[p] * base / rounds * jitter
        cum = np.minimum(np.cumsum(inc), num_chunks)
        sched[p, 1:] = np.round(cum).astype(np.int32)
    sched[:, -1] = num_chunks  # completion
    return sched


# ---------------------------------------------------------------------------
# vmapped (partition-simulation) path
# ---------------------------------------------------------------------------

def _merge_over_partitions(gla: GLA, states: Pytree, w: jnp.ndarray, merge,
                           all_alive: bool):
    """Merge states with leading partition axis [P, ...] under weights [P].

    ``all_alive`` is decided on the host before tracing: a non-additive
    merge cannot honor a liveness mask (the weights feed a tensordot), so
    it is only legal when every partition is statically alive.
    """
    P = w.shape[0]
    if gla.merge_is_additive:
        # HIGHEST: at default precision a TPU rounds f32 dot operands to
        # bf16, which would cut the merged states to 8 mantissa bits
        return jax.tree.map(
            lambda x: jnp.tensordot(w.astype(x.dtype), x, axes=(0, 0),
                                    precision=jax.lax.Precision.HIGHEST),
            states)
    if not all_alive:
        raise NotImplementedError("alive masks need merge_is_additive")
    return SC.fold_merge(merge, states, P)


def _merge_rounds(gla: GLA, states: Pytree, w_pr: jnp.ndarray, merge,
                  all_alive: bool):
    """Merge [P, R, ...] states with per-(partition, round) weights [P, R]."""
    P, R = w_pr.shape
    if gla.merge_is_additive:
        return jax.tree.map(
            lambda x: jnp.einsum(
                "pr,pr...->r...", w_pr.astype(x.dtype), x,
                precision=jax.lax.Precision.HIGHEST), states
        )
    if not all_alive:
        raise NotImplementedError("alive masks need merge_is_additive")
    return jax.vmap(lambda s: SC.fold_merge(merge, s, P), in_axes=1)(states)


@functools.partial(
    jax.jit, static_argnames=("gla", "mode", "emit", "lanes", "snapshots",
                              "confidence", "all_alive")
)
def _run_vmapped(gla: GLA, shards: dict, sched: jnp.ndarray, alive: jnp.ndarray,
                 *, mode: str, emit: str, lanes: int, snapshots: bool,
                 confidence: float, all_alive: bool):
    P, C, L = shards["_mask"].shape
    R = sched.shape[1] - 1
    d_local = jnp.sum(shards["_mask"], axis=(1, 2))
    d_total = jnp.sum(d_local)
    w_pr, w_final = SC.round_weights(alive, R)
    # fused dispatch blocks [BLOCK_CHUNKS, L] rows per column — trailing
    # dims fall back to the legacy kernels (resident shards are always
    # plain/decoded)
    fused_ok = SC.fused_available(gla) and all(
        v.ndim == 3 for v in shards.values())

    if emit == "kernel" and (gla.kernel_num_groups is not None
                             or gla.members):
        # group-by / bundled kernel dispatch: dense [G, A] states follow the
        # round emission discipline (DESIGN.md §3, §6) — no per-chunk
        # prefixes exist.  Bundles batch every member into one group_agg
        # dispatch per round-slice.
        assert lanes == 1, "emit='kernel' runs single-lane"
        if mode == "sync":
            raise NotImplementedError("sync mode requires emit='chunk'")
        # snapshots off: no round states are consumed — one whole-shard
        # dispatch (same chunk-sequential association, R-fold fewer launches)
        if fused_ok:
            # one fused selection→bucket→aggregate dispatch per round-slice
            # (DESIGN.md §12), bitwise-identical to the scan path except
            # for compiled TPU group members, which match within the
            # float64-oracle tolerance (docs/KERNELS.md rule 2)
            finals, round_states = SC.fused_rounds_states_batched(
                gla, shards, R if snapshots else 1)
        else:
            kernel_fn = (SC.bundle_kernel_rounds_states_batched if gla.members
                         else SC.kernel_rounds_states_batched)
            finals, round_states = kernel_fn(gla, shards,
                                             R if snapshots else 1)
    elif emit in ("chunk", "kernel"):
        if emit == "chunk":
            finals, prefixes = jax.vmap(
                lambda c: SC.scan_prefix(gla, c, lanes))(shards)
        elif fused_ok:
            # fused per-shard dispatch: running accumulators live in the
            # kernel's output refs, so the prefixes — and hence the scalar
            # finals — are bitwise-identical to the scan path (DESIGN.md §12)
            assert lanes == 1, "emit='kernel' runs single-lane"
            finals, prefixes = SC.fused_prefix_states_batched(gla, shards)
        else:  # legacy per-shard kernel dispatch (DESIGN.md §3)
            assert lanes == 1, "emit='kernel' runs single-lane"
            finals, prefixes = SC.kernel_prefix_states_batched(gla, shards)
        if snapshots:
            if mode == "sync":
                idx = jnp.broadcast_to(jnp.min(sched[:, 1:], axis=0), (P, R))
            else:
                idx = sched[:, 1:]
            round_states = jax.vmap(
                lambda pref, ix: jax.tree.map(lambda x: x[ix], pref)
            )(prefixes, idx)  # [P, R, ...]
        else:
            round_states = None
    elif emit == "round":
        finals, round_states = jax.vmap(
            lambda c: SC.scan_rounds(gla, c, lanes, R)
        )(shards)
        if mode == "sync":
            raise NotImplementedError("sync mode requires emit='chunk'")
    elif emit == "round_masked":
        finals, round_states = jax.vmap(
            lambda c, s: SC.scan_rounds_masked(gla, c, s, lanes)
        )(shards, sched)
    else:
        raise ValueError(f"unknown emit: {emit}")

    # Final result: plain Merge across partitions, then Terminate.
    merged_final = _merge_over_partitions(gla, finals, w_final, gla.merge,
                                          all_alive)
    final = gla.terminate(merged_final)

    if not snapshots or round_states is None:
        return QueryResult(final, None, None, d_total, d_local)

    # EstimatorTerminate per (partition, round) with the partition's |D_i|,
    # then EstimatorMerge across partitions (paper §3.1: intra- then inter-).
    def et(p_states, dl):
        return jax.vmap(lambda s: gla.estimator_terminate(s, {"d_local": dl}))(p_states)

    terminated = jax.vmap(et)(round_states, d_local)          # [P, R, ...]
    merged = _merge_rounds(gla, terminated, w_pr, gla.estimator_merge,
                           all_alive)

    estimates = None
    if gla.estimate is not None:
        estimates = jax.vmap(
            lambda s: gla.estimate(s, confidence, {"d_total": d_total})
        )(merged)

    return QueryResult(final, merged, estimates, d_total, d_local)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def normalize_plan(spec_or_gla, data, rounds: int = 8,
                   schedule: Optional[np.ndarray] = None,
                   emit: Optional[str] = None):
    """Validate emit/kernel contracts and resolve the plan.

    Canonical form: ``normalize_plan(spec, data) -> QuerySpec`` — takes a
    :class:`repro.core.spec.QuerySpec`, resolves its emission discipline
    and round schedule against the data's shape contract, and returns the
    resolved spec (``emit`` a concrete string, ``schedule`` a [P, R+1]
    ndarray, ``rounds`` its R).  This is what ``Session`` and
    ``OLAService`` call, so every entry point enforces identical
    contracts.

    Legacy form: ``normalize_plan(gla, data, rounds, schedule, emit) ->
    (rounds, schedule)`` — the pre-QuerySpec signature, kept for old
    callers.

    ``data`` is a resident [P, C, L] shards dict or a
    ``repro.data.source.ChunkSource`` (only the shape contract is
    consulted — no data is read).  Round-emission paths ("round", and
    group-by/bundle "kernel") emit at uniform round boundaries only:
    ``rounds`` degrades to the largest divisor of C with a warning, and
    an explicit ``schedule`` that is indivisible or non-uniform is a
    ValueError (those paths would silently ignore it otherwise).
    """
    if isinstance(spec_or_gla, QS.QuerySpec):
        qspec = spec_or_gla
        if qspec.is_multi:
            raise TypeError(
                "a QuerySpec holding a sequence of GLAs is a run_queries() "
                "plan — run_queries bundles it before execution")
        emit = qspec.resolved_emit()
        rounds, schedule = _resolve_rounds_schedule(
            qspec.gla, data, qspec.rounds, qspec.schedule, emit)
        return qspec.with_(rounds=rounds, schedule=schedule, emit=emit)
    rounds, schedule = _resolve_rounds_schedule(
        spec_or_gla, data, rounds, schedule,
        "chunk" if emit is None else emit)
    return rounds, schedule


def _resolve_rounds_schedule(gla: GLA, data, rounds: int,
                             schedule: Optional[np.ndarray], emit: str):
    spec = getattr(data, "spec", None)  # duck-typed: core stays data-free
    P, C, L = ((spec.P, spec.C, spec.L) if spec is not None
               else data["_mask"].shape[:3])
    if emit == "kernel":
        if gla.members:
            # one dispatch serves every member: either ALL publish the fused
            # contract (fused_agg path) or ALL publish kernel_cols (legacy
            # group_agg batching) — a mixed bundle has no single-kernel plan
            if any(m.fused is None for m in gla.members):
                missing = [m.name for m in gla.members
                           if m.kernel_cols is None]
                if missing:
                    raise ValueError(
                        f"bundle members {missing} do not publish kernel_cols "
                        "or a fused contract — emit='kernel' batches every "
                        "member into one dispatch and cannot mix in "
                        "scan-only members")
        elif gla.kernel_cols is None and gla.fused is None:
            raise ValueError(
                f"GLA {gla.name!r} publishes neither kernel_cols nor a "
                "fused kernel contract")
    needs_uniform_rounds = emit == "round" or (
        emit == "kernel" and (gla.kernel_num_groups is not None
                              or bool(gla.members)))
    if needs_uniform_rounds:
        if schedule is None:
            if C % rounds:
                best = max(d for d in range(1, rounds + 1) if C % d == 0)
                warnings.warn(
                    f"emit={emit!r} needs C % rounds == 0 (C={C}); degrading "
                    f"rounds {rounds} -> {best}", stacklevel=2)
                rounds = best
        else:
            sched_np = np.asarray(schedule)
            R = sched_np.shape[1] - 1
            if C % R:
                raise ValueError(
                    f"emit={emit!r} needs C % rounds == 0, got C={C} with a "
                    f"{R}-round schedule")
            # These paths emit states at uniform round boundaries only; a
            # schedule they would silently ignore is an error, not a hint.
            if not np.array_equal(sched_np, uniform_schedule(P, C, R)):
                raise ValueError(
                    f"emit={emit!r} emits snapshots at uniform round "
                    "boundaries and cannot honor a non-uniform schedule — "
                    "use emit='round_masked' (large states, any schedule) "
                    "or emit='chunk' (prefix states)")
    if schedule is None:
        schedule = uniform_schedule(P, C, rounds)
    return np.asarray(schedule).shape[1] - 1, np.asarray(schedule)


def _execute_full(gla: GLA, shards: dict, sched: jnp.ndarray,
                  alive_arr: jnp.ndarray, *, mode: str, emit: str, lanes: int,
                  snapshots: bool, confidence: float, all_alive: bool,
                  mesh, axis_name: str, sync_cost_model: bool) -> QueryResult:
    """Dispatch one fused whole-scan program (vmapped or sharded)."""
    if mesh is None:
        return _run_vmapped(
            gla, shards, sched, alive_arr, mode=mode, emit=emit, lanes=lanes,
            snapshots=snapshots, confidence=confidence, all_alive=all_alive,
        )
    from repro.dist import shard_engine  # local import: core must not require dist
    return shard_engine.run_sharded(
        gla, shards, sched, alive_arr, mesh=mesh, axis_name=axis_name,
        mode=mode, emit=emit, lanes=lanes, snapshots=snapshots,
        confidence=confidence, sync_cost_model=sync_cost_model,
    )


def run_query(
    spec,
    data,
    *,
    mesh=None,
    axis_name: str = "data",
    **plan,
) -> QueryResult:
    """Execute a GLA query with on-line estimation.

    A thin wrapper over :class:`repro.core.session.Session` driven to
    completion.  Without a stopping rule this runs the fused whole-scan
    program — byte-for-byte the classic engine path; with ``spec.stop``
    the session advances round by round and terminates as soon as the
    rule fires, so the result may cover fewer than ``spec.rounds``
    snapshot rounds and its ``final`` is the best partial-scan answer at
    the stopping round.

    Args:
      spec: a :class:`repro.core.spec.QuerySpec` (the canonical spelling
        — see its docstring for every plan field), or a bare GLA for the
        default plan.  The old loose plan kwargs (``rounds=``, ``emit=``,
        ``stop=``, ...) still work on a bare GLA but emit a
        ``DeprecationWarning`` (rule C009 keeps framework code off them).
      data: columnar dict, leaves [P, C, L] incl. "_mask", OR any
        ``repro.data.source.ChunkSource`` (DESIGN.md §8).  Streaming
        sources (``NpyMmapSource``/``ParquetSource``) are scanned
        out-of-core on the incremental discipline with O(slice) device
        footprint; finals/snapshots/bounds stay bitwise-identical to the
        resident path on the scan and group/bundle kernel paths.
      mesh: if given, run under shard_map with partitions on ``axis_name``
        (repro/dist/shard_engine.py).  Engine location is a per-call
        choice, never part of the spec.
    """
    from repro.core import session as SN  # local: session imports engine

    qspec = QS.coerce_spec(spec, plan, caller="run_query")
    return SN.Session(qspec, data, mesh=mesh, axis_name=axis_name).run()


def run_queries(
    specs,
    data,
    *,
    mesh=None,
    axis_name: str = "data",
    **plan,
):
    """Execute N concurrent OLA queries over a SINGLE pass of the shards.

    The paper's central claim (§3–§4) is that any number of concurrent
    estimation models ride alongside one execution with virtually no
    overhead.  This is the multi-query hot path that delivers it: the
    queries are stacked into a :func:`repro.core.gla.GLABundle` (one
    tuple-of-states GLA), every scan path feeds all of them from the same
    chunk stream, and the results are unbundled into one
    :class:`QueryResult` per query.  Each query's finals, snapshot states
    and per-round bounds are bitwise-identical to running it alone with
    ``run_query`` (tests/test_multiquery.py) — a second query no longer
    pays a second pass over the data.

    ``specs`` is a :class:`repro.core.spec.QuerySpec` whose ``gla`` is a
    sequence of GLAs, or a bare sequence for the default plan (the old
    loose kwargs also still work on a bare sequence, with a
    ``DeprecationWarning``).  The plan applies to the shared scan — one
    schedule, one mode, one emission discipline for the bundle.  ``emit``
    resolves to ``"round"`` by default because the bundle state is as
    large as its largest member — per-chunk prefix emission (``"chunk"``)
    is only sensible when every member is small.  ``emit="kernel"``
    requires every member to publish ``kernel_cols`` and batches all of
    them into one ``ops.group_agg`` dispatch per round-slice (DESIGN.md
    §6).  ``spec.stop`` applies to the shared scan: with e.g.
    ``session.rel_width`` every member that publishes an estimator must
    converge before the bundle stops — the all-queries-converged rule.

    Returns: list of :class:`QueryResult`, one per input GLA, in order.
    """
    from repro.core.gla import GLABundle  # local: avoid import cycle at load

    qspec = QS.coerce_spec(specs, plan, caller="run_queries")
    if not qspec.is_multi:
        raise TypeError("run_queries() takes a sequence of GLAs — for a "
                        "single query use run_query()")
    glas = list(qspec.gla)
    # Resolve emit while the spec still knows it is multi-query, then
    # swap in the bundle (one tuple-of-states GLA) for execution.
    qspec = qspec.with_(emit=qspec.resolved_emit(), gla=GLABundle(glas))
    res = run_query(qspec, data, mesh=mesh, axis_name=axis_name)
    out = []
    for i in range(len(glas)):
        est = res.estimates[i] if res.estimates is not None else None
        snap = res.snapshots[i] if res.snapshots is not None else None
        out.append(QueryResult(res.final[i], snap, est,
                               res.d_total, res.d_local))
    return out


def audit_plan(gla, data, *, rounds: int = 8, schedule=None,
               emit: str = "chunk", mode: str = "async", lanes: int = 1,
               snapshots: bool = True, confidence: float = 0.95,
               mesh=None, axis_name: str = "data", checks=None,
               raise_on_failure: bool = False):
    """Certify a query plan against the compiled-program invariant catalog.

    Thin re-export of :func:`repro.analysis.audit.audit_plan` so callers
    holding an engine handle can audit without importing ``repro.analysis``
    themselves.  Args mirror :func:`run_query`; returns an
    ``AuditReport``.  No data is scanned by the default (static) checks.
    """
    from repro.analysis import audit as AU  # local: analysis is optional at load

    return AU.audit_plan(
        gla, data, rounds=rounds, schedule=schedule, emit=emit, mode=mode,
        lanes=lanes, snapshots=snapshots, confidence=confidence, mesh=mesh,
        axis_name=axis_name, checks=checks,
        raise_on_failure=raise_on_failure)
