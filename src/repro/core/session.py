"""Interactive OLA sessions — the paper's headline user feature, §1/§3.4.

PF-OLA's promise is that "the user can stop the computation as soon as the
estimate is accurate enough, typically early in the execution".  The classic
engine entry points (``engine.run_query``/``run_queries``) execute every
round of every chunk inside one fused program and only then hand back
snapshots — confidence bounds can never actually save work.  This module is
the missing code path: an **incremental round driver** that advances the
scan one round-slice at a time and evaluates pluggable **stopping rules**
between rounds, so a query over N rounds that converges at round k pays
only k/N of the scan.

Execution disciplines (DESIGN.md §7):

  * *fused* — no stopping rule attached and the session is driven straight
    to completion: one whole-scan program, byte-for-byte the classic
    ``run_query`` path (``run_query`` itself is now a thin wrapper over a
    fused session).
  * *incremental* — a stopping rule is attached, or the caller advances the
    session manually with :meth:`Session.step`.  Each step jits ONE
    round-slice (``scan.scan_round_step`` / ``scan.fused_round_step`` /
    the legacy ``scan.ROUND_DELTA_FNS`` primitives — the same
    per-round-slice primitives the fused paths fold over all rounds), then
    merges that round's states across partitions and produces the round's
    :class:`Estimate`.  The chunk-sequential accumulation order is
    identical to the fused program, so round-boundary states and finals
    are bitwise-identical across disciplines on every path — scan,
    ``kernel_fused`` (scalar included), and the legacy group/bundle
    kernels (tests/test_session.py, tests/test_fused_kernel.py).

Incremental stepping works on **both** engines — the vmapped path here and
the ``shard_map`` path (``repro.dist.shard_engine.session_step_sharded``)
— and requires ``mode="async"`` with a partition-uniform schedule (the
default): the synchronized barrier and per-partition straggler schedules
are whole-scan semantics and stay on the fused discipline.

Composed Deep OLA plans (DESIGN.md §13) need nothing special here: a
``QuerySpec`` built from a ``PlanNode`` tree arrives already lowered to a
GLA, join GLAs carry their probe tables inside their fused contract (the
``kernel_fused`` path ships them as extra Pallas operands), and nested
estimators (GROUP BY + HAVING, ``gla.compose``) only wrap ``estimate`` —
states, checkpoints and stop rules are the inner plan's verbatim.  Stop
rules over nested plans see the *outer* bounds, which can widen
transiently when the HAVING predicate flips a group; pair them with
``estimators.monotone_envelope`` post-hoc for monotone UI bounds.

Sessions pause and resume across processes: :meth:`Session.pause`
serializes the per-partition round states plus the scan cursor through
``repro.checkpoint.ckpt`` and :meth:`Session.resume` continues from the
exact round boundary — resumed sessions produce bitwise-identical finals
to uninterrupted ones (the carry is restored bit-exactly and the remaining
round-slices replay the same program).  The checkpoint meta carries the
data source's **content fingerprint** (DESIGN.md §8), so resuming against
different data — even same-shape data — raises instead of silently
producing wrong finals.

Data arrives either as a resident ``[P, C, L]`` shards dict (the classic
path, wrapped in a ``repro.data.source.InMemorySource``) or as any other
:class:`repro.data.source.ChunkSource` (``NpyMmapSource``,
``ParquetSource``): streaming sources are scanned **out-of-core** — each
:meth:`Session.step` pulls one round-slice through a double-buffered
host→device prefetcher (`jax.device_put` of slice r+1 overlaps round r's
compute), so peak device footprint is O(slice), not O(dataset), and the
engine scales past accelerator RAM.  Streaming runs the incremental
discipline by definition (there is nothing resident for a fused
whole-scan program to close over), which is exactly why it stays
bitwise-identical to the fused in-memory path on the scan and
group/bundle kernel paths.
"""
from __future__ import annotations

import functools
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, List, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import engine as EN
from repro.core import scan as SC
from repro.core import spec as QS
from repro.core.uda import GLA, Estimate
from repro.data import source as DSRC

Pytree = Any

# v3 adds per-partition cursors, the runtime failure record and the fault
# estimator family to the meta (elastic resume + §4.6 runtime semantics,
# DESIGN.md §9); v2 envelopes stay readable — their fields are a subset.
_CKPT_VERSION = 3
_READABLE_VERSIONS = (2, _CKPT_VERSION)


# ---------------------------------------------------------------------------
# stopping rules
# ---------------------------------------------------------------------------

class RoundProgress(NamedTuple):
    """What a stopping rule sees after each round.

    ``estimates`` is the round's :class:`repro.core.uda.Estimate` — a tuple
    with one entry per member (``None`` for members without an estimation
    model) when the session runs a ``GLABundle``, or ``None`` when the GLA
    publishes no estimator at all.
    """

    round: int          # rounds completed so far (1-based)
    rounds_total: int
    estimates: Any
    scanned: float      # tuples scanned so far across all partitions
    d_total: float
    elapsed_s: float    # driver wall time, accumulated across pause/resume


StoppingRule = Callable[[RoundProgress], bool]


def _per_estimate(estimates, pred) -> bool:
    """True when ``pred`` holds for every available member estimate.

    ``None`` (no estimation model anywhere) can never attest convergence.
    For bundles, members without an estimator are skipped — the members
    that do estimate decide (this IS the all-queries-converged rule for
    ``GLABundle`` sessions).
    """
    if estimates is None:
        return False
    # a bundle's estimates are a plain tuple of per-member Estimates;
    # Estimate itself is a NamedTuple, so check for it first
    members = ((estimates,) if isinstance(estimates, Estimate)
               else tuple(estimates))
    present = [e for e in members if e is not None]
    if not present:
        return False
    return all(pred(e) for e in present)


def _half_widths(est) -> np.ndarray:
    lo = np.asarray(est.lower, np.float64)
    hi = np.asarray(est.upper, np.float64)
    return (hi - lo) / 2.0


def rel_width(eps: float, *, min_rounds: int = 1) -> StoppingRule:
    """Stop once every aggregate's CI half-width ≤ ``eps`` · |estimate|.

    The reduction is a max over all aggregates (and groups): every entry
    must converge.  Entries with zero half-width (e.g. empty groups, whose
    variance estimate is exactly 0) count as converged; infinite half-widths
    (the |S| ≤ 1 variance clamp in early rounds) never do — an undefined
    variance cannot trigger a premature stop.
    """
    def converged(e):
        half = _half_widths(e)
        mid = np.abs(np.asarray(e.estimate, np.float64))
        rel = np.where(half == 0.0, 0.0, half / np.maximum(mid, 1e-300))
        return bool(rel.size == 0 or np.max(rel) <= eps)

    def rule(prog: RoundProgress) -> bool:
        if prog.round < min_rounds:
            return False
        return _per_estimate(prog.estimates, converged)

    return rule


def abs_width(limit: float, *, min_rounds: int = 1) -> StoppingRule:
    """Stop once every aggregate's CI half-width ≤ ``limit`` (absolute)."""
    def converged(e):
        half = _half_widths(e)
        return bool(half.size == 0 or np.max(half) <= limit)

    def rule(prog: RoundProgress) -> bool:
        if prog.round < min_rounds:
            return False
        return _per_estimate(prog.estimates, converged)

    return rule


def budget(*, max_seconds: Optional[float] = None,
           max_tuples: Optional[float] = None,
           max_rounds: Optional[int] = None) -> StoppingRule:
    """Stop when any resource budget is exhausted, converged or not.

    ``max_seconds`` counts driver wall time accumulated across
    pause/resume; ``max_tuples`` counts scanned tuples across partitions.
    """
    def rule(prog: RoundProgress) -> bool:
        if max_seconds is not None and prog.elapsed_s >= max_seconds:
            return True
        if max_tuples is not None and prog.scanned >= max_tuples:
            return True
        if max_rounds is not None and prog.round >= max_rounds:
            return True
        return False

    return rule


def any_of(*rules: StoppingRule) -> StoppingRule:
    """Stop when ANY rule fires (e.g. converged OR out of time budget)."""
    return lambda prog: any(r(prog) for r in rules)


def all_of(*rules: StoppingRule) -> StoppingRule:
    """Stop only when EVERY rule fires."""
    return lambda prog: all(r(prog) for r in rules)


# ---------------------------------------------------------------------------
# runtime failure handling (paper §4.6 live, DESIGN.md §9)
# ---------------------------------------------------------------------------

class FaultPolicy:
    """Make mid-scan partition loss survivable instead of fatal.

    Attach to a :class:`Session` (``fault=FaultPolicy(...)``) and failures
    degrade the answer instead of crashing the driver.  They arrive two
    ways:

      * *injected* — ``fail_at`` maps partition -> failure round, the
        ``repro.dist.fault.failure_schedule`` convention: ``fail_at[p] ==
        0`` is dead from the start, and partition p's state (everything it
        accumulated) is excluded from every merge from round ``fail_at[p]``
        on.
      * *detected* — a streaming source read raises
        :class:`repro.data.source.PartitionLostError` (chaos wrapper
        ``repro.dist.fault.FailingSource``, or a real storage/device
        error); the session records the current round as that partition's
        failure round and retries the read against the survivors.

    ``estimator`` names the estimation model the GLA was built with — the
    bound handling after a failure depends on it, not on the state
    (``repro.dist.fault`` module docstring spells out why):

      * ``single`` — survives: the alive-mask-weighted merge IS the
        renormalization (Horvitz–Thompson over the surviving uniform
        sample), and the variance floor — |S| is capped below |D|, so the
        (|D|-|S|) factor in Eq. (4) never vanishes — keeps bounds finite
        and honest.
      * ``multiple`` — poisoned: bounds are (-inf, +inf) from the failure
        round on.
      * ``synchronized`` — frozen: estimates stall at the last pre-failure
        round (infinite bounds if the failure precedes the first round).

    Excluding dead partitions is a weighted merge, so the session requires
    ``gla.merge_is_additive``.
    """

    _ESTIMATORS = ("single", "multiple", "synchronized")

    def __init__(self, estimator: str = "single", *,
                 fail_at: Optional[Mapping[int, int]] = None):
        if estimator not in self._ESTIMATORS:
            raise ValueError(
                f"unknown estimator model {estimator!r}; expected one of "
                f"{self._ESTIMATORS}")
        self.estimator = estimator
        self.fail_at = {int(p): int(r) for p, r in (fail_at or {}).items()}
        for p, r in self.fail_at.items():
            if p < 0 or r < 0:
                raise ValueError(
                    f"fail_at maps partition -> failure round, both >= 0; "
                    f"got {{{p}: {r}}}")


def _map_member_ests(fn, est):
    """Apply ``fn`` to an Estimate, member-wise for a bundle's tuple
    (members without an estimation model pass through as None)."""
    if est is None:
        return None
    if isinstance(est, Estimate):
        return fn(est)
    return tuple(None if e is None else fn(e) for e in est)


# ---------------------------------------------------------------------------
# per-round jitted step (vmapped engine); the sharded twin lives in
# repro/dist/shard_engine.py next to the other shard_map programs.
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("gla", "path", "lanes", "confidence",
                              "all_alive", "first", "encodings")
)
def _step_vmapped(gla: GLA, states, slice_shards: dict, w_r: jnp.ndarray,
                  d_local: jnp.ndarray, d_total: jnp.ndarray, *, path: str,
                  lanes: int, confidence: float, all_alive: bool,
                  first: bool, encodings: tuple = ()):
    """Advance one round-slice on the vmapped engine.

    Returns (new per-partition states, per-partition round views, merged
    round state, round Estimate-or-None).  ``first`` matters only on the
    legacy kernel paths: the running sum starts from the first delta (not
    zero + delta), matching ``scan._fold_running_sum`` bit-for-bit; the
    carry-style ``"kernel_fused"`` path needs no first split.
    ``encodings`` is the source's static (name, Encoding) tuple: the fused
    path decodes inside the kernel, every other path decodes the physical
    slice generically before accumulating (same ``decode_block`` math, so
    results stay bitwise-identical to the plain source).
    """
    if encodings and path != "kernel_fused":
        from repro.data import encodings as ENC  # local: core stays data-free
        slice_shards = ENC.decode_cols(slice_shards, encodings)
    if path == "scan":
        new_states, views = jax.vmap(
            lambda st, c: SC.scan_round_step(gla, st, c, lanes)
        )(states, slice_shards)
    elif path == "kernel_fused":
        P = slice_shards["_mask"].shape[0]
        # carry-style: the per-partition state rides into the kernel; no
        # first/add split.  Unrolled over partitions for the same reason
        # as scan._unroll_partitions: Pallas calls stay out of vmap/scan.
        outs = [
            SC.fused_round_step(
                gla, jax.tree.map(lambda x, p=p: x[p], states),
                jax.tree.map(lambda x, p=p: x[p], slice_shards), encodings)
            for p in range(P)
        ]
        new_states = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        views = new_states
    else:
        delta_fn = SC.ROUND_DELTA_FNS[path]
        P = slice_shards["_mask"].shape[0]
        # unrolled over partitions for the same reason as
        # scan._unroll_partitions: Pallas calls stay out of vmap/scan.
        deltas = [delta_fn(gla, jax.tree.map(lambda x, p=p: x[p],
                                             slice_shards))
                  for p in range(P)]
        delta = jax.tree.map(lambda *xs: jnp.stack(xs), *deltas)
        new_states = delta if first else jax.tree.map(jnp.add, states, delta)
        views = new_states

    term = jax.vmap(
        lambda s, dl: gla.estimator_terminate(s, {"d_local": dl})
    )(views, d_local)
    merged = EN._merge_rounds(
        gla, jax.tree.map(lambda x: x[:, None], term), w_r[:, None],
        gla.estimator_merge, all_alive)
    merged = jax.tree.map(lambda x: x[0], merged)
    est = None
    if gla.estimate is not None:
        est = gla.estimate(merged, confidence, {"d_total": d_total})
    return new_states, views, merged, est


@functools.partial(jax.jit, static_argnames=("gla", "all_alive"))
def _final_vmapped(gla: GLA, views, w_final: jnp.ndarray, *, all_alive: bool):
    merged = EN._merge_over_partitions(gla, views, w_final, gla.merge,
                                       all_alive)
    return gla.terminate(merged)


# ---------------------------------------------------------------------------
# host -> device slice prefetch (streaming sources, DESIGN.md §8)
# ---------------------------------------------------------------------------

class _SlicePrefetcher:
    """Double-buffered host→device pipeline for streaming sources.

    One worker thread reads round-slice r+1 from the source and
    ``device_put``s it while the main thread's round-r compute runs;
    :meth:`get` hands over the ready buffer and immediately schedules the
    next fetch.  Depth 1 == double buffering: at most two slices are alive
    on device at once, so steady-state device footprint is O(slice) and
    the scan never stalls on I/O once warmed.
    """

    def __init__(self, source: DSRC.ChunkSource, bounds, put):
        self._source = source
        self._bounds = list(bounds)   # [(lo, hi)] per round
        self._put = put               # host cols dict -> device arrays
        self._ex = ThreadPoolExecutor(max_workers=1)
        self._fut = None
        self._next_r = None

    def _fetch(self, r: int):
        lo, hi = self._bounds[r]
        return self._put(self._source.slice_cols(lo, hi))

    def get(self, r: int):
        """Device buffers for round r; kicks off the fetch of round r+1
        before blocking, so (with the single worker) slice r+1 transfers
        while round r's jitted step runs."""
        if self._fut is not None and self._next_r == r:
            fut = self._fut
        else:
            fut = self._ex.submit(self._fetch, r)
        if r + 1 < len(self._bounds):
            self._fut, self._next_r = self._ex.submit(self._fetch, r + 1), r + 1
        else:
            self._fut = self._next_r = None
        return fut.result()

    def close(self) -> None:
        """Drop the pending buffer and retire the worker thread (sessions
        close the prefetcher when they finish, converge, or pause — a
        long-lived process must not accumulate one idle thread and one
        captured device slice per completed session).  Waits for an
        in-flight fetch: pause() reads the source from the main thread
        right after closing (fingerprint sampling), and e.g. pyarrow file
        handles are not safe to read from two threads at once."""
        self._fut = self._next_r = None
        self._ex.shutdown(wait=True, cancel_futures=True)


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------

class Session:
    """A long-lived OLA query: advance round by round, stop early, pause.

    ``data`` is a resident ``[P, C, L]`` shards dict or any
    :class:`repro.data.source.ChunkSource`; streaming sources
    (``NpyMmapSource``/``ParquetSource``) are scanned out-of-core through
    the double-buffered prefetcher — O(slice) device footprint — and
    always run the incremental discipline (DESIGN.md §8).

    The plan arrives as a :class:`repro.core.spec.QuerySpec` (canonical) or
    a bare GLA; the old loose plan kwargs still work with a
    ``DeprecationWarning``.  Engine location (``mesh``/``axis_name``) and
    ``audit`` stay per-call arguments.  Construction validates exactly like
    :func:`repro.core.engine.run_query` (same emit/kernel contracts, same
    round-degrade policy).  Drive it with

      * :meth:`run` — to convergence (``stop`` rule) or completion.  With no
        stopping rule and no prior :meth:`step`, this executes the fused
        whole-scan program — byte-for-byte the classic engine path.
      * :meth:`step` — one round-slice; returns the :class:`RoundProgress`
        the stopping rule saw.  Requires an incrementally-steppable config:
        ``mode="async"`` with a partition-uniform schedule and no
        failure-injection ``alive`` schedule.
      * :meth:`result` — :class:`engine.QueryResult` over the rounds
        executed so far (early-stopped sessions report the partial-scan
        final, i.e. the best current answer).
      * :meth:`pause` / :meth:`resume` — checkpoint between rounds and
        continue later, bitwise-identically, even in another process.
    """

    def __init__(self, spec, data, *, mesh=None, axis_name: str = "data",
                 audit=None, **plan):
        qspec = QS.coerce_spec(spec, plan, caller="Session")
        source = DSRC.as_source(data)
        qspec = EN.normalize_plan(qspec, source)
        self.spec = qspec  # the resolved plan, for introspection
        gla: GLA = qspec.gla
        rounds, schedule, emit = qspec.rounds, qspec.schedule, qspec.emit
        stop, mode, lanes = qspec.stop, qspec.mode, qspec.lanes
        snapshots, confidence = qspec.snapshots, qspec.confidence
        alive, fault = qspec.alive, qspec.resolved_fault()
        sync_cost_model = qspec.sync_cost_model
        self._gla = gla
        self._source = source
        self._resident = source.resident
        self._shards = source.shards if source.resident else None
        self._sched = np.asarray(schedule, np.int32)
        self._rounds = self._sched.shape[1] - 1
        self._stop = stop
        self._confidence = float(confidence)
        self._mode = mode
        self._emit = emit
        self._lanes = lanes
        self._snapshots = snapshots
        self._mesh = mesh
        self._axis_name = axis_name
        self._sync_cost_model = sync_cost_model
        P, C, L = source.spec.P, source.spec.C, source.spec.L
        self._P, self._C, self._L = P, C, L

        alive_np = None if alive is None else np.asarray(alive)
        self._alive = alive_np
        self._all_alive = alive_np is None or bool(np.all(alive_np))
        alive_arr = (jnp.ones((P,), bool) if alive_np is None
                     else jnp.asarray(alive_np, bool))
        self._alive_arr = alive_arr
        self._w_pr = self._w_final = None  # lazy, with the stats below

        self._policy = fault
        self._fail_at = {} if fault is None else dict(fault.fail_at)
        self._prefail_est = None  # last all-alive round's Estimate
        if fault is not None:
            if alive_np is not None:
                raise ValueError(
                    "pass failures either as a static alive mask or "
                    "through a FaultPolicy, not both")
            if not gla.merge_is_additive:
                raise ValueError(
                    "FaultPolicy needs additive merges: excluding dead "
                    "partitions is a weighted merge, which non-additive "
                    "GLAs cannot honor")
            for p in self._fail_at:
                if p >= P:
                    raise ValueError(
                        f"FaultPolicy.fail_at names partition {p}, but "
                        f"the data has P={P}")

        uniform = bool(np.all(self._sched == self._sched[0]))
        self._incremental_ok = (
            mode == "async" and uniform
            and (alive_np is None or alive_np.ndim == 1))
        if stop is not None and not self._incremental_ok:
            raise ValueError(
                "stopping rules need an incrementally-steppable session: "
                "mode='async' with a partition-uniform schedule and no "
                "[R, P] failure-injection alive mask (sync barriers and "
                "straggler schedules are whole-scan semantics)")
        if not self._resident and not self._incremental_ok:
            raise ValueError(
                "streaming sources scan incrementally and need an "
                "incrementally-steppable config: mode='async' with a "
                "partition-uniform schedule and no [R, P] alive schedule "
                "(whole-scan semantics require resident shards)")

        if emit == "kernel":
            if lanes != 1:
                raise ValueError("emit='kernel' runs single-lane")
            # the fused kernel (DESIGN.md §12) subsumes the legacy kernel
            # paths whenever the GLA publishes a FusedSpec and every column
            # is kernel-shaped; it is carry-style and bitwise-identical to
            # the scan path, except for group members in a compiled TPU
            # kernel: those match within the float64-oracle tolerance
            # (docs/KERNELS.md rule 2).
            if SC.fused_available(gla, self._source.spec.columns):
                self._path = "kernel_fused"
            else:
                self._path = ("kernel_bundle" if gla.members
                              else "kernel_group" if gla.kernel_num_groups
                              is not None else "kernel_scalar")
        else:
            self._path = "scan"
        # encoded sources (data/encodings.py) ship physical columns; the
        # fused path decodes them in-kernel, every other path decodes the
        # slice before accumulating (_step_vmapped / session_step_sharded).
        self._encodings = tuple(getattr(self._source, "encodings", ()) or ())

        # d_local/d_total, merge weights and the per-chunk scanned-tuple
        # prefix are only consumed by the incremental discipline; computed
        # lazily on the first step() so a fused-only session (every classic
        # run_query call, possibly itself under jit) pays nothing for them
        # — the fused program derives its own copies internally.
        self._d_local = self._d_total = None
        self._mask_cum: Optional[np.ndarray] = None
        self._prefetch: Optional[_SlicePrefetcher] = None

        self._states: Optional[Pytree] = None
        self._views: Optional[Pytree] = None
        self._merged: List[Pytree] = []
        self._ests: List[Any] = []
        self._steps = 0
        self._elapsed = 0.0
        self._converged = False
        self._fused = False
        self._result: Optional[EN.QueryResult] = None

        # audit=True certifies the plan against the static invariant
        # catalog before the first byte is scanned (audit=("name", ...)
        # selects checks); any failure raises AuditError here, in the
        # constructor, so a bad plan never runs.  The report is kept on
        # ``self.audit_report`` for callers that want the pass details.
        self.audit_report = None
        if audit:
            from repro.analysis import audit as AU
            self.audit_report = AU.audit_plan(
                gla, source, rounds=rounds, schedule=self._sched,
                emit=emit, mode=mode, lanes=lanes, snapshots=snapshots,
                confidence=self._confidence, mesh=mesh,
                axis_name=axis_name,
                checks=None if audit is True else tuple(audit),
                raise_on_failure=True)

    # -- introspection -------------------------------------------------------

    @property
    def steps_taken(self) -> int:
        """Round-slices executed so far (the k in 'pays only k/N')."""
        return self._steps

    @property
    def rounds_total(self) -> int:
        return self._rounds

    @property
    def converged(self) -> bool:
        """True once the stopping rule has fired."""
        return self._converged

    @property
    def done(self) -> bool:
        return (self._converged or self._steps >= self._rounds
                or self._result is not None)

    @property
    def elapsed_s(self) -> float:
        return self._elapsed

    # -- the incremental driver ----------------------------------------------

    def _init_states(self) -> Pytree:
        base = (SC.stack_init(self._gla, self._lanes)
                if self._path == "scan" else self._gla.init())
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self._P, *x.shape)), base)

    def _ensure_stats(self) -> None:
        if self._d_local is None:
            # Per-(partition, chunk) live-tuple counts come from the source
            # (host float64, exact for integer counts) — not from a resident
            # _mask array — so progress accounting and budget(max_tuples)
            # work without whole-dataset residency.  Counts are integers, so
            # the f32 casts match the device-side jnp.sum the fused program
            # computes, bit-for-bit, up to 2**24 tuples per reduction.
            ms = self._source.mask_chunk_sums()
            self._d_local = jnp.asarray(ms.sum(axis=1), jnp.float32)
            self._d_total = jnp.asarray(ms.sum(), jnp.float32)
            self._w_pr, self._w_final = SC.round_weights(
                self._alive_arr, self._rounds)

    def _slice_shards(self, r: int, lo: int, hi: int):
        """Round-r slice as device-consumable arrays.

        Resident sources keep the classic lazy device-array slicing;
        streaming sources go through the double-buffered prefetcher (the
        mesh path places each partition's block on its device via
        ``shard_engine.device_put_slice``)."""
        if self._resident:
            return {k: v[:, lo:hi] for k, v in self._shards.items()}
        if self._prefetch is None:
            if self._mesh is None:
                put = jax.device_put
            else:
                from repro.dist import shard_engine
                put = functools.partial(shard_engine.device_put_slice,
                                        mesh=self._mesh,
                                        axis_name=self._axis_name)
            bounds = [(int(self._sched[0, i]), int(self._sched[0, i + 1]))
                      for i in range(self._rounds)]
            self._prefetch = _SlicePrefetcher(self._source, bounds, put)
        return self._prefetch.get(r)

    def _close_prefetch(self) -> None:
        if self._prefetch is not None:
            self._prefetch.close()
            self._prefetch = None

    # -- runtime failure bookkeeping (FaultPolicy, DESIGN.md §9) -------------

    def _record_failure(self, p: int, r: int) -> None:
        if not 0 <= p < self._P:
            raise ValueError(
                f"source reported lost partition {p}, but the data has "
                f"P={self._P}")
        # first failure round wins: a partition cannot die twice, and a
        # retried read re-reporting the same loss must not move the round
        self._fail_at.setdefault(int(p), int(r))

    def _alive_now(self, r: int) -> np.ndarray:
        """[P] bool — partition p contributes to round r's merge iff it has
        not failed at or before r (``failure_schedule`` convention)."""
        a = np.ones(self._P, bool)
        for p, fr in self._fail_at.items():
            if fr <= r:
                a[p] = False
        return a

    def _first_fail_round(self) -> Optional[int]:
        return min(self._fail_at.values()) if self._fail_at else None

    def _fetch_slice(self, r: int, lo: int, hi: int):
        """Round-r slice, surviving partition loss when a policy is
        attached: a :class:`repro.data.source.PartitionLostError` records
        the newly-dead partitions at this round and the read retries
        against the survivors (the source serves them zeroed from then
        on).  Bounded by P+1 attempts — each retry must name at least one
        new partition, so the loop cannot spin."""
        for _ in range(self._P + 1):
            try:
                return self._slice_shards(r, lo, hi)
            except DSRC.PartitionLostError as e:
                if self._policy is None:
                    raise
                for p in e.partitions:
                    self._record_failure(p, r)
        raise RuntimeError(
            f"source kept losing partitions at round {r} — more loss "
            f"reports than partitions")

    def _apply_policy_est(self, est, r: int):
        """Per-round §4.6 estimator consequences.  ``single`` passes
        through (the alive-weighted merge already renormalized, and the
        variance floor keeps bounds finite); ``multiple`` poisons bounds
        from the failure round on; ``synchronized`` freezes at the last
        pre-failure round (infinite bounds when nothing preceded it)."""
        fr = self._first_fail_round()
        if fr is None or r < fr:
            self._prefail_est = est
            return est
        if self._policy is None or self._policy.estimator == "single":
            return est
        from repro.dist import fault  # local: fault imports engine
        if self._policy.estimator == "multiple":
            return _map_member_ests(fault.poison_bounds, est)
        if self._prefail_est is None:  # failed before the first round
            return _map_member_ests(fault.poison_bounds, est)
        return self._prefail_est

    def step(self) -> RoundProgress:
        """Advance one round-slice; evaluate the stopping rule; return what
        it saw.  Raises on configs that cannot step incrementally."""
        if self._result is not None:
            raise RuntimeError("session already ran to completion")
        if not self._incremental_ok:
            raise ValueError(
                "this session cannot step incrementally (sync mode, "
                "non-uniform schedule, or [R, P] alive schedule) — use "
                "run(), which executes the fused whole-scan program")
        if self.done:
            raise RuntimeError("session is done; call result()")
        t0 = time.perf_counter()
        self._ensure_stats()
        r = self._steps
        lo, hi = int(self._sched[0, r]), int(self._sched[0, r + 1])
        slice_shards = self._fetch_slice(r, lo, hi)
        first = self._path not in ("scan", "kernel_fused") and r == 0
        states = self._states
        if states is None:
            states = self._init_states()
        w_r = self._w_pr[:, r]
        all_alive = self._all_alive
        if self._fail_at:
            alive_now = self._alive_now(r)
            if not alive_now.all():
                # dead partitions drop out of this round's merge; their
                # carry keeps stepping (harmless — weight 0 forever after)
                w_r = w_r * jnp.asarray(alive_now, jnp.float32)
                all_alive = False
        if self._mesh is None:
            new_states, views, merged, est = _step_vmapped(
                self._gla, states, slice_shards, w_r, self._d_local,
                self._d_total, path=self._path, lanes=self._lanes,
                confidence=self._confidence, all_alive=all_alive,
                first=first, encodings=self._encodings)
        else:
            from repro.dist import shard_engine
            new_states, views, merged, est = shard_engine.session_step_sharded(
                self._gla, states, slice_shards, w_r, self._d_local,
                self._d_total, mesh=self._mesh, axis_name=self._axis_name,
                path=self._path, lanes=self._lanes,
                confidence=self._confidence, first=first,
                encodings=self._encodings)
        if self._policy is not None:
            est = self._apply_policy_est(est, r)
        self._states, self._views = new_states, views
        if self._snapshots:
            # snapshots off = non-interactive mode: the round's merged
            # state and estimate still exist transiently (stop rules read
            # ``est`` from RoundProgress) but no per-round history is
            # retained — O(state), not O(rounds x state), matching the
            # fused program's snapshots=False semantics
            self._merged.append(merged)
            self._ests.append(est)
        self._steps += 1
        if self._mask_cum is None:
            # per-slice mask sums folded on the host (source-provided, no
            # whole-dataset residency) — feeds scanned/budget(max_tuples)
            self._mask_cum = np.cumsum(self._source.mask_chunk_sums(), axis=1)
        scanned = float(self._mask_cum[:, hi - 1].sum()) if hi else 0.0
        self._elapsed += time.perf_counter() - t0
        prog = RoundProgress(
            round=self._steps, rounds_total=self._rounds, estimates=est,
            scanned=scanned, d_total=float(self._d_total),
            elapsed_s=self._elapsed)
        if self._stop is not None and self._stop(prog):
            self._converged = True
        if self.done:
            self._close_prefetch()
        return prog

    def run(self) -> EN.QueryResult:
        """Drive to convergence or completion and return the result.

        Resident sources with no stopping rule execute the fused
        whole-scan program; streaming sources always run the incremental
        discipline (one prefetched round-slice on device at a time)."""
        if self._result is not None:
            return self._result
        if self._resident and self._steps == 0 and (
                self._stop is None or not self._incremental_ok):
            t0 = time.perf_counter()
            self._fused = True
            alive_arr, all_alive = self._alive_arr, self._all_alive
            if self._fail_at:
                # injected failures on the fused program: ship the policy
                # as an [R, P] schedule, exactly the dist.fault path
                from repro.dist import fault
                alive_arr = jnp.asarray(fault.failure_schedule(
                    self._P, self._rounds, self._fail_at))
                all_alive = False
            self._result = EN._execute_full(
                self._gla, self._shards, jnp.asarray(self._sched),
                alive_arr, mode=self._mode, emit=self._emit,
                lanes=self._lanes, snapshots=self._snapshots,
                confidence=self._confidence, all_alive=all_alive,
                mesh=self._mesh, axis_name=self._axis_name,
                sync_cost_model=self._sync_cost_model)
            if self._fail_at and self._result.estimates is not None:
                from repro.dist import fault
                fr = self._first_fail_round()
                post = {"multiple": lambda e: fault._poison(e, fr),
                        "synchronized": lambda e: fault._stall(e, fr)}.get(
                            self._policy.estimator)
                if post is not None and fr < self._rounds:
                    self._result = self._result._replace(
                        estimates=_map_member_ests(
                            post, self._result.estimates))
            self._elapsed += time.perf_counter() - t0
            self._steps = self._rounds
            return self._result
        while not self.done:
            self.step()
        return self.result()

    def result(self) -> EN.QueryResult:
        """QueryResult over the rounds executed so far.

        ``final`` is Terminate(Merge of the current per-partition states) —
        the full-scan answer when the session completed.  For an
        early-stopped session it is the raw partial aggregate over the
        scanned prefix (Terminate does not extrapolate); the anytime
        *answer* is the last round's ``estimates`` entry, whose CI is what
        the stopping rule certified.  ``snapshots``/``estimates`` stack the
        executed rounds, leaves ``[steps_taken, ...]``.
        """
        if self._result is not None:
            return self._result
        if self._steps == 0:
            raise RuntimeError("no rounds executed yet — step() or run()")
        w_final, all_alive = self._w_final, self._all_alive
        if self._fail_at:
            alive_now = self._alive_now(self._steps - 1)
            if not alive_now.all():
                # the final is over surviving partitions' data only — a
                # dead partition's carry (data it scanned before dying)
                # is lost with it, per the §4.6 failure model
                w_final = w_final * jnp.asarray(alive_now, jnp.float32)
                all_alive = False
        if self._mesh is None:
            final = _final_vmapped(self._gla, self._views, w_final,
                                   all_alive=all_alive)
        else:
            from repro.dist import shard_engine
            final = shard_engine.session_final_sharded(
                self._gla, self._views, w_final, mesh=self._mesh,
                axis_name=self._axis_name)
        snaps = ests = None
        if self._merged:
            snaps = jax.tree.map(lambda *xs: jnp.stack(xs), *self._merged)
        if self._ests and self._ests[0] is not None:
            ests = jax.tree.map(lambda *xs: jnp.stack(xs), *self._ests)
        res = EN.QueryResult(final, snaps, ests, self._d_total, self._d_local)
        if self.done:
            self._result = res
        return res

    # -- pause / resume ------------------------------------------------------

    def _meta(self) -> dict:
        return {
            "version": _CKPT_VERSION, "gla": self._gla.name,
            "rounds": self._rounds, "steps": self._steps,
            "emit": self._emit, "mode": self._mode, "lanes": self._lanes,
            "snapshots": self._snapshots,
            "confidence": self._confidence, "path": self._path,
            "P": self._P, "C": self._C, "L": self._L,
            # the scan cursor is only meaningful against the exact same
            # round boundaries and liveness weights, so both round-trip
            "schedule": self._sched.tolist(),
            "alive": (None if self._alive is None
                      else np.asarray(self._alive, int).tolist()),
            # v3 (DESIGN.md §9): per-partition scan cursors (chunk index
            # each partition has consumed up to — the elastic resume
            # re-derives these for a new partition count), the runtime
            # failure record as [partition, round] pairs (msgpack maps
            # cannot key on ints), and the fault estimator family
            "cursors": [int(self._sched[p, self._steps])
                        for p in range(self._P)],
            "fail_at": sorted([int(p), int(r)]
                              for p, r in self._fail_at.items()),
            "fault_estimator": (None if self._policy is None
                                else self._policy.estimator),
            "elapsed_s": self._elapsed, "converged": self._converged,
            # content fingerprint (DESIGN.md §8): resume refuses different
            # data, including same-shape impostors
            "source": self._source.spec.meta(),
            "fingerprint": self._source.fingerprint(),
        }

    def _payload_like(self, steps: int) -> dict:
        """Shape/structure skeleton of the checkpoint payload, rebuilt from
        the session config so deserialization never needs live state.  The
        vmapped step's output structure is identical to the sharded one
        (global shapes), so one eval_shape serves both engines.  The slice
        skeleton comes from the source's chunk spec, never from resident
        arrays — deserialization works for streaming sources too."""
        self._ensure_stats()
        per0 = max(1, int(self._sched[0, 1] - self._sched[0, 0]))
        # physical slice shapes: encoded sources ship packed columns
        slice_like = self._source.step_slice_like(per0)
        states_like = jax.eval_shape(self._init_states)
        st, views, merged, est = _step_vmapped.eval_shape(
            self._gla, states_like, slice_like,
            jax.ShapeDtypeStruct((self._P,), jnp.float32),
            jax.ShapeDtypeStruct(self._d_local.shape, self._d_local.dtype),
            jax.ShapeDtypeStruct(self._d_total.shape, self._d_total.dtype),
            path=self._path, lanes=self._lanes,
            confidence=self._confidence, all_alive=self._all_alive,
            first=self._path not in ("scan", "kernel_fused"),
            encodings=self._encodings)
        hist = steps if self._snapshots else 0  # no history retained
        return {"states": st, "views": views,
                "merged": (merged,) * hist, "ests": (est,) * hist}

    def pause(self, path) -> None:
        """Checkpoint the session between rounds (Serialize, paper Table 1).

        Stores the per-partition scan carry, per-round merged states and
        estimates, and the scan cursor.  Resume with :meth:`Session.resume`
        — in this process or another — and drive on: the remaining rounds
        replay the exact program, so finals are bitwise-identical to an
        uninterrupted session.
        """
        if self._fused:
            raise RuntimeError(
                "session ran the fused whole-scan program — there is no "
                "incremental carry to pause; attach a stopping rule or "
                "step() to run incrementally")
        self._close_prefetch()  # paused sessions hold no worker thread
        blob = b""
        if self._steps:
            payload = {"states": self._states, "views": self._views,
                       "merged": tuple(self._merged),
                       "ests": tuple(self._ests)}
            blob = ckpt.serialize_state(payload)
        ckpt.save_envelope(path, self._meta(), blob)

    @classmethod
    def resume(cls, path, gla: GLA, data, *,
               stop: Optional[StoppingRule] = None,
               partitions: Optional[int] = None,
               fault: Optional[FaultPolicy] = None, mesh=None,
               axis_name: str = "data") -> "Session":
        """Rebuild a paused session from ``path`` + the original gla/data.

        The checkpoint stores configuration and state but not code or data:
        the caller supplies the same GLA and the same dataset — as a shards
        dict or any ChunkSource; the **content fingerprint** stored at
        pause time is re-derived from the supplied source and must match,
        so resuming against different data (even same-shape data, which
        would silently produce wrong finals) raises ``ValueError``.  The
        check is best-effort by design — per-chunk tuple counts plus
        strided column samples, not a full-content hash (repro.data.source
        docstring spells out what escapes it).  The fingerprint is
        storage-independent: a session paused over in-memory shards
        resumes over an ``.npy``/parquet copy of the same rows.  ``stop``
        is attached fresh — rules are closures and do not serialize.

        Every plan mismatch (gla name, shape, rounds, estimator family,
        data content) raises a ``ValueError`` naming the field *before any
        device work* — never a shape error from deep inside
        ``deserialize_state``.

        **Elastic resume** (DESIGN.md §9): ``partitions=P'`` continues the
        scan on a different partition count — P'|P merges carries
        (round-robin chunk interleave, ``scan.merge_carries``), P|P'
        splits them (``scan.split_carries``) — so a checkpoint taken on an
        8-way mesh resumes on a 4-way one, or vice versa.  Requires an
        all-alive checkpoint with a partition-uniform schedule; finals
        match the uninterrupted run up to merge-association order
        (bitwise for count-like monoids).

        A v3 checkpoint carries the runtime failure record and estimator
        family; ``fault`` overrides/extends the restored policy (it must
        agree on the estimator family).  ``synchronized`` sessions restore
        the frozen estimate from the snapshot history; with
        ``snapshots=False`` there is no history and post-failure rounds
        degrade to infinite bounds.
        """
        meta, blob = ckpt.load_envelope(path)
        ckpt.require_version(meta, _READABLE_VERSIONS,
                             what="session checkpoint")

        # -- validate the supplied plan against the envelope BEFORE any
        # session construction or device work, naming the field
        src = DSRC.as_source(data)
        if meta["gla"] != gla.name:
            raise ValueError(
                f"checkpoint mismatch: gla was {meta['gla']!r} at pause "
                f"time, got {gla.name!r} now")
        if meta["L"] != src.spec.L:
            raise ValueError(
                f"checkpoint mismatch: L was {meta['L']!r} at pause "
                f"time, got {src.spec.L!r} now")
        if src.spec.P != int(meta["P"]):
            # the dataset may arrive in its original layout while the
            # session was paused on an elastic view of it (or vice versa):
            # re-wrap to the pause-time layout when the counts are
            # repartition-compatible, else name the field
            try:
                src = DSRC.repartition(src, int(meta["P"]))
            except ValueError as err:
                raise ValueError(
                    f"checkpoint mismatch: P was {meta['P']!r} at pause "
                    f"time, got {src.spec.P!r} now ({err})") from None
        if meta["C"] != src.spec.C:
            raise ValueError(
                f"checkpoint mismatch: C was {meta['C']!r} at pause "
                f"time, got {src.spec.C!r} now")
        sched = np.asarray(meta["schedule"], np.int32)
        if (sched.ndim != 2 or sched.shape[0] != meta["P"]
                or meta["rounds"] != sched.shape[1] - 1
                or not 0 <= meta["steps"] <= meta["rounds"]):
            raise ValueError(
                f"checkpoint mismatch: rounds {meta['rounds']!r} / steps "
                f"{meta['steps']!r} do not agree with the stored "
                f"{list(sched.shape)}-shaped schedule")
        # fingerprint on the ORIGINAL layout — it hashes the chunk spec,
        # so it must be checked before any repartitioning view wraps src
        if meta["fingerprint"] != src.fingerprint():
            raise ValueError(
                "checkpoint mismatch: data content fingerprint differs — "
                "the supplied shards/source do not hold the data this "
                "session was paused over (same shapes are not enough; "
                "resuming would silently produce wrong finals)")

        # -- rehydrate the fault record (v2 envelopes: no failures, no
        # policy); a caller-supplied policy must agree on the family
        rec_fail = {int(p): int(r) for p, r in (meta.get("fail_at") or [])}
        rec_est = meta.get("fault_estimator")
        if (fault is not None and rec_est is not None
                and fault.estimator != rec_est):
            raise ValueError(
                f"checkpoint mismatch: fault estimator family was "
                f"{rec_est!r} at pause time, got {fault.estimator!r} now")
        if fault is None and rec_est is not None:
            fault = FaultPolicy(rec_est, fail_at=rec_fail)
        elif fault is not None and rec_fail:
            merged_at = dict(fault.fail_at)
            for p, r in rec_fail.items():
                merged_at[p] = min(r, merged_at.get(p, r))
            fault = FaultPolicy(fault.estimator, fail_at=merged_at)

        alive = (None if meta["alive"] is None
                 else np.asarray(meta["alive"], bool))

        # -- elastic resume: re-derive source view + schedule for P'
        P_old = int(meta["P"])
        factor, split = 1, False
        if partitions is not None and int(partitions) != P_old:
            P_new = int(partitions)
            if alive is not None or rec_fail:
                raise ValueError(
                    "elastic resume requires an all-alive checkpoint: "
                    "dead partitions' carries are lost and cannot be "
                    "merged or split into a new layout")
            bounds = sched[0]
            if not np.all(sched == bounds):
                raise ValueError(
                    "elastic resume requires a partition-uniform schedule")
            src = DSRC.repartition(src, P_new)  # validates divisibility
            if P_new <= P_old:
                factor, split = P_old // P_new, False
                bounds = bounds * factor
            else:
                factor, split = P_new // P_old, True
                if np.any(bounds % factor):
                    raise ValueError(
                        f"cannot split {P_old} -> {P_new} partitions: "
                        f"round boundaries {bounds.tolist()} are not all "
                        f"divisible by {factor}")
                bounds = bounds // factor
            sched = np.broadcast_to(
                bounds, (P_new, bounds.size)).astype(np.int32)

        sess = cls(
            QS.QuerySpec(
                gla, rounds=int(sched.shape[1] - 1), stop=stop,
                schedule=sched, alive=alive, fault=fault,
                confidence=meta["confidence"], sync=meta["mode"] == "sync",
                emit=meta["emit"], lanes=meta["lanes"],
                snapshots=meta["snapshots"]),
            src, mesh=mesh, axis_name=axis_name)
        if meta["steps"]:
            payload = ckpt.deserialize_state(
                blob, like=sess._payload_like(meta["steps"]))
            states, views = payload["states"], payload["views"]
            if factor > 1:
                xform = SC.split_carries if split else SC.merge_carries
                states = xform(states, factor)
                views = xform(views, factor)
            if mesh is not None:
                from repro.dist import shard_engine
                states = shard_engine.device_put_carry(
                    states, mesh=mesh, axis_name=axis_name)
                views = shard_engine.device_put_carry(
                    views, mesh=mesh, axis_name=axis_name)
            sess._states, sess._views = states, views
            # merged/est history is partition-independent (already merged
            # over P) — restored as-is under any elastic relayout
            sess._merged = list(payload["merged"])
            sess._ests = list(payload["ests"])
            if sess._ests:
                sess._prefail_est = sess._ests[-1]
        sess._steps = meta["steps"]
        sess._elapsed = meta["elapsed_s"]
        sess._converged = meta["converged"]
        return sess
