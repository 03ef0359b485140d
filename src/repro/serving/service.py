"""Concurrent OLA serving — one shared scan, many queries (DESIGN.md §11).

The paper's interactive promise is many users watching estimates tighten
at once, but the batch engines price each query (or pre-declared
``GLABundle``) at one full scan.  Following OLA-RAW's shared-cursor
design (PAPERS.md, arXiv 1702.00358), this module serves dynamically
arriving queries from ONE in-flight cyclic scan per dataset:

  * :class:`SharedScan` — the synchronous core.  It advances one
    round-slice per :meth:`SharedScan.step` over a fixed uniform
    schedule, cycling ``cursor mod R``; queries attach at any round into
    a **padded slot bundle** and detach on convergence without stopping
    the scan.  A late joiner's carry starts at zero on its attach round,
    so its estimates are built from *witnessed* rounds only — the
    Horvitz–Thompson scale-up ``d_total / scanned`` keeps bounds
    unbiased no matter when the query joined
    (``tests/test_service.py`` proves bitwise identity with a fresh
    solo Session over the witnessed chunk ranges).
  * :class:`OLAService` — the asyncio front end.  ``await
    service.submit(spec, data)`` returns a :class:`QueryHandle`;
    the service owns one SharedScan per (source fingerprint, engine),
    drives it on an executor thread, applies attach/detach between
    steps, and **parks** an idle scan after a grace period (the drive
    task exits; the scan object — cursor position and warm jit caches —
    stays for the next arrival).

Recompile discipline (the hard part): bundle membership changes on
every arrival/departure, but the jitted step's shapes must not.  Slots
live in power-of-two capacity banks; per-slot query parameters
(:class:`repro.core.gla.SlotParams`) are **dynamic** jit inputs, and an
inactive slot carries the empty predicate range (weight exactly 0).
The step functions' static arguments are only (family, bank,
confidence) — so the jit cache grows by exactly one entry per
capacity doubling per bank per engine, never per query
(``analysis/audit.py`` ``bounded_compiles_under_churn``).  Slot
generations let a detached query's state be reclaimed: attach marks the
slot ``fresh`` and the step resets its carry to the init state via
``jnp.where`` *inside* the jit region (no shape change, and no
``0 * x`` masking — that would turn negative carries into ``-0.0`` and
break bitwise identity with a fresh query's ``+0.0`` init).

Tracing: each phase of the drive records a ``jax.profiler.TraceAnnotation``
named ``ola.*`` (``ola.slice``, ``ola.params``, ``ola.dispatch`` and
``ola.stop_rule`` on the executor thread; ``ola.apply``, ``ola.finish``,
``ola.idle``, ``ola.queued`` and ``ola.grow`` on the event-loop thread).
Each is a leaf — none encloses a whole step — and costs about a
microsecond when no profiler runs (DESIGN.md §11).
"""
from __future__ import annotations

import asyncio
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.core import engine as EN
from repro.core import scan as SC
from repro.core.gla import SlotFamily, SlotParams, SlotQuery
from repro.core.session import RoundProgress
from repro.core.spec import QuerySpec
from repro.data import source as DSRC


# ---------------------------------------------------------------------------
# jitted per-round steps — the serving twins of session._step_vmapped /
# shard_engine.session_step_sharded.  Statics are (family, bank,
# confidence[, mesh]) only: per-slot query parameters are dynamic inputs,
# so the cache grows ONLY when a bank's slot capacity (the K in the
# params/states shapes) doubles.
# ---------------------------------------------------------------------------

def _reset_fresh(params: SlotParams, states: tuple) -> tuple:
    """Zero the carries of freshly (re)claimed slots — inside the jit
    region, shape-stable, and via ``jnp.where`` so reclaimed state is
    bitwise the init state (multiplicative masking would leave -0.0)."""
    def one(k, st):
        return jax.tree.map(
            lambda x: jnp.where(params.fresh[k], jnp.zeros((), x.dtype), x),
            st)

    return tuple(one(k, st) for k, st in enumerate(states))


@functools.partial(
    jax.jit, static_argnames=("family", "bank", "confidence"))
def serve_step_vmapped(family: SlotFamily, bank: str, params: SlotParams,
                       states, slice_shards: dict, w_r: jnp.ndarray,
                       d_local: jnp.ndarray, d_total: jnp.ndarray, *,
                       confidence: float):
    """Advance one bank of the shared scan one round-slice (vmapped).

    Mirrors ``session._step_vmapped``'s scan branch over the bank's
    K-slot bundle: per-partition ``scan_round_step``, estimator
    terminate, the same weighted round merge, then per-slot Estimates.
    Returns (new states tuple, tuple of K Estimates).
    """
    states = _reset_fresh(params, states)
    gla = family.bind(bank, params, d_total)
    new_states, views = jax.vmap(
        lambda st, c: SC.scan_round_step(gla, st, c, 1)
    )(states, slice_shards)
    term = jax.vmap(
        lambda s, dl: gla.estimator_terminate(s, {"d_local": dl})
    )(views, d_local)
    merged = EN._merge_rounds(
        gla, jax.tree.map(lambda x: x[:, None], term), w_r[:, None],
        gla.estimator_merge, True)
    merged = jax.tree.map(lambda x: x[0], merged)
    est = gla.estimate(merged, confidence, {"d_total": d_total})
    return new_states, est


@functools.partial(
    jax.jit,
    static_argnames=("family", "bank", "mesh", "axis_name", "confidence"))
def serve_step_sharded(family: SlotFamily, bank: str, params: SlotParams,
                       states, slice_shards: dict, w_r: jnp.ndarray,
                       d_local: jnp.ndarray, d_total: jnp.ndarray, *, mesh,
                       axis_name: str, confidence: float):
    """The shard_map twin: partitions on ``axis_name``, slot parameters
    replicated, the bank GLA bound *inside* the worker, one psum merge
    per step — the same discipline as ``session_step_sharded``."""
    from jax import lax
    from jax.sharding import PartitionSpec as PS

    from repro.dist.shard_engine import _shard_map

    states = _reset_fresh(params, states)

    def worker(pp, dt, st, cols, w_p, dl):
        st = jax.tree.map(lambda x: x[0], st)
        cols = jax.tree.map(lambda x: x[0], cols)
        gla = family.bind(bank, pp, dt)
        new_st, view = SC.scan_round_step(gla, st, cols, 1)
        term = gla.estimator_terminate(view, {"d_local": dl[0]})
        merged = lax.psum(
            jax.tree.map(lambda x: x * w_p[0].astype(x.dtype), term),
            axis_name)
        return jax.tree.map(lambda x: x[None], new_st), merged

    pspec = PS(axis_name)
    fn = _shard_map(worker, mesh, (PS(), PS(), pspec, pspec, pspec, pspec),
                    (pspec, PS()))
    new_states, merged = fn(params, d_total, states, slice_shards, w_r,
                            d_local)
    gla = family.bind(bank, params, d_total)
    est = gla.estimate(merged, confidence, {"d_total": d_total})
    return new_states, est


def serve_step_cache_sizes() -> Dict[str, Optional[int]]:
    """Current jit-cache entry counts of the serving steps — what the
    audit's churn check reads before/after a workload."""
    out = {}
    for name, fn in (("vmapped", serve_step_vmapped),
                     ("sharded", serve_step_sharded)):
        size = getattr(fn, "_cache_size", None)
        out[name] = size() if callable(size) else None
    return out


# ---------------------------------------------------------------------------
# the shared scan (synchronous core)
# ---------------------------------------------------------------------------

def _degrade_rounds(C: int, rounds: int) -> int:
    """Largest r <= rounds with C % r == 0 — one slice width for the
    whole cyclic scan, so each (bank, capacity) pair is ONE compile."""
    for r in range(min(int(rounds), C), 0, -1):
        if C % r == 0:
            return r
    return 1


@dataclasses.dataclass
class SlotRecord:
    """One attached query's slot, progress, and outcome."""

    query: SlotQuery
    bank: str
    slot: int
    generation: int
    stop: Optional[Any] = None
    witnessed: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    scanned: float = 0.0
    estimate: Any = None                  # latest per-round Estimate
    query_id: int = -1                    # the service's id, for spans
    attached_at: float = 0.0              # perf_counter() at attach
    elapsed_s: float = 0.0                # attach -> latest progress, wall
    done: bool = False
    converged: bool = False               # stop rule fired (vs full pass)
    detached: bool = False


class _Bank:
    """One capacity bank: host-side slot parameters + device carries.

    ``K`` is a power of two; parameter rows of detached slots hold the
    empty range (predicate weight exactly 0).  ``generation[k]``
    increments on every attach, so a stale handle can never read a
    reclaimed slot's results.
    """

    def __init__(self, name: str, family: SlotFamily, P: int, *,
                 mesh=None, axis_name: str = "data"):
        self.name = name
        self.family = family
        self.partials = family.partials_path(name)   # ola.dispatch metadata
        self.P = P
        self.mesh = mesh
        self.axis_name = axis_name
        self.K = 1
        n_pred = len(family.pred_cols)
        self.expr = np.zeros(1, np.int32)
        self.lo = np.full((1, n_pred), np.inf, np.float32)
        self.hi = np.full((1, n_pred), -np.inf, np.float32)
        self.fresh = np.zeros(1, bool)
        self.hv = np.full(1, np.inf, np.float32)
        self.generation = np.zeros(1, np.int64)
        self.slots: List[Optional[SlotRecord]] = [None]
        self.states = (self._zero_state(),)
        self.stepped_ks: set = set()      # capacities actually executed

    def _zero_state(self):
        z = self.family.zero_slot_state(self.name)
        z = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.P, *x.shape)), z)
        if self.mesh is None:
            return z
        # commit fresh carries to the SAME sharding the sharded step
        # outputs (partitions on the mesh axis) — otherwise the step
        # after a capacity growth sees a different input-sharding cache
        # key than steady state and recompiles once per capacity
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as PS
        sh = NamedSharding(self.mesh, PS(self.axis_name))
        return jax.tree.map(lambda x: jax.device_put(x, sh), z)

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def doublings(self) -> int:
        return int(self.K).bit_length() - 1

    def _grow(self) -> None:
        n_pred = len(self.family.pred_cols)
        K = self.K
        self.expr = np.concatenate([self.expr, np.zeros(K, np.int32)])
        self.lo = np.concatenate(
            [self.lo, np.full((K, n_pred), np.inf, np.float32)])
        self.hi = np.concatenate(
            [self.hi, np.full((K, n_pred), -np.inf, np.float32)])
        self.fresh = np.concatenate([self.fresh, np.zeros(K, bool)])
        self.hv = np.concatenate([self.hv, np.full(K, np.inf, np.float32)])
        self.generation = np.concatenate(
            [self.generation, np.zeros(K, np.int64)])
        self.slots.extend([None] * K)
        self.states = self.states + tuple(self._zero_state()
                                          for _ in range(K))
        self.K = 2 * K

    def attach(self, q: SlotQuery, stop, query_id: int) -> SlotRecord:
        try:
            k = self.slots.index(None)
        except ValueError:
            # the bank's next dispatch compiles (or loads) its 2K program
            with _span("ola.grow", bank=self.name, K=2 * self.K):
                self._grow()
            k = self.slots.index(None)
        expr_idx, lo, hi = self.family.slot_row(q)
        self.expr[k] = expr_idx
        self.lo[k], self.hi[k] = lo, hi
        self.hv[k] = np.inf if q.having is None else q.having
        self.fresh[k] = True
        self.generation[k] += 1
        rec = SlotRecord(query=q, bank=self.name, slot=k,
                         generation=int(self.generation[k]), stop=stop,
                         query_id=query_id, attached_at=time.perf_counter())
        self.slots[k] = rec
        return rec

    def detach(self, rec: SlotRecord) -> None:
        k = rec.slot
        if rec.detached or self.slots[k] is not rec:
            return                        # stale ticket: slot was reclaimed
        rec.detached = True
        self.slots[k] = None
        e, lo, hi = self.family.inactive_row()
        self.expr[k] = e
        self.lo[k], self.hi[k] = lo, hi
        self.hv[k] = np.inf
        # state is NOT cleared here — the next attach marks the slot
        # fresh and the jitted step reclaims the carry in-region

    def params(self) -> SlotParams:
        # Copies: the step is dispatched asynchronously and attach/detach
        # rewrite these rows right after, while on CPU ``jnp.asarray`` may
        # alias a NumPy buffer — the in-flight step would read the new rows.
        # hv rides along only for having banks — classic banks keep the
        # 4-field params their jitted steps were traced with
        def put(a):
            return jnp.asarray(a.copy())

        hv = put(self.hv) if self.name.endswith(":having") else None
        return SlotParams(expr=put(self.expr), lo=put(self.lo),
                          hi=put(self.hi), fresh=put(self.fresh), hv=hv)


class SharedScan:
    """One cyclic scan over one dataset, serving many slot queries.

    The scan advances one round-slice per :meth:`step`, cycling
    ``cursor mod R`` over a uniform schedule (``rounds`` degrades to the
    largest divisor of the chunk count, so every slice has the one width
    the jitted steps compiled for).  Queries :meth:`attach` at any round
    — their carry starts fresh on the next step — and complete after
    witnessing all R rounds (one full pass) or when their stopping rule
    fires; :meth:`detach` frees the slot without disturbing the cursor
    or any other query.

    Synchronous and single-threaded by contract: :class:`OLAService`
    serializes attach/detach against in-flight steps.
    """

    def __init__(self, family: SlotFamily, data, *, rounds: int = 8,
                 confidence: float = 0.95, mesh=None,
                 axis_name: str = "data"):
        self.family = family
        self.source = DSRC.as_source(data)
        self.confidence = float(confidence)
        self.mesh = mesh
        self.axis_name = axis_name
        spec = self.source.spec
        self.P, self.C = spec.P, spec.C
        self.rounds = _degrade_rounds(self.C, rounds)
        self.width = self.C // self.rounds
        ms = self.source.mask_chunk_sums()
        self._ms = ms
        self._d_local = jnp.asarray(ms.sum(axis=1), jnp.float32)
        self._d_total = jnp.asarray(ms.sum(), jnp.float32)
        self._w_r = jnp.ones((self.P,), jnp.float32)
        self.banks: Dict[str, _Bank] = {}
        self.cursor = 0
        self.steps_done = 0

    # -- membership ---------------------------------------------------------

    @property
    def active_slots(self) -> int:
        return sum(b.active for b in self.banks.values())

    def attach(self, q: SlotQuery, stop=None,
               query_id: int = -1) -> SlotRecord:
        name = self.family.bank_of(q)
        bank = self.banks.get(name)
        if bank is None:
            bank = self.banks[name] = _Bank(name, self.family, self.P,
                                            mesh=self.mesh,
                                            axis_name=self.axis_name)
        return bank.attach(q, stop, query_id)

    def detach(self, rec: SlotRecord) -> None:
        bank = self.banks.get(rec.bank)
        if bank is not None:
            bank.detach(rec)

    def compile_budget(self) -> int:
        """Jit-cache entries this scan's workload is allowed to have
        created: one per (bank, capacity) pair actually stepped — i.e.
        1 + #doublings per stepped bank — never one per arrival."""
        return sum(len(b.stepped_ks) for b in self.banks.values())

    # -- the drive ----------------------------------------------------------

    def _slice(self, lo: int, hi: int):
        if self.source.resident:
            shards = self.source.shards  # type: ignore[attr-defined]
            return {k: v[:, lo:hi] for k, v in shards.items()}
        cols = self.source.slice_cols(lo, hi)
        if self.mesh is None:
            return jax.device_put(cols)
        from repro.dist import shard_engine
        return shard_engine.device_put_slice(cols, mesh=self.mesh,
                                             axis_name=self.axis_name)

    def step(self) -> List[Tuple[SlotRecord, RoundProgress]]:
        """Advance every bank with live queries one round-slice; return
        the (record, progress) of each slot that witnessed the round.
        Completed slots come back with ``done`` set — the caller (the
        service) detaches them."""
        step = self.cursor
        r = step % self.rounds
        lo, hi = r * self.width, (r + 1) * self.width
        live = {n: b for n, b in self.banks.items() if b.active}
        if not live:
            return []
        with _span("ola.slice", step=step):
            slice_shards = self._slice(lo, hi)
            range_count = float(self._ms[:, lo:hi].sum())
        out: List[Tuple[SlotRecord, RoundProgress]] = []
        for name, bank in live.items():
            with _span("ola.params", step=step, bank=name, K=bank.K):
                params = bank.params()
            with _span("ola.dispatch", step=step, bank=name, K=bank.K,
                       partials=bank.partials):
                if self.mesh is None:
                    new_states, est = serve_step_vmapped(
                        self.family, name, params, bank.states,
                        slice_shards, self._w_r, self._d_local,
                        self._d_total, confidence=self.confidence)
                else:
                    new_states, est = serve_step_sharded(
                        self.family, name, params, bank.states,
                        slice_shards, self._w_r, self._d_local,
                        self._d_total, mesh=self.mesh,
                        axis_name=self.axis_name,
                        confidence=self.confidence)
            bank.states = new_states
            bank.fresh[:] = False
            bank.stepped_ks.add(bank.K)
            for k, rec in enumerate(bank.slots):
                if rec is None:
                    continue
                rec.witnessed.append((lo, hi))
                rec.scanned += range_count
                rec.estimate = est[k]
                rec.elapsed_s = time.perf_counter() - rec.attached_at
                prog = RoundProgress(
                    round=len(rec.witnessed), rounds_total=self.rounds,
                    estimates=est[k], scanned=rec.scanned,
                    d_total=float(self._d_total), elapsed_s=rec.elapsed_s)
                if rec.stop is not None:
                    # the first rule to read an estimate waits on the device
                    with _span("ola.stop_rule", step=step,
                               query=rec.query_id):
                        if rec.stop(prog):
                            rec.converged = True
                if rec.converged or len(rec.witnessed) >= self.rounds:
                    rec.done = True
                out.append((rec, prog))
        self.cursor += 1
        self.steps_done += 1
        return out


def witnessed_view(data, ranges) -> dict:
    """The chunk ranges a slot witnessed, concatenated in witness order,
    as a fresh [P, C', L] shards dict — the dataset a solo Session must
    scan to reproduce the slot's estimates bitwise (tests, DESIGN.md
    §11).  ``data`` is a shards dict or ChunkSource."""
    src = DSRC.as_source(data)
    parts = [src.slice_cols(lo, hi) for lo, hi in ranges]
    return {k: np.concatenate([np.asarray(p[k]) for p in parts], axis=1)
            for k in parts[0]}


# ---------------------------------------------------------------------------
# the asyncio service
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class QueryOutcome:
    """What :meth:`QueryHandle.result` resolves to."""

    estimate: Any                 # final witnessed-rounds Estimate (host)
    rounds_witnessed: int
    scanned: float
    d_total: float
    converged: bool               # stop rule fired (False = full pass)
    elapsed_s: float              # host wall seconds, attach -> last round


class QueryHandle:
    """An in-flight serving query: progress stream + awaitable result."""

    def __init__(self, query: SlotQuery, stop, query_id: int):
        self.query = query
        self.query_id = query_id
        self._stop = stop
        self._queued: Optional[_span] = None   # open submit -> attach
        self.progress: List[RoundProgress] = []
        self._done = asyncio.Event()
        self._outcome: Optional[QueryOutcome] = None
        self._record: Optional[SlotRecord] = None
        self._cancelled = False
        self._error: Optional[BaseException] = None

    @property
    def done(self) -> bool:
        return self._done.is_set()

    async def result(self) -> QueryOutcome:
        """The outcome; raises the error of a scan step that failed."""
        await self._done.wait()
        if self._error is not None:
            raise self._error
        assert self._outcome is not None
        return self._outcome

    def _dequeue(self) -> None:
        """Close the ``ola.queued`` span (attach, cancel, failure, close)."""
        if self._queued is not None:
            self._queued.__exit__(None, None, None)
            self._queued = None

    def _fail(self, exc: BaseException) -> None:
        self._error = exc
        self._done.set()

    def _finish(self, rec: SlotRecord, d_total: float) -> None:
        est = (jax.device_get(rec.estimate)
               if rec.estimate is not None else None)
        self._outcome = QueryOutcome(
            estimate=est, rounds_witnessed=len(rec.witnessed),
            scanned=rec.scanned, d_total=d_total,
            converged=rec.converged, elapsed_s=rec.elapsed_s)
        self._done.set()


class OLAService:
    """Asyncio OLA serving over shared scans (DESIGN.md §11).

    One service owns one :class:`repro.core.gla.SlotFamily` and one
    in-flight :class:`SharedScan` per (source fingerprint, engine).
    ``submit`` attaches a query to the matching scan — starting or
    un-parking it as needed — and returns a :class:`QueryHandle` whose
    ``result()`` resolves when the query converges (stop rule) or
    completes a full pass.  Convergence detaches the slot; the scan
    keeps running for the remaining queries and parks ``grace_s``
    seconds after the last one leaves (the drive task exits; the scan's
    cursor and the jitted steps' warm caches survive for the next
    arrival).

    All scan mutation happens on the event-loop thread between executor
    steps, so SharedScan itself needs no locking.
    """

    def __init__(self, family: SlotFamily, *, rounds: int = 8,
                 confidence: float = 0.95, grace_s: float = 0.25,
                 mesh=None, axis_name: str = "data"):
        self.family = family
        self.rounds = rounds
        self.confidence = confidence
        self.grace_s = grace_s
        self.mesh = mesh
        self.axis_name = axis_name
        self._runners: Dict[tuple, "_Runner"] = {}
        self._closed = False
        self._next_query = 0          # per-service query ids, for spans

    # -- public surface -----------------------------------------------------

    async def submit(self, spec, data) -> QueryHandle:
        """Attach one slot query.  ``spec`` is a
        :class:`repro.core.spec.QuerySpec` whose ``gla`` is a
        :class:`repro.core.gla.SlotQuery` (its ``stop`` rule is
        honored; ``rounds`` is scan-wide, set on the service), or a
        bare ``SlotQuery``."""
        if self._closed:
            raise RuntimeError("service is closed")
        if isinstance(spec, QuerySpec):
            query, stop = spec.gla, spec.stop
            if spec.confidence != self.confidence:
                raise ValueError(
                    f"per-query confidence {spec.confidence} != service "
                    f"confidence {self.confidence}: confidence is a "
                    "compile-time static of the shared step — set it on "
                    "OLAService(...)")
        elif isinstance(spec, SlotQuery):
            query, stop = spec, None
        else:
            raise TypeError(
                "submit() takes a SlotQuery or a QuerySpec wrapping one, "
                f"got {type(spec).__name__}")
        if not isinstance(query, SlotQuery):
            raise TypeError(
                f"QuerySpec.gla must be a SlotQuery here, got "
                f"{type(query).__name__}")
        src = DSRC.as_source(data)
        key = (src.fingerprint(),
               "vmapped" if self.mesh is None else "sharded")
        runner = self._runners.get(key)
        if runner is None:
            scan = SharedScan(self.family, src, rounds=self.rounds,
                              confidence=self.confidence, mesh=self.mesh,
                              axis_name=self.axis_name)
            runner = self._runners[key] = _Runner(scan)
        handle = QueryHandle(query, stop, self._next_query)
        self._next_query += 1
        handle._queued = _span("ola.queued", query=handle.query_id)
        handle._queued.__enter__()
        runner.pending.append(("attach", handle))
        runner.wake.set()
        if runner.task is None or runner.task.done():
            runner.task = asyncio.get_running_loop().create_task(
                self._drive(runner))
        return handle

    def cancel(self, handle: QueryHandle) -> None:
        """Detach a query before it converges; its handle resolves with
        whatever it had witnessed so far."""
        handle._cancelled = True
        for runner in self._runners.values():
            if handle in runner.handles.values() or any(
                    h is handle for _, h in runner.pending):
                runner.pending.append(("detach", handle))
                runner.wake.set()
                return

    def scan_for(self, data) -> Optional[SharedScan]:
        """The shared scan serving ``data`` on this service's engine, if
        one exists (parked or running) — introspection for tests/audit."""
        key = (DSRC.as_source(data).fingerprint(),
               "vmapped" if self.mesh is None else "sharded")
        runner = self._runners.get(key)
        return runner.scan if runner is not None else None

    def is_parked(self, data) -> bool:
        key = (DSRC.as_source(data).fingerprint(),
               "vmapped" if self.mesh is None else "sharded")
        runner = self._runners.get(key)
        return runner is not None and (runner.task is None
                                       or runner.task.done())

    async def close(self) -> None:
        self._closed = True
        tasks = [r.task for r in self._runners.values()
                 if r.task is not None and not r.task.done()]
        for t in tasks:
            t.cancel()
        for t in tasks:
            try:
                await t
            except asyncio.CancelledError:
                pass
        for r in self._runners.values():
            for _, handle in r.pending:
                handle._dequeue()

    async def __aenter__(self) -> "OLAService":
        return self

    async def __aexit__(self, *exc) -> None:
        await self.close()

    # -- the drive loop -----------------------------------------------------

    def _apply_pending(self, runner: "_Runner") -> None:
        if not runner.pending:
            return
        pending, runner.pending = runner.pending, []
        counts = {}
        if _span.is_enabled():            # no work for spans nobody records
            n = sum(op == "attach" for op, _ in pending)
            counts = dict(attaches=n, detaches=len(pending) - n)
        with _span("ola.apply", **counts):
            d_total = float(runner.scan._d_total)
            for op, handle in pending:
                if op == "attach":
                    handle._dequeue()
                    if handle._cancelled:
                        handle._finish(SlotRecord(handle.query, "", -1, 0),
                                       d_total)
                        continue
                    rec = runner.scan.attach(handle.query, handle._stop,
                                             handle.query_id)
                    handle._record = rec
                    runner.handles[id(rec)] = handle
                else:  # detach
                    rec = handle._record
                    if rec is not None and not rec.detached:
                        runner.scan.detach(rec)
                        runner.handles.pop(id(rec), None)
                        handle._finish(rec, d_total)

    def _fail_runner(self, runner: "_Runner", exc: Exception) -> None:
        """A scan step raised: every query on that scan resolves with the
        error, and the scan is dropped — the next submit starts afresh."""
        waiting = [*runner.handles.values(),
                   *(h for op, h in runner.pending if op == "attach")]
        runner.handles.clear()
        runner.pending.clear()
        for key, r in list(self._runners.items()):
            if r is runner:
                del self._runners[key]
        for handle in waiting:
            handle._dequeue()
            handle._fail(exc)

    async def _drive(self, runner: "_Runner") -> None:
        loop = asyncio.get_running_loop()
        while True:
            self._apply_pending(runner)
            if runner.scan.active_slots == 0:
                runner.wake.clear()
                if runner.pending:
                    continue
                try:
                    with _span("ola.idle"):
                        await asyncio.wait_for(runner.wake.wait(),
                                               self.grace_s)
                except asyncio.TimeoutError:
                    # a submit in the timeout's own event-loop turn found
                    # this task still running and started no other
                    if runner.pending:
                        continue
                    return                # park: scan object stays warm
                continue
            try:
                progressed = await loop.run_in_executor(None, runner.scan.step)
            except Exception as exc:  # noqa: BLE001 - handed to every waiter
                self._fail_runner(runner, exc)
                return
            with _span("ola.finish") as span:
                for rec, prog in progressed:
                    handle = runner.handles.get(id(rec))
                    if handle is None:
                        continue
                    handle.progress.append(prog)
                    if rec.done:
                        runner.scan.detach(rec)
                        runner.handles.pop(id(rec), None)
                        handle._finish(rec, float(runner.scan._d_total))
                if _span.is_enabled():
                    span.set_metadata(
                        finished=sum(rec.done for rec, _ in progressed))
            # yield so submit()/cancel() callbacks enqueue between steps
            await asyncio.sleep(0)


class _Runner:
    """One shared scan's drive state: the scan, its (possibly parked)
    task, queued attach/detach ops, and the record->handle map."""

    def __init__(self, scan: SharedScan):
        self.scan = scan
        self.task: Optional[asyncio.Task] = None
        self.pending: List[Tuple[str, QueryHandle]] = []
        self.wake = asyncio.Event()
        self.handles: Dict[int, QueryHandle] = {}
