#!/usr/bin/env python3
"""Bring-up check: the OLA main path on TPU, end to end, in one process.

    python3 chip_smoke.py [--seed 0] [--rows 67108864]
    python3 chip_smoke.py --chips 4 [--seed 0] [--rows 134217728]

One chip (the default): TPC-H lineitem at 2^26 rows — about SF-11, in
P=8 partitions of 1024-row chunks, 2 GiB resident — generated from
``--seed``, randomized and packed on the chip.  Then four phases through
the public ``repro`` entry points:

  (a) ``run_queries``: Q6 (low selectivity) + Q1-small on the default emit;
  (b) a ``Session`` with ``stop=rel_width(0.01)``, which must stop early;
  (c) ``emit="kernel"``: Q6 scalar and Q1-small group on the fused Pallas
      kernel, compiled (its program holds ``tpu_custom_call``);
  (d) an ``OLAService`` answering scalar and grouped ``SlotQuery``s over one
      shared scan, the way ``python -m repro.launch.serve`` drives it.

``--chips 4`` runs only the sharded path and what it is compared with: the
data loads with each partition's shard on its own chip (per-chip
``memory_stats`` show it), ``run_query`` and ``OLAService`` on a 4-chip
mesh, the one-chip vmapped finals, and the oracle.

Every answer is checked against ``tpch.exact_answer``, the float64 oracle,
at the tolerances written below.  One line per phase, then one JSON object
naming the device as the last line.  Exits non-zero, printing no result,
when JAX finds no TPU, when the repository's ``src/`` is missing, or when
any check fails.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import traceback

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
L = 1024             # rows per chunk
ROUNDS = 8
# A full-scan final is an f32 sum: each term is formed with at most
# TERM_ROUNDINGS roundings (Q1's charge, price·(1−discount)·(1+tax), takes
# four), then passes through at most L (inside a chunk) + C (chunk
# partials, folded in order) + P (partitions merged) additions.
# ``tpch.f32_sum_tolerance`` turns that count into Higham's γ_n.  Every
# aggregate here sums nonnegative terms, so Σ|x| is the oracle itself and
# γ_n is a relative tolerance.
# An early-stopped estimate must hold the oracle within CI_WIDEN × its 95%
# half-width — about 5.9σ, which a correct estimator misses with
# probability ~4e-9 per aggregate under the CLT.
CI_WIDEN = 3.0
TERM_ROUNDINGS = 4


class SmokeFailure(AssertionError):
    pass


class CompileMeter:
    """XLA backend-compile seconds and persistent-cache hits/misses, read
    from ``jax.monitoring`` events."""

    def __init__(self, jax):
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Phase:
    """Times one phase, splitting compile seconds from the rest; ``report``
    prints its line once the checks have run."""

    def __init__(self, meter, name, rows):
        self.meter, self.name, self.rows = meter, name, rows

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.meter.secs, self.meter.hits
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile_s = self.meter.secs - self.c0
        self.hits = self.meter.hits - self.h0
        return False

    def report(self, **fields):
        line = {"phase": self.name, "rows": self.rows,
                "compile_s": round(self.compile_s, 3),
                "run_s": round(self.wall - self.compile_s, 3),
                "cache_hits": self.hits, **fields}
        print(" ".join(f"{k}={v}" for k, v in line.items()), flush=True)


def check_close(name, got, exact, rtol):
    """|got - exact| <= rtol·|exact| elementwise; returns the max relative
    error."""
    import numpy as np
    got = np.asarray(got, np.float64).ravel()
    exact = np.asarray(exact, np.float64).ravel()
    if got.shape != exact.shape or not np.all(np.isfinite(got)):
        raise SmokeFailure(f"{name}: got {got}, oracle {exact}")
    err = np.abs(got - exact)
    if not np.all(err <= rtol * np.abs(exact)):
        raise SmokeFailure(f"{name}: relative error "
                           f"{np.max(err / np.maximum(np.abs(exact), 1e-300))}"
                           f" over the f32 tolerance {rtol}")
    return float(np.max(err / np.maximum(np.abs(exact), 1e-300)))


def check_ci(name, est, exact):
    """The oracle lies within CI_WIDEN × the estimate's 95% half-width."""
    import numpy as np
    mid = np.asarray(est.estimate, np.float64).ravel()
    half = (np.asarray(est.upper, np.float64)
            - np.asarray(est.lower, np.float64)).ravel() / 2
    exact = np.asarray(exact, np.float64).ravel()
    if not (np.all(np.isfinite(mid)) and np.all(np.isfinite(half))):
        raise SmokeFailure(f"{name}: non-finite estimate {mid} ± {half}")
    z = np.abs(mid - exact) / np.maximum(half, 1e-300)
    if not np.all(np.abs(mid - exact) <= CI_WIDEN * half):
        raise SmokeFailure(f"{name}: oracle {np.max(z):.2f} half-widths "
                           f"from the estimate (limit {CI_WIDEN})")
    return float(np.max(z))


def queries(repro, tpch, rows):
    q6 = repro.make_sum_gla(tpch.q6_func, tpch.q6_cond(tpch.Q6_LOW_WINDOW),
                            d_total=float(rows))
    q1 = repro.make_groupby_gla(tpch.q1_func, tpch.q1_cond,
                                tpch.q1_group_small, num_groups=4,
                                d_total=float(rows), num_aggs=4)
    return q6, q1


def oracles(tpch, raw):
    return {
        "q6": tpch.exact_answer(raw, tpch.q6_func,
                                tpch.q6_cond(tpch.Q6_LOW_WINDOW),
                                batch_rows=1 << 22),
        "q1": tpch.exact_answer(raw, tpch.q1_func, tpch.q1_cond,
                                tpch.q1_group_small, 4, batch_rows=1 << 22),
    }


def serve_phase(repro, tpch, meter, raw, shards, rows, rtol, *, mesh=None):
    """Phase (d): slot queries on one shared scan, as launch/serve.py
    drives it — two run a full pass (exact), two stop early (CI)."""
    family = repro.SlotFamily(
        exprs={"q6": tpch.q6_func, "qty": lambda c: c["quantity"]},
        pred_cols=("shipdate", "discount"),
        groups={"rfls": (tpch.q1_group_small, 4)})
    asks = [
        (repro.SlotQuery("q6", {"shipdate": (420.0, 785.0),
                                "discount": (0.05, 0.07)}), None),
        (repro.SlotQuery("qty", {"shipdate": (0.0, 1460.0)}, group="rfls"),
         repro.rel_width(0.01)),
        (repro.SlotQuery("q6", {"shipdate": (365.0, 1095.0)}),
         repro.rel_width(0.02)),
        (repro.SlotQuery("qty", {"shipdate": (2000.0, 2526.0)},
                         group="rfls"), None),
    ]
    src = repro.as_source(shards)

    async def serve():
        service = repro.OLAService(family, rounds=ROUNDS, mesh=mesh)
        async with service:
            handles = [await service.submit(repro.QuerySpec(q, stop=stop),
                                            src) for q, stop in asks]
            outs = [await h.result() for h in handles]
        return outs, service.scan_for(src).steps_done

    name = "d_service" if mesh is None else "sharded_service"
    with Phase(meter, name, rows) as ph:
        outs, steps = asyncio.run(serve())
    worst_rel, worst_z, early = 0.0, 0.0, 0
    for (q, stop), out in zip(asks, outs):
        fs = family.solo_gla(q, d_total=float(rows)).fused
        exact = tpch.exact_answer(raw, fs.func, fs.cond, fs.group,
                                  fs.num_groups, batch_rows=1 << 22)
        label = f"{name} {q.expr}/{q.group or 'scalar'}"
        if stop is None:
            if out.converged or out.rounds_witnessed != ROUNDS:
                raise SmokeFailure(f"{label}: no full pass")
            worst_rel = max(worst_rel, check_close(
                label, out.estimate.estimate, exact, rtol))
        else:
            if not out.converged or out.rounds_witnessed >= ROUNDS:
                raise SmokeFailure(f"{label}: did not stop early")
            early += 1
            worst_z = max(worst_z, check_ci(label, out.estimate, exact))
    ph.report(steps=steps, queries=len(asks), early_stopped=early,
              full_pass_max_rel_err=worst_rel,
              early_max_half_widths=round(worst_z, 3))


def one_chip(args, jax, meter):
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.core import engine, randomize
    from repro.data import tpch
    from repro.kernels import fused_agg as FK
    from repro.kernels.ops import _interpret_default

    P, rows = 8, args.rows or 1 << 26
    t0 = time.perf_counter()
    raw = tpch.generate_lineitem(rows, seed=args.seed)
    t_gen = time.perf_counter() - t0
    parts = randomize.randomize_global(
        {k: jnp.asarray(v) for k, v in raw.items()},
        jax.random.key(args.seed), P)
    shards = randomize.pack_partitions(parts, chunk_len=L)
    del parts
    jax.block_until_ready(shards)
    C = shards["_mask"].shape[1]
    rtol = tpch.f32_sum_tolerance(TERM_ROUNDINGS + L + C + P)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print(f"setup rows={rows} P={P} C={C} L={L} gen_s={t_gen:.3f} "
          f"load_s={time.perf_counter() - t0 - t_gen:.3f} "
          f"resident_gib={sum(v.nbytes for v in shards.values()) / 2**30:.3f} "
          f"peak_gib={stats.get('peak_bytes_in_use', 0) / 2**30:.3f} "
          f"f32_rtol={rtol:.3e}", flush=True)
    q6, q1 = queries(repro, tpch, rows)
    exact = oracles(tpch, raw)

    # (a) the default whole-scan program, both queries over one pass
    with Phase(meter, "a_run_queries", rows) as ph:
        r6, r1 = repro.run_queries(repro.QuerySpec([q6, q1], rounds=ROUNDS),
                                   shards)
        jax.block_until_ready((r6.final, r1.final))
    ph.report(steps=ROUNDS,
              q6_rel_err=check_close("a q6", r6.final, exact["q6"], rtol),
              q1_max_rel_err=check_close("a q1", r1.final, exact["q1"],
                                         rtol))
    print(f"  q6={float(np.ravel(r6.final)[0])!r} "
          f"oracle={float(exact['q6'][0])!r}", flush=True)

    # (b) a session that stops as soon as every group's CI is within 1%
    with Phase(meter, "b_session_early_stop", rows) as ph:
        sess = repro.Session(repro.QuerySpec(q1, rounds=ROUNDS,
                                             stop=repro.rel_width(0.01)),
                             shards)
        res = sess.run()
        est = jax.tree.map(lambda x: np.asarray(x)[-1], res.estimates)
    if sess.steps_taken >= ROUNDS or not sess.converged:
        raise SmokeFailure(f"b: session ran {sess.steps_taken}/{ROUNDS} "
                           "rounds without stopping early")
    ph.report(steps=sess.steps_taken,
              max_half_widths=round(check_ci("b q1", est, exact["q1"]), 3))

    # (c) the fused Pallas kernel, compiled
    if _interpret_default():
        raise SmokeFailure("c: Pallas kernels would run in interpret mode")
    with Phase(meter, "c_fused_kernel", rows) as ph:
        with FK.count_dispatches() as n_calls:
            k6 = repro.run_query(repro.QuerySpec(q6, rounds=ROUNDS,
                                                 emit="kernel"), shards)
            k1 = repro.run_query(repro.QuerySpec(q1, rounds=ROUNDS,
                                                 emit="kernel"), shards)
            jax.block_until_ready((k6.final, k1.final))
    sched = jnp.asarray(engine.uniform_schedule(P, C, ROUNDS))
    alive = jnp.ones((P,), bool)
    for g in (q6, q1):
        text = engine._run_vmapped.lower(
            g, shards, sched, alive, mode="async", emit="kernel", lanes=1,
            snapshots=True, confidence=0.95, all_alive=True
        ).compile().as_text()
        if "tpu_custom_call" not in text:
            raise SmokeFailure(f"c {g.name}: no Mosaic kernel in the program")
    ph.report(steps=ROUNDS, pallas_calls=n_calls[0],
              tpu_custom_call=True, interpret=False,
              q6_rel_err=check_close("c q6", k6.final, exact["q6"], rtol),
              q1_max_rel_err=check_close("c q1", k1.final, exact["q1"],
                                         rtol))

    # (d) the service
    serve_phase(repro, tpch, meter, raw, shards, rows, rtol)


def four_chips(args, jax, meter):
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    import repro
    from repro.core import randomize
    from repro.data import tpch

    devs = jax.devices()
    if len(devs) != 4:
        raise SmokeFailure(f"--chips 4 needs 4 chips, JAX sees {len(devs)}")
    P, rows = 4, args.rows or 1 << 27
    mesh = jax.make_mesh((P,), ("data",))
    t0 = time.perf_counter()
    raw = tpch.generate_lineitem(rows, seed=args.seed)
    # host columns: the permutation gathers on the host, and each chip
    # receives only its own partition from the packed host buffers
    parts = randomize.randomize_global(raw, jax.random.key(args.seed), P)
    shards = randomize.pack_partitions(
        parts, chunk_len=L, sharding=NamedSharding(mesh, PartitionSpec("data")))
    del parts
    jax.block_until_ready(shards)
    C = shards["_mask"].shape[1]
    rtol = tpch.f32_sum_tolerance(TERM_ROUNDINGS + L + C + P)
    # make_mesh orders the chips along the physical ring, which need not be
    # jax.devices() order: partition p belongs on the mesh's p-th chip
    ring = list(mesh.devices.flat)
    for p, sh in enumerate(sorted(shards["_mask"].addressable_shards,
                                  key=lambda s: s.index[0].start)):
        if sh.data.shape[0] != 1 or sh.device != ring[p]:
            raise SmokeFailure(f"partition {p} is not alone on mesh chip "
                               f"{p} ({ring[p]}); it is on {sh.device}")
    mem = [d.memory_stats()["bytes_in_use"] / 2**30 for d in ring]
    if max(mem) > 1.5 * min(mem):
        raise SmokeFailure(f"uneven per-chip memory after load: {mem}")
    print(f"setup rows={rows} P={P} C={C} L={L} "
          f"load_s={time.perf_counter() - t0:.3f} "
          f"per_chip_gib={[round(m, 3) for m in mem]} "
          f"partition_chip_ids={[d.id for d in ring]} "
          f"f32_rtol={rtol:.3e}", flush=True)
    q6, q1 = queries(repro, tpch, rows)
    exact = oracles(tpch, raw)

    with Phase(meter, "sharded_run_query", rows) as ph:
        s6 = repro.run_query(repro.QuerySpec(q6, rounds=ROUNDS), shards,
                             mesh=mesh)
        s1 = repro.run_query(repro.QuerySpec(q1, rounds=ROUNDS), shards,
                             mesh=mesh)
        jax.block_until_ready((s6.final, s1.final))
    ph.report(steps=ROUNDS,
              q6_rel_err=check_close("sharded q6", s6.final, exact["q6"],
                                     rtol),
              q1_max_rel_err=check_close("sharded q1", s1.final,
                                         exact["q1"], rtol))
    serve_phase(repro, tpch, meter, raw, shards, rows, rtol, mesh=mesh)

    # the one-chip reference: the same shards gathered onto chip 0, after
    # the sharded phases, through the vmapped engine.  They pass through
    # the host: a device-to-device put keeps the mesh axis in the arrays'
    # type, which a program on one device refuses.
    one = jax.device_put(jax.tree.map(np.asarray, shards), devs[0])
    with Phase(meter, "one_chip_vmapped_reference", rows) as ph:
        v6 = repro.run_query(repro.QuerySpec(q6, rounds=ROUNDS), one)
        v1 = repro.run_query(repro.QuerySpec(q1, rounds=ROUNDS), one)
        jax.block_until_ready((v6.final, v1.final))
    check_close("vmapped q6", v6.final, exact["q6"], rtol)
    check_close("vmapped q1", v1.final, exact["q1"], rtol)
    same = all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
               for a, b in ((s6.final, v6.final), (s1.final, v1.final)))
    ph.report(
        steps=ROUNDS, sharded_bitwise_vmapped=same,
        q6_sharded_vs_vmapped_rel=check_close(
            "sharded vs vmapped q6", s6.final, v6.final, 2 * rtol),
        q1_sharded_vs_vmapped_max_rel=check_close(
            "sharded vs vmapped q1", s1.final, v1.final, 2 * rtol))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path on a 4-chip mesh and "
                         "what it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="lineitem rows (default 2^26, or 2^27 with "
                         "--chips 4)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    meter = CompileMeter(jax)
    print(f"device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(args, jax, meter)
    except Exception:  # noqa: BLE001 - any failure fails the check
        traceback.print_exc()
        return 1
    print(f"total wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={meter.secs:.3f} cache_hits={meter.hits} "
          f"cache_misses={meter.misses}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
