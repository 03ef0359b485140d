"""Benchmark driver — one section per paper table/figure.

    python -m benchmarks.run              # everything (CSV to stdout)
    python -m benchmarks.run quick        # skip the heavier sweeps
    python -m benchmarks.run --smoke      # CI-sized: small rows, few repeats

Sections:
  * kernels      — jitted hot-loop throughput (chunk/group aggregation)
  * overhead     — paper Table 2 (estimation overhead incl. synchronized)
  * groupby      — paper §5.3 large-domain Q1: segment_sum scan vs the
                   per-round-slice Pallas group_agg dispatch
  * multiquery   — shared scan: N concurrent queries over ONE pass vs N
                   solo passes (DESIGN.md §6)
  * early_stop   — time-to-ε and fraction of the scan saved by the
                   incremental session driver (DESIGN.md §7)
  * fault        — mid-scan shard loss: bound-width inflation vs shards
                   lost + the cost of the failure-absorbing round
                   (DESIGN.md §9)
  * streaming    — out-of-core chunk sources vs in-memory: steady-state
                   throughput + the O(slice) transfer certificate
                   (DESIGN.md §8)
  * deepola      — fused two-table joins (probe tables in-kernel) vs the
                   legacy kernel batcher vs the scan path, plus nested
                   GROUP BY + HAVING time-to-ε (DESIGN.md §13)
  * convergence  — paper Figs. 1–3 (relative CI width curves)
  * roofline     — §Roofline table from the dry-run artifacts (if present)

Every section prints CSV to stdout and writes a machine-readable
``benchmarks/out/BENCH_<name>.json`` (``benchmarks/check_schema.py``
validates them; CI runs ``--smoke`` + the validator on every push).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def _bench(fn, repeats=5):
    fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def kernels_section(n=1 << 20):
    """Throughput of the aggregation hot loops (pure-jnp reference path on
    CPU; the Pallas kernels target TPU and are validated in tests)."""
    from repro.kernels import ref
    print("name,us_per_call,derived")
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.normal(size=n), jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    m = jnp.ones(n, jnp.float32)
    f = jax.jit(ref.chunk_agg_ref)
    us = _bench(lambda: jax.block_until_ready(f(vals, w, m)))
    print(f"kernel_chunk_agg_1M,{us:.0f},GBps={n * 12 / us / 1e3:.2f}")
    gids = jnp.asarray(rng.integers(0, 1000, n), jnp.int32)
    va = jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)
    g = jax.jit(lambda v, w_, i: ref.group_agg_ref(v, w_, i, 1000))
    us = _bench(lambda: jax.block_until_ready(g(va, w, gids)))
    print(f"kernel_group_agg_1Mx4_1000g,{us:.0f},GBps={n * 20 / us / 1e3:.2f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("mode", nargs="?", choices=["quick"],
                    help="legacy positional: skip the heavier sweeps")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: small row counts, few repeats — "
                         "exercises every section and emits every "
                         "BENCH_*.json in minutes")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    smoke = args.smoke
    quick = smoke or args.mode == "quick"

    print("# === kernels ===")
    kernels_section(n=1 << 16 if smoke else 1 << 20)

    print("# === overhead (paper Table 2) ===")
    from benchmarks import overhead
    if smoke:
        overhead.run(rows=200_000, sh_repeats=5)
    else:
        overhead.run()

    print("# === groupby (paper §5.3 large-domain Q1) ===")
    from benchmarks import groupby
    groupby.run(rows=50_000 if quick else groupby.ROWS)

    print("# === multiquery (shared scan, DESIGN.md §6) ===")
    from benchmarks import multiquery
    if smoke:
        multiquery.run(rows=multiquery.SMOKE_ROWS, repeats=2)
    else:
        multiquery.run()

    print("# === early_stop (time-to-eps, DESIGN.md §7) ===")
    from benchmarks import early_stop
    if smoke:
        early_stop.run(rows=100_000, repeats=2)
    else:
        early_stop.run()

    print("# === fault (mid-scan shard loss, DESIGN.md §9) ===")
    from benchmarks import fault
    if smoke:
        fault.run(rows=fault.SMOKE_ROWS, repeats=2)
    else:
        fault.run()

    print("# === streaming (out-of-core chunk sources, DESIGN.md §8) ===")
    from benchmarks import streaming
    if smoke:
        streaming.run(rows=streaming.SMOKE_ROWS, repeats=2)
    else:
        streaming.run()

    print("# === fused (fused kernel + encoded sources, DESIGN.md §12) ===")
    from benchmarks import fused
    if smoke:
        fused.run(rows=fused.SMOKE_ROWS, repeats=2)
    else:
        fused.run()

    print("# === deepola (fused joins + nested aggregates, DESIGN.md §13) ===")
    from benchmarks import deepola
    if smoke:
        deepola.run(rows=deepola.SMOKE_ROWS, repeats=2)
    else:
        deepola.run()

    print("# === serve (shared-scan OLA service, DESIGN.md §11) ===")
    from benchmarks import serve
    serve.run(rows=serve.SMOKE_ROWS if smoke else serve.ROWS)

    print("# === convergence (paper Figs 1-3) ===")
    from benchmarks import convergence
    tasks = ["agg_low", "agg_high"] if quick else None
    convergence.run(tasks=tasks, rows=100_000 if smoke else convergence.ROWS)

    print("# === roofline (dry-run artifacts) ===")
    from benchmarks import roofline
    rows = roofline.analyze("single")
    if not rows:
        print("no dry-run artifacts; run: python -m repro.launch.dryrun --all")
    print("name,us_per_call,derived")
    for r in rows:
        if r["status"] != "OK":
            continue
        dom_s = max(r["compute_s"], r["memory_s"], r["collective_s"])
        print(f"roofline_{r['cell']},{dom_s * 1e6:.0f},"
              f"bottleneck={r['bottleneck']};"
              f"fraction={r['roofline_fraction']:.3f}")

if __name__ == "__main__":
    main()
