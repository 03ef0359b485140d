#!/usr/bin/env python3
"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic mix by name, generates the
table on the chip(s) from ``--seed``, warms the serving programs, drives
``OLAService`` for ``--seconds``, follows every panel due in the window to
its answer, checks the answers against the float64 reference, and prints
one JSON line last.  ``--trace 1`` profiles part of the window and reports
the cell's per-layer metrics instead of its end-to-end ones.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs, or when the program's source tree is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# The traced part of a --trace 1 window: its last seconds, long enough
# for many scan steps, short enough to stop and read back in seconds.
TRACE_SECONDS = 4.0
OUT = BENCH / "out"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_or_exit(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: JAX finds no TPU (platform {devs[0].platform!r})")
        sys.exit(3)
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
        sys.exit(3)
    return devs[:chips]


def placement(jax, devs, chips):
    """(sharding of the [P, C, L] columns, mesh for the service)."""
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    if chips == 1:
        return SingleDeviceSharding(devs[0]), None
    mesh = jax.make_mesh((chips,), ("data",), devices=devs)
    return NamedSharding(mesh, PartitionSpec("data")), mesh


def load_table(jax, cfg, seed, sharding):
    import importlib

    table = importlib.import_module(f"bench.tables.{cfg['table']}")
    cols = table.generate(cfg, seed, sharding)
    jax.block_until_ready(cols)
    return cols


def fetch_round(cols, cfg):
    import numpy as np

    P, L = cfg["partitions"], cfg["chunk_rows"]
    width = cfg["rows"] // (P * L) // cfg["rounds"]

    def fetch(r):
        return {k: np.asarray(v[:, r * width:(r + 1) * width])
                for k, v in cols.items()}

    return fetch


def answers_of(panels):
    return [a for p in panels for a in p.get("answers", [])]


def execute(args, cell, jax, devs) -> dict:
    """Set-up, window and drain of one run of ``cell`` on ``devs``: every
    panel due in the window followed to its answer."""
    import numpy as np

    import repro
    from bench.lib import traffic as TR
    from bench.lib.meter import CompileMeter
    from bench.lib.serve import STREAM_LEN, Driver

    cfg, traffic, w = cell["config"], cell["traffic"], cell["workload"]
    chips = int(w["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program in the cache, however fast it compiled: each run of a
    # cell is a new process and should find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    meter = CompileMeter(jax)
    sharding, mesh = placement(jax, devs, chips)

    t = time.perf_counter()
    cols = load_table(jax, cfg, args.seed, sharding)
    gen_s = time.perf_counter() - t
    seconds = float(args.seconds)
    if traffic["loop"] == "open":
        panels = TR.open_schedule(traffic, seconds, args.seed)
    else:
        streams = TR.closed_streams(traffic, args.seed, STREAM_LEN)
    drv = Driver(repro, jax, cfg, traffic, repro.as_source(cols), mesh)
    loop = asyncio.new_event_loop()
    t = time.perf_counter()
    loop.run_until_complete(drv.warm())
    warm_s = time.perf_counter() - t
    if drv.scan.rounds != cfg["rounds"]:
        raise RuntimeError(f"the scan runs {drv.scan.rounds} rounds, the "
                           f"configuration {cfg['rounds']}")
    setup_s = time.perf_counter() - T_START
    at_setup = meter.snapshot()
    log(f"setup cell={w['name']} rows={cfg['rows']} P={cfg['partitions']} "
        f"chips={chips} gen_s={gen_s:.3f} warm_s={warm_s:.3f} "
        f"setup_s={setup_s:.3f} compile_cache={cache} {at_setup}")

    if traffic["loop"] == "open":
        coro = drv.open_loop(panels, seconds)
    else:
        coro = drv.closed_loop(streams, seconds)
    tracer = None
    if args.trace:
        from bench.lib.tracing import WindowTracer

        tracer = WindowTracer(jax, OUT / f"trace-{w['name']}-{args.seed}",
                              seconds, TRACE_SECONDS)

    async def window():
        task = asyncio.ensure_future(coro)
        if tracer is not None:
            await tracer.run()
        t0, tasks, subm = await task
        t_close = time.perf_counter()
        in_window = meter.snapshot()
        await drv.drain(tasks)
        return t0, t_close, subm, in_window

    t0, t_close, submitted, in_window = loop.run_until_complete(window())
    drain_s = time.perf_counter() - t_close
    loop.close()
    built = {k: in_window[k] - at_setup[k] for k in at_setup}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    failed = [p for p in submitted if "answers" not in p]
    ans = answers_of(submitted)
    mismatched = sum(len(a["rounds"]) != a["rounds_witnessed"] for a in ans)
    late = [p["submitted"] - p["due"] for p in submitted if "due" in p]
    tte = sorted(p["resolved"] - p["due"] for p in submitted
                 if "due" in p and "resolved" in p)
    log(f"window seconds={seconds} panels={len(submitted)} "
        f"failed={len(failed)} steps={drv.scan.steps_done} "
        f"drain_s={drain_s:.3f} programs_built_in_window="
        f"{built['compiles'] + built['cache_hits']} "
        f"generator_late_p50_ms={1e3 * np.median(late) if late else 0:.3f} "
        f"generator_late_max_ms={1e3 * max(late) if late else 0:.3f} "
        f"bank_slots={ {n: b.K for n, b in drv.scan.banks.items()} } "
        f"tte_p85_p90_p95_s={[float(np.percentile(tte, q)) for q in (85, 90, 95)] if tte else None}")
    record = {"config": cfg, "traffic": traffic, "workload": w,
              "seconds": seconds, "panels": submitted, "setup_s": setup_s,
              "chips": chips, "device_kind": devs[0].device_kind}
    return {"record": record, "cols": cols, "answers": ans,
            "confidence": drv.confidence, "failed": len(failed) + mismatched,
            "peak": int(peak),
            "trace": tracer.read() if tracer is not None else None}


def reference_of(run_: dict, precision: str = "float64"):
    """The reference over the run's table, cut at its queries' bounds."""
    from bench.lib import reference
    from bench.lib import traffic as TR

    cfg, panels = run_["record"]["config"], run_["record"]["panels"]
    used = {s["expr"] for p in panels for s in p["slots"]}
    return reference.build(cfg, TR.boundaries(panels),
                           fetch_round(run_["cols"], cfg), cfg["rounds"],
                           precision=precision, used=used)


def run(args, cell, jax, devs) -> int:
    """One run of ``cell`` on ``devs``; prints the result line."""
    from bench.lib import check, spec
    from bench.lib import trace as TRC

    r = execute(args, cell, jax, devs)
    record, trace = r["record"], r["trace"]
    t = time.perf_counter()
    numbers = check.compare(r["answers"], reference_of(r), r["confidence"])
    ref_s = time.perf_counter() - t
    limits = check.limits_for(record["config"], record["traffic"])
    correct = check.verdict(numbers, limits, r["failed"])
    metrics = {}
    for m in cell["per_layer"] if args.trace else cell["end_to_end"]:
        v = spec.metric_reader(m["name"])(record, trace)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": record["chips"], "memory_peak_bytes": r["peak"]}
    out = {"correct": bool(correct), "attempted": len(record["panels"]),
           "failed": r["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = TRC.busy_s(trace)
        device["window_s"] = TRC.window_s(trace)
        out["breakdown"] = TRC.breakdown(trace)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    checks["failed_panels"] = {"value": r["failed"], "limit": 0}
    out["checks"] = checks
    log(f"reference_s={ref_s:.3f} ci_max_z={numbers['ci_max_z']:.4f} "
        "(reported, not compared)")
    for k, c in checks.items():
        log(f"check {k} value={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(finite(out)), flush=True)
    return 0


def finite(obj):
    """The result line in strict JSON: a number that is not finite (a
    panel never answered, an answer that is NaN) prints as the largest
    double, which fails every limit and bound."""
    import math

    if isinstance(obj, float) and not math.isfinite(obj):
        return -sys.float_info.max if obj < 0 else sys.float_info.max
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (SRC / "repro").is_dir():
        log(f"bench: the program's source tree {SRC} is missing")
        return 2
    sys.path.insert(0, str(SRC))
    from bench.lib import spec

    cell = spec.resolve(args.workload, ROOT)
    if args.seed < 0:
        log("bench: --seed must be >= 0")
        return 2
    # the TPU runtime's logs stay in the checkout, not in a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))
    import jax

    os.makedirs(OUT, exist_ok=True)
    devs = devices_or_exit(jax, int(cell["workload"]["chips"]))
    try:
        return run(args, cell, jax, devs)
    finally:
        shutil.rmtree(OUT / f"trace-{args.workload}-{args.seed}",
                      ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
