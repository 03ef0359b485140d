import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""§Perf hillclimbing tool: compile ONE cell (optionally with config
overrides) and report the three roofline terms, peak memory, and the top
bytes/collective contributors — the hypothesis→change→measure loop's
measurement step.

    PYTHONPATH=src python -m repro.launch.hillclimb qwen3_32b train_4k \
        --set train_microbatches=4 --label mb4
"""
import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo_cost as HC
from repro.launch import mesh as M
from repro.launch.mesh import V5E, chip_peaks

OUT = Path(__file__).resolve().parents[3] / "experiments" / "hillclimb"


def coerce(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    if v in ("True", "False"):
        return v == "True"
    return v


def run(arch: str, shape: str, overrides: dict, label: str,
        mesh_kind: str = "single"):
    import repro.configs.base as CB
    from repro.launch import dryrun as DR

    cfg0 = CB.get_config(arch)
    cfg = dataclasses.replace(cfg0, **overrides) if overrides else cfg0

    # monkeypatch get_config so build_cell sees the overridden config
    def _patched_get_config(a):
        return cfg

    DR.get_config = _patched_get_config

    mesh = M.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    fn, args, shardings, donate, _ = DR.build_cell(arch, shape, mesh)
    shardings = jax.tree.map(lambda ps: NamedSharding(mesh, ps), shardings,
                             is_leaf=lambda x: isinstance(x, P))
    jax.set_mesh(mesh)
    with mesh:
        compiled = jax.jit(fn, in_shardings=shardings,
                           donate_argnums=donate).lower(*args).compile()
    dt = time.time() - t0
    mem = compiled.memory_analysis()
    hc = HC.HloCost(compiled.as_text())
    tot = hc.total()
    coll = sum(tot.collective_bytes.values())
    peaks = chip_peaks(V5E)   # the production mesh's chip
    t_c = tot.flops / peaks.flops_bf16
    t_m = tot.bytes / peaks.hbm_bw
    t_n = coll / peaks.ici_bw
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    rec = {
        "cell": f"{arch}.{shape}.{mesh_kind}", "label": label,
        "overrides": overrides,
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
        "dominant": max(("compute", t_c), ("memory", t_m),
                        ("collective", t_n), key=lambda kv: kv[1])[0],
        "flops_per_device": tot.flops, "bytes_per_device": tot.bytes,
        "collective_bytes": tot.collective_bytes,
        "peak_gb": peak / 1e9, "temp_gb": mem.temp_size_in_bytes / 1e9,
        "compile_s": round(dt, 1),
    }
    print(json.dumps(rec, indent=1))
    print("--- top bytes contributors (trip-scaled) ---")
    for k, v in hc.bytes_breakdown(12):
        print(f"  {v:.3e}  {k}")
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{arch}.{shape}.{label}.json").write_text(json.dumps(rec, indent=1))
    return rec


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("arch")
    ap.add_argument("shape")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value")
    ap.add_argument("--label", default="exp")
    ap.add_argument("--mesh", default="single")
    a = ap.parse_args()
    ov = {}
    for kv in getattr(a, "set"):
        k, v = kv.split("=", 1)
        ov[k] = coerce(v)
    run(a.arch, a.shape, ov, a.label, a.mesh)
