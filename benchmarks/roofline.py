"""§Roofline: three-term roofline per (arch × shape × mesh) from the
dry-run's compiled artifacts (experiments/dryrun/*.json).

    compute term    = HLO_FLOPs / (chips × 197 TFLOP/s bf16)
    memory term     = HLO_bytes / (chips × 819 GB/s HBM)
    collective term = collective_bytes / (chips × 200 GB/s ICI)

HLO_FLOPs/bytes are loop-aware per-device numbers (repro/analysis/hlo_cost)
multiplied by chip count to match the spec's global convention — the two
normalizations cancel, so each term is per-device work / per-device rate.

MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (inference), N_active
counted from the param spec tree with MoE experts scaled to top-k and the
embedding gather excluded.  ratio = MODEL_FLOPS/HLO_FLOPs exposes
remat/attention/dispatch overhead (>1 would mean the compiled graph does
LESS than the model math — a bug; ≪1 means waste or heavy attention).

roofline_fraction = (MODEL_FLOPS/chips/peak) / dominant_term — the fraction
of the dominant-resource time that is irreducible model math; this is the
score §Perf iterates on.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

from repro.configs import get_config
from repro.launch.mesh import V5E, chip_peaks
from repro.launch.shapes import SHAPES

DRYRUN_DIR = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def n_active_params(cfg) -> float:
    """Matmul-visible active params from the spec tree (embedding gather
    excluded; MoE expert leaves scaled from E to top-k)."""
    import jax.numpy as jnp
    from repro.models import transformer as T
    from repro.models.spec import is_spec
    import jax

    specs = T.param_specs(cfg, dtype=jnp.bfloat16)
    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=is_spec)[0]:
        keys = [getattr(k, "key", str(k)) for k in path]
        if keys[-1] == "embed" and not cfg.tie_embeddings:
            continue                      # gather, not matmul
        if keys[-1] == "pos_embed" or keys[-1] == "pos":
            continue
        n = float(np.prod(leaf.shape))
        if "experts" in leaf.logical:
            e_dim = leaf.shape[leaf.logical.index("experts")]
            n = n / e_dim * max(cfg.experts_per_token, 1)
        total += n
    return total


def model_flops(cfg, shape_name: str) -> float:
    info = SHAPES[shape_name]
    n = n_active_params(cfg)
    if info["kind"] == "train":
        tokens = info["batch"] * info["seq"]
        return 6.0 * n * tokens
    if info["kind"] == "prefill":
        tokens = info["batch"] * info["seq"]
        return 2.0 * n * tokens
    return 2.0 * n * info["batch"]        # decode: one token per request


def load_cells(mesh_kind: str, dir_path=None):
    rows = []
    base = Path(dir_path) if dir_path else DRYRUN_DIR
    for f in sorted(base.glob(f"*.{mesh_kind}.json")):
        d = json.loads(f.read_text())
        rows.append(d)
    return rows


def analyze(mesh_kind: str = "single", dir_path=None):
    out = []
    for d in load_cells(mesh_kind, dir_path):
        arch, shape, _ = d["cell"].split(".")
        if d["status"] != "OK":
            out.append({"cell": d["cell"], "status": d["status"],
                        "reason": d.get("reason", "")})
            continue
        cfg = get_config(arch)
        chips = d["chips"]
        flops_dev = d["flops_per_device"]
        bytes_dev = d["bytes_per_device"]
        coll_dev = sum(d["collective_bytes_per_device"].values())
        peaks = chip_peaks(V5E)   # the production mesh's chip
        t_c = flops_dev / peaks.flops_bf16
        t_m = bytes_dev / peaks.hbm_bw
        t_n = coll_dev / peaks.ici_bw
        dom = max(("compute", t_c), ("memory", t_m), ("collective", t_n),
                  key=lambda kv: kv[1])
        mf = model_flops(cfg, shape)
        ratio = mf / (flops_dev * chips) if flops_dev else 0.0
        frac = (mf / chips / peaks.flops_bf16) / dom[1] if dom[1] > 0 else 0.0
        out.append({
            "cell": d["cell"], "status": "OK",
            "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
            "bottleneck": dom[0],
            "model_flops": mf, "hlo_flops_global": flops_dev * chips,
            "model_over_hlo": ratio,
            "roofline_fraction": frac,
            "peak_gb": d["memory"]["peak_estimate"] / 1e9,
        })
    return out


def main():
    mesh_kind = sys.argv[1] if len(sys.argv) > 1 else "single"
    dir_path = sys.argv[2] if len(sys.argv) > 2 else None
    rows = analyze(mesh_kind, dir_path)
    hdr = (f"{'cell':52s} {'compute_s':>10s} {'memory_s':>10s} "
           f"{'collect_s':>10s} {'bottleneck':>10s} {'MF/HLO':>7s} "
           f"{'roofl%':>7s} {'peakGB':>7s}")
    print(hdr)
    for r in rows:
        if r["status"] != "OK":
            print(f"{r['cell']:52s} {r['status']}: {r.get('reason','')[:60]}")
            continue
        print(f"{r['cell']:52s} {r['compute_s']:10.4f} {r['memory_s']:10.4f} "
              f"{r['collective_s']:10.4f} {r['bottleneck']:>10s} "
              f"{r['model_over_hlo']:7.3f} {100*r['roofline_fraction']:6.1f}% "
              f"{r['peak_gb']:7.1f}")
    if not rows:
        # no dry-run artifacts: nothing to report — do not emit an empty
        # BENCH file (benchmarks/check_schema.py requires non-empty rows)
        print("# no dry-run artifacts; BENCH_roofline.json not written")
        return
    try:
        from benchmarks import bench_io
    except ImportError:  # direct script invocation: benchmarks/ is sys.path[0]
        import bench_io
    named = [{"name": f"roofline_{r['cell']}", **r} for r in rows]
    path = bench_io.emit("roofline", named, extra={"mesh": mesh_kind})
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
