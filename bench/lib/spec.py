"""Finds a cell's configuration, traffic mix and metrics by name.

``BENCHMARK.json`` at the checkout's root lists the cells; each names a
configuration (``bench/configs/<file>``, by the ``configs`` entry) and a
traffic mix (``bench/traffic/<mix>.json``).  Every metric, end to end or
per layer, is read by ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, read from its files."""
    bm = load_benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if cell not in cells:
        raise KeyError(f"no workload {cell!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[cell]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return {
        "workload": w, "config": cfg, "traffic": traffic,
        "run_seconds": bm["run_seconds"],
        "end_to_end": [m for m in bm["end_to_end"] if _applies(m, cell)],
        "per_layer": [m for m in bm["per_layer"] if _applies(m, cell)],
    }


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(record, trace)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
