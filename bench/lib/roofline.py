"""Bytes a scan step must read, from the cell's shapes alone.

One run of a serving step program scans one round-slice: ``rows_per_step``
rows of every column its queries read.  The least any program can read is
each such column once, so the byte count below is a floor, and the
roofline share built on it cannot pass 100% however the program is
restructured.  Columns read by every bank: the predicate columns, the
columns the run's aggregate expressions use, and the row mask.  A group key
column is read only by its own bank and is left out, which keeps the
count a floor when banks run as separate programs.
"""
from __future__ import annotations

import numpy as np

from bench.lib import exprs


def step_columns(cfg: dict, used=None) -> list:
    """Columns a step must read for the expressions ``used`` (all of the
    configuration's if None)."""
    cols = set(cfg["predicates"]) | {"_mask"}
    for name, text in cfg["exprs"].items():
        if used is None or name in used:
            cols |= exprs.names(text)
    return sorted(cols)


def bytes_per_step(cfg: dict, used=None) -> int:
    rows = cfg["rows"] // cfg["rounds"]
    return rows * sum(np.dtype(cfg["columns"][c]).itemsize
                      for c in step_columns(cfg, used))


def roofline_pct(cfg: dict, step_runs: float, device_s: float,
                 hbm_bytes_per_s: float, chips: int, used=None):
    """Share of the HBM roofline, in percent, of ``step_runs`` step
    programs that took ``device_s`` seconds per chip; None when the trace
    holds none.  On several chips each reads its own partitions."""
    if step_runs <= 0 or device_s <= 0:
        return None
    least_s = (step_runs * bytes_per_step(cfg, used) / chips
               / hbm_bytes_per_s)
    return 100.0 * least_s / device_s
