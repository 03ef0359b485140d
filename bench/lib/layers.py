"""Per-layer readings shared by the metric files of several cells."""
from __future__ import annotations

from bench.lib import peaks, roofline
from bench.lib import trace as TRC

# the serving step programs (``serve_step_vmapped`` / ``serve_step_sharded``)
SERVE_STEP = r"serve_step"
ALL_REDUCE = r"^all-reduce"


def scan_roofline(record, trace):
    """HBM roofline share of the serving step programs, in percent."""
    if trace is None:
        return None
    runs, secs = TRC.module_time_s(trace, SERVE_STEP)
    if runs <= 0:
        return None
    used = {s["expr"] for p in record.get("panels", ())
            for s in p["slots"]} or None
    return roofline.roofline_pct(
        record["config"], runs, secs,
        peaks.peaks(record["device_kind"]).hbm_bytes_per_s, record["chips"],
        used)


def device_idle_share(record, trace):
    """Percent of the traced window with no operation on the device."""
    if trace is None or not trace["devices"] or TRC.window_s(trace) <= 0:
        return None
    return 100.0 * (1.0 - TRC.busy_s(trace) / TRC.window_s(trace))


def psum_share(record, trace):
    """Percent of the serving step programs' device time in all-reduce."""
    if trace is None:
        return None
    _, secs = TRC.module_time_s(trace, SERVE_STEP)
    if secs <= 0:
        return None
    ar = TRC.op_time_s(trace, ALL_REDUCE, SERVE_STEP)
    return 100.0 * ar / secs if ar > 0 else None


def rows_witnessed_per_s(record, trace=None):
    """Rows the panels witnessed inside the window, per second: each step
    a panel's slots saw counts the step's rows once for the panel."""
    cfg = record["config"]
    per_step = cfg["rows"] / cfg["rounds"]
    seconds = record["seconds"]
    rows = 0.0
    for p in record["panels"]:
        steps = {}
        for a in p.get("answers", []):
            for t, c in a["steps"]:
                steps.setdefault(c, t)
        rows += per_step * sum(1 for t in steps.values() if 0 <= t <= seconds)
    return rows / seconds


def time_to_eps(record):
    """Seconds from each panel's due time (in a closed loop: when its
    client sent it) to its last slot's answer; a panel with no answer
    counts as infinitely late."""
    import math

    return [p["resolved"] - p.get("due", p["submitted"]) if "resolved" in p
            else math.inf for p in record["panels"]]
