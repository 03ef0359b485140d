"""From the profiler's trace to the numbers the benchmark reports.

``read_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into plain
event lists; everything else works on those lists, so the reduction is
checked in the tests on a small recorded trace (``bench/testdata``).

Events are ``[name, start_ns, duration_ns]``.  Per device: ``ops`` (the
"XLA Ops" line) and ``modules`` (the "XLA Modules" line, one event per
program run).  ``host``: the host threads' annotated spans.  ``window``:
the benchmark's own ``bench.window`` span, on the trace's clock.
"""
from __future__ import annotations

import re

WINDOW_SPAN = "bench.window"
# host spans that say nothing about what the host was doing
_IGNORED_HOST = re.compile(
    r"^(bench\.window|ThreadpoolListener|futex|Release semaphore)")


def read_xplane(path: str) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    d[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                              for e in line.events]
            if d["ops"] or d["modules"]:
                devices[plane.name] = d
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events if e.duration_ns > 0)
    spans = [e for e in host if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    w = max(spans, key=lambda e: e[2])
    return {"devices": devices, "host": host, "window": [w[1], w[1] + w[2]]}


def _clip(events, lo, hi):
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def window_s(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def busy_s(trace: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    lo, hi = trace["window"]
    per = [sum(b - a for a, b in _union(
        (a, b) for _, a, b in _clip(d["ops"], lo, hi)))
        for d in trace["devices"].values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def module_time_s(trace: dict, pattern: str) -> tuple:
    """(runs, device seconds) of the programs whose name matches
    ``pattern``, both averaged over the devices."""
    lo, hi = trace["window"]
    rx = re.compile(pattern)
    runs = secs = 0.0
    for d in trace["devices"].values():
        ev = [e for e in _clip(d["modules"], lo, hi) if rx.search(e[0])]
        runs += len(ev)
        secs += sum(b - a for _, a, b in ev)
    n = max(1, len(trace["devices"]))
    return runs / n, secs / n / 1e9


def op_time_s(trace: dict, op_pattern: str, module_pattern: str) -> float:
    """Device seconds of the ops matching ``op_pattern`` that run inside a
    program matching ``module_pattern``, averaged over the devices."""
    lo, hi = trace["window"]
    rx_op, rx_mod = re.compile(op_pattern), re.compile(module_pattern)
    total = 0.0
    for d in trace["devices"].values():
        mods = _union((a, b) for n, a, b in _clip(d["modules"], lo, hi)
                      if rx_mod.search(n))
        ops = [(a, b) for n, a, b in _clip(d["ops"], lo, hi)
               if rx_op.search(short_op(n))]
        for a, b in ops:
            for m0, m1 in mods:
                total += max(0, min(b, m1) - max(a, m0))
    return total / max(1, len(trace["devices"])) / 1e9


def short_op(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0]
    return head.lstrip("%").strip()


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device ops that took most time (loop ops, which contain other
    ops, left out), and the longest idle gaps of the first device, each
    named by the host span that overlaps it most."""
    lo, hi = trace["window"]
    totals: dict = {}
    for d in trace["devices"].values():
        for n, a, b in _clip(d["ops"], lo, hi):
            op = short_op(n)
            if op.startswith(("while", "conditional", "call")):
                continue
            totals[op] = totals.get(op, 0) + (b - a)
    n_dev = max(1, len(trace["devices"]))
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    device_ops = [[k, v / n_dev / 1e9] for k, v in ops]
    gaps = []
    first = next(iter(trace["devices"].values()), None)
    if first is not None:
        busy = _union((a, b) for _, a, b in _clip(first["ops"], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    host = [e for e in _clip(trace["host"], lo, hi)
            if not _IGNORED_HOST.search(e[0])]
    idle = []
    for a, b in gaps:
        best, over = "no host span", 0
        for n, s, e in host:
            o = min(b, e) - max(a, s)
            if o > over:
                best, over = n, o
        idle.append([best, (b - a) / 1e9])
    return {"device_ops": device_ops, "idle_gaps": idle}
