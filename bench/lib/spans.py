"""Per-layer readings from the service's own spans, for ``--trace 1`` runs.

``OLAService`` and ``SharedScan`` record each phase of their work as a
profiler annotation named ``ola.*`` (``src/repro/serving/service.py``).
They reach ``trace["host"]`` beside the benchmark's own spans, on the
device ops' clock.  One ``ola.slice`` span is one scan step, so a quantity
"per step" is divided by the ``ola.slice`` spans that start in the traced
window.  A program without these spans reads as nothing (``None``).

A span of host work can also hold a wait on the device: the first stop
rule of a step reads an estimate back and so waits for the step program,
and where the host runs ahead of a device-bound scan the runtime blocks
the next dispatch until the device catches up.  The host-work readings
therefore count a span only while the first device runs no operation:
the host time the device waited for.  A trace without a device line (the
CPU) subtracts nothing.
"""
from __future__ import annotations

import statistics

from bench.lib import trace as TRC

SLICE = "ola.slice"
PARAMS = "ola.params"
DISPATCH = "ola.dispatch"
STOP_RULE = "ola.stop_rule"
QUEUED = "ola.queued"
IDLE = "ola.idle"


def _spans(trace, name):
    """(start, end) of every host span called ``name``, unclipped."""
    return [(s, s + d) for n, s, d in trace["host"] if n == name]


def slice_starts(trace) -> list:
    """Start times of the steps that start in the window, in order."""
    lo, hi = trace["window"]
    return sorted(a for a, _ in _spans(trace, SLICE) if lo <= a < hi)


def device_idle(trace) -> list:
    """Sorted, disjoint (start, end) of the window in which the first
    device ran no operation."""
    lo, hi = trace["window"]
    first = next(iter(trace["devices"].values()), {"ops": []})
    busy = TRC._union((a, b) for _, a, b in TRC._clip(first["ops"], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _overlap_ns(xs, ys) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total = i = j = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_ms_per_step(trace, names):
    """Milliseconds per step inside the spans ``names`` while the device
    ran nothing."""
    if trace is None:
        return None
    steps = len(slice_starts(trace))
    if not steps:
        return None
    lo, hi = trace["window"]
    spans = TRC._union((a, b) for n, a, b in TRC._clip(trace["host"], lo, hi)
                       if n in names)
    return _overlap_ns(spans, device_idle(trace)) / steps / 1e6


def step_ms(record, trace):
    """Median milliseconds between consecutive step starts, leaving out
    the intervals in which the scan waited for work (``ola.idle``)."""
    if trace is None:
        return None
    starts = slice_starts(trace)
    idle = _spans(trace, IDLE)
    gaps = [b - a for a, b in zip(starts, starts[1:])
            if not any(s < b and e > a for s, e in idle)]
    return statistics.median(gaps) / 1e6 if gaps else None


def dispatch_ms(record, trace):
    """Host milliseconds per step the device waited for while the scan
    sliced the round, built each bank's slot parameters and dispatched
    its step program."""
    return idle_ms_per_step(trace, (SLICE, PARAMS, DISPATCH))


def stop_rule_ms(record, trace):
    """Host milliseconds per step the device waited for while the slots'
    stop rules ran (their wait for the step program left out)."""
    return idle_ms_per_step(trace, (STOP_RULE,))


def queue_wait_ms(record, trace):
    """Mean milliseconds from a submit to its attach, over the queries
    whose wait ended in the window."""
    if trace is None:
        return None
    lo, hi = trace["window"]
    waits = [b - a for a, b in _spans(trace, QUEUED) if lo <= b <= hi]
    return sum(waits) / len(waits) / 1e6 if waits else None
