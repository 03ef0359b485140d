"""The one traffic generator: panels of slot queries drawn from a mix file.

A mix (``bench/traffic/<mix>.json``) names its loop, its rate or clients,
and its templates.  A template has a share, parameters drawn uniformly
(``{"int": [lo, hi]}`` inclusive, or ``{"choice": [...]}``), half-open
predicate bounds written as expressions over those parameters, an
optional group key, and its slots (one per aggregate expression).  A panel
is one draw of a template: its slots share the bounds and are submitted
together.

Every seed gets the same work in another order: each template's count is
fixed by its share, an open loop's gaps are the same set of exponential
quantiles, shuffled, and closed-loop clients step through one cycle of
templates.  Only the parameter draws and the order depend on the seed.
"""
from __future__ import annotations

import math

import numpy as np

from bench.lib import exprs


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def _counts(shares, n: int):
    """Largest-remainder split of ``n`` draws by ``shares``."""
    total = float(sum(shares))
    raw = [s / total * n for s in shares]
    out = [math.floor(r) for r in raw]
    rest = sorted(range(len(raw)), key=lambda i: out[i] - raw[i])
    for i in rest[: n - sum(out)]:
        out[i] += 1
    return out


def _draw(rng, spec):
    if "int" in spec:
        lo, hi = spec["int"]
        return int(rng.integers(lo, hi + 1))
    if "choice" in spec:
        return spec["choice"][int(rng.integers(len(spec["choice"])))]
    raise ValueError(f"unknown parameter kind {sorted(spec)}")


def make_panel(tpl: dict, rng) -> dict:
    """One draw of a template: its parameters and slots."""
    env = {k: _draw(rng, v) for k, v in sorted(tpl.get("params", {}).items())}
    ranges = {col: (float(exprs.parse(lo)(env)), float(exprs.parse(hi)(env)))
              for col, (lo, hi) in tpl["ranges"].items()}
    slots = [{"expr": s["expr"], "ranges": ranges, "group": tpl.get("group")}
             for s in tpl["slots"]]
    return {"template": tpl["name"], "params": env, "slots": slots}


def _ordered_templates(traffic: dict, n: int, rng):
    tpls = traffic["templates"]
    counts = _counts([t["share"] for t in tpls], n)
    order = [i for i, c in enumerate(counts) for _ in range(c)]
    return [tpls[i] for i in rng.permutation(order)]


def open_schedule(traffic: dict, seconds: float, seed: int):
    """Panels due in a window of ``seconds`` at the mix's rate, each with
    its ``due`` offset from the window's start."""
    rate = float(traffic["rate_per_s"])
    n = max(1, round(rate * seconds))
    rng = rng_for(seed)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (
        seconds / gaps.sum())
    panels = [make_panel(t, rng) for t in _ordered_templates(traffic, n, rng)]
    for p, d in zip(panels, due):
        p["due"] = float(d)
    return panels


def closed_streams(traffic: dict, seed: int, n: int):
    """The first ``n`` panels each closed-loop client submits, in order.

    The templates follow one cycle, as long as there are clients, with
    each template's share of its places, shuffled by the seed; client
    ``c``'s ``k``-th panel takes place ``c + k`` of the cycle.  Clients
    that keep pace therefore run the mix's exact shares at every moment,
    so no seed draws a window of cheap panels only.
    """
    clients = int(traffic["clients"])
    cycle = _ordered_templates(traffic, clients, rng_for(seed))
    out = []
    for c in range(clients):
        rng = rng_for(seed, 1 + c)
        out.append([make_panel(cycle[(c + k) % clients], rng)
                    for k in range(n)])
    return out


def boundaries(panels) -> dict:
    """Per predicate column, every bound any slot uses (float32 values, as
    the program compares them)."""
    out: dict = {}
    for p in panels:
        for s in p["slots"]:
            for col, (lo, hi) in s["ranges"].items():
                out.setdefault(col, set()).update(
                    (float(np.float32(lo)), float(np.float32(hi))))
    return {c: np.array(sorted(v)) for c, v in out.items()}
