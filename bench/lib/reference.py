"""The plain reference: exact aggregates of the stored table in float64.

It imports nothing of the program.  One pass over the table, a round of
the scan at a time, sums every aggregate expression and its square into
cells: (round, one bucket per predicate column, group).  The buckets are
cut at every bound the run's queries use, so each query's predicate keeps
or drops whole cells, and any query over any set of rounds is a sum of
cells.  From those sums it gives what the program should have answered:
the Horvitz-Thompson estimate over the rounds a query witnessed, and its
normal-approximation half-width.

``precision="bfloat16"`` is the control: the float32 columns rounded to
bfloat16, the expressions in float32, the sums in float64: the reference
put in the program's place one precision below the one the configuration
states.
"""
from __future__ import annotations

import os
import statistics
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.lib import exprs


class Reference:
    def __init__(self, cfg: dict, bounds: dict, rounds: int, *,
                 precision: str = "float64", used=None):
        if precision not in ("float64", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.preds = list(cfg["predicates"])
        self.bounds = {c: np.asarray(bounds.get(c, []), np.float64)
                       for c in self.preds}
        self.groups = dict(cfg.get("groups", {}))
        self.exprs = {n: exprs.parse(t) for n, t in cfg["exprs"].items()
                      if used is None or n in used}
        self.shape = ([len(self.bounds[c]) + 1 for c in self.preds]
                      + [int(g["num_groups"]) for g in self.groups.values()])
        cells = int(np.prod(self.shape))
        self.rounds = rounds
        self.sum = {n: np.zeros((rounds, cells)) for n in self.exprs}
        self.sumsq = {n: np.zeros((rounds, cells)) for n in self.exprs}
        self.rows = np.zeros(rounds)

    @property
    def d_total(self) -> float:
        return float(self.rows.sum())

    def _env(self, cols: dict) -> dict:
        if self.precision == "float64":
            return {k: v.astype(np.float64) if v.dtype.kind == "f" else v
                    for k, v in cols.items()}
        import ml_dtypes

        return {k: (v.astype(ml_dtypes.bfloat16).astype(np.float32)
                    if v.dtype.kind == "f" else v) for k, v in cols.items()}

    def _bucket(self, col: str, v: np.ndarray) -> np.ndarray:
        """Bucket of every value: the number of bounds at or below it."""
        b = self.bounds[col]
        if v.dtype.kind in "iu":
            lo, hi = int(v.min()), int(v.max())
            if hi - lo < (1 << 20):
                lut = np.searchsorted(b, np.arange(lo, hi + 1), side="right")
                return lut[v - lo]
        return np.searchsorted(b.astype(v.dtype), v, side="right")

    def add_round(self, r: int, cols: dict) -> None:
        """Fold one round's rows (``{column: 1-D array}``, ``_mask``
        included) into round ``r``'s cells."""
        live = np.asarray(cols["_mask"]) != 0
        idx = [self._bucket(c, self._env({c: cols[c]})[c]) for c in self.preds]
        env = self._env(cols)
        idx += [np.asarray(cols[g["column"]]) for g in self.groups.values()]
        key = np.ravel_multi_index(idx, self.shape)[live]
        cells = self.sum[next(iter(self.sum))].shape[1]
        n = int(live.sum())
        for name, fn in self.exprs.items():
            v = np.broadcast_to(np.asarray(fn(env)), live.shape)[live]
            v = v.astype(np.float64)
            self.sum[name][r] = np.bincount(key, v, minlength=cells)
            self.sumsq[name][r] = np.bincount(key, v * v, minlength=cells)
        self.rows[r] = n

    def rounds_all(self):
        return range(self.rounds)

    def _cells(self, slot: dict):
        """Index into the cell grid: the buckets each predicate keeps."""
        sl = []
        for c in self.preds:
            b = self.bounds[c]
            if c in slot["ranges"]:
                lo, hi = (float(np.float32(x)) for x in slot["ranges"][c])
                sl.append(slice(int(np.searchsorted(b, lo)) + 1,
                                int(np.searchsorted(b, hi)) + 1))
            else:
                sl.append(slice(None))
        return tuple(sl)

    def sums(self, slot: dict, rounds):
        """(sum, sum of squares, rows scanned) over ``rounds``; per group
        when the slot is grouped."""
        rounds = np.asarray(list(rounds), np.int64)
        out = []
        cut = (slice(None),) + self._cells(slot)
        for arr in (self.sum[slot["expr"]], self.sumsq[slot["expr"]]):
            # cut the slot's cells first (a view), then gather its rounds
            a = arr.reshape((self.rounds, *self.shape))[cut][rounds]
            a = a.sum(axis=tuple(range(1 + len(self.preds))))
            if slot.get("group") is None:
                a = a.sum()
            else:
                names = list(self.groups)
                keep = names.index(slot["group"])
                a = a.sum(axis=tuple(i for i in range(len(names))
                                     if i != keep))
            out.append(a)
        return out[0], out[1], float(self.rows[rounds].sum())

    def answer(self, slot: dict, rounds, confidence: float):
        """(estimate, half-width) the single estimator should report after
        scanning ``rounds``."""
        s_sum, s_sq, s = self.sums(slot, rounds)
        d = self.d_total
        est = d / max(s, 1.0) * s_sum
        if s < 2:
            return est, np.full_like(np.asarray(est, np.float64), np.inf)
        var = (d * max(d - s, 0.0) / (s * s * (s - 1))
               * np.maximum(s * s_sq - s_sum * s_sum, 0.0))
        z = statistics.NormalDist().inv_cdf((1 + confidence) / 2)
        return est, z * np.sqrt(var)


def build(cfg: dict, bounds: dict, fetch_round, rounds: int, *,
          precision: str = "float64", used=None) -> Reference:
    """One pass: ``fetch_round(r)`` returns round ``r``'s rows as host
    arrays ``{column: [P, width, L]}``.  ``used`` names the expressions
    the run's queries aggregate (all of the configuration's if None)."""
    ref = Reference(cfg, bounds, rounds, precision=precision, used=used)

    def one(r):
        cols = fetch_round(r)
        ref.add_round(r, {k: np.asarray(v).reshape(-1)
                          for k, v in cols.items()})

    # rounds write disjoint rows of the sums; NumPy releases the GIL in
    # the heavy calls, so threads overlap them and the device reads
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        list(ex.map(one, range(rounds)))
    return ref
