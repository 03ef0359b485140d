"""The comparison that decides ``correct``.

Every answered slot is held to the reference over the very rounds it
witnessed: its estimate (the exact aggregate after a full pass), and for a
slot that stopped early also its half-width.  The numbers compared are the
worst relative gaps over all answers of a run:

- ``answer_rel_err``: |estimate − reference| / |reference|, every entry of
  every slot (a group slot has one entry per group);
- ``halfwidth_rel_err``: |half-width − reference's| / reference's, every
  entry of every slot that stopped early.

A gap where the reference is 0 counts 0 if the answer is 0 too, else
infinity.  ``ci_max_z`` (how many half-widths the early answers lie from
the full-table answer) is reported beside them and not compared: the
control, which changes precision, cannot move it.
"""
from __future__ import annotations

import numpy as np


def rel_gap(got, want) -> float:
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    if got.shape != want.shape:
        return float("inf")
    if not np.all(np.isfinite(got)):
        return float("inf")
    gap = np.abs(got - want)
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.where(want == 0, np.where(gap == 0, 0.0, np.inf),
                       gap / np.abs(want))
    return float(rel.max()) if rel.size else 0.0


def compare(answers, ref, confidence: float) -> dict:
    """``answers``: one dict per answered slot with ``slot``, ``rounds``
    (the rounds it witnessed), ``estimate``, ``half_width`` and
    ``converged``."""
    worst_est = worst_hw = z_max = 0.0
    full = ref.rounds_all()
    for a in answers:
        want, want_hw = ref.answer(a["slot"], a["rounds"], confidence)
        worst_est = max(worst_est, rel_gap(a["estimate"], want))
        if a["converged"]:
            worst_hw = max(worst_hw, rel_gap(a["half_width"], want_hw))
            exact, _ = ref.answer(a["slot"], full, confidence)
            hw = np.asarray(a["half_width"], np.float64).ravel()
            gap = np.abs(np.asarray(a["estimate"], np.float64).ravel()
                         - np.asarray(exact, np.float64).ravel())
            with np.errstate(divide="ignore", invalid="ignore"):
                z = np.where(hw > 0, gap / hw, np.where(gap == 0, 0, np.inf))
            z_max = max(z_max, float(z.max()))
    return {"answer_rel_err": worst_est, "halfwidth_rel_err": worst_hw,
            "ci_max_z": z_max}


def limits_for(cfg: dict, traffic: dict) -> dict:
    """The configuration's limits that bind this mix: a mix without a stop
    rule answers only full passes, whose half-width is 0 for the program
    and the control alike, so that number is not compared there."""
    limits = dict(cfg["limits"])
    if traffic.get("stop") is None:
        limits.pop("halfwidth_rel_err", None)
    return limits


def verdict(numbers: dict, limits: dict, failed: int) -> bool:
    return failed == 0 and all(numbers[k] <= v for k, v in limits.items())
