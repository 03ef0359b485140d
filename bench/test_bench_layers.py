"""``rows_witnessed_per_s``, the reading behind ``query_rows_per_s``, on
hand-made records: each step a panel witnessed inside the window counts
the step's rows once for that panel."""
import pytest

from bench.lib.layers import rows_witnessed_per_s

# 2^10 rows a step
CFG = {"rows": 1 << 16, "rounds": 64}
PER_STEP = 1 << 10


def _record(panels, seconds=10.0):
    return {"config": CFG, "seconds": seconds,
            "panels": [{"answers": [{"steps": s} for s in slots]}
                       for slots in panels]}


def test_a_step_several_slots_of_one_panel_answered_counts_once():
    # three slots (a Q1 panel's aggregates) log the same two steps
    slots = [[[1.0, 7], [1.1, 8]]] * 3
    assert rows_witnessed_per_s(_record([slots])) == 2 * PER_STEP / 10.0


@pytest.mark.parametrize("t, counted", [
    (-0.001, False), (0.0, True), (5.0, True), (10.0, True),
    (10.001, False)])
def test_only_steps_inside_the_window_count(t, counted):
    rec = _record([[[[t, 3], [5.0, 4]]]])
    assert rows_witnessed_per_s(rec) == (1 + counted) * PER_STEP / 10.0


def test_two_panels_that_saw_one_step_count_it_twice():
    one = [[[2.0, 11]]]
    assert rows_witnessed_per_s(_record([one, one])) == 2 * PER_STEP / 10.0
    # a panel that failed (no answers) witnessed nothing
    rec = _record([one])
    rec["panels"].append({"error": "cancelled"})
    assert rows_witnessed_per_s(rec) == PER_STEP / 10.0


@pytest.mark.parametrize("seconds", [1.0, 4.0, 51.0])
def test_the_reading_is_rows_over_window_seconds(seconds):
    steps = [[float(i) * seconds / 8, i] for i in range(8)]
    rec = _record([[steps], [steps[:3]]], seconds=seconds)
    assert rows_witnessed_per_s(rec) == pytest.approx(
        11 * PER_STEP / seconds, rel=1e-15)
