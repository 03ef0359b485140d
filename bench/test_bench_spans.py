"""The per-layer readings of the service's own ``ola.*`` spans, on
hand-made events, and on a small traced run of the whole benchmark on the
CPU (``testdata/drive_cpu.py``)."""
import pytest

from bench.lib import spans as SP
from bench.lib import trace as TRC
from bench.test_bench_checks import DASH, SMALL, drive


def _trace(host, ops=(), window=(0, 1000)):
    return {"window": list(window), "host": [list(e) for e in host],
            "devices": {"a": {"ops": [list(o) for o in ops],
                              "modules": []}}}


# two whole steps in the window and one that starts before it
STEPS = [
    ["ola.slice", -30, 10], ["ola.params", -20, 5],
    ["ola.dispatch", -15, 10], ["ola.stop_rule", -5, 25],
    ["ola.slice", 100, 10], ["ola.params", 110, 10],
    ["ola.dispatch", 120, 30], ["ola.stop_rule", 150, 40],
    ["ola.stop_rule", 190, 10],
    ["ola.slice", 300, 10], ["ola.params", 310, 10],
    ["ola.dispatch", 320, 30], ["ola.stop_rule", 350, 50],
]


def test_phases_are_counted_per_step_started_in_the_window():
    t = _trace(STEPS)
    assert SP.slice_starts(t) == [100, 300]
    # (10 + 10 + 30) * 2 ns over 2 steps; the earlier step's tails are
    # clipped off at the window's start
    assert SP.dispatch_ms(None, t) == pytest.approx(50e-6)
    # 20 (clipped) + 40 + 10 + 50 over 2 steps
    assert SP.stop_rule_ms(None, t) == pytest.approx(60e-6)


def test_step_period_leaves_out_waits_for_work():
    starts = [["ola.slice", s, 10] for s in (0, 100, 200, 700, 800, 1000)]
    t = _trace([*starts, ["ola.idle", 250, 300]], window=(0, 2000))
    # 100, 100, (500 has the idle wait), 100, 200
    assert SP.step_ms(None, t) == pytest.approx(100e-6)
    assert SP.step_ms(None, _trace([["ola.slice", 5, 1]])) is None


def test_queue_wait_counts_the_waits_that_end_in_the_window():
    t = _trace([["ola.queued", -50, 80], ["ola.queued", 900, 50],
                ["ola.queued", 990, 40]])
    assert SP.queue_wait_ms(None, t) == pytest.approx(65e-6)


def test_a_program_without_spans_reads_as_nothing():
    t = _trace([["bench.window", 0, 1000], ["bench.stop_rule", 10, 5]],
               ops=[["%fusion.1 = f32[] fusion(x)", 0, 10]])
    for read in (SP.step_ms, SP.dispatch_ms, SP.stop_rule_ms,
                 SP.queue_wait_ms):
        assert read(None, t) is None
        assert read(None, None) is None


def test_idle_gap_under_a_stop_rule_is_named_by_it():
    """The first stop rule of a step waits on the device and the device
    then sits idle until the next dispatch: ``breakdown`` names that gap
    by the program's ``ola.stop_rule``, not by the benchmark's own rule
    nested inside it."""
    host = [["bench.window", 0, 1000],
            ["ola.dispatch", 0, 20], ["ola.stop_rule", 20, 300],
            ["bench.stop_rule", 21, 298], ["ola.slice", 330, 20],
            ["ola.dispatch", 350, 50], ["ola.queued", 150, 180]]
    ops = [["%fusion.1 = f32[] fusion(x)", 20, 80],
           ["%fusion.1 = f32[] fusion(x)", 400, 600]]
    t = _trace(host, ops)
    gaps = TRC.breakdown(t)["idle_gaps"]
    assert gaps[0] == ["ola.stop_rule", pytest.approx(300e-9)]
    # one step; the rule's wait on the program, [20, 100), is not its work
    assert SP.stop_rule_ms(None, t) == pytest.approx(220e-6)


def test_host_spans_count_only_while_the_device_waits():
    """A device-bound step: the host runs ahead, so the runtime blocks
    the next step's slice until the device catches up.  That wait
    overlaps device work and is no host work; the dispatch after it,
    with the device idle, is."""
    host = [["ola.slice", 0, 300], ["ola.params", 300, 20],
            ["ola.dispatch", 320, 40], ["ola.stop_rule", 360, 10],
            ["ola.slice", 500, 10]]
    ops = [["%fusion.1 = f32[] fusion(x)", 0, 290],
           ["%fusion.2 = f32[] fusion(x)", 350, 500]]
    t = _trace(host, ops)
    # idle [290, 350): 10 of the slice, 20 of the params, 30 of the
    # dispatch; the stop rule ran under the next program; two steps
    assert SP.device_idle(t) == [(290, 350), (850, 1000)]
    assert SP.dispatch_ms(None, t) == pytest.approx(30e-6)
    assert SP.stop_rule_ms(None, t) == 0


@pytest.mark.parametrize("opts,names", [
    (DASH, ["step_ms.dash", "dispatch_ms.dash", "stop_rule_ms.dash",
            "queue_wait_ms.dash"]),
    (dict(SMALL, cell="tpch1c-report",
          traffic={"clients": 2, "slots_warm": {"scalar": 2, "rfls": 4}}),
     ["dispatch_ms.report"]),
], ids=["dash", "report"])
def test_traced_run_reports_the_span_metrics(tmp_path, opts, names):
    res = drive(tmp_path, dict(opts, trace=1))
    assert res["correct"]
    for name in names:
        assert res["metrics"][name]["value"] > 0, name
