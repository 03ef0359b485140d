"""The reduction from the profiler's trace to the per-layer numbers,
checked on a short trace recorded on a TPU v5e (``testdata``) and on
hand-made events."""
import json
from pathlib import Path

import pytest

from bench.lib import layers
from bench.lib import trace as TRC

RECORDED = json.loads((Path(__file__).parent / "testdata"
                       / "trace_v5e_serve_step.json").read_text())
CFG = json.loads((Path(__file__).parent / "configs"
                  / "tpch-lineitem-1chip.json").read_text())
RECORD = {"config": CFG, "chips": 1, "device_kind": "TPU v5 lite"}


def test_busy_is_the_union_of_op_intervals_in_the_window():
    t = {"window": [0, 100], "host": [],
         "devices": {"a": {"ops": [["x", -10, 30], ["y", 10, 10],
                                   ["z", 50, 70]], "modules": []},
                     "b": {"ops": [["x", 0, 100]], "modules": []}}}
    # a: [0, 20) and [50, 100) -> 70 ns; b: 100 ns; the mean over chips
    assert TRC.busy_s(t) == pytest.approx(85e-9)
    assert TRC.window_s(t) == pytest.approx(100e-9)


def test_idle_gaps_are_named_by_the_host_span_over_them():
    t = {"window": [0, 100],
         "host": [["bench.window", 0, 100], ["bench.stop_rule", 25, 20],
                  ["PjitFunction(serve_step_vmapped)", 70, 5]],
         "devices": {"a": {"ops": [["%fusion.1 = f32[8] fusion(x)", 0, 20],
                                   ["%while.2 = (s32[]) while(y)", 50, 10],
                                   ["%fusion.1 = f32[8] fusion(x)", 60, 8]],
                           "modules": []}}}
    b = TRC.breakdown(t)
    assert b["device_ops"] == [["fusion.1", pytest.approx(28e-9)]]
    # longest first: [68, 100) then [20, 50)
    assert b["idle_gaps"] == [
        ["PjitFunction(serve_step_vmapped)", pytest.approx(32e-9)],
        ["bench.stop_rule", pytest.approx(30e-9)]]


def test_recorded_chip_trace_reduces_to_sane_numbers():
    busy, window = TRC.busy_s(RECORDED), TRC.window_s(RECORDED)
    assert window == pytest.approx(2.5e-3)
    assert 0.9 * window < busy <= window
    runs, secs = TRC.module_time_s(RECORDED, layers.SERVE_STEP)
    assert runs == 2 and 0 < secs <= window
    roof = layers.scan_roofline(RECORD, RECORDED)
    assert 0 < roof <= 100
    idle = layers.device_idle_share(RECORD, RECORDED)
    assert idle == pytest.approx(100 * (1 - busy / window))
    assert layers.psum_share(RECORD, RECORDED) is None   # one chip
    ops = TRC.breakdown(RECORDED)["device_ops"]
    assert len(ops) == 10 and all(not n.startswith("while") for n, _ in ops)
    assert ops == sorted(ops, key=lambda kv: -kv[1])


def test_all_reduce_time_inside_serving_steps():
    t = {"window": [0, 100], "host": [],
         "devices": {d: {"modules": [["jit_serve_step_sharded(1)", 0, 50],
                                     ["jit_other(2)", 60, 40]],
                         "ops": [["%all-reduce.3 = f32[4] all-reduce(x)", 40,
                                  5],
                                 ["%all-reduce.4 = f32[4] all-reduce(x)", 70,
                                  5],
                                 ["%fusion.1 = f32[4] fusion(x)", 0, 40]]}
                     for d in ("a", "b")}}
    assert TRC.op_time_s(t, layers.ALL_REDUCE, layers.SERVE_STEP) == \
        pytest.approx(5e-9)
    assert layers.psum_share(RECORD, t) == pytest.approx(10.0)


def test_roofline_share_never_counts_more_bytes_than_one_read():
    # one step program per round-slice at exactly the HBM peak's time
    from bench.lib import roofline

    need = roofline.bytes_per_step(CFG) / 819e9
    assert roofline.roofline_pct(CFG, 1, need, 819e9, 1) == pytest.approx(
        100)
    assert roofline.step_columns(CFG) == sorted(set(CFG["columns"]) - {"rfls"})


def test_xplane_is_read_with_the_window_span(tmp_path):
    jax = pytest.importorskip("jax")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(TRC.WINDOW_SPAN):
        jax.numpy.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    t = TRC.read_xplane(str(path))
    assert t["window"][1] > t["window"][0]
