"""``correct`` comes out false when the timed path is broken, and the
control fails the comparison that sound runs pass (CPU, small table).

Each case runs the whole benchmark but the look for a chip, in a process
of its own (``testdata/drive_cpu.py``), so the fault planted and the
virtual devices stay out of the test process.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SMALL = {"rows": 1 << 20, "seconds": 3, "seed": 2**31 + 99}
DASH = dict(SMALL, cell="tpch1c-dash",
            traffic={"rate_per_s": 4, "slots_warm": {"scalar": 8}})
REPORT4 = dict(SMALL, cell="tpch1c-dash", mix="report",
               config="bench/configs/tpch-lineitem-4chip.json",
               traffic={"clients": 2, "slots_warm": {"scalar": 2, "rfls": 8}})


def drive(tmp_path, opts, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run(
        [sys.executable, str(BENCH / "testdata" / "drive_cpu.py"),
         json.dumps({"root": str(ROOT), **opts})],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(tmp_path):
    res = drive(tmp_path, DASH)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["checks"]) == {"answer_rel_err", "halfwidth_rel_err",
                                  "failed_panels"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_broken_step_is_not_correct(tmp_path, fault):
    res = drive(tmp_path, dict(DASH, fault=fault))
    assert res["correct"] is False
    assert res["checks"]["answer_rel_err"]["value"] > \
        res["checks"]["answer_rel_err"]["limit"]


@pytest.mark.parametrize("fault", [None, "no_exchange"])
def test_exchange_between_chips_left_out_is_not_correct(tmp_path, fault):
    res = drive(tmp_path, dict(REPORT4, fault=fault), devices=4)
    assert res["device"]["count"] == 4
    assert res["correct"] is (fault is None)


def test_control_fails_what_the_program_passes(tmp_path):
    r = drive(tmp_path, dict(DASH, control=True))
    limits = json.loads((BENCH / "configs" / "tpch-lineitem-1chip.json")
                        .read_text())["limits"]
    assert all(r["program"][k] <= v for k, v in limits.items())
    assert any(r["control"][k] > v for k, v in limits.items())
