#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <a,b,...>

Not part of a benchmark run.  For each seed it makes one run of the cell
as ``bench/run.py`` does, then prints one JSON line with two sets of the
numbers ``correct`` compares:

- ``program``: the program's answers against the float64 reference (the
  lower readings);
- ``control``: the control put in the program's place, i.e. the reference
  computed one precision below the configuration's float32 (columns
  rounded to bfloat16, expressions in float32), answering the same queries
  over the same rounds (the upper readings).

Exits non-zero, printing nothing, where ``bench/run.py`` would.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as R  # noqa: E402


def control_answers(answers, ctl, confidence):
    out = []
    for a in answers:
        est, hw = ctl.answer(a["slot"], a["rounds"], confidence)
        out.append(dict(a, estimate=est, half_width=hw))
    return out


def readings(args, cell, jax, devs) -> dict:
    from bench.lib import check

    r = R.execute(args, cell, jax, devs)
    ref = R.reference_of(r)
    ctl = R.reference_of(r, precision="bfloat16")
    conf = r["confidence"]
    return {"seed": args.seed, "answers": len(r["answers"]),
            "failed": r["failed"],
            "program": check.compare(r["answers"], ref, conf),
            "control": check.compare(control_answers(r["answers"], ctl, conf),
                                     ref, conf)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args(argv)
    if not (R.SRC / "repro").is_dir():
        R.log(f"control: the program's source tree {R.SRC} is missing")
        return 2
    sys.path.insert(0, str(R.SRC))
    os.environ.setdefault("TPU_LOG_DIR", str(R.OUT / "tpu_logs"))
    os.makedirs(R.OUT, exist_ok=True)
    import jax

    from bench.lib import spec

    for seed in (int(s) for s in a.seeds.split(",")):
        cell = spec.resolve(a.workload, R.ROOT)
        devs = R.devices_or_exit(jax, int(cell["workload"]["chips"]))
        args = R.parse_args(["--workload", a.workload, "--seed", str(seed),
                             "--seconds", str(a.seconds)])
        print(json.dumps(readings(args, cell, jax, devs)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
