"""Drives one benchmark run on the CPU at a small size, for the tests.

    python bench/testdata/drive_cpu.py '<json>'

The JSON gives the cell, the checkout root to read it from, the rows, the
seconds, the seed, overrides of the traffic mix, and optionally another
configuration file or traffic mix for the cell, a fault to plant in the
program, or ``"control": true``.  It skips the harness's look
for a chip and runs everything else: set-up, window, drain, reference and
comparison.  Prints the run's result line (or, for the control, its
readings line).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path


def plant(fault: str) -> None:
    """Break the timed path underneath the service."""
    import jax
    import jax.numpy as jnp

    from repro.core.uda import Estimate
    from repro.serving import service

    vm, sh = service.serve_step_vmapped, service.serve_step_sharded

    def wrap(step):
        def broken(family, bank, params, states, slice_shards, *a, **k):
            if fault == "half_batch":
                m = slice_shards["_mask"]
                slice_shards = dict(slice_shards,
                                    _mask=m.at[:, : m.shape[1] // 2].set(0))
            new, est = step(family, bank, params, states, slice_shards,
                            *a, **k)
            if fault == "state_unchanged":
                return states, est
            if fault == "answer_altered":
                e = est[0]
                est = (Estimate(e.estimate * jnp.float32(1 + 1e-4), e.lower,
                                e.upper, e.info), *est[1:])
            return new, est

        return broken

    if fault == "no_exchange":
        jax.lax.psum = lambda x, axis_name, **k: x
        return
    service.serve_step_vmapped = wrap(vm)
    service.serve_step_sharded = wrap(sh)


def main() -> int:
    opts = json.loads(sys.argv[1])
    root = Path(opts["root"])
    sys.path.insert(0, str(root))
    sys.path.insert(0, str(root / "src"))
    import jax

    from bench import control
    from bench import run as R
    from bench.lib import spec

    cell = spec.resolve(opts["cell"], root)
    if "config" in opts:   # another configuration file for the cell
        cell["config"] = json.loads((root / opts["config"]).read_text())
        cell["workload"]["chips"] = cell["config"]["chips"]
    if "mix" in opts:      # another traffic mix for the cell
        cell["traffic"] = json.loads(
            (root / "bench" / "traffic" / f"{opts['mix']}.json").read_text())
    cell["config"]["rows"] = int(opts["rows"])
    cell["traffic"].update(opts.get("traffic", {}))
    if opts.get("fault"):
        plant(opts["fault"])
    args = R.parse_args(["--workload", opts["cell"], "--seed",
                         str(opts["seed"]), "--seconds",
                         str(opts["seconds"]), "--trace",
                         str(opts.get("trace", 0))])
    devs = jax.devices()[: int(cell["workload"]["chips"])]
    if opts.get("control"):
        print(json.dumps(control.readings(args, cell, jax, devs)))
        return 0
    return R.run(args, cell, jax, devs)


if __name__ == "__main__":
    sys.exit(main())
