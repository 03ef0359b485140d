"""The service's own profiler spans (DESIGN.md §11): a tiny OLAService run
under ``jax.profiler`` on the CPU, read back from its ``.xplane.pb``.

Each phase of the drive is one ``ola.*`` leaf span: one ``ola.slice`` per
step, one ``ola.params`` and one ``ola.dispatch`` per live bank per step,
one ``ola.stop_rule`` per ruled slot per step, one ``ola.queued`` per
submit (closed on attach, on cancel before attach and on a failed step),
and one ``ola.grow`` per capacity doubling.
"""
import asyncio
import collections
import functools
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import gla as G
from repro.core import randomize
from repro.core.spec import QuerySpec
from repro.data import tpch
from repro.serving import service as SV

ROUNDS = 8
EXECUTOR = ("ola.slice", "ola.params", "ola.dispatch", "ola.stop_rule")


@functools.lru_cache(maxsize=None)
def _packed():
    cols = tpch.generate_lineitem(8192, seed=1)
    data = {k: jnp.asarray(v) for k, v in cols.items()}
    shards = randomize.randomize_global(data, jax.random.key(9), 4)
    return randomize.pack_partitions(shards, chunk_len=128)


def _family():
    return G.SlotFamily(
        exprs={"q6": tpch.q6_func, "qty": lambda c: c["quantity"]},
        pred_cols=("shipdate", "discount"),
        groups={"rfls": (tpch.q1_group_small, 4)})


def _stop_at(n):
    return lambda prog: prog.round >= n


async def _workload(svc, packed):
    """Three queries attach in one turn, a fourth is cancelled before its
    attach; then two queries meet a failing step, one of them still
    queued when the step fails."""
    hs = [await svc.submit(QuerySpec(q, stop=stop), packed) for q, stop in (
        (G.SlotQuery("q6", {"shipdate": (420.0, 785.0)}), _stop_at(3)),
        (G.SlotQuery("qty", {"discount": (0.02, 0.08)}), None),
        (G.SlotQuery("q6", {"shipdate": (100.0, 2000.0)}, group="rfls"),
         _stop_at(2)))]
    cancelled = await svc.submit(G.SlotQuery("qty", {}), packed)
    svc.cancel(cancelled)
    outs = [await h.result() for h in (*hs, cancelled)]
    assert [o.rounds_witnessed for o in outs] == [3, ROUNDS, 2, 0]
    scan = svc.scan_for(packed)
    steps, doublings = scan.steps_done, sum(
        b.doublings for b in scan.banks.values())
    await asyncio.sleep(4 * svc.grace_s)        # the scan parks

    def broken_step(self):
        time.sleep(0.2)
        raise RuntimeError("step failed")

    scan.step = functools.partial(broken_step, scan)
    first = await svc.submit(G.SlotQuery("qty", {}), packed)
    await asyncio.sleep(0.05)                   # the step is in flight
    late = await svc.submit(G.SlotQuery("qty", {}), packed)
    for h in (first, late):
        with pytest.raises(RuntimeError, match="step failed"):
            await h.result()
        assert h._queued is None
    return steps, doublings


def _host_spans(path):
    spans = collections.defaultdict(list)
    pd = jax.profiler.ProfileData.from_file(str(path))
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ola."):
                    spans[e.name].append((int(e.start_ns),
                                          int(e.start_ns + e.duration_ns),
                                          dict(e.stats)))
    return spans


def test_service_records_one_leaf_span_per_phase(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0

    async def main():
        async with SV.OLAService(_family(), rounds=ROUNDS,
                                 grace_s=0.05) as svc:
            return await _workload(svc, _packed())

    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        steps, doublings = asyncio.run(main())
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(next(tmp_path.glob("plugins/profile/*/*.xplane.pb")))
    count = {k: len(v) for k, v in spans.items()}

    # scalar bank live all 8 steps, the group bank for its query's 2
    assert steps == ROUNDS and doublings == 1
    assert count["ola.slice"] == steps
    assert count["ola.params"] == count["ola.dispatch"] == ROUNDS + 2
    assert sorted(s[2]["query"] for s in spans["ola.stop_rule"]) == \
        [0, 0, 0, 2, 2]
    assert sorted(s[2]["query"] for s in spans["ola.queued"]) == \
        list(range(6))
    assert [s[2] for s in spans["ola.grow"]] == [{"bank": "scalar", "K": 2}]
    assert count["ola.finish"] == steps
    # three queries finished by their rounds; the cancel is one detach
    assert sum(s[2]["finished"] for s in spans["ola.finish"]) == 3
    assert sum(s[2]["detaches"] for s in spans["ola.apply"]) == 1
    assert count["ola.apply"] >= 2 and count["ola.idle"] >= 1
    assert {s[2]["K"] for s in spans["ola.dispatch"]} == {1, 2}
    # the group bank (G = 4) takes the one-hot partials; the scalar none
    assert {(s[2]["bank"], s[2]["partials"]) for s in spans["ola.dispatch"]} \
        == {("scalar", "none"), ("rfls", "onehot")}

    # leaves: the executor's spans never overlap one another, and no
    # loop-thread phase spans a step's start
    ex = sorted((a, b) for n in EXECUTOR for a, b, _ in spans[n])
    assert all(b0 <= a1 for (_, b0), (a1, _) in zip(ex, ex[1:]))
    starts = [a for a, _, _ in spans["ola.slice"]]
    for name in ("ola.apply", "ola.finish", "ola.grow"):
        for a, b, _ in spans[name]:
            assert not any(a < s < b for s in starts), name
