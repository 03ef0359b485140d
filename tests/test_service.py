"""Serving layer (DESIGN.md §11): shared-scan slot queries.

The load-bearing claims, each proven here:

  * a late joiner's estimates are bitwise identical to a fresh solo
    Session over exactly the chunk ranges it witnessed (both engines,
    scalar and group-bank members) — unbiased bounds at any attach round;
  * detach-then-reattach reuses the freed slot with zero new compiles
    (slot generations + in-jit ``jnp.where`` carry reset);
  * compile count under arrival/departure churn is bounded by capacity
    doublings, asserted from the audit catalog
    (``bounded_compiles_under_churn``);
  * the asyncio service converges queries via their stop rules, parks an
    idle scan after the grace period, and un-parks it on the next submit
    without losing the cursor.
"""
import asyncio
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.analysis import audit
from repro.core import gla as G
from repro.core import randomize
from repro.core import session as SN
from repro.core.spec import QuerySpec
from repro.data import tpch
from repro.serving import service as SV

ROWS = 8192
PARTS = 4
CHUNK = 128


@functools.lru_cache(maxsize=None)
def _packed(parts=PARTS):
    cols = tpch.generate_lineitem(ROWS, seed=1)
    data = {k: jnp.asarray(v) for k, v in cols.items()}
    shards = randomize.randomize_global(data, jax.random.key(9), parts)
    return randomize.pack_partitions(shards, chunk_len=CHUNK)


@functools.lru_cache(maxsize=None)
def _family():
    return G.SlotFamily(
        exprs={"q6": tpch.q6_func, "qty": lambda c: c["quantity"]},
        pred_cols=("shipdate", "discount"),
        groups={"rfls": (tpch.q1_group_small, 4)})


Q_SCALAR = G.SlotQuery("q6", {"shipdate": (420.0, 785.0)})
Q_LATE = G.SlotQuery("qty", {"discount": (0.02, 0.08)})
Q_GROUP = G.SlotQuery("q6", {"shipdate": (100.0, 2000.0)}, group="rfls")


def _bits(a, b):
    return np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _solo_estimates(fam, packed, rec, d_total, mesh=None):
    """A fresh Session over exactly the chunk ranges ``rec`` witnessed —
    the reference a slot's estimates must match bitwise."""
    view = SV.witnessed_view(packed, rec.witnessed)
    solo = SN.Session(
        QuerySpec(fam.solo_gla(rec.query, d_total=d_total),
                  rounds=len(rec.witnessed), emit="chunk"),
        view, mesh=mesh)
    prog = None
    for _ in range(len(rec.witnessed)):
        prog = solo.step()
    return prog.estimates


def test_degrade_rounds():
    assert SV._degrade_rounds(16, 8) == 8
    assert SV._degrade_rounds(12, 8) == 6
    assert SV._degrade_rounds(7, 8) == 7
    assert SV._degrade_rounds(7, 4) == 1


def test_late_join_bitwise_vmapped():
    fam, packed = _family(), _packed()
    scan = SV.SharedScan(fam, packed, rounds=8)
    r1 = scan.attach(Q_SCALAR)
    for _ in range(3):
        scan.step()                      # r1 witnesses rounds 0..2
    r2 = scan.attach(Q_LATE)             # joins at cursor 3
    for _ in range(4):
        scan.step()
    assert [lo for lo, _ in r2.witnessed] == [
        c * scan.width for c in (3, 4, 5, 6)]
    d_total = float(np.asarray(scan._d_total))
    se = _solo_estimates(fam, packed, r2, d_total)
    assert _bits(r2.estimate.estimate, se.estimate)
    assert _bits(r2.estimate.lower, se.lower)
    assert _bits(r2.estimate.upper, se.upper)
    # the early joiner completes its full pass one step later
    scan.step()
    assert r1.done and not r1.converged
    assert len(r1.witnessed) == scan.rounds
    assert r1.scanned == d_total


def test_late_join_group_member_bitwise_vmapped():
    fam, packed = _family(), _packed()
    scan = SV.SharedScan(fam, packed, rounds=8)
    scan.attach(Q_SCALAR)
    scan.step()
    rg = scan.attach(Q_GROUP)            # group bank opens mid-scan
    for _ in range(3):
        scan.step()
    d_total = float(np.asarray(scan._d_total))
    se = _solo_estimates(fam, packed, rg, d_total)
    assert _bits(rg.estimate.estimate, se.estimate)
    assert _bits(rg.estimate.lower, se.lower)
    assert _bits(rg.estimate.upper, se.upper)


def test_detach_reattach_reuses_slot_without_recompile():
    fam, packed = _family(), _packed()
    scan = SV.SharedScan(fam, packed, rounds=8)
    recs = [scan.attach(G.SlotQuery("qty", {"discount": (0.0, 0.02 + i / 100)}))
            for i in range(3)]
    scan.step()
    k0 = scan.banks["scalar"].K
    c0 = SV.serve_step_cache_sizes()["vmapped"]
    victim = recs[1]
    scan.detach(victim)
    renew = scan.attach(Q_LATE)
    assert renew.slot == victim.slot          # freed slot reclaimed...
    assert renew.generation == victim.generation + 1   # ...new generation
    scan.step()
    c1 = SV.serve_step_cache_sizes()["vmapped"]
    assert scan.banks["scalar"].K == k0       # no capacity change
    if c0 is not None:                        # membership churn at fixed K
        assert c1 - c0 == 0                   # compiles nothing new
    # the reclaimed carry restarted from zero: bitwise vs a solo Session
    # over the one round the new tenant witnessed
    d_total = float(np.asarray(scan._d_total))
    se = _solo_estimates(fam, packed, renew, d_total)
    assert _bits(renew.estimate.estimate, se.estimate)


def test_slot_params_are_not_views_of_host_rows():
    """A step is dispatched asynchronously, and the service detaches the
    queries it completed right after — rewriting the bank's host rows.
    The parameters the step holds must keep the rows they were built
    from (on CPU ``jnp.asarray`` may alias a NumPy buffer)."""
    fam, packed = _family(), _packed()
    scan = SV.SharedScan(fam, packed, rounds=8)
    recs = [scan.attach(G.SlotQuery("qty", {"discount": (0.0, 0.02 + i / 100)}))
            for i in range(8)]
    bank = scan.banks["scalar"]
    for rec in recs:
        p = bank.params()
        rows = (p.expr, p.lo, p.hi, p.fresh)
        before = [np.array(x) for x in rows]
        scan.detach(rec)
        bank.fresh[:] = ~bank.fresh
        for x, b in zip(rows, before):
            assert _bits(x, b)


def test_churn_bounded_compiles_certified_by_audit():
    """The acceptance gate: compile count under arrival/departure churn
    is bounded by capacity doublings — asserted from the audit catalog,
    not ad-hoc counters."""
    report = audit.audit_service(_family(), _packed(), rounds=4)
    churn = report.result("bounded_compiles_under_churn")
    assert not churn.failed, str(churn)
    if churn.data.get("skipped"):
        pytest.skip("jit cache introspection unavailable")
    assert churn.data["cache_miss_delta"] <= churn.data["budget"]
    assert churn.data["doublings"] >= 1
    assert churn.data["arrivals"] > churn.data["budget"]


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=6),
       st.integers(min_value=1, max_value=8),
       st.floats(min_value=0.0, max_value=1200.0))
def test_witnessed_coverage_never_below_reported_scanned(join, steps, lo):
    """Property: whatever round a query joins at and however long it
    runs, the tuples inside its witnessed chunk ranges are never fewer
    than the scan reported as scanned — the estimator's scale-up
    ``d_total / scanned`` never overstates coverage."""
    fam, packed = _family(), _packed()
    scan = SV.SharedScan(fam, packed, rounds=8)
    warm = scan.attach(Q_SCALAR)          # keeps the scan advancing
    for _ in range(join):
        scan.step()
        if warm.done:
            scan.detach(warm)
            warm = scan.attach(Q_SCALAR)
    rec = scan.attach(G.SlotQuery("qty", {"shipdate": (lo, lo + 365.0)}))
    for _ in range(steps):
        scan.step()
    ms = scan._ms
    covered = sum(float(ms[:, a:b].sum()) for a, b in rec.witnessed)
    assert len(rec.witnessed) == steps
    assert covered >= rec.scanned
    assert covered == pytest.approx(rec.scanned)
    assert rec.scanned <= steps * float(np.asarray(scan._d_total))


def test_service_converge_park_unpark():
    fam, packed = _family(), _packed()

    async def main():
        async with SV.OLAService(fam, rounds=8, grace_s=0.1) as svc:
            h1 = await svc.submit(
                QuerySpec(Q_SCALAR, stop=SN.rel_width(0.9)), packed)
            h2 = await svc.submit(Q_LATE, packed)
            o1 = await h1.result()
            o2 = await h2.result()
            # generous stop rule -> early convergence detaches q1 while
            # q2 rides the same scan to a full pass
            assert o1.converged and o1.rounds_witnessed < o2.rounds_witnessed
            assert not o2.converged
            assert o2.rounds_witnessed == svc.scan_for(packed).rounds
            steps_before = svc.scan_for(packed).steps_done
            await asyncio.sleep(0.4)
            assert svc.is_parked(packed)  # grace elapsed, drive task gone
            h3 = await svc.submit(Q_SCALAR, packed)   # un-park
            o3 = await h3.result()
            assert o3.rounds_witnessed > 0
            # same scan object kept its cursor across the park
            assert svc.scan_for(packed).steps_done > steps_before

    asyncio.run(main())


def test_service_rejects_bad_submissions():
    fam, packed = _family(), _packed()

    async def main():
        async with SV.OLAService(fam, rounds=8) as svc:
            with pytest.raises(TypeError):
                await svc.submit(tpch.q6_func, packed)
            with pytest.raises(TypeError):
                # QuerySpec around a non-slot GLA
                await svc.submit(
                    QuerySpec(G.make_sum_gla(
                        tpch.q6_func, tpch.q6_cond(tpch.Q6_LOW_WINDOW),
                        d_total=float(ROWS))),
                    packed)
            with pytest.raises(ValueError):
                # confidence is a compile-time static of the shared step
                await svc.submit(QuerySpec(Q_SCALAR, confidence=0.5), packed)

    asyncio.run(main())


def test_failed_scan_step_fails_its_queries(monkeypatch):
    """A scan step that raises resolves every query on that scan with the
    error — none waits forever — and the next submit gets a fresh scan."""
    fam, packed = _family(), _packed()

    def broken_step(self):
        raise RuntimeError("step failed")

    async def main():
        async with SV.OLAService(fam, rounds=8) as svc:
            with monkeypatch.context() as m:
                m.setattr(SV.SharedScan, "step", broken_step)
                hs = [await svc.submit(q, packed)
                      for q in (Q_SCALAR, Q_LATE)]
                for h in hs:
                    with pytest.raises(RuntimeError, match="step failed"):
                        await asyncio.wait_for(h.result(), 60)
            assert svc.scan_for(packed) is None
            out = await (await svc.submit(Q_SCALAR, packed)).result()
            assert out.rounds_witnessed == 8

    asyncio.run(main())


needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs 8 devices (fake-device lane)")


@needs8
def test_late_join_bitwise_sharded():
    fam = _family()
    packed = _packed(parts=8)
    mesh = jax.make_mesh((8,), ("data",))
    scan = SV.SharedScan(fam, packed, rounds=4, mesh=mesh)
    scan.attach(Q_SCALAR)
    scan.step()
    r2 = scan.attach(Q_LATE)
    rg = scan.attach(Q_GROUP)
    scan.step()
    scan.step()
    d_total = float(np.asarray(scan._d_total))
    for rec in (r2, rg):
        se = _solo_estimates(fam, packed, rec, d_total, mesh=mesh)
        assert _bits(rec.estimate.estimate, se.estimate)
        assert _bits(rec.estimate.lower, se.lower)
        assert _bits(rec.estimate.upper, se.upper)


@needs8
def test_churn_bounded_compiles_sharded():
    mesh = jax.make_mesh((8,), ("data",))
    report = audit.audit_service(_family(), _packed(parts=8), rounds=4,
                                 mesh=mesh)
    churn = report.result("bounded_compiles_under_churn")
    assert not churn.failed, str(churn)
