"""Drives the system under test through its serving entry.

Everything goes through ``OLAService.submit`` and ``QueryHandle.result``.
Each slot carries a stop rule of the benchmark's own that logs, at every
step the slot witnesses, the scan round it covered and when, and then
defers to the mix's rule (or to none, for a full pass).  Those logs give
the rounds each answer is checked over and the rows each panel witnessed
inside the window.
"""
from __future__ import annotations

import asyncio
import time

from bench.lib import family as FM

# A panel due in the window is followed to its answer for at most this
# long after the window closes; one still open then has failed.
DRAIN_S = 120.0
# closed-loop panels drawn per client: far more than a window can use
STREAM_LEN = 512


class Driver:
    def __init__(self, repro, jax, cfg: dict, traffic: dict, src, mesh):
        self.repro, self.jax = repro, jax
        self.cfg, self.traffic, self.src = cfg, traffic, src
        self.family = FM.build_family(repro, cfg)
        self.confidence = float(traffic["confidence"])
        self.service = repro.OLAService(self.family, rounds=cfg["rounds"],
                                        confidence=self.confidence,
                                        mesh=mesh)
        self.scan = None
        stop = traffic.get("stop")
        if stop is None:
            self.inner = None
        elif stop["rule"] == "rel_width":
            self.inner = repro.rel_width(float(stop["eps"]))
        else:
            raise ValueError(f"unknown stop rule {stop['rule']!r}")

    def _rule(self, inner, log):
        annotate = self.jax.profiler.TraceAnnotation

        def rule(prog):
            scan = self.scan
            log.append((time.perf_counter(), scan.cursor))
            if inner is None:
                return False
            with annotate("bench.stop_rule"):
                return bool(inner(prog))

        return rule

    async def _submit(self, slot, rule):
        spec = self.repro.QuerySpec(FM.slot_query(self.repro, slot),
                                    stop=rule, confidence=self.confidence)
        handle = await self.service.submit(spec, self.src)
        if self.scan is None:
            self.scan = self.service.scan_for(self.src)
        return handle

    # -- set-up ---------------------------------------------------------------

    async def warm(self) -> None:
        """Grow each bank to the mix's slot capacity and step it twice: the
        first step runs the program on fresh carries, the second on the
        carries a step returns, which is what every later step sees."""
        from bench.lib import traffic as TR

        rng = TR.rng_for(0)
        protos = {}
        for tpl in self.traffic["templates"]:
            slot = TR.make_panel(tpl, rng)["slots"][0]
            bank = self.family.bank_of(FM.slot_query(self.repro, slot))
            protos.setdefault(bank, slot)
        handles = []
        for bank, k in sorted(self.traffic["slots_warm"].items()):
            for _ in range(int(k)):
                handles.append(await self._submit(
                    protos[bank], lambda prog: prog.round >= 2))
        outs = await asyncio.gather(*(h.result() for h in handles))
        if any(o.rounds_witnessed != 2 for o in outs):
            raise RuntimeError("warm-up slots did not witness two steps")
        # Let the idle scan park before the window.  A submit that lands in
        # the same event-loop turn as the parking timeout is never served
        # (the drive task exits without looking at the queue), and the
        # host work between set-up and window would make that likely.
        deadline = time.perf_counter() + 10 * self.service.grace_s
        while not self.service.is_parked(self.src):
            if time.perf_counter() > deadline:
                raise RuntimeError("the idle scan did not park")
            await asyncio.sleep(self.service.grace_s)

    # -- the window -----------------------------------------------------------

    async def _panel(self, p: dict, t0: float) -> None:
        logs = [[] for _ in p["slots"]]
        p["submitted"] = time.perf_counter() - t0
        with self.jax.profiler.TraceAnnotation("bench.submit"):
            handles = [await self._submit(s, self._rule(self.inner, log))
                       for s, log in zip(p["slots"], logs)]
        try:
            outs = await asyncio.gather(*(h.result() for h in handles))
        except Exception as exc:  # noqa: BLE001 - the panel failed; say why
            p["error"] = repr(exc)
            return
        p["resolved"] = time.perf_counter() - t0
        p["answers"] = []
        for slot, log, o in zip(p["slots"], logs, outs):
            est = o.estimate
            p["answers"].append({
                "slot": slot,
                "rounds": [c % self.scan.rounds for _, c in log],
                "steps": [[t - t0, c] for t, c in log],
                "rounds_witnessed": o.rounds_witnessed,
                "converged": bool(o.converged),
                "estimate": est.estimate,
                "half_width": (est.upper - est.lower) / 2,
            })

    async def open_loop(self, panels, seconds: float) -> tuple:
        t0 = time.perf_counter()
        tasks = []
        for p in sorted(panels, key=lambda q: q["due"]):
            delay = t0 + p["due"] - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.create_task(self._panel(p, t0)))
            await asyncio.sleep(0)
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            await asyncio.sleep(rest)
        return t0, tasks, panels

    async def closed_loop(self, streams, seconds: float) -> tuple:
        t0 = time.perf_counter()
        t_end = t0 + seconds
        submitted = []

        async def client(stream):
            for p in stream:
                if time.perf_counter() >= t_end:
                    return
                submitted.append(p)
                await self._panel(p, t0)

        tasks = [asyncio.create_task(client(s)) for s in streams]
        rest = t_end - time.perf_counter()
        if rest > 0:
            await asyncio.sleep(rest)
        return t0, tasks, submitted

    async def drain(self, tasks) -> None:
        _, pending = await asyncio.wait(tasks, timeout=DRAIN_S) if tasks \
            else (set(), set())
        await self.service.close()
        for t in pending:
            t.cancel()
        for t in pending:
            try:
                await t
            except asyncio.CancelledError:
                pass
