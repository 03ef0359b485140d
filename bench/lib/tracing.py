"""Profiles a slice of the window, for ``--trace 1`` runs."""
from __future__ import annotations

import asyncio
import glob
import shutil

from bench.lib import trace as TRC


class WindowTracer:
    def __init__(self, jax, out_dir, seconds: float, span: float):
        self.jax = jax
        self.dir = str(out_dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        self.span = min(span, seconds)
        self.start = seconds - self.span

    async def run(self) -> None:
        """Trace the last ``span`` seconds of the window, under one
        ``bench.window`` span that marks the traced window on the trace's
        own clock.  Stopping the profiler holds the event loop for tens of
        seconds on a v5e host; at the window's close every panel is
        already submitted, so the hold delays answers but queues no
        arrivals.  The Python tracer stays off: it would record every
        call of the host."""
        prof = self.jax.profiler
        await asyncio.sleep(self.start)
        opts = prof.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        prof.start_trace(self.dir, profiler_options=opts)
        try:
            with prof.TraceAnnotation(TRC.WINDOW_SPAN):
                await asyncio.sleep(self.span)
        finally:
            prof.stop_trace()

    def read(self) -> dict:
        paths = glob.glob(f"{self.dir}/plugins/profile/*/*.xplane.pb")
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace under {self.dir}, "
                               f"found {paths}")
        return TRC.read_xplane(paths[0])
