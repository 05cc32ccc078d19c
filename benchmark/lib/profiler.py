"""Trace a stretch of the window with the JAX profiler and reduce it."""

from __future__ import annotations

import shutil
from pathlib import Path

from benchmark.lib import trace


class DeviceTracer:
    """``start()`` ... ``stop()`` around a few steps or seconds;
    ``reduce()`` afterwards, outside the window.  The raw trace lives
    under ``<checkout>/.bench_trace/`` and is removed once reduced."""

    def __init__(self, root: Path, workload: str):
        self.dir = Path(root) / ".bench_trace" / workload
        self.started = self.stopped = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.started = True

    def stop(self) -> None:
        import jax

        if self.started and not self.stopped:
            jax.profiler.stop_trace()
            self.stopped = True

    def reduce(self):
        """The reduction, or None where the trace holds no device plane
        (a CPU rehearsal)."""
        if not self.stopped:
            return None
        try:
            planes = trace.load_device_planes(trace.find_xplane(str(self.dir)))
            return trace.reduce_planes(planes) if planes else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
