"""Device tracing — the TPU analog of the reference's NVTX ranges and
pytorch-profiler integration (``deepspeed/utils/nvtx.py instrument_w_nvtx``,
``accelerator range_push/range_pop``, ``docs/_tutorials/pytorch-profiler.md``).

On TPU the profiler artifact is an XPlane trace viewable in
TensorBoard/XProf/Perfetto: ``jax.profiler.start_trace(logdir)`` captures
host + device timelines, ``TraceAnnotation`` plays the role of
``nvtx.range_push`` (named host spans that bracket the device ops they
dispatch), and ``StepTraceAnnotation`` marks training steps so the trace
viewer groups per-step work.  The engine drives this from the
``"profiler"`` config block (see runtime/config.py ProfilerConfig);
:class:`TraceProfiler` is the standalone surface.
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax

from deepspeed_tpu.utils.logging import logger


def instrument_w_trace(func=None, *, name: Optional[str] = None):
    """Decorator: run the function under a named trace annotation (ref
    instrument_w_nvtx, utils/nvtx.py) — shows up as a host span in the
    XPlane trace when a capture is active; free otherwise."""

    def deco(f):
        label = name or getattr(f, "__qualname__", getattr(f, "__name__",
                                                           "fn"))

        @functools.wraps(f)
        def wrapped(*args, **kw):
            with jax.profiler.TraceAnnotation(label):
                return f(*args, **kw)

        return wrapped

    return deco(func) if func is not None else deco


CLOCK_ANCHOR = "dstpu.clock_anchor"


def write_clock_anchor(label: str = "") -> int:
    """Write a clock anchor into a running profiler capture and return
    the ``time.monotonic_ns()`` it carries.

    The anchor is a :class:`jax.profiler.TraceAnnotation` named
    :data:`CLOCK_ANCHOR` whose ``monotonic_ns`` argument is this process's
    monotonic clock at the instant the annotation begins.  The capture
    stamps the annotation on its own host clock, so one anchor gives the
    offset between a ``Tracer`` trace (``telemetry/tracing.py``, spans on
    ``time.monotonic()``) and the capture's host plane, and two (one when
    the capture starts, one when it stops) give the drift between them.
    The host plane is NOT on the device planes' clock: whoever reduces
    the capture measures that offset from the runtime's own events (see
    ``benchmark/lib/attribute.py``).  Free when no capture is running."""
    t = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(CLOCK_ANCHOR, monotonic_ns=t,
                                      label=label):
        pass
    return t


def range_push(msg: str) -> None:
    """Delegates to the accelerator's range stack (the single owner —
    a second independent stack here would let mixed push/pop pairs exit
    the wrong annotation).  Ref accelerator range_push,
    abstract_accelerator.py:190."""
    from deepspeed_tpu.accelerator import get_accelerator

    get_accelerator().range_push(msg)


def range_pop() -> None:
    """Pop the innermost accelerator range.  Unbalanced pops (empty
    stack) warn and no-op rather than raising — see
    ``abstract_accelerator.range_pop``."""
    from deepspeed_tpu.accelerator import get_accelerator

    get_accelerator().range_pop()


class TraceProfiler:
    """Windowed XPlane capture driven by step numbers.

    ``maybe_start/maybe_stop(step)`` bracket the configured
    [start_step, start_step + num_steps) window; ``step(n)`` returns a
    ``StepTraceAnnotation`` context for one train step (the TensorBoard
    profile plugin uses these markers for its per-step breakdown)."""

    def __init__(self, output_dir: str, start_step: int = 1,
                 num_steps: int = 3):
        self.output_dir = output_dir
        self.start_step = int(start_step)
        self.num_steps = int(num_steps)
        self.active = False
        self.done = False

    def maybe_start(self, step: int) -> None:
        if self.done or self.active or step < self.start_step:
            return
        if step >= self.start_step + self.num_steps:
            # resumed past the configured window (e.g. checkpoint reload
            # with start_step=1): capturing one arbitrary late step would
            # not be what the config asked for
            logger.warning(
                f"TraceProfiler: step {step} is past the configured window "
                f"[{self.start_step}, {self.start_step + self.num_steps}) "
                "— skipping capture")
            self.done = True
            return
        try:
            jax.profiler.start_trace(self.output_dir)
            self.active = True
            write_clock_anchor("start")
            logger.info(f"TraceProfiler: capturing steps "
                        f"[{step}, {step + self.num_steps}) → "
                        f"{self.output_dir}")
        except Exception as e:  # profiler already active elsewhere
            logger.warning(f"TraceProfiler: start_trace failed: {e}")
            self.done = True

    def step(self, step: int):
        if self.active:
            return jax.profiler.StepTraceAnnotation("train_batch",
                                                    step_num=step)
        import contextlib

        return contextlib.nullcontext()

    def maybe_stop(self, step: int) -> None:
        if not self.active or step < self.start_step + self.num_steps:
            return
        self.close()

    def close(self) -> None:
        """Flush an active capture (engine.destroy() calls this so a run
        that ends inside the window still writes its trace)."""
        if not self.active:
            return
        try:
            # drain the device before stopping: train_batch returns at
            # dispatch time, and stop_trace while the window's steps are
            # still executing truncates their device timeline.  TPU
            # streams are in-order, so a fresh op is ready only after
            # everything queued before it.
            import jax.numpy as _jnp

            jax.block_until_ready(_jnp.zeros(()))
            write_clock_anchor("stop")
            jax.profiler.stop_trace()
        finally:
            self.active = False
            self.done = True
        logger.info(f"TraceProfiler: trace written to {self.output_dir}")
