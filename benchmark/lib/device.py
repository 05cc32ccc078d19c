"""The device as JAX reports it, the compile cache, and compile counts."""

from __future__ import annotations

import os


class NoAccelerator(RuntimeError):
    """The cell cannot run here: no TPU, or fewer chips than it asks."""


def require_chips(chips: int) -> None:
    """Raise unless JAX's default backend is a TPU with exactly ``chips``
    devices whose kind is in the peaks table."""
    import jax

    from benchmark.lib.peaks import peaks_for

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no accelerator: jax.devices() reports "
                            f"platform {devs[0].platform!r}")
    if len(devs) != chips:
        raise NoAccelerator(f"the cell asks for {chips} chip(s), "
                            f"jax.devices() reports {len(devs)}")
    try:
        peaks_for(devs[0].device_kind)
    except KeyError as e:
        raise NoAccelerator(str(e)) from e


def facts() -> dict:
    import jax

    devs = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts compile requests and persistent-cache hits since it was
    made; ``mark()`` starts the window."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        self._mark = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> None:
        self._mark = self.requests

    @property
    def since_mark(self) -> int:
        return self.requests - self._mark


def setup_compile_cache() -> str:
    """The program's own placement (``$JAX_COMPILATION_CACHE_DIR`` if set,
    else ``<checkout>/.jax_cache``), and every program cached however
    quick its compile: a run after the first must compile nothing."""
    import jax

    from deepspeed_tpu.utils.platform import setup_compile_cache as place

    path = place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    os.makedirs(path, exist_ok=True)
    return path
