"""Accelerator selection: ``get_accelerator()`` / ``set_accelerator()``.

Analog of ``accelerator/real_accelerator.py:51``.  Selection order:
1. ``DS_ACCELERATOR`` env var ("tpu" | "gpu" | "cpu") — explicit override,
   mirroring the reference's env-based selection.
2. JAX's own default backend (the reference probes module imports;
   here JAX's platform selection plays that role — a TPU that fails to
   initialise raises there instead of selecting the CPU).
"""

from __future__ import annotations

import os
from typing import Optional

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator
from deepspeed_tpu.utils.logging import logger

_ACCELERATOR: Optional[DeepSpeedAccelerator] = None

_KNOWN = ("tpu", "gpu", "cuda", "cpu")


def _probe_platform() -> str:
    import jax

    backend = jax.default_backend()
    return backend if backend in ("tpu", "gpu") else "cpu"


def _make(name: str) -> DeepSpeedAccelerator:
    if name == "cpu":
        from deepspeed_tpu.accelerator.cpu_accelerator import CPU_Accelerator

        return CPU_Accelerator()
    from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator

    return TPU_Accelerator(platform="gpu" if name in ("gpu", "cuda") else "tpu")


def get_accelerator() -> DeepSpeedAccelerator:
    global _ACCELERATOR
    if _ACCELERATOR is None:
        name = os.environ.get("DS_ACCELERATOR", "").lower()
        if name and name not in _KNOWN:
            raise ValueError(f"DS_ACCELERATOR={name!r} not in {_KNOWN}")
        if not name:
            name = _probe_platform()
        _ACCELERATOR = _make(name)
        logger.info(f"accelerator: {_ACCELERATOR.device_name()} "
                    f"(comm backend {_ACCELERATOR.communication_backend_name()})")
    return _ACCELERATOR


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    global _ACCELERATOR
    _ACCELERATOR = accel


def is_current_accelerator_supported() -> bool:
    return get_accelerator().device_name().split(":")[0] in _KNOWN
