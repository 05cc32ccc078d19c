"""TPU accelerator backend (drives any PJRT device: tpu, gpu, cpu).

TPU-native analog of ``accelerator/cuda_accelerator.py``: device handles are
JAX devices, memory stats come from PJRT, RNG state is a functional PRNG key
held in a mutable slot for API parity with the torch-style surface.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator


class TPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self, platform: str = "tpu"):
        super().__init__()
        self._name = platform
        self._communication_backend_name = "xla"
        self._compile_backend = "jax.jit"
        self._current = 0
        self._seed = 0
        self._key = jax.random.PRNGKey(0)

    # -- identification -------------------------------------------------
    def is_synchronized_device(self) -> bool:
        return False  # async dispatch queue, like CUDA streams

    # -- devices --------------------------------------------------------
    def _devices(self):
        return jax.devices(self._name)

    def device(self, device_index: Optional[int] = None):
        devs = self._devices()
        return devs[device_index if device_index is not None else self._current]

    def device_count(self) -> int:
        return len(self._devices())

    def set_device(self, device_index: int) -> None:
        self._current = device_index

    def current_device(self) -> int:
        return self._current

    def is_available(self) -> bool:
        try:
            return len(jax.devices(self._name)) > 0
        except RuntimeError:
            return False

    # -- RNG ------------------------------------------------------------
    def random(self):
        return jax.random

    def set_rng_state(self, new_state, device_index: Optional[int] = None) -> None:
        self._key = jax.numpy.asarray(np.asarray(new_state, dtype=np.uint32))

    def get_rng_state(self, device_index: Optional[int] = None):
        return np.asarray(self._key)

    def manual_seed(self, seed: int) -> None:
        self._seed = int(seed)
        self._key = jax.random.PRNGKey(self._seed)

    def initial_seed(self) -> int:
        return self._seed

    def next_key(self):
        """Split and return a fresh PRNG key (functional RNG convenience)."""
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- sync -----------------------------------------------------------
    def synchronize(self, device_index: Optional[int] = None) -> None:
        for d in self._devices():
            try:
                d.synchronize_all_activity()
            except Exception:
                pass
        jax.effects_barrier()

    # -- memory ---------------------------------------------------------
    def memory_stats(self, device_index: Optional[int] = None) -> Dict[str, int]:
        try:
            stats = self.device(device_index).memory_stats() or {}
        except Exception:
            stats = {}
        return {k: int(v) for k, v in stats.items()}

    def total_memory(self, device_index: Optional[int] = None) -> int:
        stats = self.memory_stats(device_index)
        return stats.get("bytes_limit", stats.get("bytes_reservable_limit", 0))

    # -- dtypes ---------------------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True  # emulated via f32 accumulate on MXU

    # -- misc -----------------------------------------------------------
    def communication_backend_name(self) -> str:
        return self._communication_backend_name
