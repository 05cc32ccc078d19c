"""The package's one platform predicate and its compile-cache placement.

``on_tpu()`` is the only place the package asks which platform it runs
on; every kernel dispatch gate calls it.  It lets a failing
``jax.devices()`` raise: a run that cannot reach its device stops there
instead of finishing on a fallback path.

``setup_compile_cache()`` places JAX's persistent compilation cache.
The cache key includes the directory, so the directory never moves:
``$JAX_COMPILATION_CACHE_DIR`` where the caller set it (JAX reads that
itself — nothing is set in code), else ``<checkout>/.jax_cache``.

``device_facts_from_child()`` is for the parents that start children
which need the chip (the autotuner's isolated trials with
``isolate_trials=True``).  A process that has initialised a JAX backend holds
the chip until it exits, so such a parent never asks JAX itself.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax

_CHECKOUT = Path(__file__).resolve().parents[2]


def on_tpu() -> bool:
    """Whether the default backend's devices are TPUs."""
    return jax.devices()[0].platform == "tpu"


def setup_compile_cache() -> str:
    """Place the persistent compile cache; returns its directory.  Call
    before the first compile.  Child processes inherit the directory
    through the environment."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
        jax.config.update("jax_compilation_cache_dir", path)
    return path


_FACTS_SNIPPET = (
    "import json, jax; d = jax.devices(); print(json.dumps({"
    "'platform': d[0].platform, 'device_kind': d[0].device_kind, "
    "'n_devices': len(d), "
    "'bytes_limit': (d[0].memory_stats() or {}).get('bytes_limit', 0)}))")


def device_facts_from_child(timeout_s: float = 300.0) -> dict:
    """``platform``, ``device_kind``, ``n_devices`` and device 0's
    ``bytes_limit``, asked of a short-lived child that frees the chip
    when it exits.  Raises when the child cannot reach its device."""
    out = subprocess.run([sys.executable, "-c", _FACTS_SNIPPET],
                         capture_output=True, text=True, timeout=timeout_s,
                         check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])
