"""`dstpu_report` — environment/compatibility report.

Analog of the reference's ``ds_report`` (``deepspeed/env_report.py``):
prints framework version, JAX/backend versions, visible devices, memory,
and which optional native/host ops are usable (AIO library, host-offload
support), mirroring the reference's op-compatibility table.
"""

from __future__ import annotations

import importlib
import os
import shutil
import sys

GREEN_OK = "[OKAY]"
RED_NO = "[NO]"


def _try_version(mod: str) -> str:
    try:
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "unknown")
    except Exception:
        return "not installed"


def op_compat_report() -> "list[tuple[str, bool, str]]":
    """(op name, usable, detail) rows — analog of ds_report's op table."""
    rows = []
    # AIO: our csrc/aio host library
    try:
        from deepspeed_tpu.ops.aio import aio_available
        ok = aio_available()
        rows.append(("async_io (csrc/aio)", ok, "" if ok else "build csrc/aio"))
    except Exception as e:  # pragma: no cover
        rows.append(("async_io (csrc/aio)", False, str(e)))
    # Pallas flash attention
    try:
        importlib.import_module("jax.experimental.pallas.ops.tpu.flash_attention")
        rows.append(("pallas_flash_attention", True, ""))
    except Exception as e:
        rows.append(("pallas_flash_attention", False, str(e)))
    # Host offload (memory kinds)
    try:
        import jax
        kinds = sorted({m.kind for m in jax.devices()[0].addressable_memories()}) \
            if jax.devices() else []
        ok = "pinned_host" in kinds or "unpinned_host" in kinds
        rows.append(("host_offload (memory kinds)", ok, ",".join(kinds)))
    except Exception as e:  # pragma: no cover
        rows.append(("host_offload (memory kinds)", False, str(e)))
    # Native toolchain for building host ops
    for tool in ("g++", "cmake", "ninja"):
        rows.append((f"toolchain:{tool}", shutil.which(tool) is not None, ""))
    return rows


def report_lines() -> "list[str]":
    import deepspeed_tpu

    lines = []
    lines.append("-" * 66)
    lines.append("deepspeed_tpu environment report")
    lines.append("-" * 66)
    lines.append(f"deepspeed_tpu ......... {deepspeed_tpu.__version__}")
    lines.append(f"python ................ {sys.version.split()[0]}")
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy"):
        lines.append(f"{mod:<22} {_try_version(mod)}")
    try:
        import jax
        devs = jax.devices()
        lines.append(f"backend ............... {devs[0].platform if devs else 'none'}")
        lines.append(f"devices ............... {len(devs)}"
                     + (f" × {devs[0].device_kind}" if devs else ""))
        lines.append(f"process ............... {jax.process_index()}/{jax.process_count()}")
    except Exception as e:  # pragma: no cover
        lines.append(f"backend ............... error: {e}")
    lines.append("-" * 66)
    lines.append("op compatibility")
    for name, ok, detail in op_compat_report():
        status = GREEN_OK if ok else RED_NO
        lines.append(f"{name:<34} {status:<7} {detail}")
    lines.append("-" * 66)
    env_keys = [k for k in os.environ if k.startswith(("DSTPU_", "JAX_", "XLA_", "TPU_"))]
    for k in sorted(env_keys):
        lines.append(f"env {k}={os.environ[k]}")
    return lines


def main() -> int:
    print("\n".join(report_lines()))
    return 0


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
