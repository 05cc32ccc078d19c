"""StepRecord — the one machine-readable per-step telemetry record.

Assembled once per train (or serving) step and fanned out everywhere:
the JSONL step log, the Prometheus registry, MonitorMaster backends, and
the auto-capture report all read THIS object, so "what MFU did step 500
get" has exactly one answer.

Schema stability: ``SCHEMA_VERSION`` is embedded in every record and the
key set is linted by ``tools/telemetry_check.py`` — change either in the
same commit as the docs table in ``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

SCHEMA_VERSION = 3

# bf16 peak FLOP/s by TPU device kind (matmul peak; the MFU denominator).
# Sources: public TPU spec sheets (v5e: 197e12, as benchmark/lib has it).
_PEAK_FLOPS_BY_KIND = {
    "tpu v2": 45e12,
    "tpu v3": 123e12,
    "tpu v4": 275e12,
    "tpu v5": 197e12,       # v5e / v5 litepod
    "tpu v5e": 197e12,
    "tpu v5 lite": 197e12,
    "tpu v5p": 459e12,
    "tpu v6": 918e12,       # Trillium
    "tpu v6e": 918e12,
}

# Host backends (CPU test meshes) have no spec sheet: a value generous
# enough that a host can never exceed it keeps their MFU a (0, 1]
# fraction.  It is never used for a TPU.
_HOST_PEAK_FLOPS = 1e13


def detect_peak_flops_per_sec() -> float:
    """Per-device peak FLOP/s from the JAX device kind.  A TPU whose
    kind is not in the table raises — an MFU against a guessed peak is
    not a measurement."""
    import jax

    from deepspeed_tpu.utils.platform import on_tpu

    kind = jax.devices()[0].device_kind.lower()
    for key in sorted(_PEAK_FLOPS_BY_KIND, key=len, reverse=True):
        if key in kind:
            return _PEAK_FLOPS_BY_KIND[key]
    if on_tpu():
        raise ValueError(
            f"no peak FLOP/s on record for TPU device kind {kind!r}; add "
            f"it to telemetry/record.py:_PEAK_FLOPS_BY_KIND with its source")
    return _HOST_PEAK_FLOPS


def collect_hbm_stats(max_devices: int = 64) -> Dict[str, Dict[str, int]]:
    """Per-device HBM watermarks via the accelerator ``memory_stats()``
    (PJRT on TPU; /proc RSS on the CPU fallback).  Keys are
    ``device_<i>``; values carry whatever of bytes_in_use /
    peak_bytes_in_use / bytes_limit the backend reports."""
    try:
        from deepspeed_tpu.accelerator import get_accelerator

        acc = get_accelerator()
        n = min(acc.device_count(), max_devices)
    except Exception:
        return {}
    out: Dict[str, Dict[str, int]] = {}
    for i in range(n):
        stats = acc.memory_stats(i)
        if not stats:
            continue
        out[f"device_{i}"] = {
            k: int(stats[k])
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}
    return out


@dataclass
class StepRecord:
    """Typed per-step telemetry record (see docs/OBSERVABILITY.md)."""

    step: int
    kind: str = "train"                    # train | serving
    schema: int = SCHEMA_VERSION
    # the run this record belongs to (telemetry.run_id, shared with
    # Tracer metadata and FleetSampler rows; "" = none)
    run_id: str = ""
    # timing / throughput
    wall_time_s: float = 0.0
    tokens: int = 0
    tokens_per_sec: float = 0.0
    # flops / MFU (per-chip denominators)
    flops_per_step: float = 0.0            # whole train batch, one device
    achieved_flops_per_sec: float = 0.0
    peak_flops_per_sec: float = 0.0
    mfu: float = 0.0                       # clamped to [0, 1]
    flops_source: str = "none"             # measured | analytic | none
    # goodput: fraction of optimizer steps so far that actually applied
    # (1.0 - skipped/total); per-step productivity is `not skipped`
    goodput: float = 1.0
    skipped: bool = False
    # training scalars
    loss: Optional[float] = None
    grad_norm: Optional[float] = None
    lr: Optional[float] = None
    loss_scale: Optional[float] = None
    # chunked offload pipeline: fraction of the d2h/h2d transfer time the
    # host optimizer step hid this step (None off the chunked path)
    offload_overlap_fraction: Optional[float] = None
    # memory watermarks: {"device_0": {"bytes_in_use": ..,
    #                                  "peak_bytes_in_use": ..}, ...}
    hbm: Dict[str, Dict[str, int]] = field(default_factory=dict)
    # cumulative comm volume by collective (trace-time exact counts):
    # {"all_reduce": {"count": n, "bytes": b}, ...}
    comm: Dict[str, Dict[str, int]] = field(default_factory=dict)
    # serving-only stats (queue/preemption/KV), empty for train records
    serving: Dict[str, float] = field(default_factory=dict)

    def __post_init__(self):
        if self.wall_time_s > 0 and self.tokens and not self.tokens_per_sec:
            self.tokens_per_sec = self.tokens / self.wall_time_s
        if self.wall_time_s > 0 and self.flops_per_step \
                and not self.achieved_flops_per_sec:
            self.achieved_flops_per_sec = \
                self.flops_per_step / self.wall_time_s
        if self.peak_flops_per_sec > 0 and self.achieved_flops_per_sec \
                and not self.mfu:
            self.mfu = min(
                1.0, self.achieved_flops_per_sec / self.peak_flops_per_sec)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        """One JSONL line: keys sorted (schema-lint relies on this)."""
        return json.dumps(self.to_dict(), sort_keys=True,
                          separators=(",", ":"), default=float)


def record_keys() -> list:
    """The stable top-level key set (consumed by tools/telemetry_check)."""
    return sorted(f.name for f in dataclasses.fields(StepRecord))
