"""What a driver hands back and what readers are given."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


@dataclass
class Run:
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]          # all but setup_s
    setup_s: float
    counters: Dict[str, Any] = field(default_factory=dict)
    spans: List[dict] = field(default_factory=list)   # the program's Tracer
    trace: Optional[Any] = None           # lib.trace.Reduction, traced runs
    notes: List[str] = field(default_factory=list)


def seed32(seed: int) -> int:
    """``--seed`` may pass 2**31; what is handed to the program as a PRNG
    seed fits 31 bits."""
    return int(seed) % (2 ** 31 - 1)


def log(msg: str) -> None:
    print(msg, flush=True)
