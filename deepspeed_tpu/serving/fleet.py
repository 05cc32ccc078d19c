"""Fleet observability plane: per-tier snapshots on a cadence thread.

ROADMAP item 4's autoscaler "watches the router's per-tier telemetry
(TTFT/TPOT percentiles, queue depth, evictable headroom, handoff
volume, spec accept rate)" — but those signals natively live in N
per-replica ``MetricsRegistry`` instances plus router counters nobody
rolls up by tier.  The :class:`FleetSampler` is that sensor layer: a
cadence thread that polls every LIVE replica and folds the fleet into
one frozen-schema :class:`TierSnapshot` row per tier per tick
(:data:`TIER_SNAPSHOT_KEYS`, schema :data:`TIER_SNAPSHOT_SCHEMA` —
linted by ``tools/telemetry_check.py`` like the StepRecord key set),
appended to a bounded in-memory ring, an optional JSONL file, and
Prometheus gauges / MonitorMaster tags.  ``latest()`` is the
autoscaler's live query surface.

Aggregation rules worth stating once:

* **Percentiles pool samples.**  A tier p95 is a percentile of the
  POOLED per-replica latency samples (``ServingMetrics.latency_values``)
  — never an average of per-replica p95s, which has no distributional
  meaning.  Build replicas with ``metrics_window_s`` set so the pooled
  windows are TIME-bounded and an idle tier's percentiles decay.
* **Rates are tick deltas keyed by tier NAME.**  Counter deltas divide
  by the tick's elapsed time; keying by tier (not replica index) is
  what makes live ``grow()/shrink()/respawn()`` safe — a dead replica
  simply stops contributing at the next tick, a respawned one re-enters,
  and no dynamic index can KeyError.
* **Dead replicas drop within one tick.**  Only ``replica.alive``
  members contribute; the snapshot's ``replicas_alive`` is the
  autoscaler's capacity denominator.

With an :class:`~deepspeed_tpu.telemetry.slo.SLOSpec`, every tick also
feeds the per-tier :class:`~deepspeed_tpu.telemetry.slo.SLOLedger`
(attainment / violations / error-budget burn) and marks the snapshot's
``slo_violation`` flag, emitting an ``slo.violation`` trace instant.

Like the rest of ``serving/``, this module imports no jax.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from deepspeed_tpu.serving.admission import AdmissionController
from deepspeed_tpu.serving.metrics import spec_accept_rate
from deepspeed_tpu.telemetry.registry import MetricsRegistry, _percentile
from deepspeed_tpu.telemetry.slo import SLOLedger, SLOSpec
from deepspeed_tpu.telemetry.tracing import NULL_TRACER
from deepspeed_tpu.utils.logging import log_dist

#: TierSnapshot schema version (bump on any key change)
TIER_SNAPSHOT_SCHEMA = 2

#: frozen key set of one TierSnapshot row — every signal ROADMAP item 4
#: names, flat and sorted; linted against docs/OBSERVABILITY.md by
#: tools/telemetry_check.py (check_fleet)
TIER_SNAPSHOT_KEYS = (
    "evictable_headroom_blocks",   # pool-wide evictable pages (sum)
    "handoff_bytes_per_sec",       # KV handoff volume, this tick
    "handoffs_per_sec",            # KV handoffs (in+out), this tick
    "kv_utilization",              # mean fraction of KV pool in use
    "prefix_hit_rate",             # lifetime hits/(hits+misses)
    "queue_depth",                 # queued requests (sum)
    "queue_wait_p50_ms",
    "queue_wait_p95_ms",
    "queue_wait_p99_ms",
    "replicas_alive",
    "run_id",                      # owning run (schema 2; "" = none)
    "running",                     # admitted + decoding requests (sum)
    "schema",                      # TIER_SNAPSHOT_SCHEMA
    "slo_violation",               # 1 = this tick breached a target
    "spec_accept_rate",            # lifetime accepted/proposed
    "tick",                        # sampler tick counter
    "tier",                        # prefill | decode | unified
    "tokens_per_sec",              # decoded tokens, this tick
    "tpot_p50_ms",
    "tpot_p95_ms",
    "tpot_p99_ms",
    "ts",                          # wall-clock unix seconds
    "ttft_p50_ms",
    "ttft_p95_ms",
    "ttft_p99_ms",
)

# counters whose tick-over-tick deltas become the snapshot's rates
_RATE_COUNTERS = ("tokens_out", "handoffs", "handoff_bytes")


def _pool_pct(samples: List[float], q: float) -> float:
    """Percentile (ms) of pooled second-valued latency samples."""
    return round(_percentile(sorted(samples), q) * 1e3, 3)


class FleetSampler:
    """Cadence thread folding a ReplicaSet into per-tier snapshots.

    ``router`` is optional (its RouterMetrics are exported alongside);
    ``telemetry`` is a ``telemetry.Telemetry`` hub — its registry hosts
    the ``fleet_<tier>_<key>`` gauges and its tracer records the
    ``fleet.sample`` span per tick (standalone samplers keep their own
    registry and stay untraced).  ``jsonl_path`` appends one JSON line
    per tier per tick.  Use as a context manager or ``start()/stop()``;
    ``sample_once()`` works without the thread (tests, bench rows).
    """

    def __init__(self, replicas: Any, router: Any = None,
                 slo: Optional[SLOSpec] = None, cadence_s: float = 1.0,
                 ring: int = 512, jsonl_path: str = "",
                 telemetry: Any = None, monitor: Any = None,
                 run_id: str = ""):
        if cadence_s <= 0:
            raise ValueError(f"fleet cadence_s={cadence_s}: must be > 0")
        self.replicas = replicas
        self.router = router
        # the stitching key every snapshot row carries (schema 2):
        # explicit arg wins, else inherited from the telemetry hub
        self.run_id = str(run_id
                          or getattr(telemetry, "run_id", "") or "")
        self.cadence_s = float(cadence_s)
        self.jsonl_path = str(jsonl_path)
        self.telemetry = telemetry
        self.monitor = monitor
        self.tracer = (telemetry.tracer if telemetry is not None
                       else NULL_TRACER)
        self.registry = (telemetry.registry if telemetry is not None
                         else MetricsRegistry())
        self.slo = slo if (slo is not None and slo.enabled) else None
        self.ledger = SLOLedger(self.slo) if self.slo is not None else None
        self._ring: deque = deque(maxlen=max(1, int(ring)))
        self._latest: Dict[str, Dict[str, Any]] = {}
        self._prev: Dict[str, Any] = {}   # tier -> (t, {counter: value})
        self._tick = 0
        self._export_tiers: set = set()   # tiers with live gauges
        self._lock = threading.Lock()
        # serialises whole ticks: a manual sample_once() may overlap the
        # cadence thread, and _prev pairing + ring/JSONL ordering assume
        # one tick at a time (self._lock alone only guards the fields)
        self._tick_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "FleetSampler":
        if self._thread is not None:
            raise RuntimeError("fleet sampler already started")
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="ds-fleet-sampler",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=max(5.0, 4 * self.cadence_s))
            self._thread = None

    def __enter__(self) -> "FleetSampler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _loop(self) -> None:
        while not self._stop.wait(self.cadence_s):
            try:
                self.sample_once()
            except Exception as e:   # sampling must never kill serving
                log_dist(f"fleet sampler: tick failed: {e!r}",
                         level="warning")

    # -- one cadence tick ------------------------------------------------
    def sample_once(self) -> Dict[str, Dict[str, Any]]:
        """Poll the fleet; returns ``{tier: TierSnapshot}`` (also the
        value ``latest()`` serves until the next tick).  Safe to call
        concurrently with the cadence thread: whole ticks are serialised
        so two ticks can never pair one tick's clock with the other's
        counters or interleave their ring/JSONL rows."""
        with self._tick_lock:
            return self._sample_once_locked()

    def _sample_once_locked(self) -> Dict[str, Dict[str, Any]]:
        span = self.tracer.span("fleet.sample") if self.tracer.enabled \
            else None
        now = time.monotonic()
        with self._lock:
            self._tick += 1
            tick = self._tick
        by_tier: Dict[str, List[Any]] = {}
        for rep in list(self.replicas):
            if rep.alive:
                by_tier.setdefault(rep.tier, []).append(rep)
        out: Dict[str, Dict[str, Any]] = {}
        for tier in sorted(by_tier):
            out[tier] = self._tier_snapshot(tier, by_tier[tier], now, tick)
        with self._lock:
            self._latest = out
            for snap in out.values():
                self._ring.append(snap)
            # a tier with no live replicas stops advancing _prev: when
            # it comes back its first rates restart from the new counts
            self._prev = {t: self._prev.get(t) for t in out
                          if self._prev.get(t) is not None}
            for tier, snap in out.items():
                self._prev[tier] = (now, snap.pop("_counters"))
        self._export(out, tick)
        if span is not None:
            span.end(tick=tick, tiers=len(out))
        return out

    def _tier_snapshot(self, tier: str, reps: List[Any], now: float,
                       tick: int) -> Dict[str, Any]:
        pooled: Dict[str, List[float]] = {"ttft": [], "tpot": [],
                                          "queue_wait": []}
        counters = {k: 0 for k in _RATE_COUNTERS}
        queue_depth = running = 0
        headroom = 0
        kv_util = 0.0
        hits = misses = proposed = accepted = 0
        for rep in reps:
            m = rep.server.metrics
            for k, vals in m.latency_values().items():
                pooled[k].extend(vals)
            counters["tokens_out"] += m.tokens_out
            counters["handoffs"] += m.handoffs_in + m.handoffs_out
            counters["handoff_bytes"] += m.handoff_bytes
            queue_depth += len(rep.server.admission)
            running += len(rep.server._active)
            headroom += AdmissionController.evictable_headroom(
                rep.engine, rep.server.prefix_cache)
            kv_util += 1.0 - rep.kv_headroom
            hits += m.prefix_hits
            misses += m.prefix_misses
            proposed += m.spec_proposed
            accepted += m.spec_accepted
        n = len(reps)
        prev = self._prev.get(tier)
        rates = {k: 0.0 for k in _RATE_COUNTERS}
        if prev is not None:
            t_prev, c_prev = prev
            dt = max(now - t_prev, 1e-9)
            for k in _RATE_COUNTERS:
                # max(0, ·): a replica death/respawn can step a pooled
                # lifetime counter backwards; a negative rate is noise
                rates[k] = max(0, counters[k] - c_prev.get(k, 0)) / dt
        snap: Dict[str, Any] = {
            "schema": TIER_SNAPSHOT_SCHEMA,
            "run_id": self.run_id,
            "tick": tick,
            "ts": round(time.time(), 3),
            "tier": tier,
            "replicas_alive": n,
            "queue_depth": queue_depth,
            "running": running,
            "evictable_headroom_blocks": headroom,
            "kv_utilization": round(kv_util / max(1, n), 4),
            "ttft_p50_ms": _pool_pct(pooled["ttft"], 50.0),
            "ttft_p95_ms": _pool_pct(pooled["ttft"], 95.0),
            "ttft_p99_ms": _pool_pct(pooled["ttft"], 99.0),
            "tpot_p50_ms": _pool_pct(pooled["tpot"], 50.0),
            "tpot_p95_ms": _pool_pct(pooled["tpot"], 95.0),
            "tpot_p99_ms": _pool_pct(pooled["tpot"], 99.0),
            "queue_wait_p50_ms": _pool_pct(pooled["queue_wait"], 50.0),
            "queue_wait_p95_ms": _pool_pct(pooled["queue_wait"], 95.0),
            "queue_wait_p99_ms": _pool_pct(pooled["queue_wait"], 99.0),
            "tokens_per_sec": round(rates["tokens_out"], 3),
            "handoffs_per_sec": round(rates["handoffs"], 3),
            "handoff_bytes_per_sec": round(rates["handoff_bytes"], 3),
            "prefix_hit_rate": round(hits / max(1, hits + misses), 4),
            "spec_accept_rate": round(spec_accept_rate(proposed,
                                                       accepted), 4),
            "slo_violation": 0,
        }
        if self.ledger is not None:
            bad = self.ledger.observe(tier, snap["ttft_p95_ms"],
                                      snap["tpot_p95_ms"],
                                      snap["queue_wait_p95_ms"])
            snap["slo_violation"] = int(bad)
            if bad and self.tracer.enabled:
                self.tracer.instant("slo.violation", "", tier=tier,
                                    ttft_p95_ms=snap["ttft_p95_ms"],
                                    tpot_p95_ms=snap["tpot_p95_ms"])
        if tuple(sorted(snap)) != TIER_SNAPSHOT_KEYS:
            raise RuntimeError(       # schema tripwire (StepRecord rule)
                "TierSnapshot drifted from TIER_SNAPSHOT_KEYS: "
                f"{sorted(set(snap) ^ set(TIER_SNAPSHOT_KEYS))}")
        snap["_counters"] = counters   # stripped before export
        return snap

    # -- export ----------------------------------------------------------
    def _export(self, out: Dict[str, Dict[str, Any]], tick: int) -> None:
        # a tier that lost its last live replica drops out of `out`, but
        # its gauges would otherwise hold the final tick's values forever
        # — a registry consumer would keep seeing a healthy-looking dead
        # tier.  Zero every gauge of a disappeared tier so monitors see
        # replicas_alive=0 instead of frozen last-known-good numbers.
        for tier in self._export_tiers - set(out):
            for k in TIER_SNAPSHOT_KEYS:
                if k in ("tier", "schema", "run_id"):
                    continue
                self.registry.gauge(f"fleet_{tier}_{k}").set(0.0)
        self._export_tiers = set(out)
        for tier, snap in out.items():
            for k, v in snap.items():
                if k in ("tier", "schema", "run_id"):
                    continue
                self.registry.gauge(f"fleet_{tier}_{k}").set(float(v))
        if self.monitor is not None:
            events = [(f"fleet/{tier}/{k}", float(v), tick)
                      for tier, snap in out.items()
                      for k, v in snap.items()
                      if k not in ("tier", "schema", "run_id")]
            self.monitor.write_events(events)
        if self.jsonl_path:
            parent = os.path.dirname(os.path.abspath(self.jsonl_path))
            os.makedirs(parent, exist_ok=True)
            with open(self.jsonl_path, "a", encoding="utf-8") as f:
                for tier in sorted(out):
                    f.write(json.dumps(out[tier], sort_keys=True) + "\n")

    # -- reading ---------------------------------------------------------
    def latest(self) -> Dict[str, Dict[str, Any]]:
        """Most recent ``{tier: TierSnapshot}`` — the autoscaler's live
        query surface (empty before the first tick)."""
        with self._lock:
            return {t: dict(s) for t, s in self._latest.items()}

    def history(self) -> List[Dict[str, Any]]:
        """Ring contents, oldest first (every tier's rows interleaved)."""
        with self._lock:
            return [dict(s) for s in self._ring]

    def slo_snapshot(self) -> Dict[str, Dict[str, float]]:
        """Per-tier SLO ledger rows (empty without an enabled SLOSpec)."""
        return self.ledger.snapshot() if self.ledger is not None else {}
