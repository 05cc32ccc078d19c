"""Telemetry fan-out: JSONL step log, Prometheus text exposition, and
the MonitorMaster bridge.

One ``Telemetry`` hub owns the shared :class:`MetricsRegistry`, the
append-only JSONL writer, the optional Prometheus textfile, the
MonitorMaster bridge (so TensorBoard/CSV/WandB see the same tags), and
the budgeted auto-capture manager.  The engine and the serving loop
each push :class:`StepRecord` objects; everything downstream is a pure
function of those records.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.telemetry.flight import (dump_bundle,
                                            make_span_recorder,
                                            make_watchdog)
from deepspeed_tpu.telemetry.record import (StepRecord, collect_hbm_stats,
                                            detect_peak_flops_per_sec)
from deepspeed_tpu.telemetry.registry import (Counter, Gauge, Histogram,
                                              MetricsRegistry)
from deepspeed_tpu.utils.logging import logger

Event = Tuple[str, float, int]

# Every MonitorMaster tag the train-side bridge can emit.  The docs
# table in docs/OBSERVABILITY.md must list each of these —
# tools/telemetry_check.py enforces it.
EXPORT_TAGS = (
    "telemetry/step_time_ms",
    "telemetry/tokens_per_sec",
    "telemetry/mfu",
    "telemetry/goodput",
    "telemetry/achieved_tflops",
    "telemetry/hbm_bytes_in_use",
    "telemetry/hbm_peak_bytes_in_use",
    "telemetry/comm_bytes_total",
    "telemetry/loss",
    "telemetry/grad_norm",
    "telemetry/lr",
    "telemetry/loss_scale",
)


class JsonlExporter:
    """Append-only JSONL writer (one StepRecord per line, keys sorted)."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._fh = None
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)

    def write(self, record: StepRecord) -> None:
        line = record.to_json()
        with self._lock:
            if self._fh is None:
                self._fh = open(self.path, "a", encoding="utf-8")
            self._fh.write(line + "\n")
            self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


def render_prometheus(registry: MetricsRegistry) -> str:
    """Prometheus text exposition (v0.0.4).  Histograms render as
    summaries (pre-computed quantiles over the sliding window)."""
    lines: List[str] = []
    for m in registry.collect():
        if m.help:
            lines.append(f"# HELP {m.name} {m.help}")
        if isinstance(m, Counter):
            lines.append(f"# TYPE {m.name} counter")
            lines.append(f"{m.name} {m.value:g}")
        elif isinstance(m, Gauge):
            lines.append(f"# TYPE {m.name} gauge")
            lines.append(f"{m.name} {m.value:g}")
        elif isinstance(m, Histogram):
            snap = m.snapshot()
            count, total = m.lifetime()
            lines.append(f"# TYPE {m.name} summary")
            for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
                lines.append(
                    f'{m.name}{{quantile="{q}"}} {snap[key]:g}')
            lines.append(f"{m.name}_sum {total:g}")
            lines.append(f"{m.name}_count {count}")
    return "\n".join(lines) + "\n"


def write_prometheus_textfile(registry: MetricsRegistry, path: str) -> None:
    """Atomic write for node-exporter textfile collectors (a scraper must
    never see a half-written file)."""
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=parent, suffix=".prom.tmp")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            f.write(render_prometheus(registry))
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def events_from_record(rec: StepRecord,
                       tags: Tuple[str, ...] = EXPORT_TAGS) -> List[Event]:
    """Flatten a StepRecord into MonitorMaster ``(tag, value, step)``
    events — the bridge that makes TensorBoard/CSV/WandB see the same
    numbers the JSONL carries."""
    hbm0 = next(iter(rec.hbm.values()), {})
    comm_bytes = sum(int(v.get("bytes", 0)) for v in rec.comm.values())
    values: Dict[str, Optional[float]] = {
        "telemetry/step_time_ms": rec.wall_time_s * 1e3,
        "telemetry/tokens_per_sec": rec.tokens_per_sec,
        "telemetry/mfu": rec.mfu,
        "telemetry/goodput": rec.goodput,
        "telemetry/achieved_tflops": rec.achieved_flops_per_sec / 1e12,
        "telemetry/hbm_bytes_in_use": hbm0.get("bytes_in_use"),
        "telemetry/hbm_peak_bytes_in_use": hbm0.get("peak_bytes_in_use"),
        "telemetry/comm_bytes_total": comm_bytes,
        "telemetry/loss": rec.loss,
        "telemetry/grad_norm": rec.grad_norm,
        "telemetry/lr": rec.lr,
        "telemetry/loss_scale": rec.loss_scale,
    }
    return [(tag, float(values[tag]), rec.step) for tag in tags
            if values.get(tag) is not None]


class Telemetry:
    """The per-process telemetry hub (config: the ``telemetry`` block).

    Thread contract: ``record_train_step`` is called by the training
    thread, ``record_serving_step`` by the serve loop; the registry and
    exporters are individually locked, so the two may coexist.
    """

    def __init__(self, cfg, monitor: Any = None,
                 registry: Optional[MetricsRegistry] = None):
        self.cfg = cfg
        self.monitor = monitor
        self.registry = registry or MetricsRegistry()
        # one run_id per training or serving run, stamped into every
        # StepRecord, the Tracer's trace metadata, and (via FleetSampler)
        # every TierSnapshot row — the key they are joined on
        self.run_id = str(getattr(cfg, "run_id", "") or "")
        self.peak_flops_per_sec = (
            float(cfg.peak_flops_per_sec) if cfg.peak_flops_per_sec
            else detect_peak_flops_per_sec())
        self.interval_steps = max(1, int(getattr(cfg, "interval_steps", 1)))
        self.last_record: Optional[StepRecord] = None
        # flops for one whole train batch, set once by the engine
        # (profile_compiled or the analytic model profile)
        self._flops_per_step: Optional[float] = None
        self._flops_source = "none"
        # static per-device memory plan for the compiled step, set by the
        # engine's flops handshake ({"backend", "peak_bytes", ...}) —
        # capture reports diff runtime HBM watermarks against it
        self.static_memory: Optional[Dict] = None
        self._steps = 0
        self._skipped = 0
        self._tokens = 0

        self.jsonl = (JsonlExporter(cfg.jsonl_path)
                      if getattr(cfg, "jsonl_path", "") else None)
        self.prometheus_path = getattr(cfg, "prometheus_path", "") or None

        w = int(getattr(cfg, "window", 0)) or None
        reg = self.registry
        hist_kw = {"window": w} if w else {}
        self.step_time = reg.histogram(
            "telemetry_step_time_seconds",
            "train_batch wall time per optimizer step", **hist_kw)
        self.g_mfu = reg.gauge("telemetry_mfu",
                               "model flops utilization, last step")
        self.g_tps = reg.gauge("telemetry_tokens_per_sec",
                               "tokens/s, last step")
        self.g_goodput = reg.gauge(
            "telemetry_goodput",
            "fraction of optimizer steps that applied (not skipped)")
        self.g_hbm = reg.gauge("telemetry_hbm_bytes_in_use",
                               "device 0 HBM bytes in use")
        self.g_hbm_peak = reg.gauge("telemetry_hbm_peak_bytes_in_use",
                                    "device 0 HBM peak bytes in use")
        self.c_steps = reg.counter("telemetry_steps_total",
                                   "optimizer steps recorded")
        self.c_tokens = reg.counter("telemetry_tokens_total",
                                    "tokens processed")
        self.c_skipped = reg.counter("telemetry_skipped_steps_total",
                                     "overflow-skipped optimizer steps")

        cap_cfg = getattr(cfg, "capture", None)
        self.capture = None
        if cap_cfg is not None and getattr(cap_cfg, "enabled", False):
            from deepspeed_tpu.telemetry.capture import AutoCapture

            self.capture = AutoCapture(cap_cfg, telemetry=self)

        # -- software spans + flight recorder (tracing.py / flight.py) --
        tr_cfg = getattr(cfg, "tracing", None)
        self._flight_cfg = fl_cfg = getattr(cfg, "flight", None)
        self.tracer, self.flight_ring = make_span_recorder(
            tracing_enabled=getattr(tr_cfg, "enabled", False),
            flight_enabled=getattr(fl_cfg, "enabled", False),
            max_events=getattr(tr_cfg, "max_events", 0) or 0,
            ring_size=getattr(fl_cfg, "ring_size", 0) or 0)
        # the trace *file* is gated on the tracing block itself: a
        # flight-only config records spans (for the ring) but a user who
        # disabled tracing must not get a trace written at shutdown
        self.trace_path = (getattr(tr_cfg, "trace_path", "") or ""
                           if getattr(tr_cfg, "enabled", False) else "")
        if self.run_id:
            self.tracer.run_id = self.run_id

    # -- tracing / flight recorder ---------------------------------------
    def make_watchdog(self, name: str):
        """A hang :class:`Watchdog` for one hot loop (``None`` unless the
        ``telemetry.flight`` block is enabled).  The caller owns
        start()/beat()/stop()."""
        return make_watchdog(name, self._flight_cfg,
                             ring=self.flight_ring, telemetry=self,
                             tracer=self.tracer)

    def dump_flight(self, reason: str,
                    error: Optional[BaseException] = None) -> Optional[str]:
        """Crash-forensics bundle on demand (serve-loop crash handler,
        ``engine.destroy()`` during exception unwind).  No flight config
        ⇒ no bundle."""
        fl = self._flight_cfg
        if fl is None or not getattr(fl, "enabled", False):
            return None
        return dump_bundle(fl.output_dir, reason, ring=self.flight_ring,
                           telemetry=self, error=error)

    def export_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome trace-event JSON (Perfetto-viewable); returns
        the path, or ``None`` when tracing never recorded anything."""
        path = path or self.trace_path
        if not path or not self.tracer.enabled:
            return None
        return self.tracer.export_chrome_trace(path)

    # -- flops handshake (engine) ---------------------------------------
    def _capture_wants_times(self) -> bool:
        return (self.capture is not None
                and self.capture.regression_factor > 0
                and self.capture.budget_left > 0)

    def should_record(self, step: int) -> bool:
        """The engine thins record assembly on this gate: off-interval
        steps skip the hard host sync entirely, not just the export.
        While a regression-triggered capture still has budget it needs
        every step's wall time (else the trigger distribution goes
        blind) — those steps return True but the engine only feeds
        ``observe_step_time`` unless the interval also matches."""
        if self._capture_wants_times():
            return True
        return step % self.interval_steps == 0

    def is_full_record_step(self, step: int) -> bool:
        """True when ``step`` gets the full record+export; a
        should_record step that isn't is trigger-bookkeeping only."""
        return step % self.interval_steps == 0

    def observe_step_time(self, wall_time_s: float) -> None:
        """Trigger-only feed for off-interval steps: no record, no
        export — just the capture's trailing step-time window."""
        if self.capture is not None:
            self.capture.observe_step_time(wall_time_s)

    def needs_flops(self) -> bool:
        return self._flops_per_step is None

    def set_flops(self, flops_per_step: float, source: str) -> None:
        self._flops_per_step = float(flops_per_step)
        self._flops_source = source

    def set_static_memory(self, totals: Optional[Dict]) -> None:
        """Record the compiled step's static memory plan (engine flops
        handshake) for the capture report's ``hbm`` cross-check."""
        self.static_memory = dict(totals) if totals else None

    # -- record paths ----------------------------------------------------
    def record_train_step(self, step: int, wall_time_s: float, tokens: int,
                          loss: Optional[float] = None,
                          grad_norm: Optional[float] = None,
                          lr: Optional[float] = None,
                          loss_scale: Optional[float] = None,
                          skipped: bool = False,
                          comm: Optional[Dict] = None,
                          offload_overlap_fraction: Optional[float] = None
                          ) -> StepRecord:
        self._steps += 1
        self._skipped += int(bool(skipped))
        self._tokens += int(tokens)
        goodput = 1.0 - self._skipped / max(1, self._steps)
        rec = StepRecord(
            step=step, kind="train", run_id=self.run_id,
            wall_time_s=float(wall_time_s),
            tokens=int(tokens),
            flops_per_step=float(self._flops_per_step or 0.0),
            peak_flops_per_sec=self.peak_flops_per_sec,
            flops_source=self._flops_source,
            goodput=goodput, skipped=bool(skipped),
            loss=loss, grad_norm=grad_norm, lr=lr, loss_scale=loss_scale,
            offload_overlap_fraction=offload_overlap_fraction,
            hbm=collect_hbm_stats(),
            comm=comm if comm is not None else self._comm_totals())
        self._update_registry(rec)
        if self.capture is not None:
            # the single feed point for the regression trigger's trailing
            # step-time window (AutoCapture keeps no second clock)
            self.capture.observe_step_time(rec.wall_time_s)
        self.last_record = rec
        self._export(rec)
        return rec

    def record_recovery(self, step: int, outage_s: float) -> StepRecord:
        """Goodput-gap record: one recovery outage counts as a SKIPPED
        step whose wall time is the whole detection→resumed gap, so the
        cumulative ``goodput`` curve (1 − skipped/total) prices outages
        next to overflow-skipped steps and the JSONL shows the gap as a
        first-class row (``kind: "recovery"``) rather than a hole in the
        step sequence.  Emitted by the recovery supervisor
        (resilience/supervisor.py) when post-restart progress resumes."""
        self._steps += 1
        self._skipped += 1
        goodput = 1.0 - self._skipped / max(1, self._steps)
        rec = StepRecord(
            step=step, kind="recovery", run_id=self.run_id,
            wall_time_s=float(outage_s),
            peak_flops_per_sec=self.peak_flops_per_sec,
            goodput=goodput, skipped=True, comm={})
        self.g_goodput.set(goodput)
        # both counters, like _update_registry: anyone deriving goodput
        # from the exported steps/skipped totals must agree with the gauge
        self.c_steps.inc()
        self.c_skipped.inc()
        self.last_record = rec
        self._export(rec)
        return rec

    def record_serving_step(self, step: int,
                            snapshot: Dict[str, Any]) -> StepRecord:
        """Serving-side record: queue/preemption/KV stats ride the
        ``serving`` field; throughput comes from the snapshot."""
        flat: Dict[str, float] = {}
        for k, v in snapshot.items():
            if isinstance(v, dict):
                for sub, x in v.items():
                    flat[f"{k}_{sub}"] = float(x)
            else:
                flat[k] = float(v)
        rec = StepRecord(
            step=step, kind="serving", run_id=self.run_id,
            tokens=int(snapshot.get("tokens_out", 0)),
            tokens_per_sec=float(snapshot.get("tokens_per_sec", 0.0)),
            peak_flops_per_sec=self.peak_flops_per_sec,
            hbm=collect_hbm_stats(), comm=self._comm_totals(),
            serving=flat)
        self.last_record = rec
        self._export(rec)
        return rec

    # -- internals -------------------------------------------------------
    @staticmethod
    def _comm_totals() -> Dict[str, Dict[str, int]]:
        from deepspeed_tpu.utils.comms_logging import get_comms_logger

        return get_comms_logger().totals()

    def _update_registry(self, rec: StepRecord) -> None:
        self.step_time.observe(rec.wall_time_s)
        self.g_mfu.set(rec.mfu)
        self.g_tps.set(rec.tokens_per_sec)
        self.g_goodput.set(rec.goodput)
        hbm0 = next(iter(rec.hbm.values()), {})
        if "bytes_in_use" in hbm0:
            self.g_hbm.set(hbm0["bytes_in_use"])
        if "peak_bytes_in_use" in hbm0:
            self.g_hbm_peak.set(hbm0["peak_bytes_in_use"])
        self.c_steps.inc()
        self.c_tokens.inc(rec.tokens)
        if rec.skipped:
            self.c_skipped.inc()

    def _export(self, rec: StepRecord) -> None:
        if self.jsonl is not None:
            try:
                self.jsonl.write(rec)
            except OSError as e:
                logger.warning(f"telemetry: jsonl write failed: {e}")
        if self.prometheus_path:
            try:
                write_prometheus_textfile(self.registry,
                                          self.prometheus_path)
            except OSError as e:
                logger.warning(f"telemetry: prometheus write failed: {e}")
        if self.monitor is not None and getattr(self.monitor, "enabled",
                                                True):
            try:
                self.monitor.write_events(events_from_record(rec))
            except Exception as e:
                logger.warning(f"telemetry: monitor export failed: {e}")

    def close(self) -> None:
        if self.capture is not None:
            self.capture.close()
        if self.jsonl is not None:
            self.jsonl.close()
        try:
            self.export_trace()
        except OSError as e:
            logger.warning(f"telemetry: trace export failed: {e}")


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    """Load a telemetry JSONL step log (helper for tools/tests)."""
    out = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
