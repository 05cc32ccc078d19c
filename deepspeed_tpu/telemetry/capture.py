"""Budgeted auto-capture windows + persisted per-capture overlap report.

Closes the loop the ISSUE's motivation describes: instead of
hand-driving XProf, a capture window arms itself — on a configured step,
or when the step-time distribution regresses (p95 > k × trailing
median) — records an XPlane trace via :class:`TraceProfiler`, and
post-processes it with ``utils/xplane`` into a small JSON report:
collective-overlap fraction (the T3/Domino "was the all-reduce hidden?"
number), the top-10 device ops, and an MFU cross-check against the
analytic StepRecord.

Captures are budgeted (``budget`` per process) because a trace is not
free: stop_trace hard-syncs the device and the XPlane file can be
hundreds of MB at scale.
"""

from __future__ import annotations

import json
import os
from collections import deque
from typing import Deque, Dict, Optional, Sequence, Tuple

from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.trace import TraceProfiler


# Engine span names whose per-window totals make up the software-span
# overlap estimate (the CPU-degraded stand-in for the XPlane number).
PHASE_SPANS = ("train.data_ingest", "train.dispatch", "train.sync",
               "train.telemetry")


def spans_overlap_estimate(window_totals: Dict[str, Dict]) -> Dict:
    """Software-span overlap estimate from a capture window's per-span
    ``{name: {count, total_ms}}`` totals (Tracer.summary shape).

    The ``train.sync`` span is the host blocked on the device with
    nothing left to overlap — the software-visible analog of exposed
    communication; the other phase spans are host work the runtime
    pipelines under the device's execution.  ``overlap_estimate`` =
    1 − sync/step is therefore a coarse host-side proxy for "how much of
    the step was the pipeline kept busy" — it lets the overlap
    scheduler's decision logic run where XPlane has no device planes
    (the CPU mesh), and the on-chip XPlane fraction supersedes it
    whenever device planes exist."""
    phase = {name.rsplit(".", 1)[1] + "_ms":
             round(float(window_totals.get(name, {}).get("total_ms", 0.0)),
                   3)
             for name in PHASE_SPANS}
    step_ms = round(sum(phase.values()), 3)
    sync_ms = phase["sync_ms"]
    est = (max(0.0, min(1.0, 1.0 - sync_ms / step_ms))
           if step_ms > 0 else 0.0)
    return {**phase, "step_ms": step_ms, "exposed_ms": sync_ms,
            "overlap_estimate": round(est, 4)}


def trailing_regressed(times: Sequence[float], factor: float,
                       min_samples: int = 8) -> bool:
    """The capture-trigger heuristic: windowed ``p95 > factor × median``.

    ``times`` is the trailing window of step wall-times (the capture
    controller feeds its deque).  Fewer than ``min_samples`` samples or
    a non-positive factor never trigger.
    """
    if factor <= 0 or len(times) < min_samples:
        return False
    xs = sorted(times)
    median = xs[len(xs) // 2]
    p95 = xs[min(len(xs) - 1, int(0.95 * (len(xs) - 1)))]
    return median > 0 and p95 > factor * median


def hbm_cross_check(static_memory: Optional[Dict],
                    step_record=None) -> Tuple[Optional[Dict], str]:
    """The report's ``hbm`` block: runtime HBM watermarks (the
    StepRecord's per-device ``memory_stats()`` peaks) diffed against the
    compiled step's static memory plan (the engine's flops-handshake
    ``set_static_memory``), so ``model_drift`` has a runtime cross-check.

    Degrades to ``(None, note)`` when no static plan was recorded, when
    the backend is not a TPU (the CPU accelerator's watermarks are host
    RSS — process-wide, not device HBM, so the diff would be
    meaningless), or when the record carries no watermarks."""
    if not static_memory:
        return None, "hbm cross-check omitted (no static memory plan " \
                     "recorded — telemetry.measure_flops off?)"
    if static_memory.get("backend") != "tpu":
        return None, ("hbm cross-check omitted on "
                      f"{static_memory.get('backend', '?')} backend "
                      "(host RSS watermarks are not device HBM)")
    marks = dict(getattr(step_record, "hbm", None) or {})
    peaks = [int(v.get("peak_bytes_in_use", 0)) for v in marks.values()
             if isinstance(v, dict)]
    if not any(peaks):
        return None, "hbm cross-check omitted (no device watermarks in " \
                     "the capture-window StepRecord)"
    predicted = int(static_memory.get("peak_bytes", 0))
    measured = max(peaks)
    return {
        "predicted_peak_bytes": predicted,
        "measured_peak_bytes": measured,
        "drift_ratio": (round(measured / predicted, 4) if predicted
                        else None),
        "per_device": marks,
    }, ""


def build_capture_report(logdir: str, device_substr: str = "TPU",
                         step_record=None, span_totals=None,
                         static_memory: Optional[Dict] = None) -> Dict:
    """Pure post-processing of one capture directory → report dict.

    Degrades explicitly when the capture has no device planes (CPU runs
    carry host events only): overlap_fraction pins to 0.0 with a note,
    the top-ops table falls back to host planes, and — when the caller
    hands per-window span totals — the ``spans`` block carries the
    software overlap estimate so the report still feeds the overlap
    scheduler's decision inputs.

    ``stages``: device time by stage of the model
    (``telemetry.tracing.STAGE_NAMES``, read from the operations' name
    stacks by ``utils/xplane.py:time_by_stage``), and each ``top_ops``
    row's ``stage``: what ``fusion.424`` is a part of.
    ``dominant_collective``: the largest of ALL the capture's collectives,
    in ``top_ops`` (self times, the ten largest) or not, an asynchronous
    one by its time from start to done."""
    from deepspeed_tpu.utils import xplane

    report: Dict = {"logdir": logdir, "device_substr": device_substr,
                    "overlap_fraction": 0.0, "devices": {},
                    "top_ops": [], "stages": {},
                    "dominant_collective": None,
                    "spans": spans_overlap_estimate(span_totals or {}),
                    "note": ""}
    try:
        files = xplane.find_xplane_files(logdir)
        if not files:
            report["note"] = f"no xplane files under {logdir}"
        else:
            res = xplane.analyze_logdir(logdir,
                                        device_substr=device_substr)
            if "error" in res:
                report["note"] = res["error"]
            else:
                report["overlap_fraction"] = res["mean_overlap_fraction"]
                report["devices"] = res["devices"]
            tops: Dict[str, Dict] = {}
            collectives: Dict[str, Dict] = {}
            stages: Dict[str, Dict[str, float]] = {"stages": {}, "outer": {}}

            def add(acc: Dict[str, Dict], op: Dict) -> None:
                agg = acc.setdefault(op["name"], dict(op, total_ms=0.0,
                                                      count=0))
                agg["total_ms"] = round(agg["total_ms"] + op["total_ms"], 4)
                agg["count"] += op["count"]

            for path in files:
                xspace = xplane.load_xspace(path)
                for op in xplane.top_device_ops(
                        xspace, device_substr=device_substr):
                    add(tops, op)
                for op in xplane._collective_ops(xspace, device_substr):
                    add(collectives, op)
                by_stage = xplane.time_by_stage(xspace, device_substr)
                for table, acc in stages.items():
                    for name, ms in by_stage[table].items():
                        acc[name] = round(acc.get(name, 0.0) + ms, 4)
            total = sum(stages["stages"].values())
            report["stages"] = {
                "total_ms": round(total, 4), **stages,
                "unscoped_share": round(
                    stages["stages"].get("unscoped", 0.0) / total, 4)
                if total else 0.0}
            report["top_ops"] = sorted(tops.values(),
                                       key=lambda o: -o["total_ms"])[:10]
            report["dominant_collective"] = xplane.dominant_collective(
                list(collectives.values()))
    except Exception as e:  # a broken trace must not kill training
        report["note"] = f"capture post-processing failed: {e!r}"
    hbm, hbm_note = hbm_cross_check(static_memory, step_record)
    report["hbm"] = hbm
    if hbm_note:
        report["note"] = (report["note"] + "; " + hbm_note).lstrip("; ")
    if step_record is not None:
        # MFU cross-check: the analytic record's number next to what the
        # capture actually saw, so a disagreement is one diff away
        dev = next(iter(report["devices"].values()), {})
        report["mfu_cross_check"] = {
            "record_step": step_record.step,
            "analytic_mfu": step_record.mfu,
            "analytic_step_time_ms": step_record.wall_time_s * 1e3,
            "flops_source": step_record.flops_source,
            "capture_compute_ms": dev.get("compute_ms", 0.0),
            "capture_collective_ms": dev.get("collective_ms", 0.0),
        }
    return report


class AutoCapture:
    """Arms TraceProfiler windows and persists per-capture reports.

    Engine contract (mirrors the ``profiler`` block's TraceProfiler):

        cap.on_step_start(step)      # before dispatching step `step`
        ... run the step ...
        cap.on_step_end(next_step)   # after; next_step = step + 1

    Triggers: ``capture_step`` forces a window at that step; with
    ``regression_factor`` k > 0, a window also arms when the step-time
    p95 over the trailing window exceeds k × its median (needs at least
    8 samples).  Each finished window writes
    ``<output_dir>/capture_step<N>/report.json``.
    """

    MIN_SAMPLES = 8

    def __init__(self, cfg, telemetry=None):
        self.cfg = cfg
        self.telemetry = telemetry
        self.output_dir = cfg.output_dir
        self.num_steps = max(1, int(cfg.num_steps))
        self.budget_left = max(0, int(cfg.budget))
        self.capture_step = int(cfg.capture_step)
        self.regression_factor = float(cfg.regression_factor)
        self.device_substr = getattr(cfg, "device_substr", "TPU")
        self._times: Deque[float] = deque(maxlen=max(8, int(cfg.window)))
        self._profiler: Optional[TraceProfiler] = None
        self._armed_at = 0
        self._span_base: Optional[Dict] = None  # tracer totals at arming
        self.reports: list = []   # report paths written this process

    # -- trigger logic ---------------------------------------------------
    def _regressed(self) -> bool:
        return trailing_regressed(list(self._times),
                                  self.regression_factor,
                                  self.MIN_SAMPLES)

    def observe_step_time(self, wall_time_s: float) -> None:
        self._times.append(float(wall_time_s))

    # -- engine hooks ----------------------------------------------------
    def on_step_start(self, step: int) -> None:
        if self._profiler is not None or self.budget_left <= 0:
            return
        forced = self.capture_step and step == self.capture_step
        if not forced and not self._regressed():
            return
        reason = "forced" if forced else "regression"
        logdir = os.path.join(self.output_dir, f"capture_step{step}")
        prof = TraceProfiler(logdir, start_step=step,
                             num_steps=self.num_steps)
        prof.maybe_start(step)
        if not prof.active:   # another profiler owns the backend
            return
        self._profiler = prof
        self._armed_at = step
        self._span_base = self._span_totals()
        self.budget_left -= 1
        logger.info(f"telemetry capture: armed at step {step} "
                    f"({reason}; {self.budget_left} capture(s) left)")

    def _span_totals(self) -> Optional[Dict]:
        """Per-span totals + drop counter from the hub's tracer
        (``None`` when tracing is off — the spans block then reports
        zeros).  The summary covers the tracer's BOUNDED event ring, so
        a base/now diff is only valid while nothing was evicted."""
        tracer = getattr(self.telemetry, "tracer", None) \
            if self.telemetry is not None else None
        if tracer is None or not getattr(tracer, "enabled", False):
            return None
        return {"summary": tracer.summary(),
                "dropped": tracer.dropped_events}

    def _span_window(self) -> Optional[Dict]:
        """Per-span totals accumulated SINCE the window armed (the
        report must describe only the captured steps)."""
        if self._span_base is None:
            return None
        now = self._span_totals()
        if now is None:
            return None
        if now["dropped"] != self._span_base["dropped"]:
            # the tracer's bounded ring wrapped during the window:
            # events from the base snapshot were evicted, so the diff
            # would under-count (or go negative) — degrade to no spans
            # rather than report a wrong overlap estimate
            logger.warning("telemetry capture: tracer ring wrapped during "
                           "the window; spans estimate omitted")
            return None
        base_sum = self._span_base["summary"]
        out = {}
        for name, row in now["summary"].items():
            base = base_sum.get(name, {"count": 0, "total_ms": 0.0})
            d_count = max(0, row["count"] - base["count"])
            d_ms = max(0.0, round(row["total_ms"] - base["total_ms"], 3))
            if d_count or d_ms:
                out[name] = {"count": d_count, "total_ms": d_ms}
        return out

    def on_step_end(self, next_step: int,
                    wall_time_s: Optional[float] = None) -> None:
        if wall_time_s is not None:
            self.observe_step_time(wall_time_s)
        prof = self._profiler
        if prof is None:
            return
        prof.maybe_stop(next_step)
        if prof.active:
            return          # window spans more steps
        self._profiler = None
        self._write_report(prof.output_dir)

    def _write_report(self, logdir: str) -> Optional[str]:
        rec = self.telemetry.last_record if self.telemetry else None
        if rec is not None and not (self._armed_at <= rec.step
                                    < self._armed_at + self.num_steps):
            # interval-thinned telemetry: the last record describes an
            # OLDER step than the capture window — cross-checking the
            # trace against it would report a phantom MFU disagreement
            rec = None
        report = build_capture_report(
            logdir, device_substr=self.device_substr, step_record=rec,
            span_totals=self._span_window(),
            static_memory=getattr(self.telemetry, "static_memory", None)
            if self.telemetry is not None else None)
        self._span_base = None
        if rec is None and self.telemetry is not None:
            report["note"] = (report["note"] + "; no StepRecord inside "
                              "the capture window (interval-thinned "
                              "telemetry) — mfu_cross_check omitted"
                              ).lstrip("; ")
        report["armed_at_step"] = self._armed_at
        report["step"] = self._armed_at
        report["num_steps"] = self.num_steps
        path = os.path.join(logdir, "report.json")
        try:
            os.makedirs(logdir, exist_ok=True)
            with open(path, "w", encoding="utf-8") as f:
                json.dump(report, f, indent=1, sort_keys=True)
        except OSError as e:
            logger.warning(f"telemetry capture: report write failed: {e}")
            return None
        logger.info(
            f"telemetry capture: report at {path} "
            f"(overlap_fraction={report['overlap_fraction']})")
        self.reports.append(path)
        return path

    def close(self) -> None:
        """Flush a window cut short by the end of training."""
        prof = self._profiler
        if prof is None:
            return
        self._profiler = None
        prof.close()
        self._write_report(prof.output_dir)
