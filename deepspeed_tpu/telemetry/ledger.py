"""Run ledger: the READ side of the telemetry layer.

Eighteen rounds of instrumentation write StepRecord JSONL, Chrome
traces, TierSnapshot fleet logs, SLO blocks, flight bundles, and
``BENCH_*.json`` row tables — and until this module nothing ingested
them across runs.  The ledger turns that artifact pile into an
auditable trajectory:

* :func:`new_run_id` / :func:`write_manifest` — every ``bench.py`` row
  stamps ONE ``run_id`` through Telemetry / Tracer / FleetSampler and
  writes a ``manifest.json`` next to its artifacts, so stitching a run
  back together never relies on directory-listing guesses.
* :func:`rollup_from_manifest` / :func:`rollup_from_bench_row` /
  :func:`load_bench_history` — parse any manifest (or the committed
  ``BENCH_r*`` / ``BENCH_MEASURED_r*`` history) into a typed,
  frozen-key per-run **Rollup** (:data:`ROLLUP_KEYS` and the per-domain
  ``train`` / ``serve`` / ``recovery`` sub-keys), computed through
  ``telemetry.derive`` — the SAME module bench.py's row math uses, so
  row math and ledger math cannot drift.
* :func:`diff_rollups` / :func:`gate_findings` — the regression
  sentinel: per-metric direction + noise-tolerance bands
  (:data:`METRIC_POLICY`), the frozen verdict vocabulary
  (:data:`VERDICTS`), and graft_lint-style fingerprint suppression via
  ``tools/obs_baseline.json``.
* :func:`scan_run` — the in-run anomaly scan (:data:`ANOMALY_KINDS`):
  step-time spikes vs trailing median (the capture-trigger heuristic,
  via ``derive``), MFU cliffs, goodput gaps, SLO-burn acceleration —
  each cross-linked to the covering trace span and any flight bundle.
* :func:`plan_drift` — joins planner evidence with a measured rollup
  into per-metric drift ratios, the calibration input ROADMAP item 3
  asks to feed back into the analytic cost model.

All key sets and vocabularies here are FROZEN and linted by
``tools/telemetry_check.py`` (``check_obs_ledger``) against
docs/OBSERVABILITY.md — the StepRecord contract, applied to the reader.
Pure stdlib, no jax: auditing history needs no device.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deepspeed_tpu.telemetry import derive

# ---------------------------------------------------------------------------
# Frozen vocabularies (docs/OBSERVABILITY.md "Run ledger & regression
# sentinel"; linted by tools/telemetry_check.py check_obs_ledger)
# ---------------------------------------------------------------------------

LEDGER_SCHEMA = 1

#: file name a bench row writes next to its artifacts
MANIFEST_NAME = "manifest.json"

#: top-level key set of one manifest.json
MANIFEST_KEYS = ("artifacts", "created_utc", "ledger_schema", "row",
                 "run_id", "schema_versions", "smoke")

#: the artifact slots a manifest links (absent artifact -> null)
MANIFEST_ARTIFACT_KEYS = ("fleet_jsonl", "flight_dir", "resolved_config",
                          "slo", "telemetry_jsonl", "trace_json")

#: top-level key set of one per-run Rollup
ROLLUP_KEYS = ("error", "metric", "recovery", "round", "row", "run_id",
               "serve", "smoke", "source", "stale", "train", "unit",
               "value", "vs_baseline")

#: train-domain rollup keys (``rollup["train"]``)
ROLLUP_TRAIN_KEYS = ("comm_bytes_by_collective", "goodput",
                     "hbm_peak_bytes", "mfu", "offload_overlap_fraction",
                     "step_time_p50_ms", "step_time_p95_ms",
                     "tokens_per_sec")

#: serve-domain rollup keys (``rollup["serve"]``)
ROLLUP_SERVE_KEYS = ("error_budget_burn", "handoff_bytes_per_req",
                     "prefix_hit_rate", "queue_wait_p95_ms",
                     "slo_attainment", "spec_accept_rate",
                     "tokens_per_sec", "tpot_p50_ms", "tpot_p95_ms",
                     "ttft_p50_ms", "ttft_p95_ms")

#: recovery-domain rollup keys (``rollup["recovery"]``)
ROLLUP_RECOVERY_KEYS = ("goodput_after", "loss_gap", "outage_s")

#: frozen sentinel verdicts (one per compared metric)
VERDICTS = ("flat", "improved", "missing", "new", "regressed", "stale")

#: frozen anomaly kinds the in-run scan can emit
ANOMALY_KINDS = ("goodput_gap", "heal_latency", "mfu_cliff",
                 "slo_burn_spike", "step_time_spike")

#: key set of one anomaly record
ANOMALY_KEYS = ("flight_bundle", "kind", "run_id", "step", "threshold",
                "tier", "trace_span", "value")

#: key set of one plan-vs-actual drift entry (ratio = actual/predicted)
DRIFT_KEYS = ("actual", "metric", "predicted", "ratio", "row")

#: key set of one sentinel finding
FINDING_KEYS = ("baseline", "current", "delta", "fingerprint", "metric",
                "requeue_cmd", "row", "verdict")

# per-metric-path comparison policy: direction ("higher" / "lower" is
# better) + relative noise-tolerance band.  Paths not listed fall back
# to _policy_for's name/unit heuristic.
METRIC_POLICY: Dict[str, Tuple[str, float]] = {
    "value": ("higher", 0.10),
    "vs_baseline": ("higher", 0.10),
    "train.tokens_per_sec": ("higher", 0.10),
    "train.mfu": ("higher", 0.10),
    "train.step_time_p50_ms": ("lower", 0.15),
    "train.step_time_p95_ms": ("lower", 0.25),
    "train.goodput": ("higher", 0.02),
    "train.hbm_peak_bytes": ("lower", 0.10),
    "train.offload_overlap_fraction": ("higher", 0.15),
    "serve.tokens_per_sec": ("higher", 0.10),
    "serve.ttft_p50_ms": ("lower", 0.25),
    "serve.ttft_p95_ms": ("lower", 0.25),
    "serve.tpot_p50_ms": ("lower", 0.25),
    "serve.tpot_p95_ms": ("lower", 0.25),
    "serve.queue_wait_p95_ms": ("lower", 0.25),
    "serve.slo_attainment": ("higher", 0.02),
    "serve.error_budget_burn": ("lower", 0.50),
    "serve.handoff_bytes_per_req": ("lower", 0.20),
    "serve.spec_accept_rate": ("higher", 0.10),
    "serve.prefix_hit_rate": ("higher", 0.10),
    "recovery.outage_s": ("lower", 0.30),
    "recovery.loss_gap": ("lower", 0.50),
    "recovery.goodput_after": ("higher", 0.05),
}

# the last round with real on-chip measurements; chip rows carried
# forward past it are `stale` (satellite: tools/bench_backlog.py flags
# the same boundary)
LAST_MEASURED_ROUND = 4


# ---------------------------------------------------------------------------
# run_id + manifest (the write side bench.py calls)
# ---------------------------------------------------------------------------

def new_run_id(name: str) -> str:
    """One process-unique, sortable run id: ``<row>-<utc>-<pid>``."""
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    return f"{name}-{stamp}-{os.getpid():x}"


def _schema_versions() -> Dict[str, Optional[int]]:
    from deepspeed_tpu.telemetry.record import SCHEMA_VERSION
    try:
        from deepspeed_tpu.serving.fleet import TIER_SNAPSHOT_SCHEMA
    except Exception:       # serving layer absent/broken: still stitchable
        TIER_SNAPSHOT_SCHEMA = None
    return {"ledger": LEDGER_SCHEMA, "step_record": SCHEMA_VERSION,
            "tier_snapshot": TIER_SNAPSHOT_SCHEMA}


def write_manifest(path: str, row_name: str, run_id: str,
                   artifacts: Dict[str, Any], smoke: bool = False,
                   row: Optional[dict] = None) -> str:
    """Write one RunManifest (frozen :data:`MANIFEST_KEYS`) to ``path``.

    ``artifacts`` values outside :data:`MANIFEST_ARTIFACT_KEYS` are
    rejected — the slot list is part of the frozen contract.  ``row``
    optionally embeds the full bench row dict so a manifest is
    self-contained even if the one-line-per-row stdout log is lost.
    """
    bad = set(artifacts) - set(MANIFEST_ARTIFACT_KEYS)
    if bad:
        raise ValueError(f"unknown manifest artifact keys {sorted(bad)} "
                         f"(allowed: {MANIFEST_ARTIFACT_KEYS})")
    if row_name:
        row = dict(row) if row else {"metric": row_name}
        row.setdefault("_row_name", row_name)
    manifest = {
        "artifacts": {k: artifacts.get(k) for k in MANIFEST_ARTIFACT_KEYS},
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "ledger_schema": LEDGER_SCHEMA,
        "row": row,
        "run_id": str(run_id),
        "schema_versions": _schema_versions(),
        "smoke": bool(smoke),
    }
    assert tuple(sorted(manifest)) == MANIFEST_KEYS
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=1, sort_keys=True, default=float)
    os.replace(tmp, path)
    return path


def _read_jsonl(path: str) -> List[dict]:
    out: List[dict] = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


# ---------------------------------------------------------------------------
# Rollups
# ---------------------------------------------------------------------------

def _empty_rollup(row: str, source: str) -> Dict[str, Any]:
    return {
        "error": None, "metric": None,
        "recovery": {k: None for k in ROLLUP_RECOVERY_KEYS},
        "round": None, "row": row, "run_id": "",
        "serve": {k: None for k in ROLLUP_SERVE_KEYS},
        "smoke": False, "source": source, "stale": False,
        "train": {k: None for k in ROLLUP_TRAIN_KEYS},
        "unit": None, "value": None, "vs_baseline": None,
    }


def _row_name_from_cmd(cmd: str) -> Optional[str]:
    m = re.search(r"--row\s+([A-Za-z0-9_]+)", cmd or "")
    if m:
        return m.group(1)
    if "--peak-entry" in (cmd or ""):
        return "peak_params"
    return None


def _row_name_from_metric(metric: str) -> str:
    """Best-effort metric -> bench row name for history rows without a
    ``cmd`` field (early BENCH_r0* primaries)."""
    known = ("gpt2_350m_commquant", "gpt2_350m_autosched", "gpt2_350m",
             "llama8b_class_zero3", "longseq_flash", "longseq_ring",
             "peak_params", "v2_decode", "serve_load_multi",
             "serve_load", "serve_disagg", "chaos", "plan_validate")
    for name in known:
        if metric.startswith(name):
            return name
    aliases = {"llama3_8b_class": "llama8b_class_zero3",
               "longseq_32768_flash": "longseq_flash"}
    for prefix, name in aliases.items():
        if metric.startswith(prefix):
            return name
    return metric


def rollup_from_bench_row(row: dict, round_no: Optional[int] = None,
                          source: str = "chip") -> Dict[str, Any]:
    """One committed bench-row dict -> one frozen-key Rollup.

    Handles every historical shape: the r01 primary (metric/value/unit
    only), error rows (``error`` key, value 0), the r04
    measured rows (cmd + mfu + note), and current rows with slo blocks
    and disagg suffixes.
    """
    metric = str(row.get("metric", ""))
    name = (_row_name_from_cmd(str(row.get("cmd", "")))
            or row.get("_row_name") or _row_name_from_metric(metric))
    r = _empty_rollup(name, source)
    r["metric"] = metric or None
    r["round"] = round_no
    r["run_id"] = str(row.get("run_id", "") or "")
    r["error"] = row.get("error")
    r["unit"] = row.get("unit")
    if isinstance(row.get("value"), (int, float)):
        r["value"] = float(row["value"])
    if isinstance(row.get("vs_baseline"), (int, float)):
        r["vs_baseline"] = float(row["vs_baseline"])

    def num(*keys):
        for k in keys:
            v = row.get(k)
            if isinstance(v, (int, float)):
                return float(v)
        return None

    train, serve, rec = r["train"], r["serve"], r["recovery"]
    serving_row = ("serve" in name or "decode" in name
                   or "prefill" in metric)
    if serving_row:
        serve["tokens_per_sec"] = (r["value"] if r["unit"] == "tokens/s"
                                   else None)
        serve["ttft_p50_ms"] = num("ttft_p50_ms", "ttft_p50_ms_disagg")
        serve["ttft_p95_ms"] = num("ttft_p95_ms", "ttft_p95_ms_disagg",
                                   "ttft_p95_ms_cache")
        serve["tpot_p50_ms"] = num("tpot_p50_ms", "tpot_p50_ms_disagg")
        serve["tpot_p95_ms"] = num("tpot_p95_ms", "tpot_p95_ms_disagg")
        serve["queue_wait_p95_ms"] = num("queue_wait_p95_ms")
        serve["handoff_bytes_per_req"] = num("handoff_bytes_per_req")
        serve["spec_accept_rate"] = num("spec_accept_rate")
        serve["prefix_hit_rate"] = num("prefix_hit_rate")
        slo = row.get("slo")
        if isinstance(slo, dict):
            serve["slo_attainment"] = num_of(slo.get("attainment"))
            serve["error_budget_burn"] = num_of(
                slo.get("error_budget_burn"))
    elif name == "chaos":
        rec["outage_s"] = num("recovery_s", "outage_s")
        rec["loss_gap"] = num("loss_gap")
        rec["goodput_after"] = num("goodput_after", "goodput")
    else:
        train["tokens_per_sec"] = (r["value"] if r["unit"] == "tokens/s"
                                   else num("tokens_per_sec"))
        train["mfu"] = num("mfu", "mfu_tuned")
        train["goodput"] = num("goodput")
        train["offload_overlap_fraction"] = num("offload_overlap_fraction",
                                                "overlap_fraction")
    return r


def num_of(v) -> Optional[float]:
    return float(v) if isinstance(v, (int, float)) else None


def rollup_from_manifest(path: str) -> Dict[str, Any]:
    """One manifest.json -> one Rollup, recomputing the deep stats from
    the linked StepRecord / TierSnapshot JSONL through ``derive`` (the
    same math bench.py's rows use)."""
    with open(path, "r", encoding="utf-8") as f:
        manifest = json.load(f)
    row = manifest.get("row") or {}
    r = rollup_from_bench_row(row, round_no=None, source="manifest")
    r["run_id"] = str(manifest.get("run_id", "") or r["run_id"])
    r["smoke"] = bool(manifest.get("smoke", False))
    arts = manifest.get("artifacts") or {}
    train, serve, rec = r["train"], r["serve"], r["recovery"]

    tel_path = arts.get("telemetry_jsonl")
    if tel_path and os.path.exists(tel_path):
        records = _read_jsonl(tel_path)
        steps = [x for x in records if x.get("kind") == "train"]
        recov = [x for x in records if x.get("kind") == "recovery"]
        if steps:
            times_ms = [1e3 * float(x.get("wall_time_s", 0.0))
                        for x in steps]
            train["step_time_p50_ms"] = round(derive.p50(times_ms), 3)
            train["step_time_p95_ms"] = round(derive.p95(times_ms), 3)
            tps = [float(x["tokens_per_sec"]) for x in steps
                   if x.get("tokens_per_sec")]
            if tps and train["tokens_per_sec"] is None:
                train["tokens_per_sec"] = round(derive.p50(tps), 1)
            mfus = [float(x["mfu"]) for x in steps if x.get("mfu")]
            if mfus and train["mfu"] is None:
                train["mfu"] = round(derive.p50(mfus), 4)
            train["goodput"] = num_of(steps[-1].get("goodput"))
            comm = steps[-1].get("comm") or {}
            train["comm_bytes_by_collective"] = {
                op: int(st.get("bytes", 0)) for op, st in comm.items()
            } or None
            peaks = [int(d.get("peak_bytes_in_use",
                               d.get("bytes_in_use", 0)))
                     for x in steps for d in (x.get("hbm") or {}).values()]
            train["hbm_peak_bytes"] = max(peaks) if peaks else None
            overlaps = [float(x["offload_overlap_fraction"]) for x in steps
                        if x.get("offload_overlap_fraction") is not None]
            if overlaps:
                train["offload_overlap_fraction"] = round(
                    derive.p50(overlaps), 4)
        if recov and rec["outage_s"] is None:
            rec["outage_s"] = round(sum(
                float(x.get("wall_time_s", 0.0)) for x in recov), 3)

    fleet_path = arts.get("fleet_jsonl")
    if fleet_path and os.path.exists(fleet_path):
        rows = _read_jsonl(fleet_path)
        # prefer the decode tier (the latency-bearing one), else unified
        by_tier: Dict[str, List[dict]] = {}
        for t in rows:
            by_tier.setdefault(str(t.get("tier", "")), []).append(t)
        tier = ("decode" if "decode" in by_tier
                else "unified" if "unified" in by_tier
                else (sorted(by_tier)[0] if by_tier else None))
        if tier:
            last = by_tier[tier][-1]
            for src, dst in (("ttft_p50_ms", "ttft_p50_ms"),
                             ("ttft_p95_ms", "ttft_p95_ms"),
                             ("tpot_p50_ms", "tpot_p50_ms"),
                             ("tpot_p95_ms", "tpot_p95_ms"),
                             ("queue_wait_p95_ms", "queue_wait_p95_ms")):
                if serve[dst] is None:
                    serve[dst] = num_of(last.get(src))
    slo = arts.get("slo") or row.get("slo")
    if isinstance(slo, dict):
        if serve["slo_attainment"] is None:
            serve["slo_attainment"] = num_of(slo.get("attainment"))
        if serve["error_budget_burn"] is None:
            serve["error_budget_burn"] = num_of(
                slo.get("error_budget_burn"))
    return r


# ---------------------------------------------------------------------------
# History backfill (the committed BENCH_r* / BENCH_MEASURED_r* files)
# ---------------------------------------------------------------------------

def load_bench_history(repo: str) -> List[Dict[str, Any]]:
    """Parse every committed ``BENCH_rNN.json`` and
    ``BENCH_MEASURED_rNN.json`` into rollups (source ``"chip"``).

    * ``BENCH_rNN`` carries a ``parsed`` primary row (an error row has
      an empty ``rows`` list — kept, with ``error`` set, so the
      trajectory shows the outage).
    * ``BENCH_MEASURED_r04`` has the last real ``rows``;
      r05+ carry ``rows_last_measured_r04`` forward — those rollups are
      marked ``stale`` with the latest queued re-measurement command
      attached by :func:`attach_requeue_cmds`.
    """
    rollups: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        rnd = int(m.group(1))
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            sub_rows = parsed.get("rows") or []
            primary = {k: v for k, v in parsed.items() if k != "rows"}
            rollups.append(rollup_from_bench_row(primary, rnd))
            for row in sub_rows:
                if isinstance(row, dict):
                    rollups.append(rollup_from_bench_row(row, rnd))
    for path in sorted(glob.glob(
            os.path.join(repo, "BENCH_MEASURED_r*.json"))):
        m = re.search(r"BENCH_MEASURED_r(\d+)\.json$", path)
        if not m:
            continue
        rnd = int(m.group(1))
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        for row in doc.get("rows") or []:
            if isinstance(row, dict):
                rollups.append(rollup_from_bench_row(row, rnd))
        for row in _carried_rows(doc, repo):
            r = rollup_from_bench_row(row, rnd)
            r["stale"] = rnd > LAST_MEASURED_ROUND
            rollups.append(r)
    return rollups


def _carried_rows(doc: dict, repo: str, depth: int = 0) -> List[dict]:
    """Resolve ``rows_last_measured_r04``: a literal row list (r05-r07)
    or a "see BENCH_MEASURED_rNN.json (carried forward unchanged)"
    string reference (r08+) chased to the referenced file's rows."""
    carried = doc.get("rows_last_measured_r04")
    if isinstance(carried, list):
        return [row for row in carried if isinstance(row, dict)]
    if isinstance(carried, str) and depth < 4:
        m = re.search(r"(BENCH_MEASURED_r\d+\.json)", carried)
        if m:
            ref = os.path.join(repo, m.group(1))
            if os.path.exists(ref):
                with open(ref, "r", encoding="utf-8") as f:
                    ref_doc = json.load(f)
                rows = [row for row in (ref_doc.get("rows") or [])
                        if isinstance(row, dict)]
                return rows or _carried_rows(ref_doc, repo, depth + 1)
    return []


def collect_queued_cmds(repo: str) -> Dict[str, str]:
    """{row_name: latest queued re-measurement command} from every
    ``queued_measurements_rNN`` list in the measured files."""
    out: Dict[str, str] = {}
    for path in sorted(glob.glob(
            os.path.join(repo, "BENCH_MEASURED_r*.json"))):
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        for key in sorted(doc):
            if not key.startswith("queued_measurements"):
                continue
            for entry in doc[key] or []:
                cmd = str(entry.get("cmd", ""))
                name = _row_name_from_cmd(cmd)
                if name:
                    out[name] = cmd       # later rounds overwrite: latest wins
    return out


def attach_requeue_cmds(rollups: Sequence[Dict[str, Any]],
                        queued: Dict[str, str]) -> Dict[str, str]:
    """{stale row_name: requeue cmd} for the stale rollups present.
    Rows with no queued entry fall back to their own historic cmd shape
    (``python bench.py --row <name>``)."""
    out: Dict[str, str] = {}
    for r in rollups:
        if r.get("stale"):
            out[r["row"]] = queued.get(
                r["row"], f"python bench.py --row {r['row']}")
    return out


def latest_rollups(rollups: Sequence[Dict[str, Any]]
                   ) -> Dict[str, Dict[str, Any]]:
    """{row_name: most-recent non-error rollup} (highest round wins;
    error rollups only win when a row never measured cleanly)."""
    out: Dict[str, Dict[str, Any]] = {}
    for r in sorted(rollups, key=lambda x: (x["round"] is not None,
                                            x["round"] or 0)):
        cur = out.get(r["row"])
        if r.get("error") and cur is not None and not cur.get("error"):
            continue
        out[r["row"]] = r
    return out


# ---------------------------------------------------------------------------
# Regression sentinel
# ---------------------------------------------------------------------------

def flatten_metrics(rollup: Dict[str, Any]) -> Dict[str, float]:
    """Rollup -> flat {metric_path: number} for diffing; dict-valued
    train.comm_bytes_by_collective fans out per collective."""
    out: Dict[str, float] = {}
    for key in ("value", "vs_baseline"):
        if isinstance(rollup.get(key), (int, float)):
            out[key] = float(rollup[key])
    for domain in ("train", "serve", "recovery"):
        for k, v in (rollup.get(domain) or {}).items():
            path = f"{domain}.{k}"
            if isinstance(v, dict):
                for sub, sv in v.items():
                    if isinstance(sv, (int, float)):
                        out[f"{path}.{sub}"] = float(sv)
            elif isinstance(v, (int, float)):
                out[path] = float(v)
    return out


_LOWER_BETTER_SUFFIXES = ("_ms", "_s", "_bytes", "bytes_per_req",
                          "error_budget_burn", "loss_gap", "outage_s")


def _policy_for(path: str, unit: Optional[str] = None
                ) -> Tuple[str, float]:
    """(direction, rel_tolerance) for one metric path; exact
    :data:`METRIC_POLICY` entry, else prefix match (per-collective comm
    bytes), else a name/unit heuristic."""
    if path in METRIC_POLICY:
        return METRIC_POLICY[path]
    for known, pol in METRIC_POLICY.items():
        if path.startswith(known + "."):
            return pol
    if path == "value" and unit in ("s", "ms"):
        return ("lower", 0.25)
    if any(path.endswith(sfx) or sfx.strip("_") in path
           for sfx in _LOWER_BETTER_SUFFIXES):
        return ("lower", 0.20)
    return ("higher", 0.10)


def fingerprint(row: str, metric: str, verdict: str) -> str:
    """Stable id for one finding — the suppression key in
    tools/obs_baseline.json (graft_lint's model)."""
    h = hashlib.sha256(f"obs|{row}|{metric}|{verdict}".encode()).hexdigest()
    return h[:12]


def _verdict(base: Optional[float], cur: Optional[float],
             direction: str, tol: float, stale: bool) -> Optional[str]:
    if base is None and cur is None:
        return None
    if base is None:
        return "new"
    if cur is None:
        return "missing"
    if base == 0:
        delta = 0.0 if cur == 0 else (1.0 if cur > 0 else -1.0)
    else:
        delta = (cur - base) / abs(base)
    gain = delta if direction == "higher" else -delta
    if gain > tol:
        verdict = "improved"
    elif gain < -tol:
        verdict = "regressed"
    else:
        verdict = "flat"
    if verdict == "flat" and stale:
        return "stale"
    return verdict


def diff_rollups(rollups: Sequence[Dict[str, Any]], baseline: dict,
                 requeue: Optional[Dict[str, str]] = None
                 ) -> List[Dict[str, Any]]:
    """Sentinel core: compare each rollup against the committed baseline
    (``rows`` for chip/history rollups, ``smoke_rows`` for smoke runs)
    and emit one finding (:data:`FINDING_KEYS`) per compared metric.

    A smoke rollup's metrics missing from ``smoke_rows`` are verdict
    ``new`` — smoke numbers are plumbing checks, not perf claims, so an
    unbaselined smoke metric never gates.
    """
    requeue = requeue or {}
    findings: List[Dict[str, Any]] = []
    # smoke and chip rollups of the SAME row diff against different
    # baseline sections — partition before taking latest, or a chip
    # history row would shadow the fresh smoke run of the same name
    latest: Dict[Tuple[bool, str], Dict[str, Any]] = {}
    for smoke_flag in (False, True):
        subset = [r for r in rollups
                  if bool(r.get("smoke")) == smoke_flag]
        for row_name, r in latest_rollups(subset).items():
            latest[(smoke_flag, row_name)] = r
    for smoke_flag, row_name in sorted(latest):
        r = latest[(smoke_flag, row_name)]
        section = "smoke_rows" if smoke_flag else "rows"
        base_row = (baseline.get(section) or {}).get(row_name, {})
        cur = flatten_metrics(r)
        for path in sorted(set(cur) | set(base_row)):
            direction, tol = _policy_for(path, r.get("unit"))
            v = _verdict(num_of(base_row.get(path)), cur.get(path),
                         direction, tol, bool(r.get("stale")))
            if v is None:
                continue
            findings.append({
                "baseline": num_of(base_row.get(path)),
                "current": cur.get(path),
                "delta": (None if base_row.get(path) in (None, 0)
                          or path not in cur else round(
                              (cur[path] - base_row[path])
                              / abs(base_row[path]), 4)),
                "fingerprint": fingerprint(row_name, path, v),
                "metric": path,
                "requeue_cmd": (requeue.get(row_name)
                                if r.get("stale") else None),
                "row": row_name,
                "verdict": v,
            })
    return findings


def load_baseline(path: Optional[str]) -> dict:
    if not path or not os.path.exists(path):
        return {"rows": {}, "smoke_rows": {}, "suppress": []}
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    doc.setdefault("rows", {})
    doc.setdefault("smoke_rows", {})
    doc.setdefault("suppress", [])
    return doc


def gate_findings(findings: Sequence[Dict[str, Any]],
                  suppress: Sequence[str] = ()
                  ) -> List[Dict[str, Any]]:
    """The findings that fail the gate: ``regressed`` and not
    fingerprint-suppressed.  ``stale`` / ``new`` / ``missing`` report
    but never gate — a carried-forward history must pass."""
    sup = set(suppress)
    return [f for f in findings
            if f["verdict"] == "regressed"
            and f["fingerprint"] not in sup]


# ---------------------------------------------------------------------------
# In-run anomaly scan
# ---------------------------------------------------------------------------

def _covering_span(trace_events: Sequence[dict], step: Optional[int]
                   ) -> Optional[Dict[str, Any]]:
    """The trace span whose args.step matches (train.step spans stamp
    it), else None — the cross-link from an anomaly to its window."""
    if step is None:
        return None
    for ev in trace_events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        if args.get("step") == step:
            return {"name": ev.get("name"), "ts": ev.get("ts"),
                    "dur": ev.get("dur"),
                    "trace_id": args.get("trace_id")}
    return None


def _latest_flight_bundle(flight_dir: Optional[str]) -> Optional[str]:
    if not flight_dir or not os.path.isdir(flight_dir):
        return None
    bundles = sorted(
        d for d in glob.glob(os.path.join(flight_dir, "*"))
        if os.path.isdir(d))
    return bundles[-1] if bundles else None


def scan_run(records: Sequence[dict], fleet_rows: Sequence[dict] = (),
             *, factor: float = 2.0, window: int = 32,
             min_samples: int = 8, mfu_cliff_ratio: float = 0.6,
             objective: float = 0.99, burn_window: int = 20,
             trace_events: Sequence[dict] = (),
             flight_dir: Optional[str] = None,
             run_id: str = "") -> List[Dict[str, Any]]:
    """Scan one run's StepRecords + fleet rows for anomalies
    (:data:`ANOMALY_KINDS`), each cross-linked to the covering trace
    span and the latest flight bundle (if any).

    * ``step_time_spike`` — wall time > ``factor`` × trailing median
      (the capture-trigger heuristic, shared via ``derive``).
    * ``mfu_cliff`` — MFU < ``mfu_cliff_ratio`` × trailing median.
    * ``goodput_gap`` — cumulative goodput dropped (a skipped step) or a
      recovery record interrupted progress.
    * ``slo_burn_spike`` — a tier's windowed error-budget burn crossed
      1.0 (budget for the window exhausted).
    * ``heal_latency`` — a ``fleet.heal`` respawn instant reported
      ``heal_s`` over the supervisor's ``deadline_s`` (the replica
      healed, but too slowly to count as self-healing); the anomaly's
      ``tier`` field carries the replica name.
    """
    bundle = _latest_flight_bundle(flight_dir)

    def anomaly(kind: str, step: Optional[int], value: float,
                threshold: float, tier: Optional[str] = None) -> dict:
        a = {"flight_bundle": bundle, "kind": kind, "run_id": run_id,
             "step": step, "threshold": round(threshold, 6),
             "tier": tier, "trace_span": _covering_span(trace_events, step),
             "value": round(value, 6)}
        assert tuple(sorted(a)) == ANOMALY_KEYS
        return a

    out: List[Dict[str, Any]] = []
    steps = [x for x in records if x.get("kind") == "train"]
    times = [float(x.get("wall_time_s", 0.0)) for x in steps]
    for i, value, threshold in derive.step_time_spikes(
            times, factor, window=window, min_samples=min_samples):
        out.append(anomaly("step_time_spike", int(steps[i]["step"]),
                           value, threshold))
    mfus = [float(x["mfu"]) if x.get("mfu") else None for x in steps]
    for i, value, threshold in derive.value_cliffs(
            mfus, mfu_cliff_ratio, window=window,
            min_samples=min_samples):
        out.append(anomaly("mfu_cliff", int(steps[i]["step"]),
                           value, threshold))
    prev_goodput: Optional[float] = None
    for x in records:
        g = num_of(x.get("goodput"))
        if x.get("kind") == "recovery":
            out.append(anomaly("goodput_gap", int(x.get("step", 0)),
                               g if g is not None else 0.0,
                               prev_goodput or 1.0))
        elif g is not None:
            if prev_goodput is not None and g < prev_goodput:
                out.append(anomaly("goodput_gap", int(x.get("step", 0)),
                                   g, prev_goodput))
            prev_goodput = g

    by_tier: Dict[str, List[dict]] = {}
    for t in fleet_rows:
        by_tier.setdefault(str(t.get("tier", "")), []).append(t)
    allowed_per_tick = 1.0 - objective
    for tier in sorted(by_tier):
        rows = by_tier[tier]
        flags = [int(bool(t.get("slo_violation", 0))) for t in rows]
        prev_burn = 0.0
        for i in range(len(flags)):
            lo = max(0, i + 1 - burn_window)
            n = i + 1 - lo
            viol = sum(flags[lo:i + 1])
            allowed = allowed_per_tick * n
            burn = (0.0 if viol == 0 else
                    (999.0 if allowed <= 0 else viol / allowed))
            if burn >= 1.0 and prev_burn < 1.0:
                out.append(anomaly("slo_burn_spike", i, burn, 1.0,
                                   tier=tier))
            prev_burn = burn

    for ev in trace_events:
        if ev.get("ph") != "i" or ev.get("name") != "fleet.heal":
            continue
        args = ev.get("args") or {}
        if args.get("state") != "respawned":
            continue
        heal_s = num_of(args.get("heal_s"))
        deadline = num_of(args.get("deadline_s"))
        if heal_s is not None and deadline and heal_s > deadline:
            out.append(anomaly("heal_latency", None, heal_s, deadline,
                               tier=str(args.get("replica", ""))))
    return out


def scan_manifest(path: str, **kw) -> List[Dict[str, Any]]:
    """Anomaly-scan the artifacts a manifest links."""
    with open(path, "r", encoding="utf-8") as f:
        manifest = json.load(f)
    arts = manifest.get("artifacts") or {}
    records: List[dict] = []
    tel = arts.get("telemetry_jsonl")
    if tel and os.path.exists(tel):
        records = _read_jsonl(tel)
    fleet_rows: List[dict] = []
    fl = arts.get("fleet_jsonl")
    if fl and os.path.exists(fl):
        fleet_rows = _read_jsonl(fl)
    trace_events: List[dict] = []
    tr = arts.get("trace_json")
    if tr and os.path.exists(tr):
        with open(tr, "r", encoding="utf-8") as f:
            trace_events = (json.load(f) or {}).get("traceEvents", [])
    slo = arts.get("slo") or {}
    objective = (float(slo.get("objective", 0.99))
                 if isinstance(slo, dict) else 0.99)
    kw.setdefault("objective", objective)
    kw.setdefault("flight_dir", arts.get("flight_dir"))
    kw.setdefault("run_id", str(manifest.get("run_id", "")))
    return scan_run(records, fleet_rows, trace_events=trace_events, **kw)


# ---------------------------------------------------------------------------
# Plan-vs-actual drift (ROADMAP item 3's calibration input)
# ---------------------------------------------------------------------------

def plan_drift(rollup: Dict[str, Any], evidence: Dict[str, Any]
               ) -> List[Dict[str, Any]]:
    """Join one planner evidence block (``PLAN_EVIDENCE_KEYS``) with one
    measured rollup into per-metric drift entries (:data:`DRIFT_KEYS`);
    ``ratio = actual / predicted`` (1.0 = the cost model was right).
    Pairs with a missing side are skipped — drift is only meaningful
    where both exist."""
    train = rollup.get("train") or {}
    comm = train.get("comm_bytes_by_collective") or {}
    actual_wire = (float(sum(comm.values())) if comm else None)
    pairs = (
        ("step_ms", num_of(evidence.get("predicted_step_ms")),
         train.get("step_time_p50_ms")),
        ("peak_bytes", num_of(evidence.get("predicted_peak_bytes")),
         train.get("hbm_peak_bytes")),
        ("overlap_fraction", num_of(evidence.get("overlap_fraction")),
         train.get("offload_overlap_fraction")),
        ("wire_bytes_total", num_of(evidence.get("wire_bytes_total")),
         actual_wire),
    )
    out: List[Dict[str, Any]] = []
    for metric, predicted, actual in pairs:
        if predicted in (None, 0) or actual is None:
            continue
        entry = {"actual": float(actual), "metric": metric,
                 "predicted": float(predicted),
                 "ratio": round(float(actual) / float(predicted), 4),
                 "row": rollup.get("row")}
        assert tuple(sorted(entry)) == DRIFT_KEYS
        out.append(entry)
    return out
