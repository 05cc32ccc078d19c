"""Run ledger & regression sentinel (telemetry/ledger.py +
tools/obs_report.py; docs/OBSERVABILITY.md "Run ledger & regression
sentinel").

Covers the tier-1 acceptance set:

* backfill — every BENCH_r*/BENCH_MEASURED_r*.json of a synthetic
  history (tests/conftest.py ``bench_history``) parses into rollups, the
  trajectory spans r01→r18, and the r04-carried rows come out ``stale``
  with a runnable requeue command attached;
* planted regressions — an MFU cliff, a TTFT-p95 regression, a goodput
  gap, and an SLO-burn spike are each detected with the right verdict /
  anomaly kind, and the planted-regression gate exits 1;
* jittered-in-band series produce ZERO findings (no false positives);
* the real gate: ``obs_report --gate`` on in-session smoke artifacts
  (written through the real Telemetry + write_manifest path) against
  the committed ``tools/obs_baseline.json`` is clean.
"""

import importlib.util
import json
import os

import pytest

from deepspeed_tpu.telemetry import ledger

REPO = os.path.join(os.path.dirname(__file__), "..")


def _load_tool(name):
    path = os.path.join(REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _train_records(n, wall_s=0.1, mfu=0.5, goodput=1.0):
    return [{"kind": "train", "step": i + 1, "wall_time_s": wall_s,
             "mfu": mfu, "goodput": goodput, "tokens_per_sec": 1000.0}
            for i in range(n)]


# ----------------------------------------------------------------------
# backfill: the committed history parses, end to end
# ----------------------------------------------------------------------
def test_backfill_parses_all_committed_bench_files(bench_history):
    rollups = ledger.load_bench_history(bench_history)
    # 3 primaries + 5 measured (r04) + 4 rounds x 5 carried rows
    assert len(rollups) == 28
    rounds = {r["round"] for r in rollups if r["round"] is not None}
    assert min(rounds) == 1 and max(rounds) == 18
    rows = {r["row"] for r in rollups}
    assert {"gpt2_350m", "llama8b_class_zero3", "longseq_flash",
            "peak_params", "v2_decode"} <= rows
    assert [r["error"] for r in rollups if r["round"] == 3] != [None]
    for r in rollups:
        assert tuple(sorted(r)) == ledger.ROLLUP_KEYS
        assert tuple(sorted(r["train"])) == ledger.ROLLUP_TRAIN_KEYS
        assert tuple(sorted(r["serve"])) == ledger.ROLLUP_SERVE_KEYS


def test_backfill_flags_carried_rows_stale_with_requeue_cmds(bench_history):
    rollups = ledger.load_bench_history(bench_history)
    stale = {r["row"] for r in rollups if r["stale"]}
    assert stale == {"gpt2_350m", "llama8b_class_zero3", "longseq_flash",
                     "peak_params", "v2_decode"}
    # nothing measured at or before r04 is stale
    for r in rollups:
        if r["round"] is not None and r["round"] <= ledger.LAST_MEASURED_ROUND:
            assert not r["stale"]
    requeue = ledger.attach_requeue_cmds(
        rollups, ledger.collect_queued_cmds(bench_history))
    assert set(requeue) == stale
    for row, cmd in requeue.items():
        assert f"--row {row}" in cmd or "--peak-entry" in cmd


def test_queued_cmd_row_names_are_clean(bench_history):
    # the for-loop wrapped queue entries must not leak shell punctuation
    # into row names ("peak_params;" would silently duplicate the key)
    queued = ledger.collect_queued_cmds(bench_history)
    assert "peak_params" in queued
    for name in queued:
        assert name == name.strip(";&|")
    loop = ("for CB in 1 2; do DSTPU_CHUNK_BYTES=$CB "
            "python bench.py --row peak_params; done")
    assert ledger._row_name_from_cmd(loop) == "peak_params"


# ----------------------------------------------------------------------
# sentinel verdicts: planted regression / improvement / stale / new
# ----------------------------------------------------------------------
def test_planted_ttft_p95_regression_detected_and_gates():
    rollup = ledger.rollup_from_bench_row(
        {"metric": "serve_load_sim", "value": 900.0, "unit": "tokens/s",
         "ttft_p95_ms": 400.0}, round_no=19)
    baseline = {"rows": {"serve_load": {"serve.ttft_p95_ms": 100.0,
                                        "serve.tokens_per_sec": 1000.0}},
                "smoke_rows": {}, "suppress": []}
    findings = ledger.diff_rollups([rollup], baseline)
    by_metric = {f["metric"]: f for f in findings}
    assert by_metric["serve.ttft_p95_ms"]["verdict"] == "regressed"
    assert by_metric["serve.tokens_per_sec"]["verdict"] == "flat"
    gate = ledger.gate_findings(findings, baseline["suppress"])
    assert [f["metric"] for f in gate] == ["serve.ttft_p95_ms"]
    # fingerprint suppression clears the gate without touching verdicts
    fp = by_metric["serve.ttft_p95_ms"]["fingerprint"]
    assert ledger.gate_findings(findings, [fp]) == []
    assert fp == ledger.fingerprint("serve_load", "serve.ttft_p95_ms",
                                    "regressed")


def test_stale_and_new_and_missing_never_gate():
    rollup = ledger.rollup_from_bench_row(
        {"metric": "gpt2_350m_train", "value": 1000.0,
         "unit": "tokens/s", "mfu": 0.4}, round_no=19)
    rollup["stale"] = True
    baseline = {"rows": {"gpt2_350m": {"value": 1000.0,
                                       "train.goodput": 1.0}},
                "smoke_rows": {}, "suppress": []}
    requeue = {"gpt2_350m": "python bench.py --row gpt2_350m"}
    findings = ledger.diff_rollups([rollup], baseline, requeue)
    by_metric = {f["metric"]: f for f in findings}
    assert by_metric["value"]["verdict"] == "stale"
    assert by_metric["value"]["requeue_cmd"] == requeue["gpt2_350m"]
    assert by_metric["train.mfu"]["verdict"] == "new"
    assert by_metric["train.goodput"]["verdict"] == "missing"
    assert ledger.gate_findings(findings) == []


def test_smoke_rollup_diffs_smoke_rows_not_chip_rows():
    chip = ledger.rollup_from_bench_row(
        {"metric": "gpt2_350m_train", "value": 1000.0,
         "unit": "tokens/s"}, round_no=4)
    smoke = ledger.rollup_from_bench_row(
        {"metric": "gpt2_350m_train", "goodput": 0.5}, round_no=None,
        source="manifest")
    smoke["smoke"] = True
    baseline = {"rows": {"gpt2_350m": {"value": 1000.0}},
                "smoke_rows": {"gpt2_350m": {"train.goodput": 1.0}},
                "suppress": []}
    findings = ledger.diff_rollups([chip, smoke], baseline)
    verdicts = {(f["row"], f["metric"]): f["verdict"] for f in findings}
    # the chip row must not shadow the smoke run of the same name
    assert verdicts[("gpt2_350m", "value")] == "flat"
    assert verdicts[("gpt2_350m", "train.goodput")] == "regressed"


# ----------------------------------------------------------------------
# in-run anomaly scan: planted anomalies + jittered-in-band clean run
# ----------------------------------------------------------------------
def test_planted_step_time_spike_and_mfu_cliff_detected():
    records = _train_records(12)
    records.append({"kind": "train", "step": 13, "wall_time_s": 0.5,
                    "mfu": 0.1, "goodput": 1.0})
    trace = [{"ph": "X", "name": "train.step", "ts": 1, "dur": 2,
              "args": {"step": 13, "trace_id": "t-13"}}]
    anomalies = ledger.scan_run(records, trace_events=trace,
                                run_id="run-x")
    kinds = {a["kind"] for a in anomalies}
    assert kinds == {"step_time_spike", "mfu_cliff"}
    for a in anomalies:
        assert tuple(sorted(a)) == ledger.ANOMALY_KEYS
        assert a["step"] == 13 and a["run_id"] == "run-x"
        # cross-linked to the covering trace span
        assert a["trace_span"]["name"] == "train.step"
        assert a["trace_span"]["trace_id"] == "t-13"


def test_planted_goodput_gap_detected():
    records = _train_records(10)
    records.append({"kind": "train", "step": 11, "wall_time_s": 0.1,
                    "mfu": 0.5, "goodput": 0.8})
    anomalies = ledger.scan_run(records)
    gaps = [a for a in anomalies if a["kind"] == "goodput_gap"]
    assert len(gaps) == 1
    assert gaps[0]["step"] == 11
    assert gaps[0]["value"] == pytest.approx(0.8)
    assert gaps[0]["threshold"] == pytest.approx(1.0)


def test_recovery_record_is_a_goodput_gap():
    records = _train_records(5)
    records.append({"kind": "recovery", "step": 6, "wall_time_s": 42.0,
                    "goodput": 0.9})
    anomalies = ledger.scan_run(records)
    assert [a["kind"] for a in anomalies] == ["goodput_gap"]


def test_planted_slo_burn_spike_detected_per_tier():
    fleet = ([{"tier": "decode", "slo_violation": 0} for _ in range(5)]
             + [{"tier": "decode", "slo_violation": 1}]
             + [{"tier": "prefill", "slo_violation": 0}
                for _ in range(6)])
    anomalies = ledger.scan_run([], fleet_rows=fleet, objective=0.99)
    burns = [a for a in anomalies if a["kind"] == "slo_burn_spike"]
    assert len(burns) == 1 and burns[0]["tier"] == "decode"
    assert burns[0]["value"] >= 1.0


def test_jittered_in_band_run_has_zero_findings():
    # ±20% step-time jitter, mild MFU wobble, monotone goodput, no SLO
    # violations: the scan and the sentinel must both stay silent
    jitter = [0.10, 0.12, 0.09, 0.11, 0.10, 0.08, 0.12, 0.11,
              0.09, 0.10, 0.11, 0.12, 0.10, 0.09, 0.11, 0.10]
    records = [{"kind": "train", "step": i + 1, "wall_time_s": w,
                "mfu": 0.5 + 0.02 * (i % 3), "goodput": 1.0,
                "tokens_per_sec": 1000.0 + 10 * (i % 5)}
               for i, w in enumerate(jitter)]
    fleet = [{"tier": "decode", "slo_violation": 0} for _ in range(30)]
    assert ledger.scan_run(records, fleet_rows=fleet) == []

    rollup = ledger.rollup_from_bench_row(
        {"metric": "gpt2_350m_train", "value": 1020.0,
         "unit": "tokens/s", "mfu": 0.51}, round_no=19)
    baseline = {"rows": {"gpt2_350m": {"value": 1000.0,
                                       "train.mfu": 0.50,
                                       "train.tokens_per_sec": 1000.0}},
                "smoke_rows": {}, "suppress": []}
    findings = ledger.diff_rollups([rollup], baseline)
    assert {f["verdict"] for f in findings} == {"flat"}
    assert ledger.gate_findings(findings) == []


# ----------------------------------------------------------------------
# manifest round-trip + obs_report CLI (trend, gate both ways)
# ----------------------------------------------------------------------
def _write_run(tmp_path, name, *, smoke=True, skipped=0, steps=4):
    """Write telemetry artifacts through the REAL write path (Telemetry
    + write_manifest) and return the manifest path.  ``skipped`` plants
    that many overflow-skipped trailing steps, dragging cumulative
    goodput below 1.0."""
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.telemetry import Telemetry

    jsonl = str(tmp_path / f"{name}.jsonl")
    run_id = ledger.new_run_id(name)
    tel = Telemetry(TelemetryConfig(enabled=True, jsonl_path=jsonl,
                                    run_id=run_id))
    for s in range(1, steps + 1):
        tel.record_train_step(step=s, wall_time_s=0.1, tokens=128,
                              skipped=(s > steps - skipped))
    tel.close()
    return ledger.write_manifest(
        str(tmp_path / f"{name}.manifest.json"), name, run_id,
        {"telemetry_jsonl": jsonl}, smoke=smoke)


def test_manifest_roundtrip_rollup_and_run_id(tmp_path):
    path = _write_run(tmp_path, "gpt2_350m")
    manifest = json.load(open(path))
    assert tuple(sorted(manifest)) == ledger.MANIFEST_KEYS
    sv = manifest["schema_versions"]
    assert sv["ledger"] == ledger.LEDGER_SCHEMA
    assert sv["step_record"] == 3 and sv["tier_snapshot"] == 2
    r = ledger.rollup_from_manifest(path)
    assert r["row"] == "gpt2_350m" and r["smoke"] and r["source"] == "manifest"
    assert r["run_id"] == manifest["run_id"] != ""
    assert r["train"]["goodput"] == 1.0
    assert r["train"]["step_time_p50_ms"] == pytest.approx(100.0, rel=0.01)
    # the run_id is stamped on every record too
    for rec in (json.loads(line) for line in open(
            str(tmp_path / "gpt2_350m.jsonl"))):
        assert rec["run_id"] == manifest["run_id"]
        assert rec["schema"] == 3


def test_obs_report_gate_clean_on_smoke_run_vs_committed_baseline(
        tmp_path, capsys, monkeypatch, bench_history):
    """The tier-1 gate: a fresh in-session smoke run diffed against the
    committed tools/obs_baseline.json must be clean, and the trend must
    span the full bench history r01→r18."""
    _write_run(tmp_path, "gpt2_350m")
    obs = _load_tool("obs_report")
    monkeypatch.setattr(obs, "REPO", bench_history)
    rc = obs.main(["--scan", str(tmp_path), "--gate"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "GATE: clean" in out
    assert "r01" in out and "r18" in out       # trajectory renders
    assert "stale rows" in out                 # requeue worklist renders


def test_obs_report_gate_exits_1_on_planted_regression_set(
        tmp_path, capsys):
    path = _write_run(tmp_path, "gpt2_350m", skipped=2)
    # two skipped steps drop cumulative goodput to 0.5, below the
    # baselined 1.0 (tolerance 2%) -> regressed -> gate trips
    assert ledger.rollup_from_manifest(path)["train"]["goodput"] < 0.98
    obs = _load_tool("obs_report")
    rc = obs.main(["--scan", str(tmp_path), "--gate", "--no-history"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "GATE: 1 unbaselined regression(s)" in out
    assert "gpt2_350m.train.goodput" in out
