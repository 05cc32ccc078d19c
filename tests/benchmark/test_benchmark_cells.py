"""Every kind of cell end to end at a tiny preset on the CPU, in a
temporary copy of the benchmark to which the tiny configurations, traffic
mixes, cells and one new per-layer metric were ADDED as files and entries,
with no edit to a file that was there (``bench_tiny.make_copy``).  A run
here rehearses control flow and counts; its times mean nothing and go
nowhere."""

import time

import jax
import numpy as np
import pytest

import bench_tiny
from benchmark.lib import device, harness, manifest

TRAIN = {"train_tokens_per_s", "setup_s"}
OPEN = {"ttft_mean_ms", "token_gap_mean_ms", "setup_s"}
CLOSED = {"serve_tokens_per_s", "ttft_p95_ms", "token_gap_p95_ms", "setup_s"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return bench_tiny.make_copy(tmp_path_factory.mktemp("bench_copy"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """XLA:CPU programs read back from a persistent cache warn about the
    machine they were compiled on; the suite needs no cache."""
    monkeypatch.setattr(device, "setup_compile_cache", lambda: "(off)")
    # conftest's "highest" matmul precision is part of every jit cache
    # key, and a `with` would not reach the serve loop's thread: the
    # warm-up and the served steps must compile under the same setting
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield
    jax.config.update("jax_default_matmul_precision", was)


def _run(copy, cell, traced, seconds=1.5, seed=2 ** 31 + 5):
    return harness.run_cell(copy, cell, seed, seconds, traced,
                            time.perf_counter())


@pytest.mark.parametrize("cell,chips,names", [
    ("gpt2-tiny.tiny_steps", 1, TRAIN),
    ("opt-tiny.tiny_steps", 4, TRAIN),
    ("mistral-tiny.tiny_open", 1, OPEN),
    ("mistral-tiny.tiny_closed", 1, CLOSED)])
def test_cell_end_to_end(copy, cell, chips, names, monkeypatch):
    if chips != len(jax.devices()):
        # the four-chip cell runs on four of the suite's virtual devices
        monkeypatch.setattr(jax, "devices",
                            lambda *a, _d=jax.devices(): _d[:chips])
    out = _run(copy, cell, traced=False)
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == names
    for m in out["metrics"].values():
        assert np.isfinite(m["value"]) and m["value"] > 0 and m["unit"]
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips


@pytest.mark.parametrize("cell,expected", [
    ("gpt2-tiny.tiny_steps", {"step_ms_p50.train", "steps_counted"}),
    ("mistral-tiny.tiny_open", {
        "queue_wait_p50_ms.open", "serve_step_ms_p50.open",
        "prefill_tokens_per_s.open", "compiles_in_window.open",
        "ttft_p95_ms.open", "token_gap_p95_ms.open", "loadgen_late_p95_ms",
        "steps_counted"}),
    ("mistral-tiny.tiny_closed", {
        "queue_wait_p50_ms", "serve_step_ms_p50", "prefill_tokens_per_s",
        "compiles_in_window", "steps_counted"})])
def test_traced_run_reports_layer_metrics_it_can_read(copy, cell, expected):
    """On the CPU the profiler's trace has no device plane, so the readers
    of device metrics return nothing and those metrics are left out: no
    device number comes from a CPU run."""
    out = _run(copy, cell, traced=True)
    assert set(out["metrics"]) == expected
    assert "busy_s" not in out["device"] and "breakdown" not in out
    for name in expected:
        if name.startswith("compiles_in_window"):
            assert out["metrics"][name]["value"] == 0


def test_the_added_metric_was_found_by_name(copy):
    cell = manifest.load_cell(copy, "gpt2-tiny.tiny_steps")
    assert cell.readers["steps_counted"].__module__.startswith(
        "_bench_readers_extra")
    assert "steps_counted" not in manifest.load_cell(
        bench_tiny.REPO, "gpt2-350m.pretrain_1k").readers


def test_wrong_output_is_not_correct(copy, monkeypatch):
    """``correct`` is a comparison that can fail: a reference that
    disagrees by more than the tolerance turns it false."""
    cell = manifest.load_cell(copy, "gpt2-tiny.tiny_steps")
    ref = cell.reference()
    monkeypatch.setattr(ref, "loss",
                        lambda *a, _f=ref.loss: _f(*a) + 0.05)
    assert _run(copy, "gpt2-tiny.tiny_steps", False,
                seconds=0.5)["correct"] is False


def test_a_lower_precision_fails_the_serving_gate(copy, monkeypatch, capsys):
    """``tools/gate_probe.py`` at the tiny preset: the engine as
    configured passes its own tolerance; weights round-tripped through
    int8 against a reference that keeps the originals read a larger
    error.  What the real tolerance refuses is read on the chip."""
    import json

    probe = manifest.load_code(copy, "tools", "gate_probe")
    monkeypatch.setattr(probe, "BIG", 1000)
    assert probe.main(["mistral-tiny.tiny_open", "3", str(2 ** 31 + 9)],
                      root=copy, need_chip=False) == 0
    rows = [json.loads(line[5:]) for line in capsys.readouterr().out
            .splitlines() if line.startswith("GATE ")]
    by = {}
    for r in rows:
        by.setdefault(r["variant"], []).append(r)
    assert set(by) == {"as configured", "weights through int8",
                       "int8 KV cache"}
    assert all(len(v) == 2 for v in by.values())
    assert all(r["passes"] for r in by["as configured"])
    base = max(r["rms"] for r in by["as configured"])
    assert min(r["rms"] for r in by["weights through int8"]) > base
