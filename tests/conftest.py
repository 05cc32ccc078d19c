"""Test harness: 8 virtual CPU devices.

TPU translation of the reference's distributed-without-a-cluster fixture
(tests/unit/common.py DistributedExec): instead of spawning N processes with
a file store, we run single-process JAX with
``--xla_force_host_platform_device_count=8`` so every mesh shape up to 8
"chips" is exercised for real (collectives included) on a GPU/TPU-less CI
machine — the same role the CPU accelerator plays for the reference.
"""

import os

# Must be set before the CPU backend initializes (backends are lazy, so
# setting it at conftest import is early enough).  Optimization level 0:
# the CPU mesh exists to check numerics and collective structure, not
# codegen quality — skipping XLA:CPU's heavy optimization passes cuts
# suite compile time ~30% with identical results (measured on
# test_engine: 115s → 80s).
for _flag in ("--xla_force_host_platform_device_count=8",
              "--xla_backend_optimization_level=0"):
    if _flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " " + _flag
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The suite runs on the CPU; JAX reads the platform from the environment.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_global_topology():
    """Each test builds its own mesh; clear the global between tests."""
    yield
    from deepspeed_tpu.parallel import topology

    topology._GLOBAL_TOPOLOGY = None


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_lm_batch(rng, batch: int, seq: int, vocab: int):
    ids = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}


# ----------------------------------------------------------------------
# a small synthetic bench history (the shapes telemetry/ledger.py's
# backfill reads: BENCH_rNN.json primaries, BENCH_MEASURED_rNN.json
# measured / carried / queued rows), for the ledger and backlog tests
# ----------------------------------------------------------------------
# values as frozen in tools/obs_baseline.json, so the gate diffs clean
_MEASURED_R04_ROWS = [
    {"metric": "gpt2_350m_zero1_train_tokens_per_sec_per_chip",
     "value": 40892.0, "unit": "tokens/s", "vs_baseline": 1.168,
     "mfu": 0.472, "cmd": "python bench.py --row gpt2_350m"},
    {"metric": "llama3_8b_class_2L_zero3_tokens_per_sec_per_chip",
     "value": 36002.4, "unit": "tokens/s", "vs_baseline": 0.726,
     "mfu": 0.632, "cmd": "python bench.py --row llama8b_class_zero3"},
    {"metric": "longseq_32768_flash_train_tokens_per_sec_per_chip",
     "value": 8513.1, "unit": "tokens/s", "vs_baseline": 0.546, "mfu": 0.3,
     "cmd": "python bench.py --row longseq_flash"},
    {"metric": "peak_params_trained_one_chip", "value": 2647.7,
     "unit": "Mparams", "vs_baseline": 0.407, "model": "gpt2-2.7b-stream",
     "cmd": "python bench.py --peak-entry 2"},
    {"metric": "v2_decode_tokens_per_sec", "value": 2967.1,
     "unit": "tokens/s", "vs_baseline": 0.444,
     "prefill_tokens_per_sec": 2549.9,
     "cmd": "python bench.py --row v2_decode"},
]


@pytest.fixture
def bench_history(tmp_path):
    """A directory laid out like a repo root that holds rounds r01-r18 of
    bench records, plus links to the real ``bench.py`` and ``tools/`` the
    backlog validator checks queued commands against."""
    import json

    repo = os.path.join(os.path.dirname(__file__), "..")
    root = tmp_path / "history"
    root.mkdir()
    for name in ("bench.py", "tools"):
        os.symlink(os.path.abspath(os.path.join(repo, name)), root / name)

    def write(name, doc):
        (root / name).write_text(json.dumps(doc))

    primary = {"metric": "gpt2_350m_zero1_train_tokens_per_sec_per_chip",
               "unit": "tokens/s"}
    write("BENCH_r01.json", {"parsed": {**primary, "value": 34492.7,
                                        "vs_baseline": 0.986}})
    write("BENCH_r02.json", {"parsed": {**primary, "value": 40832.1,
                                        "vs_baseline": 1.167, "mfu": 0.471}})
    write("BENCH_r03.json", {"parsed": {**primary, "value": 0.0,
                                        "vs_baseline": 0.0, "rows": [],
                                        "error": "backend unreachable"}})
    write("BENCH_MEASURED_r04.json", {"rows": _MEASURED_R04_ROWS})
    # r05-r07 carry the r04 rows as a literal list, r08+ by reference
    write("BENCH_MEASURED_r05.json", {
        "rows_last_measured_r04": _MEASURED_R04_ROWS,
        "queued_measurements_r05": [
            {"what": "primary row", "cmd": "python bench.py --row gpt2_350m"}]})
    write("BENCH_MEASURED_r07.json", {
        "rows_last_measured_r04": _MEASURED_R04_ROWS,
        "queued_measurements_r07": [
            {"what": "decode re-measure",
             "cmd": "python bench.py --row v2_decode"},
            {"what": "chunk sweep",
             "cmd": "for CB in 1 2; do DSTPU_CHUNK_BYTES=$CB "
                    "python bench.py --row peak_params; done"},
            {"what": "flash block sweep",
             "cmd": "python tools/bench_flash_longseq.py --sweep"}]})
    ref = "see BENCH_MEASURED_r04.json (carried forward unchanged)"
    write("BENCH_MEASURED_r08.json", {
        "rows_last_measured_r04": ref,
        "queued_measurements_r08": [
            {"what": "zero3 row  # after the tiling change",
             "cmd": "python bench.py --row llama8b_class_zero3"}]})
    write("BENCH_MEASURED_r18.json", {
        "rows_last_measured_r04": ref,
        "queued_measurements_r18": [
            {"what": "long sequence row",
             "cmd": "python bench.py --row longseq_flash"},
            {"what": "disagg A/B",
             "cmd": "python bench.py --row serve_disagg  # with overrides"}]})
    return str(root)
