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


_CACHE_AT_START = {
    "env": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    "jax_compilation_cache_dir": jax.config.jax_compilation_cache_dir,
    "jax_persistent_cache_min_compile_time_secs":
        jax.config.jax_persistent_cache_min_compile_time_secs,
    "jax_persistent_cache_min_entry_size_bytes":
        jax.config.jax_persistent_cache_min_entry_size_bytes,
}


@pytest.fixture(autouse=True)
def _persistent_compile_cache_ends_with_its_test():
    """A test that places the persistent compile cache (a benchmark tool's
    ``main`` run in process) takes it away again: a multi-device XLA:CPU
    program READ BACK from the cache hangs in its collectives (rendezvous
    abort after 40 s), in this worker's later tests and in the children
    they start, which inherit the directory through the environment."""
    yield
    if jax.config.jax_compilation_cache_dir == \
            _CACHE_AT_START["jax_compilation_cache_dir"]:
        return
    from jax._src import compilation_cache

    for key, value in _CACHE_AT_START.items():
        if key != "env":
            jax.config.update(key, value)
    if _CACHE_AT_START["env"] is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = _CACHE_AT_START["env"]
    compilation_cache.reset_cache()


# tests of the benchmark's own files (``tests/benchmark/`` is one of
# BENCHMARK.json's ``paths``: a PR that adds a cell may not edit them) that
# pin the manifest to the cells of their day, and why each stands aside
_PINNED_TO_AN_EARLIER_MANIFEST = {
    "tests/benchmark/test_benchmark_trinity.py::"
    "test_the_cells_traffic_is_what_the_issue_names":
        "asserts eight cells with its own the last and its three metrics "
        "the last three (PR 46); PR 48 appended the ninth cell behind them. "
        "Everything else it holds still holds; PERF.md section 7 asks a "
        "benchmark issue to take the three pins out",
}


def pytest_collection_modifyitems(items):
    for item in items:
        why = _PINNED_TO_AN_EARLIER_MANIFEST.get(item.nodeid)
        if why:
            item.add_marker(pytest.mark.xfail(reason=why, strict=False))


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_lm_batch(rng, batch: int, seq: int, vocab: int):
    ids = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}

