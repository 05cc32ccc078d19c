"""``run.py`` where it must refuse, and each plain reference against the
system's own forward pass at a tiny preset."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest
from deepspeed_tpu.models import get_model_config
from deepspeed_tpu.models import transformer as tf_model

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "gpt2-350m.pretrain_1k", "--seed", "1", "--seconds",
        "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "benchmark/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_without_a_chip_it_exits_1_and_prints_no_result():
    out = _run(ROOT, {})
    assert out.returncode == 1
    assert "no accelerator" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in ("benchmark", "tests/benchmark"):
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, {})
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_an_unknown_workload_is_refused():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "nope", "--seed",
         "1", "--seconds", "1"], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert out.returncode == 1 and "no workload" in out.stderr


TINY = {
    "gpt2": ("gpt2-tiny", {"n_layer": 2, "n_head": 4,
                           "layer_norm_epsilon": 1e-5}),
    "opt": ("opt-tiny", {"num_hidden_layers": 2, "num_attention_heads": 4}),
    "mistral": ("mistral-tiny", {
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "sliding_window": 32,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0}),
}


@pytest.mark.parametrize("arch", list(TINY))
def test_reference_agrees_with_the_system_in_float32(arch):
    """Same weights, float32 on both sides: the reference, written from
    the published description, and ``models/transformer.py`` agree to
    rounding (the window of mistral-tiny, 32, is shorter than the 48
    tokens, so it binds)."""
    name, cfg = TINY[arch]
    model = get_model_config(name, dtype=jnp.float32)
    params = tf_model.init_params(model, jax.random.PRNGKey(3))
    # biases and norm gains are made as 0 and 1: move them, or a
    # reference that forgot one would pass
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        if leaf.ndim <= 2 and min(leaf.shape) <= 2 or leaf.ndim == 1
        else leaf for leaf, k in zip(leaves, keys)])
    ids = np.random.default_rng(0).integers(0, 512, size=(2, 48))
    want = tf_model.forward(params, jnp.asarray(ids), model)
    ref = manifest.load_code(ROOT, "reference", arch)
    got = ref.logits(params, ids, cfg, jax.devices()[0])
    assert got.shape == want.shape == (2, 48, 512)
    assert float(jnp.abs(got - want).max()) < 2e-4
    labels = np.roll(ids, -1, axis=1)
    labels[:, -1] = -100
    mine = ref.loss(params, ids, labels, cfg, jax.devices()[0])
    theirs = tf_model.loss_fn(params, {"input_ids": jnp.asarray(ids),
                                       "labels": jnp.asarray(labels)}, model)
    assert float(abs(mine - theirs)) < 1e-4
