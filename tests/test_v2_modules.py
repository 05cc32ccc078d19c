"""v2 module registry + heuristics (ref inference/v2/modules/
module_registry.py + heuristics.py): named implementations, auto
resolution by hardware/shape, engine config overrides."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import model as v2_model  # registers impls
from deepspeed_tpu.inference.v2.modules import (available, module_overrides,
                                                register_module, resolve)


def test_builtin_attention_impls_registered():
    names = available("attention")
    assert "paged_pallas" in names and "paged_xla" in names


def test_auto_resolution_by_context():
    # CPU / no tables → xla fallback
    impl = resolve("attention", "auto", block_size=16, head_dim=64,
                   on_tpu=False, has_tables=False)
    assert impl is v2_model._attn_impl_xla
    # TPU-shaped context with servable geometry → pallas
    impl = resolve("attention", "auto", block_size=16, head_dim=128,
                   on_tpu=True, has_tables=True)
    assert impl is v2_model._attn_impl_pallas
    # head_dim 64: the chip's compiler refuses the page slice, so
    # supports() does too and the registry names the XLA path
    impl = resolve("attention", "auto", block_size=16, head_dim=64,
                   on_tpu=True, has_tables=True)
    assert impl is v2_model._attn_impl_xla


def test_explicit_name_and_errors():
    assert resolve("attention", "paged_xla") is v2_model._attn_impl_xla
    with pytest.raises(KeyError, match="unknown attention"):
        resolve("attention", "nope")
    with pytest.raises(KeyError, match="no implementations"):
        resolve("rotary", "auto")


def test_custom_registration_and_priority():
    calls = []

    @register_module("testkind", "special",
                     default_for=lambda fast=False, **_: fast)
    def special():
        calls.append("special")

    @register_module("testkind", "plain")
    def plain():
        calls.append("plain")

    resolve("testkind", "auto", fast=True)()
    resolve("testkind", "auto", fast=False)()
    assert calls == ["special", "plain"]


def test_engine_override_reaches_model_config():
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_model_config

    model = get_model_config("llama-tiny")
    eng = InferenceEngineV2(model, {"modules": {"attention": "paged_xla"}})
    assert dict(eng.model_config.v2_modules)["attention"] == "paged_xla"
    # generation still works through the pinned implementation
    out = eng.generate([[1, 2, 3]], max_new_tokens=4)
    assert len(out[0]) == 4
    assert module_overrides({"modules": {"attention": "paged_xla"}}) == {
        "attention": "paged_xla"}
    assert module_overrides({}) == {}
