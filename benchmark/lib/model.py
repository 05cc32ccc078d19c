"""Build the program's model object from a configuration file and hold
the file to what was built."""

from __future__ import annotations


def build_model(config: dict):
    import jax.numpy as jnp

    from deepspeed_tpu.models import get_model_config

    reg = config["registry"]
    overrides = dict(reg.get("overrides", {}))
    for key in ("param_dtype", "dtype"):
        if isinstance(overrides.get(key), str):
            overrides[key] = getattr(jnp, overrides[key])
    model = get_model_config(reg["name"], **overrides)
    for key, want in config["expect"].items():
        got = getattr(model, key)
        if got != want:
            raise ValueError(f"configuration file says {key}={want!r}, the "
                             f"program built {key}={got!r}")
    return model


def fwd_flops_per_tok(model, seq: int) -> float:
    from benchmark.lib import flops

    return flops.fwd_flops_per_tok(
        model.hidden_size, model.num_layers, model.vocab_size,
        model.intermediate_size, model.num_heads, model.kv_heads,
        model.activation == "swiglu", seq)
