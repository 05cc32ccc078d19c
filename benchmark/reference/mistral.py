"""Mistral 7B (Jiang et al. 2023; ``mistralai/Mistral-7B-v0.1``): token
embedding, pre-RMSNorm blocks of grouped-query causal attention (rotary
positions, a sliding window of ``sliding_window`` keys) and a SwiGLU
feed-forward, no biases, a final RMSNorm and an untied output head.

Departures from the published model: none in the arithmetic; depth is the
configuration file's ``num_hidden_layers``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common as c


def _layer(cfg, positions):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    window = cfg.get("sliding_window")

    def layer(x, w):
        b, s, h = x.shape
        a = c.rms_norm(x, w["ln1"]["scale"], eps)
        at = w["attn"]
        q = c.rope((a @ at["wq"]).reshape(b, s, heads, -1), positions, theta)
        k = c.rope((a @ at["wk"]).reshape(b, s, kv, -1), positions, theta)
        v = (a @ at["wv"]).reshape(b, s, kv, -1)
        x = x + c.attention(q, k, v, window).reshape(b, s, -1) @ at["wo"]
        m = c.rms_norm(x, w["ln2"]["scale"], eps)
        mlp = w["mlp"]
        return x + (jax.nn.silu(m @ mlp["wg"]) * (m @ mlp["wi"])) @ mlp["wo"]
    return layer


def logits(params, input_ids, cfg, device, last: int = 0):
    """Logits of every position, or of the ``last`` positions only (the
    head is the largest single matmul and the caller wants a few rows)."""
    with c.highest():
        ids = jax.device_put(jnp.asarray(input_ids), device)
        positions = jnp.broadcast_to(jnp.arange(ids.shape[1])[None],
                                     ids.shape)
        emb = c.f32(jax.device_put(params["embed"]["tokens"], device))
        x = emb[ids]
        del emb
        x = c.run_layers(_layer(cfg, positions), x, params["layers"],
                         cfg["num_hidden_layers"], device)
        fn = c.f32(jax.device_put(params["final_norm"], device))
        x = c.rms_norm(x[:, -last:], fn["scale"], cfg["rms_norm_eps"])
        return x @ c.f32(jax.device_put(params["lm_head"], device))


def loss(params, input_ids, labels, cfg, device):
    with c.highest():
        return c.cross_entropy(logits(params, input_ids, cfg, device),
                               jax.device_put(jnp.asarray(labels), device))
