"""GPT-2 (Radford et al. 2019; ``openai-community/gpt2-medium``): learned
token and position embeddings, pre-LayerNorm blocks of causal multi-head
attention and a 4x GELU (tanh form, ``gelu_new``) feed-forward, biases
everywhere, a final LayerNorm, and the output head tied to the token
embedding.

Departures from the published model: none in the arithmetic.  The weights
are the system's own, read from its parameter tree (separate q, k, v
matrices where the checkpoint fuses them into ``c_attn``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common as c


def _layer(cfg):
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]

    def layer(x, w):
        b, s, h = x.shape
        a = c.layer_norm(x, w["ln1"]["scale"], w["ln1"]["bias"], eps)
        at = w["attn"]
        q = (a @ at["wq"] + at["bq"]).reshape(b, s, heads, -1)
        k = (a @ at["wk"] + at["bk"]).reshape(b, s, heads, -1)
        v = (a @ at["wv"] + at["bv"]).reshape(b, s, heads, -1)
        x = x + c.attention(q, k, v).reshape(b, s, h) @ at["wo"] + at["bo"]
        m = c.layer_norm(x, w["ln2"]["scale"], w["ln2"]["bias"], eps)
        mlp = w["mlp"]
        hid = jax.nn.gelu(m @ mlp["wi"] + mlp["bi"], approximate=True)
        return x + hid @ mlp["wo"] + mlp["bo"]
    return layer


def logits(params, input_ids, cfg, device):
    with c.highest():
        ids = jax.device_put(jnp.asarray(input_ids), device)
        emb = c.f32(jax.device_put(params["embed"], device))
        pos = jnp.arange(ids.shape[1])
        x = emb["tokens"][ids] + emb["positions"][pos][None]
        x = c.run_layers(_layer(cfg), x, params["layers"], cfg["n_layer"],
                         device)
        fn = c.f32(jax.device_put(params["final_norm"], device))
        return c.layer_norm(x, fn["scale"], fn["bias"],
                            cfg["layer_norm_epsilon"]) @ emb["tokens"].T


def loss(params, input_ids, labels, cfg, device):
    with c.highest():
        return c.cross_entropy(logits(params, input_ids, cfg, device),
                               jax.device_put(jnp.asarray(labels), device))
