"""OPT (Zhang et al. 2022; ``facebook/opt-1.3b``): learned token and
position embeddings, pre-LayerNorm blocks (``do_layer_norm_before``) of
causal multi-head attention and a 4x ReLU feed-forward, biases everywhere,
a final LayerNorm, output head tied to the token embedding.

Departure from the published checkpoint layout: HF's position table has
two leading padding rows and looks position p up at row p + 2.  The table
here is the system's, ``max_position_embeddings`` rows looked up at row p;
with weights made from a seed the offset carries nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common as c

EPS = 1e-5        # torch.nn.LayerNorm's default, which OPT keeps


def _layer(cfg):
    heads = cfg["num_attention_heads"]

    def layer(x, w):
        b, s, h = x.shape
        a = c.layer_norm(x, w["ln1"]["scale"], w["ln1"]["bias"], EPS)
        at = w["attn"]
        q = (a @ at["wq"] + at["bq"]).reshape(b, s, heads, -1)
        k = (a @ at["wk"] + at["bk"]).reshape(b, s, heads, -1)
        v = (a @ at["wv"] + at["bv"]).reshape(b, s, heads, -1)
        x = x + c.attention(q, k, v).reshape(b, s, h) @ at["wo"] + at["bo"]
        m = c.layer_norm(x, w["ln2"]["scale"], w["ln2"]["bias"], EPS)
        mlp = w["mlp"]
        hid = jax.nn.relu(m @ mlp["wi"] + mlp["bi"])
        return x + hid @ mlp["wo"] + mlp["bo"]
    return layer


def logits(params, input_ids, cfg, device):
    with c.highest():
        ids = jax.device_put(jnp.asarray(input_ids), device)
        emb = c.f32(jax.device_put(params["embed"], device))
        pos = jnp.arange(ids.shape[1])
        x = emb["tokens"][ids] + emb["positions"][pos][None]
        x = c.run_layers(_layer(cfg), x, params["layers"],
                         cfg["num_hidden_layers"], device)
        fn = c.f32(jax.device_put(params["final_norm"], device))
        return c.layer_norm(x, fn["scale"], fn["bias"], EPS) @ emb["tokens"].T


def loss(params, input_ids, labels, cfg, device):
    with c.highest():
        return c.cross_entropy(logits(params, input_ids, cfg, device),
                               jax.device_put(jnp.asarray(labels), device))
