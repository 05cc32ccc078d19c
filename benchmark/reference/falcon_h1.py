"""Falcon-H1 (TII 2025; ``tiiuae/Falcon-H1-34B-Instruct``, HF
``falcon_h1``): in every block a grouped-query attention and a Mamba-2
mixer read the same RMS-normed input, their outputs are scaled and added
to the residual, and a SwiGLU feed-forward follows; muP multipliers scale
the embedding, the keys, both mixers' inputs and outputs, the five parts
of the mixer's input projection, the feed-forward's gate and output, and
the logits.  Untied head.

The mixer as ``modeling_falcon_h1.FalconH1Mixer.torch_forward`` computes
it, with the scan written as the plain recurrence over positions it is
(``lax.scan`` over time; no chunks, no cache, no slots)::

    p = ((h * ssm_in_multiplier) W_in) * mu      mu: ssm_multipliers over
    z, xBC, dt = split(p)                        [z | x | B | C | dt]
    xBC = silu(causal depthwise conv(xBC, width mamba_d_conv) + bias)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    y = rms_grouped(y * silu(z)) * w;  out = y W_out

head j taking B and C of group j // (heads / groups), the mean square of
the norm over each group's channels (``mamba_rms_norm`` true,
``mamba_norm_before_gate`` false).

Departures from the published code: none in the arithmetic (everything
here is float32; the published ``torch_forward`` also upcasts the scan).
The published configuration's ``attention_in_multiplier`` is 1 and is
applied all the same.  Depth is the file's ``num_hidden_layers``.  To fit
beside the engine's weights on one chip, the embedding's rows are
gathered from the table as it is stored and only those converted, the
head is taken in column blocks for the ``last`` rows, and a layer's
weights are converted a matrix at a time; none of it changes a value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import common as c

HEAD_COLUMNS = 32768        # of the output head converted at once


def _sizes(cfg):
    d_ssm = cfg["mamba_d_ssm"] or cfg["mamba_expand"] * cfg["hidden_size"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return d_ssm, gn, cfg["mamba_n_heads"]


def ssm(h, w, cfg):
    """The Mamba-2 mixer.  h: [B, S, H] (the block's normed input);
    w: the layer's ``ssm`` weights, float32."""
    bsz, s, _ = h.shape
    d_ssm, gn, heads = _sizes(cfg)
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    k = cfg["mamba_d_conv"]
    mu = jnp.concatenate([jnp.full((size,), m, c.F32) for size, m in
                          zip((d_ssm, d_ssm, gn, gn, heads),
                              cfg["ssm_multipliers"])])
    p = ((h * cfg["ssm_in_multiplier"]) @ w["in_proj"]) * mu
    z, xbc, dt = jnp.split(p, [d_ssm, 2 * d_ssm + 2 * gn], axis=-1)

    # causal depthwise convolution: tap j sees the input k-1-j rows back
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + s] * w["conv_w"][:, j] for j in range(k))
    if cfg["mamba_conv_bias"]:
        conv = conv + w["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_ssm].reshape(bsz, s, heads, -1)
    per = heads // groups
    b = jnp.repeat(xbc[..., d_ssm:d_ssm + gn].reshape(bsz, s, groups, n),
                   per, axis=2)
    cc = jnp.repeat(xbc[..., d_ssm + gn:].reshape(bsz, s, groups, n),
                    per, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [B, S, heads]
    a = -jnp.exp(w["A_log"])

    def step(state, row):
        x_t, b_t, c_t, dt_t = row
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    rows = tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, cc, dt))
    _, y = lax.scan(step, jnp.zeros((bsz, heads, x.shape[-1], n), c.F32),
                    rows)
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x          # [B, S, h, P]
    y = y.reshape(bsz, s, d_ssm) * jax.nn.silu(z)
    if cfg["mamba_rms_norm"]:
        if cfg["mamba_norm_before_gate"]:
            raise NotImplementedError("mamba_norm_before_gate")
        yg = y.reshape(bsz, s, groups, -1)
        yg = yg / jnp.sqrt((yg * yg).mean(-1, keepdims=True)
                           + cfg["rms_norm_eps"])
        y = yg.reshape(bsz, s, d_ssm) * w["norm"]
    return y @ w["out_proj"]


def _mixers(cfg, positions):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    # the published file writes the base as a whole number, 10**11
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])

    def mixers(x, w):
        b, s, _ = x.shape
        h = c.rms_norm(x, w["ln1"]["scale"], eps)
        a = h * cfg["attention_in_multiplier"]
        at = w["attn"]
        q = c.rope((a @ at["wq"]).reshape(b, s, heads, -1), positions, theta)
        k = c.rope((a @ at["wk"]).reshape(b, s, kv, -1)
                   * cfg["key_multiplier"], positions, theta)
        v = (a @ at["wv"]).reshape(b, s, kv, -1)
        attn = c.attention(q, k, v).reshape(b, s, -1) @ at["wo"]
        return x + attn * cfg["attention_out_multiplier"] \
            + ssm(h, w["ssm"], cfg) * cfg["ssm_out_multiplier"]
    return mixers


def _layers(x, layers, cfg, positions, device):
    """Every block in turn, one piece of its weights in float32 at a
    time: the two mixers, then the feed-forward a matrix at a time."""
    m_gate, m_down = cfg["mlp_multipliers"]
    eps = cfg["rms_norm_eps"]
    mixers = jax.jit(_mixers(cfg, positions))
    norm = jax.jit(lambda x, g: c.rms_norm(x, g, eps))
    gate = jax.jit(lambda g, w: jax.nn.silu((g @ w) * m_gate))
    up = jax.jit(lambda g, act, w: act * (g @ w))
    down = jax.jit(lambda x, act, w: x + (act @ w) * m_down)

    def take(tree, i):
        return c.f32(jax.tree.map(lambda a: jax.device_put(a[i], device),
                                  tree))

    for i in range(cfg["num_hidden_layers"]):
        x = mixers(x, take({k: layers[k] for k in ("ln1", "attn", "ssm")},
                           i))
        g = norm(x, take(layers["ln2"]["scale"], i))
        act = gate(g, take(layers["mlp"]["wg"], i))
        act = up(g, act, take(layers["mlp"]["wi"], i))
        x = down(x, act, take(layers["mlp"]["wo"], i))
    return x


def logits(params, input_ids, cfg, device, last: int = 0):
    """Logits of every position, or of the ``last`` positions only."""
    with c.highest():
        ids = jax.device_put(jnp.asarray(input_ids), device)
        positions = jnp.broadcast_to(jnp.arange(ids.shape[1])[None],
                                     ids.shape)
        table = jax.device_put(params["embed"]["tokens"], device)
        x = c.f32(table[ids]) * cfg["embedding_multiplier"]
        x = _layers(x, params["layers"], cfg, positions, device)
        fn = c.f32(jax.device_put(params["final_norm"], device))
        x = c.rms_norm(x[:, -last:], fn["scale"], cfg["rms_norm_eps"])
        head = jax.device_put(params["lm_head"], device)
        out = [x @ c.f32(head[:, i:i + HEAD_COLUMNS])
               for i in range(0, head.shape[1], HEAD_COLUMNS)]
        return jnp.concatenate(out, -1) * cfg["lm_head_multiplier"]
