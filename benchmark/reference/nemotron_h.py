"""Nemotron-H (``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, HF
``nemotron_h``), the language model: every block is ONE mixer,

    x <- x + f(u),    u = RMSNorm(x; gain, eps layer_norm_epsilon)

with ``f`` chosen per layer by the letter of ``hybrid_override_pattern``;
no bias anywhere but the convolution's, a final RMSNorm and an untied
head.  ``x`` is the stream ``[1, S, hidden]``:

``M``, a Mamba-2 mixer (``d_inner = mamba_num_heads x mamba_head_dim``,
NOT ``expand x hidden_size``; ``G = n_groups``, ``N = ssm_state_size``)::

    [z | xBC | dt] = u W_in           widths d_inner | d_inner + 2GN | heads
    xBC = silu(causal depthwise conv(xBC, width conv_kernel) + bias)
    x, B, C = split(xBC)              head h reads group h // (heads / G)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t
    y = y * silu(z)                   the gate FIRST,
    y = y / sqrt(mean over each group's channels of y^2 + eps) * gain
    f = y W_out

the scan written as the plain recurrence over positions it is
(``lax.scan`` over time; no chunks, no cache, no slots).

``*``, attention: ``q = u W_q`` (heads of ``head_dim``), ``k = u W_k``,
``v = u W_v`` (``num_key_value_heads``), NO rotary and no other position
signal, causal ``softmax(q k^T / sqrt(head_dim)) v``, ``f = heads W_o``.

``E``, experts: ``s = sigmoid(u W_r)`` over ALL experts; chosen = the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` (by
sorting; ``n_group`` = ``topk_group`` = 1 is no group limit);
``w = s[chosen] / (sum(s[chosen]) + 1e-20) x routed_scaling_factor``
(``norm_topk_prob``); ``expert_e(u) = relu(u W_up,e)^2 W_down,e``, no gate;
``f = sum_{e chosen and held} w_e expert_e(u) + expert_shared(u)``.

Departures from the published description, each listed in the
configuration file under ``assumed`` and taken the same way by the
program (``deepspeed_tpu/inference/v2/model.py:_hybrid_trunk``): the
attention applies no rotary (the HF ``nemotron_h`` attention reads neither
``rope_theta`` nor ``partial_rotary_factor``); ``time_step_min`` / ``_max``
/ ``_floor`` are ranges of an initialisation and nothing in the forward
pass; everything here is float32 (the published stream is bf16).

The share: this chip's experts are ``experts_held_first`` ..
``+ n_routed_experts`` (the configuration file's count is the count HELD)
of the router's width (the params' own); what the absent experts would
add is left out, here as in the program, and that partial result goes on
to the next layer.  The vocabulary is the slice the params hold.

To fit beside the engine's weights: the embedding's rows are gathered
from the table as it is stored and only those converted, a layer's
weights are converted to float32 a layer at a time and an expert at a
time, and the head is taken in column blocks for the ``last`` rows; none
of it changes a value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import common as c

HEAD_COLUMNS = 32768        # of the output head converted at once


def _take(tree, i, device):
    """Layer ``i`` of stacked weights, on ``device`` in float32."""
    return c.f32(jax.tree.map(lambda a: jax.device_put(a[i], device), tree))


def _relu2(x, w):
    """``relu(x W_up)^2 W_down``; a routed expert's up matrix is stored
    ``wu`` [F, hidden], a hidden unit's weights a row."""
    up = w["wu"].T if "wu" in w else w["wi"]
    return jnp.square(jax.nn.relu(x @ up)) @ w["wo"]


def mamba(cfg):
    """``fn(u, w) -> f``: the Mamba-2 mixer over u [B, S, hidden]; ``w``
    the layer's ``ssm`` weights, float32."""
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    k, eps = cfg["conv_kernel"], cfg["layer_norm_epsilon"]
    d_inner, gn = heads * p, groups * n

    @jax.jit
    def mixer(u, w):
        bsz, s, _ = u.shape
        z, xbc, dt = jnp.split(u @ w["in_proj"], [d_inner, 2 * d_inner
                                                  + 2 * gn], axis=-1)
        # causal depthwise convolution: tap j sees the input k-1-j rows back
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(padded[:, j:j + s] * w["conv_w"][:, j] for j in range(k))
        if cfg["use_conv_bias"]:
            conv = conv + w["conv_b"]
        xbc = jax.nn.silu(conv)
        x = xbc[..., :d_inner].reshape(bsz, s, heads, p)
        per = heads // groups
        b = jnp.repeat(xbc[..., d_inner:d_inner + gn]
                       .reshape(bsz, s, groups, n), per, axis=2)
        cc = jnp.repeat(xbc[..., d_inner + gn:].reshape(bsz, s, groups, n),
                        per, axis=2)
        dt = jax.nn.softplus(dt + w["dt_bias"])              # [B, S, heads]
        a = -jnp.exp(w["A_log"])

        def step(state, row):
            x_t, b_t, c_t, dt_t = row
            state = jnp.exp(dt_t * a)[..., None, None] * state \
                + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
            return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

        rows = tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, cc, dt))
        _, y = lax.scan(step, jnp.zeros((bsz, heads, p, n), c.F32), rows)
        y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x      # [B, S, h, P]
        y = y.reshape(bsz, s, d_inner) * jax.nn.silu(z)
        yg = y.reshape(bsz, s, groups, -1)
        yg = yg / jnp.sqrt((yg * yg).mean(-1, keepdims=True) + eps)
        return (yg.reshape(bsz, s, d_inner) * w["norm"]) @ w["out_proj"]
    return mixer


def attention(cfg):
    """``fn(u, w) -> f``: causal grouped-query attention without rotary."""
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]

    @jax.jit
    def attend(u, w):
        b, s, _ = u.shape
        q = (u @ w["wq"]).reshape(b, s, nh, d)
        k = (u @ w["wk"]).reshape(b, s, nkv, d)
        v = (u @ w["wv"]).reshape(b, s, nkv, d)
        return c.attention(q, k, v).reshape(b, s, -1) @ w["wo"]
    return attend


def expert_layer(cfg, device):
    """``fn(u, moe, i) -> f``: the held experts' part and the shared
    expert of the ``i``-th expert layer on the normed rows ``u``, whose
    weights ``moe`` holds stacked, as stored; an expert is converted at a
    time.  Held: ``experts_held_first`` .. ``+ n_routed_experts`` of the
    router's width."""
    k = int(cfg["num_experts_per_tok"])
    first, held = int(cfg["experts_held_first"]), int(cfg["n_routed_experts"])
    scale = float(cfg["routed_scaling_factor"])
    norm_topk = bool(cfg["norm_topk_prob"])

    @jax.jit
    def route(u, router, bias):
        s = jax.nn.sigmoid(u @ router)                        # [B, S, E]
        chosen = jnp.argsort(-(s + bias), axis=-1)[..., :k]
        w = jnp.take_along_axis(s, chosen, -1)
        if norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return chosen, w * scale

    @jax.jit
    def add_expert(y, u, chosen, w, e, we):
        mine = ((chosen == e) * w).sum(-1)                    # [B, S]
        return y + mine[..., None] * _relu2(u, we)

    shared = jax.jit(_relu2)

    def experts(u, moe, i):
        chosen, w = route(u, _take(moe["router"], i, device),
                          _take(moe["bias"], i, device))
        y = shared(u, _take(moe["shared"], i, device))
        for e in range(held):
            # [i, e] at once: a layer's experts sliced out first are a
            # copy of 0.6 GB a matrix beside the engine's pools
            we = c.f32({n: jax.device_put(moe[n][i, e], device)
                        for n in ("wu", "wo")})
            y = add_expert(y, u, chosen, w, first + e, we)
        return y
    return experts


def logits(params, input_ids, cfg, device, last: int = 0):
    """Logits of every position, or of the ``last`` positions only."""
    with c.highest():
        ids = jax.device_put(jnp.asarray(input_ids), device)
        layers = params["layers"]
        eps = cfg["layer_norm_epsilon"]
        pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
        mixers = {"M": mamba(cfg), "*": attention(cfg)}
        stacks = {"M": layers.get("ssm"), "*": layers.get("attn")}
        experts = expert_layer(cfg, device)
        norm = jax.jit(lambda x, g: c.rms_norm(x, g, eps))

        table = jax.device_put(params["embed"]["tokens"], device)
        x = c.f32(table[ids])
        seen = {"M": 0, "*": 0, "E": 0}     # layers of each kind so far
        for i, kind in enumerate(pattern):
            u = norm(x, _take(layers["norm"]["scale"], i, device))
            at = seen[kind]
            seen[kind] += 1
            if kind == "E":
                x = x + experts(u, layers["moe"], at)
            else:
                x = x + mixers[kind](u, _take(stacks[kind], at, device))
        fn = c.f32(jax.device_put(params["final_norm"], device))
        x = c.rms_norm(x[:, -last:], fn["scale"], eps)
        head = jax.device_put(params["lm_head"], device)
        out = [x @ c.f32(head[:, i:i + HEAD_COLUMNS])
               for i in range(0, head.shape[1], HEAD_COLUMNS)]
        return jnp.concatenate(out, -1)
