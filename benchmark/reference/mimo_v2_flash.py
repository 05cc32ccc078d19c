"""MiMo-V2-Flash (``XiaomiMiMo/MiMo-V2-Flash``, HF ``mimo_v2_flash``), the
language model: grouped-query attention of two kinds by layer, the window
kind with a learned sink a head in its softmax, keys wider than values,
and sigmoid-routed experts after one dense layer.

Every norm is an RMSNorm (eps ``layernorm_epsilon``), no bias anywhere, a
final RMSNorm and an untied head.  ``x`` is the stream ``[S, hidden]``,
``H`` query heads, ``G`` KV heads (``num_key_value_heads`` in a full
layer, ``swa_num_key_value_heads`` in a window layer)::

    1. a = rms_in(x)
    2. q = a W_q [S, H, head_dim];  k = a W_k [S, G, head_dim];
       v = a W_v [S, G, v_head_dim]
    3. rotary on dims 0 .. int(head_dim * partial_rotary_factor) - 1 of
       every head of q and k, half-split pairs, theta rope_theta in a
       full layer and swa_rope_theta in a window layer; the other dims
       pass unrotated
    4. v = attention_value_scale * v
    5. s[t,u] = q[t,h] . k[u,g(h)] / sqrt(head_dim) for u <= t, and in a
       window layer (hybrid_layer_pattern[l] == 1) only for
       t - u < sliding_window
    6. full layer: p = softmax_u(s).  Window layer, with the head's
       learned b_h: p[t,u] = exp(s[t,u] - m) / (exp(b_h - m) + sum_u'
       exp(s[t,u'] - m)), m the largest of the row's scores and b_h: the
       sink takes probability and adds no value
    7. o[t,h] = sum_u p[t,u] v[u,g(h)];  x = x + concat_h(o) W_o
    8. m = rms_post(x).  moe_layer_freq[l] == 0: x = x + SwiGLU(m) of
       intermediate_size.  Else r = sigmoid(m W_r) over ALL experts; the
       chosen are the num_experts_per_tok largest of r + e_bias (by
       sorting; the bias for the choice only); w = r[chosen] /
       sum(r[chosen]) (norm_topk_prob) times routed_scaling_factor (null
       = 1); x = x + sum over the chosen that are HELD of w_e SwiGLU_e(m);
       no shared expert
    9. after the last layer rms_final(x), logits x W_head

What the published config and its description do not settle, each listed
in the configuration file under ``assumed`` and taken the same way by the
program (``deepspeed_tpu/inference/v2/model.py``): the form of (6) and
that the sink is per head and per window layer; (4) applied to v before
the product; the rotary layout of (3) and that the partial rotary holds
for both kinds; ``t - u < sliding_window`` as the window;
``sliding_window_size`` and ``attention_chunk_size`` taken as that same
window; ``n_group`` = ``topk_group`` = 1 as no group limit.  The three
multi-token-prediction layers of the description have no key in the
config and are not built, nor are V2.5's encoders.

The share: this chip's experts are ``experts_held_first`` ..
``+ n_routed_experts`` (the configuration file's count is the count HELD)
of the router's width (the params' own); what the absent experts would
add is left out, here as in the program, and that partial result goes on
to the next layer.  The vocabulary is the slice the params hold.  The
attention weights are stacked a kind (``layers/attn_full``,
``layers/attn_window``), in layer order, as the program stores them.

To fit beside the engine's weights at the 12,000 positions
``tools/gate_probe_window.py`` asks for: queries go through attention
``QUERY_BLOCK`` rows at a time against every key under a dense mask
(scores ``[heads, block, S]``, never ``[heads, S, S]``), a layer's weights
are converted to float32 a group at a time and an expert at a time, and
the positions are padded with token 0 to a whole number of blocks (a
later position is seen by no earlier one); none of it changes a value.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import common as c

QUERY_BLOCK = 128


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wi"])) @ w["wo"]


def _take(tree, i, device):
    """Layer ``i`` of stacked weights, on ``device`` in float32."""
    return c.f32(jax.tree.map(lambda a: jax.device_put(a[i], device), tree))


def _partial_rope(x, positions, theta, rot):
    """(3): rotary on the first ``rot`` dims of every head, the rest as
    they are."""
    return jnp.concatenate(
        [c.rope(x[..., :rot], positions, theta), x[..., rot:]], -1)


def _attention(cfg, full: bool, positions):
    """``fn(x, ln1, w) -> x + attention`` of one kind of layer over x
    [1, S, hidden]; ``w`` holds a ``sink`` ``[H]`` where the kind's
    softmax has one."""
    eps = cfg["layernorm_epsilon"]
    nh, d, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                 cfg["v_head_dim"])
    nkv = cfg["num_key_value_heads" if full else "swa_num_key_value_heads"]
    window = int(cfg["sliding_window"])
    theta = float(cfg["rope_theta" if full else "swa_rope_theta"])
    rot = int(d * cfg["partial_rotary_factor"])
    value_scale = float(cfg["attention_value_scale"])
    sink = bool(cfg["add_full_attention_sink_bias" if full
                    else "add_swa_attention_sink_bias"])

    @jax.jit
    def project(x, ln1, w):
        b, s, _ = x.shape
        a = c.rms_norm(x, ln1, eps)
        q = _partial_rope((a @ w["wq"]).reshape(b, s, nh, d), positions,
                          theta, rot)
        k = _partial_rope((a @ w["wk"]).reshape(b, s, nkv, d), positions,
                          theta, rot)
        return q, k, value_scale * (a @ w["wv"]).reshape(b, s, nkv, dv)

    @jax.jit
    def block(q, k, v, start, bias):
        """Rows ``start`` on of q [1, n, nh, d] against every key."""
        n, s = q.shape[1], k.shape[1]
        rep = nh // nkv
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, 2)) \
            / math.sqrt(d)
        t = start + jnp.arange(n)[:, None]
        j = jnp.arange(s)[None, :]
        seen = j <= t
        if not full:
            seen = seen & (t - j < window)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        if sink:
            # (6): the sink as one more column, its probability dropped
            col = jnp.broadcast_to(bias[None, :, None, None],
                                   scores.shape[:3] + (1,))
            p = jax.nn.softmax(jnp.concatenate([scores, col], -1),
                               -1)[..., :-1]
        else:
            p = jax.nn.softmax(scores, -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, rep, 2))

    @jax.jit
    def finish(x, o, wo):
        b, s, _ = x.shape
        return x + o.reshape(b, s, -1) @ wo

    def attention(x, ln1, w):
        q, k, v = project(x, ln1, w)
        bias = w["sink"] if sink else jnp.zeros((nh,), c.F32)
        outs = [block(q[:, i:i + QUERY_BLOCK], k, v, i, bias)
                for i in range(0, q.shape[1], QUERY_BLOCK)]
        return finish(x, jnp.concatenate(outs, 1), w["wo"])
    return attention


def expert_layer(cfg, device):
    """``fn(m, moe, i) -> F(m)``: the held experts' part of the ``i``-th
    expert layer on the normed rows ``m``, whose weights ``moe`` holds
    stacked, as stored; an expert is converted at a time.  Held:
    ``experts_held_first`` .. ``+ n_routed_experts`` of the router's
    width.  No shared expert."""
    k = int(cfg["num_experts_per_tok"])
    first, held = int(cfg["experts_held_first"]), int(cfg["n_routed_experts"])
    scale = float(cfg["routed_scaling_factor"] or 1.0)
    norm = bool(cfg["norm_topk_prob"])

    @jax.jit
    def route(m, router, bias):
        r = jax.nn.sigmoid(m @ router)                        # [B, S, E]
        chosen = jnp.argsort(-(r + bias), axis=-1)[..., :k]
        w = jnp.take_along_axis(r, chosen, -1)
        if norm:
            w = w / w.sum(-1, keepdims=True)
        return chosen, w * scale

    @jax.jit
    def add_expert(y, m, chosen, w, e, we):
        mine = ((chosen == e) * w).sum(-1)                    # [B, S]
        return y + mine[..., None] * _swiglu(m, we)

    def experts(m, moe, i):
        chosen, w = route(m, _take(moe["router"], i, device),
                          _take(moe["bias"], i, device))
        y = jnp.zeros_like(m)
        for e in range(held):
            we = c.f32({n: jax.device_put(moe[n][i, e], device)
                        for n in ("wg", "wi", "wo")})
            y = add_expert(y, m, chosen, w, first + e, we)
        return y
    return experts


def logits(params, input_ids, cfg, device, last: int = 0):
    """Logits of every position, or of the ``last`` positions only."""
    with c.highest():
        ids = jnp.asarray(input_ids)
        real = ids.shape[1]
        pad = -real % QUERY_BLOCK
        ids = jax.device_put(jnp.pad(ids, ((0, 0), (0, pad))), device)
        positions = jnp.broadcast_to(jnp.arange(ids.shape[1])[None],
                                     ids.shape)
        layers = params["layers"]
        eps = cfg["layernorm_epsilon"]

        def take(tree, i):
            return _take(tree, i, device)

        attend = {full: _attention(cfg, full, positions)
                  for full in (True, False)}
        experts = expert_layer(cfg, device)
        pre = jax.jit(lambda x, g: c.rms_norm(x, g, eps))
        dense = jax.jit(_swiglu)

        table = jax.device_put(params["embed"]["tokens"], device)
        x = c.f32(table[ids])
        n = cfg["num_hidden_layers"]
        seen = {True: 0, False: 0}          # layers of each kind so far
        n_dense = n_moe = 0
        for i in range(n):
            full = cfg["hybrid_layer_pattern"][i] == 0
            stack = layers["attn_full" if full else "attn_window"]
            x = attend[full](x, take(layers["ln1"]["scale"], i),
                             take(stack, seen[full]))
            seen[full] += 1
            m = pre(x, take(layers["ln2"]["scale"], i))
            if cfg["moe_layer_freq"][i] == 0:
                x = x + dense(m, take(layers["mlp"], n_dense))
                n_dense += 1
            else:
                x = x + experts(m, layers["moe"], n_moe)
                n_moe += 1
        fn = c.f32(jax.device_put(params["final_norm"], device))
        x = c.rms_norm(x[:, real - last if last else 0:real], fn["scale"],
                       eps)
        return x @ c.f32(jax.device_put(params["lm_head"], device))
