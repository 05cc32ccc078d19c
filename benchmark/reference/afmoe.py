"""Trinity (``arcee-ai/Trinity-Large-Preview``, HF ``afmoe``), the language
model: grouped-query attention of two kinds by layer, gated, between
sandwich norms, and sigmoid-routed experts after the leading dense layers.

Every norm is an RMSNorm (eps ``rms_norm_eps``), no bias anywhere, a final
RMSNorm and an untied head.  ``x`` is the stream ``[S, hidden]``::

    x0 = Emb(id) * sqrt(hidden)                      (mup_enabled)
    a  = rms_in(x)
    q = a W_q, k = a W_k, v = a W_v, g = a W_g       (heads of head_dim)
    q = rms_q(q), k = rms_k(k)     over the dims of a head, one gain each
    layer_types[l] == "sliding_attention": rotary on q and k (rope_theta,
        half-split pairs, all dims); key s visible to row t iff
        0 <= t - s < sliding_window
    layer_types[l] == "full_attention":    NO rotary; causal
    o = softmax(q k^T / sqrt(head_dim)) v;  o = o * sigmoid(g)
    x = x + rms_post_attn(o W_o)
    m = rms_pre_mlp(x);  x = x + rms_post_mlp(F(m))
    F, l < num_dense_layers: SwiGLU of intermediate_size
    F, else: s = sigmoid(m W_r) over ALL experts;  chosen = the
        num_experts_per_tok largest of s + b (by sorting; b the
        expert_bias, for the choice only);
        w = s[chosen] / sum(s[chosen]) * route_scale      (route_norm)
        F(m) = sum_{e chosen and held} w_e SwiGLU_e(m) + SwiGLU_shared(m)

What the published config and its description do not settle, each listed
in the configuration file under ``assumed`` and taken the same way by the
program (``deepspeed_tpu/inference/v2/model.py``): the gate is a
full-width product of the NORMED input, applied to the heads' outputs
before ``W_o``; the q/k norms come before rotary; full layers carry no
rotary; the embedding multiplier is ``sqrt(hidden_size)``; window 4096 is
the token and the 4,095 before it; ``n_group`` = ``topk_group`` = 1 is no
group limit; "depth-scaled" names how the norms' gains were initialised
and is no equation.

The share: this chip's experts are ``experts_held_first`` ..
``+ num_experts`` (the configuration file's count is the count HELD) of
the router's width (the params' own); what the absent experts would add
is left out, here as in the program.  The vocabulary is the slice the
params hold.

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


def _attention(cfg, full: bool, positions):
    """``fn(x, norms, w) -> x + rms_post_attn(attention)`` of one kind of
    layer over x [1, S, hidden]; ``norms``: the block's ``ln1`` and
    ``post_attn`` gains."""
    eps = cfg["rms_norm_eps"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    window = int(cfg["sliding_window"])
    theta = float(cfg["rope_theta"])

    @jax.jit
    def project(x, ln1, w):
        b, s, _ = x.shape
        a = c.rms_norm(x, ln1, eps)
        q = c.rms_norm((a @ w["wq"]).reshape(b, s, nh, d), w["q_norm"], eps)
        k = c.rms_norm((a @ w["wk"]).reshape(b, s, nkv, d), w["k_norm"], eps)
        v = (a @ w["wv"]).reshape(b, s, nkv, d)
        if not full:
            q, k = c.rope(q, positions, theta), c.rope(k, positions, theta)
        return q, k, v, jax.nn.sigmoid(a @ w["wg"])

    @jax.jit
    def block(q, k, v, start):
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
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                          jnp.repeat(v, rep, 2))

    @jax.jit
    def finish(x, o, gate, wo, post):
        b, s, _ = x.shape
        return x + c.rms_norm((o.reshape(b, s, -1) * gate) @ wo, post, eps)

    def attention(x, norms, w):
        q, k, v, gate = project(x, norms["ln1"], w)
        outs = [block(q[:, i:i + QUERY_BLOCK], k, v, i)
                for i in range(0, q.shape[1], QUERY_BLOCK)]
        return finish(x, jnp.concatenate(outs, 1), gate, w["wo"],
                      norms["post_attn"])
    return attention


def expert_layer(cfg, device):
    """``fn(m, moe, i) -> F(m)``: the held experts' part and the shared
    expert of the ``i``-th expert layer on the normed rows ``m``, whose
    weights ``moe`` holds stacked, as stored; an expert is converted at a
    time.  Held: ``experts_held_first`` .. ``+ num_experts`` of the
    router's width."""
    k = int(cfg["num_experts_per_tok"])
    first, held = int(cfg["experts_held_first"]), int(cfg["num_experts"])
    scale = float(cfg["route_scale"])
    route_norm = bool(cfg["route_norm"])

    @jax.jit
    def route(m, router, bias):
        s = jax.nn.sigmoid(m @ router)                        # [B, S, E]
        chosen = jnp.argsort(-(s + bias), axis=-1)[..., :k]
        w = jnp.take_along_axis(s, chosen, -1)
        if route_norm:
            w = w / w.sum(-1, keepdims=True)
        return chosen, w * scale

    @jax.jit
    def add_expert(y, m, chosen, w, e, we):
        mine = ((chosen == e) * w).sum(-1)                    # [B, S]
        return y + mine[..., None] * _swiglu(m, we)

    def experts(m, moe, i):
        chosen, w = route(m, _take(moe["router"], i, device),
                          _take(moe["bias"], i, device))
        y = _swiglu(m, _take(moe["shared"], i, device))
        for e in range(held):
            # [i, e] at once: a layer's 32 experts sliced out first are a
            # copy of 0.6 GB a matrix beside the engine's pools
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
        eps = cfg["rms_norm_eps"]

        def take(tree, i):
            return _take(tree, i, device)

        attend = {full: _attention(cfg, full, positions)
                  for full in (True, False)}
        experts = expert_layer(cfg, device)
        pre = jax.jit(lambda x, g: c.rms_norm(x, g, eps))
        add = jax.jit(lambda x, y, g: x + c.rms_norm(y, g, eps))
        dense = jax.jit(_swiglu)

        table = jax.device_put(params["embed"]["tokens"], device)
        x = c.f32(table[ids])
        if cfg["mup_enabled"]:
            x = x * math.sqrt(cfg["hidden_size"])
        n_dense = int(cfg["num_dense_layers"])
        types = cfg["layer_types"][:cfg["num_hidden_layers"]]
        for i, kind in enumerate(types):
            norms = {n: take(layers[n]["scale"], i)
                     for n in ("ln1", "post_attn", "ln2", "post_mlp")}
            x = attend[kind == "full_attention"](x, norms,
                                                 take(layers["attn"], i))
            m = pre(x, norms["ln2"])
            y = (dense(m, take(layers["mlp"], i)) if i < n_dense
                 else experts(m, layers["moe"], i - n_dense))
            x = add(x, y, norms["post_mlp"])
        fn = c.f32(jax.device_put(params["final_norm"], device))
        x = c.rms_norm(x[:, real - last if last else 0:real], fn["scale"],
                       eps)
        return x @ c.f32(jax.device_put(params["lm_head"], device))
