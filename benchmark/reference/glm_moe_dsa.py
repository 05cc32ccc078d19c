"""GLM-5 (``zai-org/GLM-5``, HF ``glm_moe_dsa``): the language model
(:func:`logits`) and its multi-token-prediction module over a whole
sequence, teacher-forced (:func:`draft_logits`).

Pre-norm residual blocks (RMSNorm, eps ``rms_norm_eps``), a final
RMSNorm, an untied head.  ``h`` is a block's normed input::

    attention (every layer; MLA with DeepSeek-V3.2's indexer)
      c_q = rms(h W_qa);  q_j = c_q W_qb  -> heads x (nope | rope)
      [c_kv | k_r] = h W_kva;  c_kv = rms(c_kv)
      q_rope, k_r rotated, pairs (2i, 2i + 1)  (rope_interleave)
      k_j = [c_kv W_kb,j^T | k_r];  v_j = c_kv W_vb,j
      indexer: qI = c_q W_Iq -> index_n_heads x index_head_dim,
               kI_s = layer_norm(h_s W_Ik), the first qk_rope_head_dim
               dims of both rotated, pairs (2i, 2i + 1)
               (indexer_rope_interleave);
               w_t = h_t W_Iw * n_heads^-.5 * dim^-.5
               I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
      S_t = the index_topk largest I[t, s] over s <= t (all of them while
            t < index_topk), by sorting
      p = softmax over S_t of q . k / sqrt(nope + rope)
      out = concat_j(sum_s p_s v_j,s) W_o          (no gate, no rescale)
    feed-forward: SwiGLU of intermediate_size in the first
      first_k_dense_replace layers; after them
      s = sigmoid(h W_r);  choose the num_experts_per_tok largest of s + b
      (by sorting; n_group = topk_group = 1 is one group: no limit);
      w_e = s_e / sum_chosen s  (norm_topk_prob)
      y = routed_scaling_factor * sum_{e chosen and held} w_e SwiGLU_e(h)
          + SwiGLU_shared(h)
    the module (num_nextn_predict_layers = 1; DeepSeek-V3's MTP), for
      position i with trunk output g_i (AFTER the trunk's final norm):
      u_i = [rms_e(Emb(x_{i+1})) ; rms_h(g_i)] W_eh     (2 hidden -> hidden)
      one block of the kind above on u (its own attention over the
      module's own keys of positions 0..i, its own indexer, router,
      experts and shared expert), then rms and the TRUNK's head:
      the logits of x_{i+2}.

What the published config does not settle, each listed in the
configuration file under ``assumed`` and taken the same way by the
program: the indexer as DeepSeek-V3.2 publishes it, without its Hadamard
rotation and FP8; the module's input ``g_i`` as the trunk's output after
its final norm, the embedding's half of ``W_eh`` first, embedding and
head the trunk's; the selection bias ``b`` (zeros in the benchmark's
weights).  Rotary here is the interleaved form itself, the rotated pair
written back to ``(2i, 2i + 1)``; the program leaves the pairs'
members in two halves (HF's ``rope_interleave`` does too): every dot
product is the same.

The share: this chip's experts are ``experts_held_first`` ..
``+ n_routed_experts`` of the router's width (the params' own); what the
absent experts would add is left out, here as in the program.  The
vocabulary is the slice the params hold.

To fit beside the engine's weights and at thousands of positions:
queries go through attention ``QUERY_BLOCK`` rows at a time against every
key, a layer's weights are converted to float32 a group at a time and an
expert at a time, and the positions are padded with token 0 to a whole
number of blocks (a later position is seen by no earlier one); none of
it changes a value.  Shares no code with ``deepspeed_tpu``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import common as c

QUERY_BLOCK = 128


def rope_pairs(x, positions, theta):
    """Rotary over all dims of x [B, S, H, D], pair ``i`` the neighbours
    ``(2i, 2i + 1)`` turned by ``position * theta**(-2i/D)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=c.F32) / d)
    ang = positions[..., None].astype(c.F32) * inv            # [B, S, D/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def _rotate(cfg, indexer: bool):
    interleaved = bool(cfg["indexer_rope_interleave" if indexer
                           else "rope_interleave"])
    return rope_pairs if interleaved else c.rope


def _attention(cfg, positions):
    """``fn(x, ln1, w) -> x + attention`` of one layer."""
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    topk = int(cfg["index_topk"])
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    turn, turn_i = _rotate(cfg, False), _rotate(cfg, True)

    def first(x, fn):
        return jnp.concatenate([fn(x[..., :rope], positions, theta),
                                x[..., rope:]], -1)

    def project(x, ln1, w):
        b, s, _ = x.shape
        h = c.rms_norm(x, ln1, eps)
        c_q = c.rms_norm(h @ w["wq_a"], w["q_norm"], eps)
        q = (c_q @ w["wq_b"]).reshape(b, s, heads, nope + rope)
        q = jnp.concatenate([q[..., :nope],
                             turn(q[..., nope:], positions, theta)], -1)
        kv = h @ w["wkv_a"]
        c_kv = c.rms_norm(kv[..., :rank], w["kv_norm"], eps)
        k_r = turn(kv[..., None, rank:], positions, theta)
        k = jnp.concatenate(
            [jnp.einsum("bsr,hnr->bshn", c_kv, w["wk_b"]),
             jnp.broadcast_to(k_r, (b, s, heads, rope))], -1)
        v = jnp.einsum("bsr,hrv->bshv", c_kv, w["wv_b"])
        q_i = first((c_q @ w["idx_wq"]).reshape(b, s, ih, idim), turn_i)
        k_i = c.layer_norm(h @ w["idx_wk"], w["idx_k_norm"]["scale"],
                           w["idx_k_norm"]["bias"], 1e-6)
        k_i = first(k_i[:, :, None], turn_i)[:, :, 0]
        w_i = (h @ w["idx_ww"]) * (ih ** -0.5 * idim ** -0.5)
        return q, k, v, q_i, k_i, w_i

    def block(q, k, v, q_i, k_i, w_i, start):
        """Queries ``start`` .. of one block against every key."""
        n, s = q.shape[1], k.shape[1]
        t = start + jnp.arange(n)[:, None]
        seen = jnp.arange(s)[None, :] <= t
        index = jnp.einsum(
            "btj,btjs->bts", w_i,
            jax.nn.relu(jnp.einsum("btjd,bsd->btjs", q_i, k_i)))
        index = jnp.where(seen[None], index, -jnp.inf)
        # the rank of every key among the row's, largest first
        rank_ = jnp.argsort(jnp.argsort(-index, axis=-1), axis=-1)
        seen = seen[None] & (rank_ < topk)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(nope + rope)
        scores = jnp.where(seen[:, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    project, block = jax.jit(project), jax.jit(block)

    def attention(x, ln1, w):
        b, s, _ = x.shape
        q, k, v, q_i, k_i, w_i = project(x, ln1, w)
        outs = []
        for start in range(0, s, QUERY_BLOCK):
            cut = slice(start, start + QUERY_BLOCK)
            outs.append(block(q[:, cut], k, v, q_i[:, cut], k_i,
                              w_i[:, cut], start))
        return x + jnp.concatenate(outs, 1).reshape(b, s, -1) @ w["wo"]
    return attention


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wi"])) @ w["wo"]


def _take(tree, i, device):
    """Layer ``i`` of stacked weights, on ``device`` in float32."""
    return c.f32(jax.tree.map(lambda a: jax.device_put(a[i], device), tree))


def _on(tree, device):
    return c.f32(jax.tree.map(lambda a: jax.device_put(a, device), tree))


def _experts(cfg, device):
    """``fn(x, ln2, moe, i) -> x + experts``: the held experts' part and
    the shared expert of the ``i``-th expert layer, whose weights ``moe``
    holds stacked, as stored; an expert is converted at a time."""
    eps, k = cfg["rms_norm_eps"], int(cfg["num_experts_per_tok"])
    first, held = int(cfg["experts_held_first"]), int(cfg["n_routed_experts"])
    scaling = float(cfg["routed_scaling_factor"])
    norm_topk = bool(cfg["norm_topk_prob"])
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise NotImplementedError("group-limited routing with more than "
                                  "one group")

    @jax.jit
    def route(x, ln2, router, bias):
        h = c.rms_norm(x, ln2, eps)
        s = jax.nn.sigmoid(h @ router)                        # [B, S, E]
        chosen = jnp.argsort(-(s + bias), axis=-1)[..., :k]
        w = jnp.take_along_axis(s, chosen, -1)
        if norm_topk:
            w = w / w.sum(-1, keepdims=True)
        return h, chosen, w * scaling

    @jax.jit
    def add_expert(y, h, chosen, w, e, we):
        mine = ((chosen == e) * w).sum(-1)                    # [B, S]
        return y + mine[..., None] * _swiglu(h, we)

    def experts(x, ln2, moe, i):
        h, chosen, w = route(x, ln2, _take(moe["router"], i, device),
                             _take(moe["bias"], i, device))
        y = _swiglu(h, _take(moe["shared"], i, device))
        for e in range(held):
            we = _take({n: moe[n][i] for n in ("wg", "wi", "wo")}, e, device)
            y = add_expert(y, h, chosen, w, first + e, we)
        return x + y
    return experts


def _padded(input_ids, device):
    ids = jnp.asarray(input_ids)
    real = ids.shape[1]
    ids = jax.device_put(jnp.pad(ids, ((0, 0), (0, -real % QUERY_BLOCK))),
                         device)
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1])[None], ids.shape)
    return ids, real, positions


def _trunk(params, ids, positions, cfg, device):
    """The trunk's output after its final norm, every (padded) position."""
    layers, eps = params["layers"], cfg["rms_norm_eps"]
    attend = _attention(cfg, positions)
    experts = _experts(cfg, device)
    dense = jax.jit(lambda x, ln2, w: x + _swiglu(c.rms_norm(x, ln2, eps), w))
    x = c.f32(jax.device_put(params["embed"]["tokens"], device)[ids])
    n_dense = int(cfg["first_k_dense_replace"])
    for i in range(int(cfg["num_hidden_layers"])):
        x = attend(x, _take(layers["ln1"]["scale"], i, device),
                   _take(layers["full"], i, device))
        ln2 = _take(layers["ln2"]["scale"], i, device)
        if i < n_dense:
            x = dense(x, ln2, _take(layers["mlp"], i, device))
        else:
            x = experts(x, ln2, layers["moe"], i - n_dense)
    return c.rms_norm(x, _on(params["final_norm"]["scale"], device), eps)


def logits(params, input_ids, cfg, device, last: int = 0):
    """The language model's logits of every position, or of the ``last``
    positions only."""
    with c.highest():
        ids, real, positions = _padded(input_ids, device)
        g = _trunk(params, ids, positions, cfg, device)
        return g[:, real - last if last else 0:real] \
            @ _on(params["lm_head"], device)


def draft_logits(params, input_ids, cfg, device, last: int = 0):
    """The module's logits, teacher-forced over the whole sequence:
    entry ``i`` (of ``S - 1``) is made of the trunk's output at position
    ``i`` and the embedding of token ``i + 1``, and predicts token
    ``i + 2``; every entry, or the ``last`` only."""
    if int(cfg.get("num_nextn_predict_layers", 0)) != 1:
        raise ValueError("the configuration has no (one) module")
    with c.highest():
        ids, real, positions = _padded(input_ids, device)
        eps, mp = cfg["rms_norm_eps"], params["mtp"]
        g = _trunk(params, ids, positions, cfg, device)
        table = jax.device_put(params["embed"]["tokens"], device)
        # the token after the last padded position does not matter: no
        # entry kept reads it
        following = c.f32(table[jnp.roll(ids, -1, axis=1)])
        u = jnp.concatenate(
            [c.rms_norm(following, _on(mp["enorm"]["scale"], device), eps),
             c.rms_norm(g, _on(mp["hnorm"]["scale"], device), eps)],
            -1) @ _on(mp["eh_proj"], device)
        u = _attention(cfg, positions)(
            u, _on(mp["attn_norm"]["scale"], device), _on(mp["full"], device))
        u = _experts(cfg, device)(u, _on(mp["ffn_norm"]["scale"], device),
                                  mp["moe"], 0)
        u = c.rms_norm(u, _on(mp["norm"]["scale"], device), eps)
        n = real - 1
        return u[:, n - last if last else 0:n] @ _on(params["lm_head"],
                                                     device)
