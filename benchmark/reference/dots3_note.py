"""dots3-note (``dots-studio/dots3-note-prev``, HF ``dots3_note``), the
language model alone: text in, text out (the vision and audio towers and
a multi-token-prediction module are outside the published ``config``).

Pre-norm residual blocks (RMSNorm, eps ``rms_norm_eps``), a final
RMSNorm, an untied head.  ``x`` is a block's normed input::

    full layer (layer_types[i] == "full_attention")
      c_q = rms(x W_qa) * sqrt(hidden / q_lora_rank)
      q_h = c_q W_qb                -> heads x (nope | rope), rope rotated
      [c_kv | k_r] = x W_kva;  c_kv = rms(c_kv) * sqrt(hidden / kv_lora_rank)
      k_r = rope(k_r), one for all heads
      k_nope,h = c_kv W_kb,h^T;  v_h = c_kv W_vb,h
      indexer: qI = c_q W_Iq -> index_n_heads x index_head_dim,
               kI_s = layer_norm(x_s W_Ik), the first qk_rope_head_dim
               dims of both rotated;  w_t = x_t W_Iw * n_heads^-.5 * dim^-.5
               I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
      S_t = the index_topk largest I[t, s] over s <= t (all while
            t < index_topk), by sorting
      p = softmax over S_t of (q_nope.k_nope + q_rope.k_r) / sqrt(nope+rope)
      out = concat_h(sigmoid(x W_g)_h * sum_s p_s v_h,s) W_o
    window layer ("sliding_attention"): the same at the swa_* sizes, no
      indexer, keys s <= t with t - s < sliding_window_size
    feed-forward: SwiGLU of intermediate_size in the first
      first_k_dense_replace layers; after them
      s = sigmoid(x W_r);  choose the num_experts_per_tok largest of s + b
      (by sorting);  w_e = s_e / sum_chosen s * routed_scaling_factor
      y = sum_{e chosen and held} w_e SwiGLU_e(x) + SwiGLU_shared(x)

What the published config does not settle, each listed in the
configuration file under ``assumed`` and taken the same way by the
program: ``apply_mla_qkv_lora_rescale`` as LongCat-Flash's
``mla_scale_*_lora`` (the normed latents times sqrt(hidden / rank));
``attention_gate_type: headwise`` as the headwise variant of gated
attention; the indexer as DeepSeek-V3.2 publishes it, without its
Hadamard rotation and FP8 (a rotation of both sides changes no dot
product); ``sliding_window_size`` 513 as the token and the 512 before it;
no group-limited routing (the config has no ``n_group``); rotary pairs in
the half-split layout (``common.rope``), on the LAST ``rope`` dims of a
head's query and key and on the FIRST of the indexer's.

The share: this chip's experts are ``experts_held_first`` ..
``+ n_routed_experts`` of the router's width (the params' own); what the
absent experts would add is left out, here as in the program.  The
vocabulary is the slice the params hold.

To fit beside the engine's weights, and at the thousands of positions
``tools/gate_probe_dsa.py`` asks for: queries go through attention
``QUERY_BLOCK`` rows at a time against every key (scores ``[heads, block,
S]``, never ``[heads, S, S]``), a layer's weights are converted to
float32 a group at a time and an expert at a time, and the positions are
padded with token 0 to a whole number of blocks (one set of compiled
shapes for prompts of nearly one length; a later position is seen by no
earlier one); none of it changes a value.  ``selected`` (a list) is given
each full layer's own chosen sets as a boolean ``[S, S]``; ``forced``
(one ``[S, k]`` array of key positions a full layer, -1 where a row has
fewer) makes the full layers ATTEND over those sets in place of their
own, which they still compute and report: how a program's arithmetic
compares over the keys the program chose.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import common as c

QUERY_BLOCK = 128


def _widths(cfg, full: bool):
    p = "" if full else "swa_"
    return {"heads": cfg["num_attention_heads" if full
                         else "swa_num_attention_heads"],
            "q_rank": cfg[p + "q_lora_rank"],
            "kv_rank": cfg[p + "kv_lora_rank"],
            "nope": cfg[p + "qk_nope_head_dim"],
            "rope": cfg[p + "qk_rope_head_dim"],
            "v": cfg[p + "v_head_dim"],
            "theta": float(cfg["rope_theta" if full else "swa_rope_theta"])}


def _rope_last(x, positions, n, theta):
    """Rotate the last ``n`` dims of x [B, S, H, D]."""
    return jnp.concatenate(
        [x[..., :-n], c.rope(x[..., -n:], positions, theta)], -1)


def _rope_first(x, positions, n, theta):
    return jnp.concatenate(
        [c.rope(x[..., :n], positions, theta), x[..., n:]], -1)


def _attention(cfg, full: bool, positions, selected, forced=None):
    """One layer's attention: ``fn(x, ln1, w) -> x + attention``."""
    forced = iter(forced) if forced is not None else None
    s_ = _widths(cfg, full)
    heads, nope, rope = s_["heads"], s_["nope"], s_["rope"]
    hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    rescale = bool(cfg["apply_mla_qkv_lora_rescale"])
    q_mul = math.sqrt(hidden / s_["q_rank"]) if rescale else 1.0
    kv_mul = math.sqrt(hidden / s_["kv_rank"]) if rescale else 1.0
    window = int(cfg["sliding_window_size"])
    topk = int(cfg["index_topk"])
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]

    def project(x, ln1, w):
        b, s, _ = x.shape
        h = c.rms_norm(x, ln1, eps)
        c_q = c.rms_norm(h @ w["wq_a"], w["q_norm"], eps) * q_mul
        q = _rope_last((c_q @ w["wq_b"]).reshape(b, s, heads, nope + rope),
                       positions, rope, s_["theta"])
        kv = h @ w["wkv_a"]
        c_kv = c.rms_norm(kv[..., :s_["kv_rank"]], w["kv_norm"], eps) * kv_mul
        k_r = c.rope(kv[..., None, s_["kv_rank"]:], positions, s_["theta"])
        k = jnp.concatenate(
            [jnp.einsum("bsr,hnr->bshn", c_kv, w["wk_b"]),
             jnp.broadcast_to(k_r, (b, s, heads, rope))], -1)
        v = jnp.einsum("bsr,hrv->bshv", c_kv, w["wv_b"])
        gate = jax.nn.sigmoid(h @ w["wg"])                   # [B, S, heads]
        if not full:
            return q, k, v, gate, None, None, None
        q_i = _rope_first((c_q @ w["idx_wq"]).reshape(b, s, ih, idim),
                          positions, rope, s_["theta"])
        k_i = c.layer_norm(h @ w["idx_wk"], w["idx_k_norm"]["scale"],
                           w["idx_k_norm"]["bias"], 1e-6)
        k_i = _rope_first(k_i[:, :, None], positions, rope,
                          s_["theta"])[:, :, 0]
        w_i = (h @ w["idx_ww"]) * (ih ** -0.5 * idim ** -0.5)
        return q, k, v, gate, q_i, k_i, w_i

    def block(q, k, v, q_i, k_i, w_i, start, keys=None):
        """Queries ``start`` .. of one block against every key; ``keys``
        [n, k]: the positions to attend over in place of the chosen."""
        n, s = q.shape[1], k.shape[1]
        t = start + jnp.arange(n)[:, None]
        j = jnp.arange(s)[None, :]
        seen = j <= t
        own = None
        if full:
            index = jnp.einsum(
                "btj,btjs->bts", w_i,
                jax.nn.relu(jnp.einsum("btjd,bsd->btjs", q_i, k_i)))
            index = jnp.where(seen[None], index, -jnp.inf)
            # the rank of every key among the row's, largest first
            order = jnp.argsort(-index, axis=-1)
            rank = jnp.argsort(order, axis=-1)
            seen = own = seen[None] & (rank < topk)
            if keys is not None:
                # -1 (a row with fewer keys) lands past the end: dropped
                at = jnp.where(keys >= 0, keys, s)
                seen = jnp.zeros((n, s), bool).at[
                    jnp.arange(n)[:, None], at].set(True, mode="drop")[None]
        else:
            seen = (seen & (t - j < window))[None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(nope + rope)
        scores = jnp.where(seen[:, None], scores, -jnp.inf)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        return out, own

    project, block = jax.jit(project), jax.jit(block)

    def attention(x, ln1, w):
        b, s, _ = x.shape
        q, k, v, gate, q_i, k_i, w_i = project(x, ln1, w)
        keys = jnp.asarray(next(forced)) if full and forced is not None \
            else None
        outs, sets = [], []
        for start in range(0, s, QUERY_BLOCK):
            cut = slice(start, start + QUERY_BLOCK)
            out, own = block(q[:, cut], k, v,
                             None if q_i is None else q_i[:, cut], k_i,
                             None if w_i is None else w_i[:, cut], start,
                             None if keys is None else keys[cut])
            outs.append(out)
            sets.append(own)
        if full and selected is not None:
            selected.append(jnp.concatenate(sets, 1)[0])
        out = jnp.concatenate(outs, 1) * gate[..., None]
        return x + out.reshape(b, s, -1) @ w["wo"]
    return attention


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wi"])) @ w["wo"]


def _take(tree, i, device):
    """Layer ``i`` of stacked weights, on ``device`` in float32."""
    return c.f32(jax.tree.map(lambda a: jax.device_put(a[i], device), tree))


def _experts(cfg, device):
    """``fn(x, ln2, moe, i) -> x + experts``: the held experts' part and
    the shared expert of the ``i``-th expert layer, whose weights ``moe``
    holds stacked, as stored; an expert is converted at a time."""
    eps, k = cfg["rms_norm_eps"], int(cfg["num_experts_per_tok"])
    first, held = int(cfg["experts_held_first"]), int(cfg["n_routed_experts"])
    scaling = float(cfg["routed_scaling_factor"])
    norm_topk = bool(cfg["norm_topk_prob"])

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


def logits(params, input_ids, cfg, device, last: int = 0, selected=None,
           forced=None):
    """Logits of every position, or of the ``last`` positions only.
    ``selected``: a list that is given every full layer's chosen key sets
    (boolean ``[S, S]``, row = query), in layer order; ``forced``: the
    sets the full layers attend over instead (the module's docstring)."""
    with c.highest():
        ids = jnp.asarray(input_ids)
        real = ids.shape[1]
        pad = -real % QUERY_BLOCK
        ids = jax.device_put(jnp.pad(ids, ((0, 0), (0, pad))), device)
        if forced is not None:
            # a padding row attends over key 0: it must attend to something
            forced = [jnp.pad(jnp.asarray(f), ((0, pad), (0, 0)))
                      for f in forced]
        positions = jnp.broadcast_to(jnp.arange(ids.shape[1])[None],
                                     ids.shape)
        layers = params["layers"]
        eps = cfg["rms_norm_eps"]

        def take(tree, i):
            return _take(tree, i, device)

        attend = {True: _attention(cfg, True, positions, selected, forced),
                  False: _attention(cfg, False, positions, None)}
        experts = _experts(cfg, device)
        dense = jax.jit(lambda x, ln2, w: x + _swiglu(
            c.rms_norm(x, ln2, eps), w))

        table = jax.device_put(params["embed"]["tokens"], device)
        x = c.f32(table[ids])
        count = {"full": 0, "window": 0, "mlp": 0, "moe": 0}
        types = cfg["layer_types"][:cfg["num_hidden_layers"]]
        for i, kind in enumerate(types):
            full = kind == "full_attention"
            name = "full" if full else "window"
            x = attend[full](x, take(layers["ln1"]["scale"], i),
                             take(layers[name], count[name]))
            count[name] += 1
            if i < cfg["first_k_dense_replace"]:
                x = dense(x, take(layers["ln2"]["scale"], i),
                          take(layers["mlp"], count["mlp"]))
                count["mlp"] += 1
            else:
                x = experts(x, take(layers["ln2"]["scale"], i),
                            layers["moe"], count["moe"])
                count["moe"] += 1
        fn = c.f32(jax.device_put(params["final_norm"], device))
        if selected is not None:
            selected[:] = [own[:real, :real] for own in selected]
        x = c.rms_norm(x[:, real - last if last else 0:real], fn["scale"],
                       eps)
        return x @ c.f32(jax.device_put(params["lm_head"], device))
