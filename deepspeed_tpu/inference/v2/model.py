"""Ragged (paged-KV) transformer forward for continuous batching.

TPU-native replacement for the reference's blocked-flash-attention kernels
(ref inference/v2/kernels/ragged_ops/: blocked flash attn w/ KV-block table,
linear+blocked-KV rotary, logits_gather, embed): one forward processes an
arbitrary prefill/decode mix as a flat token list with per-token metadata.

Design (vs the reference's CUDA kernels):
* KV cache pages are rows of ONE pool for every layer, ``[L, kv_heads, P, d]``
  (P = num_blocks·block_size; kv-head-major for the kernels' page blocks).
  On the kernel path (``paged_pallas``) a step puts its rows' KV into the
  pool in place a page at a time, both pools in one kernel
  (``ops/pallas/kv_append.py``), and the Pallas block-table kernels
  (``ops/pallas/paged_attention.py``) read the layer's pages out of the
  whole pool by the layer index.  The XLA path (head 64,
  ALiBi, the CPU) takes ONE layer's pages out of the pool, scatters into
  them, *gathers* each token's context rows through the block table, and
  puts the layer back.
* Every shape is fixed by (token_budget, max_seqs, max_ctx): one compiled
  executable serves all batch mixes (the reference re-launches variable-size
  kernels instead).
* The layer loop is ``lax.scan`` over the stacked parameters (the training
  forward's layout) with the pools in its CARRY, whole: a donated pool is
  updated in place and comes back as the buffer it came in (as the scan's
  xs/ys the whole pool was rewritten every step to change a few rows of
  it).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deepspeed_tpu.inference.v2.modules import (register_module, resolve,
                                                resolve_name)
from deepspeed_tpu.models.transformer import (TransformerConfig, _mlp_block,
                                              _norm)
from deepspeed_tpu.ops.pallas.kv_append import kv_append, step_pages
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, row_width, supports)
from deepspeed_tpu.ops.pallas.ssd_ragged import run_layout, ssd_ragged
from deepspeed_tpu.utils.platform import on_tpu


def _rope_tok(x, positions, cfg: TransformerConfig):
    """Rotary embedding over per-token positions. x: [T, H, D], positions:
    [T].  Honors ``rotary_pct`` (Phi partial rotary) like models._rope."""
    d = cfg.dim_per_head
    rot_d = d if cfg.rotary_pct >= 1.0 else max(2, int(d * cfg.rotary_pct) // 2 * 2)
    freqs = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot_d, 2, dtype=jnp.float32) / rot_d))
    angles = positions[:, None].astype(jnp.float32) * freqs  # [T, rot_d/2]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    xf = x.astype(jnp.float32)
    xr, x_pass = xf[..., :rot_d], xf[..., rot_d:]
    if cfg.rope_interleaved:
        # GPT-J "rotate every two" pairing
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        xr = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       axis=-1).reshape(xr.shape)
    else:
        x1, x2 = jnp.split(xr, 2, axis=-1)
        xr = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1)
    return jnp.concatenate([xr, x_pass], axis=-1).astype(x.dtype)


def _is_quant_cache(pages) -> bool:
    """Int8 KV cache layout: {"q": int8 payload, "s": fp32 per-row scales}
    (ref KV-block layout inference/v2/ragged/kv_cache.py:40; quantization
    per (head, row) over head_dim)."""
    return isinstance(pages, dict)


def _kv_append(pool, x, token_dest, layer=None):
    """Scatter this step's KV rows [T, nkv, d] into ``layer``'s pages of
    the pool [L, nkv, P, d], in place (``layer`` None: into one layer's
    pages [nkv, P, d]) — quantizing on append when the cache is int8."""
    if _is_quant_cache(pool):
        xf = x.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
        q8 = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
        return {"q": _kv_append(pool["q"], q8, token_dest, layer),
                "s": _kv_append(pool["s"], scale, token_dest, layer)}
    x = x.astype(pool.dtype)
    if layer is None:
        return pool.at[:, token_dest].set(x.swapaxes(0, 1))
    # every (row, head) is an update of its own, a window of the minor
    # dimension alone: the scatter then takes the pool in the layout the
    # kernels read it in (with the heads in the window the TPU's compiler
    # relays the whole pool out for the scatter and back for the kernel)
    heads = jnp.arange(pool.shape[1], dtype=jnp.int32)
    return pool.at[layer, heads[None, :], token_dest[:, None]].set(x)


def _paged_attention_xla(q, k_pages, v_pages, gather_idx, token_pos,
                         token_ctx_len, cfg: TransformerConfig, sink=None):
    """Gather-based fallback (non-TPU backends / oversize shapes).

    q: [T, nh, d]; k_pages: [nkv, P, d] and v_pages: [nkv, P, dv] (or int8
    dict caches); gather_idx: [T, C] flat page-row indices of each token's
    context.  GQA-native: queries are grouped by KV head instead of
    repeating KV.  ``sink`` [nh]: one more logit a head in every row's
    softmax, which takes probability and adds no value.  Returns
    [T, nh, dv].
    """
    t, nh, d = q.shape
    if _is_quant_cache(k_pages):
        nkv = k_pages["q"].shape[0]
        k_ctx = (k_pages["q"][:, gather_idx].astype(q.dtype)
                 * k_pages["s"][:, gather_idx, None].astype(q.dtype))
        v_ctx = (v_pages["q"][:, gather_idx].astype(q.dtype)
                 * v_pages["s"][:, gather_idx, None].astype(q.dtype))
    else:
        nkv = k_pages.shape[0]
        k_ctx = k_pages[:, gather_idx]  # [nkv, T, C, d]
        v_ctx = v_pages[:, gather_idx]
    g = nh // nkv
    qg = q.reshape(t, nkv, g, d)
    scale = (cfg.attn_scale if cfg.attn_scale is not None
             else 1.0 / math.sqrt(cfg.dim_per_head))
    scores = jnp.einsum("tkgd,ktcd->tkgc", qg, k_ctx) * scale
    c_pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    if cfg.use_alibi:
        # Bloom ALiBi (key-position form; softmax-shift equivalent)
        from deepspeed_tpu.models.transformer import alibi_slopes

        sl = alibi_slopes(nh).reshape(nkv, g)
        scores = scores + (sl[None, :, :, None]
                           * c_pos.astype(jnp.float32)[None, None, None, :]
                           ).astype(scores.dtype)
    valid = (c_pos[None, :] <= token_pos[:, None]) & \
            (c_pos[None, :] < token_ctx_len[:, None])       # [T, C]
    if cfg.sliding_window:
        valid = valid & (token_pos[:, None] - c_pos[None, :]
                         < cfg.sliding_window)
    scores = jnp.where(valid[:, None, None, :], scores.astype(jnp.float32),
                       -1e30)
    if sink is not None:
        # the sink as one more column, its probability dropped
        col = jnp.broadcast_to(sink.astype(jnp.float32).reshape(nkv, g, 1),
                               (t, nkv, g, 1))
        scores = jnp.concatenate([scores, col], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if sink is not None:
        probs = probs[..., :-1]
    out = jnp.einsum("tkgc,ktcd->tkgd", probs, v_ctx)
    return out.reshape(t, nh, v_ctx.shape[-1])


def _pallas_attn_default(block_size=0, head_dim=0, on_tpu=False,
                         has_tables=False, use_alibi=False, value_dim=None,
                         **_):
    if not (has_tables and on_tpu) or use_alibi:
        # alibi rides the XLA gather path (the Pallas kernel has no
        # score-bias lane)
        return False
    return supports(block_size, head_dim, value_dim)


@register_module("attention", "paged_pallas",
                 default_for=_pallas_attn_default)
def _attn_impl_pallas(q, k_pages, v_pages, gather_idx, token_pos,
                      token_ctx_len, cfg, block_tables, token_slot,
                      block_size, layer=None, sink=None):
    """Pallas block-table kernel (ops/pallas/paged_attention.py: runs of
    a sequence's rows share one page walk with online softmax — no
    [T, C, ...] gather materialisation, no per-token page table).
    Ref kernel: inference/v2/kernels/ragged_ops/blocked_flash."""
    if block_tables is None:
        raise ValueError(
            "attention='paged_pallas' needs block tables (the prefill "
            "mixed path carries none) — use 'auto' or 'paged_xla'")
    if cfg.use_alibi:
        raise ValueError(
            "attention='paged_pallas' has no ALiBi score-bias lane — use "
            "'auto' or 'paged_xla' for bloom-class models")
    scale = (cfg.attn_scale if cfg.attn_scale is not None
             else 1.0 / math.sqrt(cfg.dim_per_head))
    kw = dict(window=cfg.sliding_window or None, token_slot=token_slot,
              layer=layer, sink=sink)
    if _is_quant_cache(k_pages):
        return paged_decode_attention(
            q, k_pages["q"], v_pages["q"], block_tables, token_pos,
            token_ctx_len, block_size, scale, k_scales=k_pages["s"],
            v_scales=v_pages["s"], **kw)
    return paged_decode_attention(
        q, k_pages, v_pages, block_tables, token_pos, token_ctx_len,
        block_size, scale, **kw)


@register_module("attention", "paged_xla")
def _attn_impl_xla(q, k_pages, v_pages, gather_idx, token_pos,
                   token_ctx_len, cfg, block_tables, token_slot,
                   block_size, sink=None):
    return _paged_attention_xla(q, k_pages, v_pages, gather_idx, token_pos,
                                token_ctx_len, cfg, sink)


def attention_impl_name(cfg: TransformerConfig, block_size: int,
                        has_tables: bool = True) -> str:
    """The attention implementation the module registry (modules.py — ref
    inference/v2/modules/heuristics.py) picks for this geometry: 'auto'
    takes the Pallas block-table kernel on TPU when ``supports()`` says
    the chip's compiler takes the shape (K rows of the head's width, V
    rows of ``cfg.value_width``, a sink or none), the XLA gather path
    otherwise (ALiBi, a head narrower than a lane tile, a value width
    that is no whole tile); ``cfg.v2_modules`` pins a name explicitly."""
    name = dict(cfg.v2_modules or ()).get("attention", "auto")
    return resolve_name("attention", name, block_size=block_size,
                        head_dim=cfg.dim_per_head, on_tpu=on_tpu(),
                        has_tables=has_tables, use_alibi=cfg.use_alibi,
                        value_dim=cfg.value_width)


def _paged_attention(q, k_pages, v_pages, gather_idx, token_pos, token_ctx_len,
                     cfg: TransformerConfig, block_tables=None, token_slot=None,
                     block_size: int = 0, layer=None, sink=None):
    """Attention of T query tokens against their sequences' KV pages
    (K [nkv, P, d], V [nkv, P, dv]), through the implementation
    :func:`attention_impl_name` resolves; with ``layer``, against that
    layer's pages of every layer's pool [L, nkv, P, .] (``paged_pallas``
    alone reads it so); with ``sink`` [nh], a learned logit a head in the
    softmax's denominator."""
    impl = resolve("attention", attention_impl_name(
        cfg, block_size, has_tables=block_tables is not None))
    kw = {} if layer is None else {"layer": layer}
    if sink is not None:
        kw["sink"] = sink
    return impl(q, k_pages, v_pages, gather_idx, token_pos, token_ctx_len,
                cfg, block_tables, token_slot, block_size, **kw)


@register_module("ssm", "ssd_pallas",
                 default_for=lambda on_tpu=False, **_: on_tpu)
def _ssm_impl_pallas(*args, layer, chunk):
    """The chunked scan kernel (ops/pallas/ssd_ragged.py): a run's state
    read once from its slot and written back in place."""
    return ssd_ragged(*args, layer=layer, impl="pallas", chunk=chunk)


@register_module("ssm", "ssd_xla")
def _ssm_impl_xla(*args, layer, chunk):
    """The plain-XLA scan: a starting state per row — the CPU's, and a
    test's; too large at published widths."""
    return ssd_ragged(*args, layer=layer, impl="xla")


def ssm_impl_name(cfg: TransformerConfig) -> str:
    """The scan a model with a mixer runs: the kernel on a TPU, the XLA
    formulation elsewhere; ``cfg.v2_modules`` pins a name."""
    name = dict(cfg.v2_modules or ()).get("ssm", "auto")
    return resolve_name("ssm", name, on_tpu=on_tpu())


def new_ssm_state(cfg: TransformerConfig, max_seqs: int, zeros=jnp.zeros):
    """The per-sequence slots of a model with a mixer, zeroed: ``ssm``
    [L, max_seqs + 1, heads, head_dim, state] float32 and ``conv``
    [L, max_seqs + 1, kernel - 1, channels] in the compute dtype (the
    last inputs of the depthwise convolution, oldest first; channels
    minor, so a row is whole lanes), ``L`` the layers that HAVE a mixer
    (``cfg.ssm_layers``).  Slot ``max_seqs`` is the padding rows'
    garbage slot.  A one-mixer-a-layer model's tails ride its layer walk
    whole, as ``conv`` [L * slots, (kernel - 1) * channels], a sequence's
    tails of a layer ONE row (``layer * slots + slot``; the slots made up
    to a multiple of 8): the chip lays an array out by its shape, lanes
    and sublanes along dims that fill their tiles, and the step gathers
    and scatters whole rows; as [L, max_seqs + 1, 3, channels] it came in
    with the LAYERS on the sublanes and all of it was relaid, in and
    out, every step."""
    m = cfg.ssm
    ssm = zeros((cfg.ssm_layers, max_seqs + 1, m.num_heads, m.head_dim,
                 m.state_size), jnp.float32)
    if cfg.hybrid is not None:
        return {"ssm": ssm,
                "conv": zeros((cfg.ssm_layers * (-(-(max_seqs + 1) // 8) * 8),
                               (m.conv_kernel - 1) * m.conv_dim),
                              cfg.dtype)}
    return {
        "ssm": ssm,
        "conv": zeros((cfg.num_layers, max_seqs + 1, m.conv_kernel - 1,
                       m.conv_dim), cfg.dtype),
    }


def _ragged_mixer(h, p, state, ssm_meta, cfg: TransformerConfig):
    """The Mamba-2 mixer over the flat rows ``h`` [T, H] of a step, which
    are runs of consecutive rows of one sequence each: a row's
    predecessors in the convolution and in the scan are its own run's,
    and before the run's start its slot's (zeros where the run starts at
    position 0).  ``state``: ``ssm``, EVERY layer's recurrent slots (the
    scan over layers carries the array whole, so that the kernel updates
    it in place), ``layer``, this one's index in it, and ``conv``, this
    layer's tails [S + 1, k - 1, C], or every layer's [L * slots, (k - 1)
    * C], carried whole as ``ssm`` is (:func:`new_ssm_state`); returns
    (out [T, H], state')."""
    m = cfg.ssm
    slot, token_pos, run_start, from_zero, is_last = ssm_meta
    dt_ = h.dtype
    f32 = jnp.float32
    t = h.shape[0]
    k = m.conv_kernel
    pad = state["ssm"].shape[1] - 1
    # every layer's tails: a sequence's are row layer * slots + slot
    whole = state["conv"].ndim == 2
    if whole:
        first = state["layer"] * (state["conv"].shape[0]
                                  // state["ssm"].shape[0])

    with jax.named_scope("ssm.in"):
        mup = jnp.concatenate([jnp.full((n,), v, dt_) for n, v in
                               zip(m.part_sizes(), m.ssm_multipliers)])
        proj = ((h * m.ssm_in_multiplier) @ p["in_proj"].astype(dt_)) * mup
        z, xbc, dt = jnp.split(proj, [m.d_ssm, m.d_ssm + m.conv_dim],
                               axis=-1)

    with jax.named_scope("ssm.conv"):
        # depthwise causal convolution over each run: the input d rows back
        # is the row d above where the run reaches that far, else the
        # slot's tail
        if whole:
            tail = jnp.where(from_zero[:, None], 0,
                             state["conv"][first + slot]).astype(dt_)
            tap = lambda i: tail[:, i * m.conv_dim:(i + 1) * m.conv_dim]
        else:
            tail = jnp.where(from_zero[:, None, None], 0,
                             state["conv"][slot]).astype(dt_)  # [T, k-1, C]
            tap = lambda i: tail[:, i]
        in_run = jnp.arange(t, dtype=jnp.int32) - run_start
        back = []                               # d = k-1 .. 1 rows back
        for d in range(k - 1, 0, -1):
            v = jnp.roll(xbc, d, axis=0)
            for j in range(d):                  # the run is j rows old
                v = jnp.where((in_run == j)[:, None], tap(k - 1 - d + j), v)
            back.append(v)
        w = p["conv_w"].astype(f32)                          # [C, k]
        conv = xbc.astype(f32) * w[:, k - 1]
        for j, v in enumerate(back):
            conv = conv + v.astype(f32) * w[:, j]
        if "conv_b" in p:
            conv = conv + p["conv_b"].astype(f32)
        if whole:
            new_tail = jnp.concatenate(back[1:] + [xbc], axis=1)
            dest = first + jnp.where(is_last, slot, pad)
        else:
            new_tail = jnp.stack(back[1:] + [xbc], axis=1)   # [T, k-1, C]
            dest = jnp.where(is_last, slot, pad)
        conv_state = state["conv"].at[dest].set(
            new_tail.astype(state["conv"].dtype))
        xbc = jax.nn.silu(conv).astype(dt_)

    with jax.named_scope("ssm.scan"):
        gn = m.n_groups * m.state_size
        x = xbc[:, :m.d_ssm].reshape(t, m.num_heads, m.head_dim)
        b = xbc[:, m.d_ssm:m.d_ssm + gn].reshape(t, m.n_groups, m.state_size)
        c = xbc[:, m.d_ssm + gn:].reshape(t, m.n_groups, m.state_size)
        dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
        a = -jnp.exp(p["A_log"].astype(f32))
        y, ssm_state = resolve("ssm", ssm_impl_name(cfg))(
            x, dt, a, b, c, state["ssm"], slot, token_pos,
            layer=state["layer"], chunk=m.chunk_size)
        y = y + p["D"].astype(f32)[None, :, None] * x.astype(f32)

    with jax.named_scope("ssm.out"):
        # gated RMSNorm, the gate first, the mean square over each group
        y = y.reshape(t, m.d_ssm) * jax.nn.silu(z.astype(f32))
        yg = y.reshape(t, m.n_groups, -1)
        yg = yg * lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
                            + cfg.layernorm_eps)
        y = (yg.reshape(t, m.d_ssm) * p["norm"].astype(f32)).astype(dt_)
        return (y @ p["out_proj"].astype(dt_),
                {"ssm": ssm_state, "conv": conv_state,
                 "layer": state["layer"]})


def _ssm_meta(cfg: TransformerConfig, state, token_slot, token_pos):
    """What every layer's mixer needs of the step's row layout, once."""
    if cfg.ssm is None:
        return None
    if state is None:
        raise ValueError(
            "this model has a Mamba-2 SSM mixer: the ragged step needs its "
            "per-sequence state slots (state=new_ssm_state(...)); a caller "
            "that keeps none (inference.kv_generate) cannot run it")
    if cfg.is_moe or cfg.alt_window or cfg.parallel_block:
        raise NotImplementedError(
            "an SSM mixer beside attention is served in the plain "
            "sequential block only (no MoE, alt_window or parallel_block)")
    with jax.named_scope("ssm.scan"):
        return (token_slot, token_pos) + run_layout(token_slot, token_pos)


def _ragged_layer(x, lp, cache_k, cache_v, layer, meta,
                  cfg: TransformerConfig, layer_is_moe=False, state=None,
                  ssm_meta=None):
    """Block ``layer`` over flat tokens [T, H]; appends its KV to the
    pools [L, nkv, P, d] in place and attends via its pages of them
    (``cfg`` says the layer's KV heads, window and rotary: of a
    mixed-attention model, its KIND's; V rows are ``cfg.value_width``
    wide, and K rows and queries are padded with zeros to the width the K
    pool keeps, ``row_width``).
    Returns (x, cache_k, cache_v, state): ``state`` is the layer's
    recurrent slots where the block has an SSM mixer, else None."""
    (token_pos, token_dest, gather_idx, token_ctx_len, token_slot,
     block_tables, block_size, dest_pages) = meta
    mixer = cfg.ssm
    # q/k norms, a gate on the attention output, a norm after attention
    # and after the feed-forward, held experts (a mixed-attention model)
    mx = cfg.mixed
    t = x.shape[0]
    nh, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
    dv = cfg.value_width
    dt = x.dtype

    def proj(w, b_):
        # a value of its own, [T, heads*d] rows-major: with the heads'
        # reshape folded into the product the TPU's compiler wants the
        # weight [out][in], and slices the layer's matrix out of the stack
        # and transposes the copy before it multiplies; behind the barrier
        # the product streams the stack from HBM as wo's and the MLP's do,
        # and the relayout falls on the step's rows.  float32 through bias
        # and rope and rounded once, as the folded product was; weight and
        # bias enter as dt holds them.
        y = lax.optimization_barrier(jnp.matmul(
            h_attn, w.astype(dt), preferred_element_type=jnp.float32))
        return y + b_.astype(dt).astype(y.dtype) if b_ is not None else y

    with jax.named_scope("attn.qkv"):
        h = _norm(x, lp["ln1"], cfg)
        h_attn = h * mixer.attention_in_multiplier if mixer else h
        q = proj(lp["attn"]["wq"], lp["attn"].get("bq")).reshape(t, nh, d)
        k = proj(lp["attn"]["wk"], lp["attn"].get("bk")).reshape(t, nkv, d)
        v = proj(lp["attn"]["wv"], lp["attn"].get("bv")).reshape(t, nkv, dv)
        if mixer:
            # the multiplier as the model's dtype holds it (it scaled a dt k)
            k = k * jnp.asarray(mixer.key_multiplier, dt).astype(k.dtype)
        if mx is not None and mx.gate:
            gate = proj(lp["attn"]["wg"], None)
        if mx is not None and mx.qk_norm:
            # over the dims of a head, one gain for all heads, before rotary
            q = _norm(q, {"scale": lp["attn"]["q_norm"]}, cfg)
            k = _norm(k, {"scale": lp["attn"]["k_norm"]}, cfg)
        if cfg.use_rope:
            q = _rope_tok(q, token_pos, cfg)
            k = _rope_tok(k, token_pos, cfg)
        if mx is not None and mx.value_scale != 1.0:
            v = v * mx.value_scale
        q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
        pad = 0 if _is_quant_cache(cache_k) else cache_k.shape[-1] - d
        if pad:
            # the K pool keeps whole lane tiles a row: zeros add nothing
            # to a score
            q, k = (jnp.pad(a, ((0, 0), (0, 0), (0, pad))) for a in (q, k))

    # Write this step's KV to its pages (padding tokens target page 0 =
    # garbage, so no mask needed; ref: linear_blocked_kv_copy). A layer's
    # pages are [nkv, P, d] (kv-head-major for the Pallas kernel's page
    # blocks), quantized on append when the cache is int8 (_kv_append).
    attend = functools.partial(
        _paged_attention, q, gather_idx=gather_idx, token_pos=token_pos,
        token_ctx_len=token_ctx_len, cfg=cfg, block_tables=block_tables,
        token_slot=token_slot, block_size=block_size,
        sink=lp["attn"].get("sink"))
    if attention_impl_name(cfg, block_size,
                           block_tables is not None) == "paged_pallas":
        # the kernels read the layer's pages out of the whole pool; the
        # rows reach it by whole pages, both pools in one call (by the
        # row scatter where _step_meta made no page list)
        with jax.named_scope("attn.append"):
            if dest_pages is None:
                cache_k = _kv_append(cache_k, k, token_dest, layer)
                cache_v = _kv_append(cache_v, v, token_dest, layer)
            else:
                cache_k, cache_v = kv_append(cache_k, cache_v, k, v,
                                             dest_pages, layer, block_size)
        with jax.named_scope("attn.read"):
            attn = attend(cache_k, cache_v, layer=layer)
    else:
        # the XLA gather path (head 64, ALiBi, the CPU) works on the
        # layer's pages as a value of their own and puts them back: one
        # layer read and written where the kernels touch the step's rows
        # (scatter and gather into the whole pool at once make the TPU's
        # compiler relay the pool out between them, for every layer)
        with jax.named_scope("attn.append"):
            k_pages = _kv_append(jax.tree.map(lambda c: c[layer], cache_k),
                                 k, token_dest)
            v_pages = _kv_append(jax.tree.map(lambda c: c[layer], cache_v),
                                 v, token_dest)
        with jax.named_scope("attn.read"):
            attn = attend(k_pages, v_pages)
        with jax.named_scope("attn.append"):
            cache_k, cache_v = jax.tree.map(
                lambda c, pages: c.at[layer].set(pages),
                (cache_k, cache_v), (k_pages, v_pages))
    with jax.named_scope("attn.out"):
        attn = attn.reshape(t, nh * dv)
        if mx is not None and mx.gate:
            attn = attn * jax.nn.sigmoid(gate).astype(dt)
        attn = attn @ lp["attn"]["wo"].astype(dt)
        if mx is not None and mx.sandwich_norm:
            attn = _norm(attn, lp["post_attn"], cfg)
        if lp["attn"].get("bo") is not None:
            attn = attn + lp["attn"]["bo"].astype(dt)
    if mixer:
        # the mixer reads the same normed input, beside attention
        mix, state = _ragged_mixer(h, lp["ssm"], state, ssm_meta, cfg)
        with jax.named_scope("attn.out"):
            attn = (attn * mixer.attention_out_multiplier
                    + mix * mixer.ssm_out_multiplier)

    if cfg.parallel_block:
        # Falcon/Phi: attention and MLP read the shared input norm;
        # Falcon-40B/GPT-NeoX (parallel_norms): the MLP gets its own
        # ln2 on the same residual input (HF use_parallel_residual)
        with jax.named_scope("mlp"):
            h_mlp = _norm(x, lp["ln2"], cfg) if cfg.parallel_norms else h
            return (x + attn + _mlp_block(h_mlp, lp["mlp"], cfg), cache_k,
                    cache_v, state)

    with jax.named_scope("attn.out"):
        x = x + attn

    experts = "moe" in lp
    held = "held" in lp         # this program's share of sigmoid-routed ones
    with jax.named_scope("moe.router" if experts or held else "mlp"):
        h2 = _norm(x, lp["ln2"], cfg)
    if mx is not None:
        return (_mixed_feed_forward(x, h2, lp, cfg), cache_k, cache_v,
                state)
    if not experts:
        with jax.named_scope("mlp"):
            return (x + _mlp_block(h2, lp["mlp"], cfg), cache_k, cache_v,
                    state)

    from deepspeed_tpu.moe.sharded_moe import moe_forward, moe_forward_ep
    from deepspeed_tpu.parallel.topology import get_topology

    def moe_branch(hh):
        topo = get_topology()
        tt = hh.shape[0]
        # expert-parallel ragged step: tokens split over the expert axis,
        # explicit all_to_all dispatch (ref mixtral model_implementations +
        # _AllToAll).  Needs a static branch (shard_map under lax.cond is
        # unsafe), hence the moe_every == 1 static selection above.
        if (isinstance(layer_is_moe, bool) and topo is not None
                and topo.ep_size > 1 and tt % topo.ep_size == 0):
            ep = topo.ep_size
            out, _ = moe_forward_ep(hh.reshape(ep, tt // ep, hh.shape[1]),
                                    lp["moe"], cfg, topo)
            return out.reshape(tt, -1)
        if topo is not None and topo.ep_size > 1:
            from deepspeed_tpu.utils.logging import log_dist

            log_dist(
                f"expert_parallel requested (ep={topo.ep_size}) but the "
                f"ragged step fell back to the single-group MoE "
                f"(tokens={tt} not divisible, or moe_layer_freq > 1 makes "
                "the selection traced) — dispatch will be auto-partitioned",
                level="warning")
        out, _ = moe_forward(hh[None], lp["moe"], cfg)
        return out[0]

    def dense_branch(hh):
        with jax.named_scope("mlp"):
            return _mlp_block(hh, lp["mlp"], cfg)

    if isinstance(layer_is_moe, bool):
        y = moe_branch(h2) if layer_is_moe else dense_branch(h2)
    else:
        y = lax.cond(layer_is_moe, moe_branch, dense_branch, h2)
    with jax.named_scope("mlp" if layer_is_moe is False else "moe.combine"):
        return x + y, cache_k, cache_v, state


def _at(tree, i):
    """Layer ``i`` of weights stacked on axis 0."""
    return jax.tree.map(lambda a: a[i], tree)


def _mixed_feed_forward(x, h2, lp, cfg: TransformerConfig):
    """The feed-forward of a mixed-attention model's block on the normed
    rows ``h2``, with its norm after and the residual add: dense
    (``lp["mlp"]``), or this program's routed experts and the shared one
    (``lp["held"]``: the expert layers' stack and this layer's index in
    it, as ``moe_forward_held`` takes them; a model without a shared
    expert has no ``shared`` in the stack)."""
    from deepspeed_tpu.moe.sharded_moe import moe_forward_held

    mx = cfg.mixed
    post = ((lambda y: _norm(y, lp["post_mlp"], cfg)) if mx.sandwich_norm
            else (lambda y: y))
    if "mlp" in lp:
        with jax.named_scope("mlp"):
            return x + post(_mlp_block(h2, lp["mlp"], cfg))
    stack, i = lp["held"]
    routed = moe_forward_held(
        h2, stack, i, top_k=mx.num_experts_per_tok,
        first=mx.experts_held[0], scale=mx.route_scale)
    if "shared" in stack:
        with jax.named_scope("moe.shared"):
            routed = routed + _mlp_block(h2, _at(stack["shared"], i), cfg)
    with jax.named_scope("moe.combine"):
        return x + post(routed)


def layer_segments(kinds) -> list:
    """``kinds`` (one hashable a layer) cut into ``(start, period,
    repeats)``: where a stretch of ``period`` layers repeats at least
    twice it is one segment (the stretch that covers most layers, the
    shortest among equals), any other layer a segment of its own."""
    out, i, n = [], 0, len(kinds)
    while i < n:
        best = (1, 1)
        for p in range(1, (n - i) // 2 + 1):
            r = 1
            while kinds[i + r * p:i + (r + 1) * p] == kinds[i:i + p]:
                r += 1
            if r > 1 and r * p > best[0] * best[1]:
                best = (p, r)
        out.append((i,) + best)
        i += best[0] * best[1]
    return out


def new_window_pools(cfg: TransformerConfig, full_rows: int,
                     window_rows: int, zeros=jnp.zeros, dtype=None):
    """``(cache_k, cache_v, state)`` of a mixed-attention model, zeroed,
    each pool in its KIND's shape: the full layers' ``cache_k`` ``[full
    layers, kv_heads, full_rows, key row]`` and ``cache_v`` ``[.., value
    width]`` and, in ``state``, the window layers' ``k`` ``[window layers,
    window_kv_heads, window_rows, key row]`` and ``v`` ``[.., value
    width]``.  A key row is ``row_width(head_dim)`` lanes (192 dims in
    256, the pad zeros), a value row ``cfg.value_width``."""
    dtype = dtype or cfg.dtype
    n_win = cfg.window_layers
    dk, dv = row_width(cfg.dim_per_head), cfg.value_width
    full = (cfg.num_layers - n_win, cfg.kv_heads, full_rows)
    win = (n_win, cfg.window_kv_heads, window_rows)
    return (zeros(full + (dk,), dtype=dtype), zeros(full + (dv,), dtype=dtype),
            {"k": zeros(win + (dk,), dtype=dtype),
             "v": zeros(win + (dv,), dtype=dtype)})


def _mixed_trunk(params, cache_k, cache_v, token_ids, token_slot, token_pos,
                 token_dest, block_tables, ctx_lens, state,
                 cfg: TransformerConfig, block_size: int, window):
    """:func:`_ragged_trunk` of a mixed-attention model (``cfg.mixed``):
    window layers and full layers by ``layer_types``, each kind's rows in
    a pool of its own (the full layers' ``cache_k`` / ``cache_v`` under
    ``block_tables`` / ``token_dest``, the window layers' ``state["k"]`` /
    ``["v"]`` under ``window`` = ``(window_dest, window_tables)``), dense
    feed-forwards first and held experts after.  The layer list is walked
    in :func:`layer_segments`: one ``lax.scan`` over the repeats where a
    stretch repeats, its body a stretch unrolled so that every layer's
    window and rotary are static; all four pools ride the carry whole.
    A kind of layer reads its own configuration
    (``TransformerConfig.mixed_kind``: KV heads, rotary base, window) and,
    where the kinds' attention weights differ, its own stack
    (``layers/attn_full`` / ``attn_window``, indexed as the kind's pool
    is)."""
    mx = cfg.mixed
    if state is None or window is None:
        raise ValueError(
            "this model's window layers keep their rows in a page pool of "
            "their own with tables of their own (state=new_window_pools("
            "...)[2]: K [window layers, window_kv_heads, rows, key row] "
            "and V [.., value width], beside the full layers' [full "
            "layers, kv_heads, rows, .]; a PackedIndex with window "
            "arrays); a caller that keeps one pool (inference.kv_generate) "
            "cannot run it")
    if _is_quant_cache(cache_k):
        raise ValueError("a mixed-attention model's pools are kept in the "
                         "compute dtype (no int8 cache)")
    window_dest, window_tables = window
    layers = params["layers"]
    with jax.named_scope("embed"):
        x = params["embed"]["tokens"].astype(cfg.dtype)[token_ids]
        if mx.embed_multiplier != 1.0:
            x = x * mx.embed_multiplier
    kind_cfg = {is_full: cfg.mixed_kind(is_full) for is_full in (True, False)}
    meta = {True: _step_meta(token_slot, token_pos, token_dest, block_tables,
                             ctx_lens, block_size, cache_k, kind_cfg[True],
                             cache_v),
            False: _step_meta(token_slot, token_pos, window_dest,
                              window_tables, ctx_lens, block_size,
                              state["k"], kind_cfg[False], state["v"])}
    kinds = mx.kinds(cfg.num_layers)
    fulls = [is_full for is_full, _ in kinds]

    def stretch(carry, j, start, period):
        """Layers ``start + j * period`` on, ``period`` of them."""
        x, pools = carry
        for k in range(start, start + period):
            is_full, has_experts = kinds[k]
            layer = k + j * period
            # this layer's index in its kind's pool: the kind's layers
            # before it in the list, and in the repeats before this one
            in_pool = fulls[:k].count(is_full) \
                + j * fulls[start:start + period].count(is_full)
            lp = {name: _at(layers[name], layer)
                  for name in ("attn", "ln1", "ln2", "post_attn", "post_mlp")
                  if name in layers}
            if mx.attn_by_kind:
                # stacked a kind, as the pools are
                lp["attn"] = _at(layers["attn_full" if is_full
                                        else "attn_window"], in_pool)
            if has_experts:
                lp["held"] = (layers["moe"], layer - mx.num_dense_layers)
            else:
                lp["mlp"] = _at(layers["mlp"], layer)
            ck, cv = pools[is_full]
            x, ck, cv, _ = _ragged_layer(
                x, lp, ck, cv, in_pool, meta[is_full], kind_cfg[is_full])
            pools = {**pools, is_full: (ck, cv)}
        return (x, pools), None

    carry = (x, {True: (cache_k, cache_v), False: (state["k"], state["v"])})
    with jax.named_scope("layers"):
        for start, period, repeats in layer_segments(kinds):
            if repeats == 1:
                carry, _ = stretch(carry, 0, start, period)
            else:
                carry, _ = lax.scan(
                    lambda c, j, s=start, p=period: stretch(c, j, s, p),
                    carry, jnp.arange(repeats, dtype=jnp.int32))
    x, pools = carry
    with jax.named_scope("head"):
        return (_norm(x, params["final_norm"], cfg), *pools[True],
                {"k": pools[False][0], "v": pools[False][1]})


def _hybrid_attention(h, p, cache_k, cache_v, layer, meta,
                      cfg: TransformerConfig):
    """The mixer of a ``"*"`` layer of a one-mixer-a-layer model on the
    normed rows ``h``: grouped-query attention without rotary or bias
    over ``layer``'s pages of the pools, which hold the ``"*"`` layers
    alone; ``(out [T, H], cache_k', cache_v')``.  The append and the read
    as :func:`_ragged_layer` makes them."""
    (token_pos, token_dest, gather_idx, token_ctx_len, token_slot,
     block_tables, block_size, dest_pages) = meta
    t, dt = h.shape[0], h.dtype
    nh, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head

    def proj(w, heads):
        # behind a barrier, for _ragged_layer's reason: the product
        # streams the weight from its stack as it lies
        return lax.optimization_barrier(jnp.matmul(
            h, w.astype(dt), preferred_element_type=jnp.float32)
        ).reshape(t, heads, d).astype(dt)

    with jax.named_scope("attn.qkv"):
        q, k, v = proj(p["wq"], nh), proj(p["wk"], nkv), proj(p["wv"], nkv)
    attend = functools.partial(
        _paged_attention, q, gather_idx=gather_idx, token_pos=token_pos,
        token_ctx_len=token_ctx_len, cfg=cfg, block_tables=block_tables,
        token_slot=token_slot, block_size=block_size)
    if attention_impl_name(cfg, block_size,
                           block_tables is not None) == "paged_pallas":
        with jax.named_scope("attn.append"):
            if dest_pages is None:
                cache_k = _kv_append(cache_k, k, token_dest, layer)
                cache_v = _kv_append(cache_v, v, token_dest, layer)
            else:
                cache_k, cache_v = kv_append(cache_k, cache_v, k, v,
                                             dest_pages, layer, block_size)
        with jax.named_scope("attn.read"):
            attn = attend(cache_k, cache_v, layer=layer)
    else:
        with jax.named_scope("attn.append"):
            k_pages = _kv_append(jax.tree.map(lambda c: c[layer], cache_k),
                                 k, token_dest)
            v_pages = _kv_append(jax.tree.map(lambda c: c[layer], cache_v),
                                 v, token_dest)
        with jax.named_scope("attn.read"):
            attn = attend(k_pages, v_pages)
        with jax.named_scope("attn.append"):
            cache_k, cache_v = jax.tree.map(
                lambda c, pages: c.at[layer].set(pages),
                (cache_k, cache_v), (k_pages, v_pages))
    with jax.named_scope("attn.out"):
        return (attn.reshape(t, nh * d) @ p["wo"].astype(dt), cache_k,
                cache_v)


def _hybrid_experts(h, moe, layer, cfg: TransformerConfig):
    """The mixer of an ``"E"`` layer on the normed rows ``h``: this
    program's routed experts' part and the shared expert whole, each
    ``relu(h W_i)^2 W_o``; ``moe``: the expert layers' stack, ``layer``
    this one's index in it (as ``moe_forward_held`` takes them)."""
    from deepspeed_tpu.moe.sharded_moe import moe_forward_held

    hy, dt = cfg.hybrid, h.dtype
    routed = moe_forward_held(
        h, moe, layer, top_k=hy.num_experts_per_tok,
        first=hy.experts_held[0], scale=hy.route_scale)
    with jax.named_scope("moe.shared"):
        shared = moe["shared"]
        act = jnp.square(jax.nn.relu(h @ shared["wi"][layer].astype(dt)))
        return routed + act @ shared["wo"][layer].astype(dt)


def _hybrid_trunk(params, cache_k, cache_v, token_ids, token_slot, token_pos,
                  token_dest, block_tables, ctx_lens, state,
                  cfg: TransformerConfig, block_size: int, state_slot):
    """:func:`_ragged_trunk` of a one-mixer-a-layer model
    (``cfg.hybrid``): every block is ``x + f(RMSNorm(x))`` with ONE ``f``
    by the layer's kind, ``"M"`` the Mamba-2 mixer
    (:func:`_ragged_mixer`), ``"*"`` attention without rotary
    (:func:`_hybrid_attention`), ``"E"`` held experts and the shared one
    (:func:`_hybrid_experts`).  A walk over the list of kinds in
    :func:`layer_segments`, as :func:`_mixed_trunk` walks its own: one
    ``lax.scan`` over the repeats where a stretch repeats.  Each kind's
    weights and carried buffers are stacked over THAT kind's layers (the
    recurrent slots and the convolution's tails over the ``"M"`` layers,
    the KV pools over the ``"*"`` layers), and a layer reads and updates
    them by its index in its kind; all four buffers ride the carry
    whole."""
    layers = params["layers"]
    kinds = cfg.hybrid.kinds(cfg.num_layers)
    ssm_meta = _ssm_meta(cfg, state, token_slot if state_slot is None
                         else state_slot, token_pos)
    with jax.named_scope("embed"):
        x = params["embed"]["tokens"].astype(cfg.dtype)[token_ids]
    meta = _step_meta(token_slot, token_pos, token_dest, block_tables,
                      ctx_lens, block_size, cache_k, cfg)

    def stretch(carry, j, start, period):
        """Layers ``start + j * period`` on, ``period`` of them."""
        x, ck, cv, ssm, conv = carry
        for k in range(start, start + period):
            kind = kinds[k]
            layer = k + j * period
            # this layer's index among its kind's: those before it in the
            # list, and in the repeats before this one
            at = kinds[:k].count(kind) \
                + j * kinds[start:start + period].count(kind)
            gain = _at(layers["norm"], layer)
            # the pre-norm and the residual add are counted to the mixer's
            # first and last stage
            if kind == "M":
                with jax.named_scope("ssm.in"):
                    h = _norm(x, gain, cfg)
                f, st = _ragged_mixer(
                    h, _at(layers["ssm"], at),
                    {"ssm": ssm, "conv": conv, "layer": at}, ssm_meta, cfg)
                ssm, conv = st["ssm"], st["conv"]
                with jax.named_scope("ssm.out"):
                    x = x + f
            elif kind == "*":
                with jax.named_scope("attn.qkv"):
                    h = _norm(x, gain, cfg)
                f, ck, cv = _hybrid_attention(h, _at(layers["attn"], at),
                                              ck, cv, at, meta, cfg)
                with jax.named_scope("attn.out"):
                    x = x + f
            else:
                with jax.named_scope("moe.router"):
                    h = _norm(x, gain, cfg)
                f = _hybrid_experts(h, layers["moe"], at, cfg)
                with jax.named_scope("moe.combine"):
                    x = x + f
        return (x, ck, cv, ssm, conv), None

    carry = (x, cache_k, cache_v, state["ssm"], state["conv"])
    with jax.named_scope("layers"):
        for start, period, repeats in layer_segments(list(kinds)):
            if repeats == 1:
                carry, _ = stretch(carry, 0, start, period)
            else:
                carry, _ = lax.scan(
                    lambda c, j, s=start, p=period: stretch(c, j, s, p),
                    carry, jnp.arange(repeats, dtype=jnp.int32))
    x, cache_k, cache_v, ssm, conv = carry
    with jax.named_scope("head"):
        return (_norm(x, params["final_norm"], cfg), cache_k, cache_v,
                {"ssm": ssm, "conv": conv})


def _embed_rows(params, token_ids, token_pos, cfg: TransformerConfig):
    """The step's flat rows [T, H] as the first block takes them."""
    dt = cfg.dtype
    x = params["embed"]["tokens"].astype(dt)[token_ids]  # [T, H]
    if cfg.ssm:
        x = x * cfg.ssm.embedding_multiplier
    if cfg.has_learned_positions and "positions" in params["embed"]:
        # gpt2/opt/gpt-neo learned positions (OPT's +2 offset is already
        # stripped at conversion, so token_pos indexes directly)
        x = x + params["embed"]["positions"].astype(dt)[token_pos]
    if cfg.embed_norm:
        x = _norm(x, params["embed"]["norm"], cfg)  # Bloom embedding LN
    return x


def _step_meta(token_slot, token_pos, token_dest, block_tables, ctx_lens,
               block_size: int, cache_k, cfg: TransformerConfig,
               cache_v=None):
    """What every block needs of the step's layout, once: the context
    gather indices (ref: atom_builder) and, on the kernels' path, the
    rows' destinations by page for the append (None where the pools keep
    the row scatter: the int8 cache, which no deployment runs, and a
    shape whose pages do not fit the append's VMEM)."""
    dest_pages = None
    if not _is_quant_cache(cache_k) and attention_impl_name(
            cfg, block_size, block_tables is not None) == "paged_pallas":
        with jax.named_scope("attn.append"):
            dest_pages = step_pages(cache_k, token_dest, block_size, cache_v)
    with jax.named_scope("attn.read"):
        nb = block_tables.shape[1]
        c = jnp.arange(nb * block_size, dtype=jnp.int32)
        ctx_idx = (block_tables[:, c // block_size] * block_size
                   + c % block_size)              # [S+1, C]
        gather_idx = ctx_idx[token_slot]          # [T, C]
        token_ctx_len = ctx_lens[token_slot]      # [T]
    return (token_pos, token_dest, gather_idx, token_ctx_len, token_slot,
            block_tables, block_size, dest_pages)


def _ragged_trunk(params, cache_k, cache_v, token_ids, token_slot, token_pos,
                  token_dest, block_tables, ctx_lens, state,
                  cfg: TransformerConfig, block_size: int, state_slot=None,
                  window=None):
    """Embedding, every block and the final norm over a step's flat rows:
    (x [T, H], cache_k', cache_v', state').  The one layer loop of every
    ragged program: the pools (and a mixer's recurrent slots) ride its
    carry whole and each block updates them in place."""
    if cfg.mla is not None:
        # latent attention: pools of latent rows, rings in the slots
        from deepspeed_tpu.inference.v2.latent import latent_trunk

        return latent_trunk(params, cache_k, cache_v, token_ids, token_slot,
                            token_pos, token_dest, block_tables, ctx_lens,
                            state, cfg, block_size, state_slot)
    if cfg.mixed is not None:
        # window and full layers by layer, a pool each
        return _mixed_trunk(params, cache_k, cache_v, token_ids, token_slot,
                            token_pos, token_dest, block_tables, ctx_lens,
                            state, cfg, block_size, window)
    if cfg.hybrid is not None:
        # one mixer a layer, each kind's buffers over its own layers
        return _hybrid_trunk(params, cache_k, cache_v, token_ids, token_slot,
                             token_pos, token_dest, block_tables, ctx_lens,
                             state, cfg, block_size, state_slot)
    ssm_meta = _ssm_meta(cfg, state, token_slot if state_slot is None
                         else state_slot, token_pos)
    with jax.named_scope("embed"):
        x = _embed_rows(params, token_ids, token_pos, cfg)
    meta = _step_meta(token_slot, token_pos, token_dest, block_tables,
                      ctx_lens, block_size, cache_k, cfg)

    moe_every = max(1, cfg.moe_layer_freq)
    layers = params["layers"]
    # GPT-Neo alternating global/local: scan layer PAIRS so each member's
    # window is static (see models/transformer scan_segment)
    period = 2 if cfg.alt_window else 1
    if cfg.alt_window:
        if cfg.is_moe:
            raise NotImplementedError("alt_window + MoE not supported")
        if cfg.num_layers % 2:
            raise NotImplementedError(
                "alt_window needs an even layer count (the ragged path "
                f"scans layer pairs; got {cfg.num_layers})")
        layers = jax.tree.map(
            lambda a: a.reshape((cfg.num_layers // 2, 2) + a.shape[1:]),
            layers)

    def body(carry, scanned):
        h, ck, cv, ssm = carry
        lp, first, conv = scanned
        for j in range(period):
            idx, sub, lcfg = first + j, lp, cfg
            if cfg.alt_window:
                sub = jax.tree.map(lambda p, j=j: p[j], lp)
                lcfg = cfg if j % 2 else cfg.replace(sliding_window=None)
            if not cfg.is_moe:
                is_moe_layer = False
            elif moe_every == 1:
                # static: every layer is MoE — keeps the selection out of
                # lax.cond so the expert-parallel shard_map path can apply
                is_moe_layer = True
            else:
                is_moe_layer = (idx % moe_every) == (moe_every - 1)
            st = ({"ssm": ssm, "conv": conv, "layer": idx} if cfg.ssm
                  else None)
            h, ck, cv, st = _ragged_layer(
                h, sub, ck, cv, idx, meta, lcfg, layer_is_moe=is_moe_layer,
                state=st, ssm_meta=ssm_meta)
            if cfg.ssm:
                ssm, conv = st["ssm"], st["conv"]
        return (h, ck, cv, ssm), conv

    # the convolution's tails (a few MiB) are sliced and stacked by the
    # scan; the pools and the recurrent slots are not
    with jax.named_scope("layers"):
        (x, cache_k, cache_v, ssm), conv = lax.scan(
            body, (x, cache_k, cache_v, state["ssm"] if cfg.ssm else None),
            (layers, jnp.arange(0, cfg.num_layers, period),
             state["conv"] if cfg.ssm else None))
    if cfg.ssm:
        state = {"ssm": ssm, "conv": conv}
    with jax.named_scope("head"):
        return _norm(x, params["final_norm"], cfg), cache_k, cache_v, state


def _lm_head(x, params, cfg: TransformerConfig):
    dt = cfg.dtype
    if cfg.tie_embeddings:
        return x @ params["embed"]["tokens"].astype(dt).T
    return x @ params["lm_head"].astype(dt)


def ragged_forward(params, cache_k, cache_v, token_ids, token_slot, token_pos,
                   token_dest, block_tables, ctx_lens, logits_idx,
                   state=None, *, cfg: TransformerConfig, block_size: int,
                   state_slot=None, window=None):
    """One ragged step.

    cache_k/cache_v: [L, nkv, P, d], carried through the layer loop and
    updated in place (donate them: they come back as the buffers they
    came in); block_tables: [S+1, NB]; returns
    (logits [S+1, V], cache_k', cache_v').  A model with an SSM mixer
    (``cfg.ssm``) also takes ``state``, its recurrent slots
    (:func:`new_ssm_state`), and returns them fourth; ``state_slot`` [T]
    names each row's slot where that is not ``token_slot``.  A
    mixed-attention model (``cfg.mixed``) takes its window layers' pools
    as ``state`` (:func:`new_window_pools`) and their destinations and
    tables as ``window`` (``PackedIndex.window_arrays``).
    """
    x, cache_k, cache_v, state = _ragged_trunk(
        params, cache_k, cache_v, token_ids, token_slot, token_pos,
        token_dest, block_tables, ctx_lens, state, cfg, block_size,
        state_slot=state_slot, window=window)
    with jax.named_scope("head"):
        logits = _lm_head(x[logits_idx], params, cfg)  # ref: logits_gather
        if cfg.ssm:
            logits = logits * cfg.ssm.lm_head_multiplier
        logits = logits.astype(jnp.float32)
    if state is not None:
        # a mixer's recurrent slots, a latent model's window rings
        return logits, cache_k, cache_v, state
    return logits, cache_k, cache_v


# The engine's step programs: this module's forwards with the seven index
# arrays as ONE argument, a ``ragged.PackedIndex`` that reached the device
# as one transfer and is cut apart here by static slices.
def ragged_step(params, cache_k, cache_v, index, state=None, *,
                cfg: TransformerConfig, block_size: int):
    """:func:`ragged_forward` on a packed index buffer."""
    return ragged_forward(params, cache_k, cache_v, *index.arrays(), state,
                          cfg=cfg, block_size=block_size,
                          window=index.window_arrays())


def ragged_step_sampled(params, cache_k, cache_v, index, prev, key,
                        temperature, cfg: TransformerConfig, block_size: int,
                        greedy: bool, top_k: int = 0, top_p=None,
                        state=None):
    """:func:`ragged_forward_sampled` on a packed index buffer (its body,
    not a call of it: a frame fewer under every traced operation).

    ``prev`` [S+1] int32: the tokens the step before this one sampled, by
    slot, still on the device.  A row whose token id is negative
    (``ragged.IN_FLIGHT``: the host launched this step before that token
    reached it) reads ``prev`` at its slot instead; any other row, and a
    first step's ``prev`` of zeros, leaves the ids as the host wrote
    them."""
    token_ids, token_slot, *rest = index.arrays()
    with jax.named_scope("embed"):
        token_ids = jnp.where(token_ids < 0, prev[token_slot], token_ids)
    logits, *carried = ragged_forward(
        params, cache_k, cache_v, token_ids, token_slot, *rest, state,
        cfg=cfg, block_size=block_size, window=index.window_arrays())
    with jax.named_scope("head"):
        nxt = sample_tokens(logits, key, temperature, greedy, top_k, top_p)
    return (nxt, *carried)


def ragged_verify(params, cache_k, cache_v, index, *,
                  cfg: TransformerConfig, block_size: int):
    """:func:`ragged_forward_verify` on a packed index buffer."""
    return ragged_forward_verify(params, cache_k, cache_v, *index.arrays(),
                                 cfg=cfg, block_size=block_size)


def ragged_forward_verify(params, cache_k, cache_v, token_ids, token_slot,
                          token_pos, token_dest, block_tables, ctx_lens,
                          logits_idx, cfg: TransformerConfig,
                          block_size: int
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Speculative-decoding verify-k step: the same ragged trunk, but the
    greedy argmax is taken at EVERY token row — [T] int32 — instead of
    only at each sequence's final row.

    Feeding a sequence's pending token plus its k draft proposals as one
    "prefill chunk" makes row j's argmax the target model's greedy
    next-token after the prefix ending at that row, which is exactly the
    acceptance oracle: proposal i is accepted iff it equals the argmax
    at the row of proposal i-1 (row of the pending token for i=1), and
    the argmax at the last accepted row is the free bonus token.  The
    head matmul contracts the same hidden dimension as the per-sequence
    gather path, so the emitted chain is bit-identical to one-token-at-
    a-time greedy decoding (pinned by the spec-decode parity tests).

    ``logits_idx`` is accepted (unused) so the verify step shares the
    exact argument tuple — and therefore the audit/bench plumbing — of
    ``ragged_forward``.

    Refused, by name, for what a rejected row would leave behind and no
    later write undoes: a mixer's recurrent state, a window latent
    layer's ring.  A latent model whose layers are all full (held
    experts included) is served: a rejected row leaves a latent row and
    an index key at a position the next step rewrites before any row
    reads it.
    """
    del logits_idx
    if cfg.ssm is not None:
        raise NotImplementedError(
            "speculative verify needs state snapshots: a rejected draft "
            "row has already advanced the Mamba-2 SSM mixer's recurrent "
            "state, and no copy of the state before it is kept")
    if cfg.mla is not None and cfg.mla.has_window(cfg.num_layers):
        raise NotImplementedError(
            "speculative verify needs a copy of the window latent layers' "
            "rows before the draft: a rejected draft row has already "
            "overwritten its ring position, and only the last "
            f"{cfg.mla.sliding_window} positions and one step are kept")
    if cfg.mixed is not None:
        raise NotImplementedError(
            "speculative verify needs the window layers' pages at the "
            "positions a rejected draft row gives back: this model's window "
            f"layers free their pages {cfg.mixed.sliding_window} positions "
            "behind a sequence's last row, on the host, a step at a time")
    if cfg.alt_window or (cfg.is_moe and cfg.mla is None):
        raise NotImplementedError(
            "speculative verify step supports the scanned-layer ragged "
            "path and latent models without window layers only (no "
            "alt_window, no capacity-routed MoE)")
    x, cache_k, cache_v, _ = _ragged_trunk(
        params, cache_k, cache_v, token_ids, token_slot, token_pos,
        token_dest, block_tables, ctx_lens, None, cfg, block_size)
    with jax.named_scope("head"):
        logits = _lm_head(x, params, cfg)
        nxt = jnp.argmax(logits.astype(jnp.float32),
                         axis=-1).astype(jnp.int32)
    return nxt, cache_k, cache_v


def ragged_draft_step(params, cache_k, cache_v, index, prev, *,
                      cfg: TransformerConfig, block_size: int):
    """One SELF-DRAFTING greedy step of a latent model with a
    multi-token-prediction module (``cfg.mla.mtp_layers``), on a packed
    index buffer that also carries ``token_next`` [T] and ``verify``
    [S+1] (``ragged.PackedIndex.draft_arrays``): trunk, the greedy argmax
    at both rows of every verify run, accept, module, next draft, as ONE
    program.

    A decoding sequence whose draft is ``d`` brought the rows ``(pending,
    d)``; ``a1``, ``a2`` are the trunk's argmax at the two.  ``d`` stands
    iff ``d == a1``.  The module then runs over every row with the token
    that follows it: the host's ``token_next`` where the host knows it (a
    prompt's next token), else the argmax just taken at that row.  The
    next draft is the module's argmax at the sequence's last row that
    stands (the pending token's where ``d`` was refused: the refused
    row's cache rows, the trunk's and the module's, lie at a position
    the next step writes before any row reads it).

    Returns ``(out [4, S+1] int32, cache_k', cache_v')``, ``out`` by
    slot: the first token delivered, the second (read only where a
    draft was accepted), whether one was, the next draft.  Any other
    run of rows (a prefill chunk, a sequence without a draft) delivers
    the argmax at its last row, as ``ragged_step_sampled`` does.

    ``prev`` [4, S+1] int32: the ``out`` of the step before this one,
    still on the device (zeros before any).  A run the host LAUNCHED
    AHEAD of that step's fetch (``verify`` holds 2 beside its 1) was
    written for the outcome in which the draft that step verified was
    refused: its rows read their tokens from ``prev`` at their slot (the
    pending token is that step's second where its draft stood, else its
    first; the draft row is its next draft) and, where the draft stood,
    lie one position on: position, context length and cache row
    (recomputed from the step's own block table) move by ``prev``'s
    ``accepted``.  Any other run, and a first step's zeros, leave the
    arrays as the host wrote them: ONE program, ahead or not."""
    from deepspeed_tpu.inference.v2.latent import latent_trunk, mtp_rows

    (token_ids, token_slot, token_pos, token_dest, block_tables, ctx_lens,
     last) = index.arrays()
    token_next, verify = index.draft_arrays()
    with jax.named_scope("embed"):
        # (few operations, gathers that promise their bounds, lax's own
        # division: what is traced here is traced in every step program,
        # and set-up pays for it a program)
        was_first, was_second, stood, was_draft = prev
        ahead = verify >> 1                         # [S+1], 0 or 1
        verify = verify & 1
        stood = stood * ahead
        rows = token_ids.shape[0]
        by_slot = jnp.stack([
            ahead, stood, lax.select(stood > 0, was_second, was_first),
            was_draft, lax.select(verify > 0, last, last - rows)])
        row_ahead, row_stood, pending, drafted, draft_row = by_slot.at[
            :, token_slot].get(mode="promise_in_bounds")    # [T] each
        row_ahead = row_ahead > 0
        token_ids = lax.select(
            row_ahead, lax.select(lax.iota(jnp.int32, rows) == draft_row,
                                  drafted, pending), token_ids)
        token_pos = token_pos + row_stood
        ctx_lens = ctx_lens + stood
        page = block_tables.at[
            token_slot, lax.div(token_pos, block_size)].get(
                mode="promise_in_bounds")
        token_dest = lax.select(
            row_ahead, page * block_size + lax.rem(token_pos, block_size),
            token_dest)
    x, cache_k, cache_v, _ = latent_trunk(
        params, cache_k, cache_v, token_ids, token_slot, token_pos,
        token_dest, block_tables, ctx_lens, None, cfg, block_size)

    def argmax_at(hidden, rows):
        with jax.named_scope("head"):
            return jnp.argmax(_lm_head(hidden[rows], params, cfg).astype(
                jnp.float32), axis=-1).astype(jnp.int32)

    slots = last.shape[0]
    with jax.named_scope("verify"):
        first = jnp.maximum(last - 1, 0)
    both = argmax_at(x, jnp.concatenate([first, last]))
    with jax.named_scope("verify"):
        a_first, a_last = both[:slots], both[slots:]
        verify = verify > 0
        accepted = verify & (token_ids[last] == a_first)
        own = jnp.where(jnp.arange(token_ids.shape[0]) == last[token_slot],
                        a_last[token_slot], a_first[token_slot])
        next_ids = jnp.where(token_next >= 0, token_next, own)
    with jax.named_scope("mtp"):
        hidden, cache_k, cache_v = mtp_rows(
            params, x, next_ids, cache_k, cache_v, token_slot, token_pos,
            token_dest, block_tables, ctx_lens, cfg, block_size)
        draft = argmax_at(hidden, jnp.where(verify & ~accepted, first, last))
    with jax.named_scope("verify"):
        out = jnp.stack([jnp.where(verify, a_first, a_last), a_last,
                         accepted.astype(jnp.int32), draft])
    return out, cache_k, cache_v


def check_sampling_params(top_k: int, top_p, vocab_size: int):
    """API-boundary validation + normalization (outside jit): rejects
    degenerate values that would silently emit token 0 (top_p <= 0) or
    crash deep inside lax.top_k (top_k > vocab).  Returns the
    ``(top_k_static, top_p_traced)`` pair the jitted samplers take —
    top_k clamped to vocab, top_p None when disabled (>= 1.0) else a
    traced fp32 scalar (so per-request values never recompile): a HOST
    scalar, which the jitted call ships with its other arguments."""
    if top_p is not None and not (0.0 < float(top_p) <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    tp = None if top_p is None or float(top_p) >= 1.0 else np.float32(top_p)
    return min(int(top_k), vocab_size), tp


def sample_tokens(logits, key, temperature, greedy: bool,
                  top_k: int = 0, top_p=None) -> jnp.ndarray:
    """On-device token sampling with FastGen-style logit processing
    (ref inference/v2/model_implementations sampler + logits processors):
    greedy argmax, or temperature categorical restricted to the top-k
    logits and/or the top-p nucleus.  ``top_k`` is static per compile
    (0 disables); ``top_p`` is a TRACED scalar (None disables) so
    per-request nucleus values never recompile."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    if top_p is not None:
        # nucleus: keep the smallest prefix of desc-sorted tokens whose
        # cumulative probability reaches top_p (first always kept)
        order = jnp.argsort(-logits, axis=-1)
        sorted_p = jax.nn.softmax(
            jnp.take_along_axis(logits, order, axis=-1), axis=-1)
        keep_sorted = (jnp.cumsum(sorted_p, axis=-1) - sorted_p) < top_p
        inv = jnp.argsort(order, axis=-1)
        keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
        logits = jnp.where(keep, logits, -jnp.inf)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def ragged_forward_sampled(params, cache_k, cache_v, token_ids, token_slot,
                           token_pos, token_dest, block_tables, ctx_lens,
                           logits_idx, key, temperature,
                           cfg: TransformerConfig, block_size: int,
                           greedy: bool, top_k: int = 0, top_p=None,
                           state=None):
    """Ragged step + ON-DEVICE sampling: the host receives [S+1] int32
    tokens instead of [S+1, V] logits.  Same sampling semantics as the
    fused decode loop (greedy argmax / temperature categorical with
    optional top-k/top-p), so a generation that alternates prefill and
    decode phases stays consistent.  ``state``: as ``ragged_forward``.
    """
    logits, *carried = ragged_forward(
        params, cache_k, cache_v, token_ids, token_slot, token_pos,
        token_dest, block_tables, ctx_lens, logits_idx, state, cfg=cfg,
        block_size=block_size)
    with jax.named_scope("head"):
        nxt = sample_tokens(logits, key, temperature, greedy, top_k, top_p)
    return (nxt, *carried)


def ragged_decode_loop(params, cache_k, cache_v, tokens0, ctx_lens0,
                       active, block_tables, key, temperature,
                       cfg: TransformerConfig, block_size: int,
                       n_steps: int, greedy: bool, top_k: int = 0,
                       top_p=None, state=None):
    """Fused multi-step decode: ``lax.scan`` over ``n_steps`` single-token
    steps with on-device sampling — ONE dispatch for the whole decode
    phase, so per-step host/driver latency is paid once instead of per
    token.

    tokens0 [S]: each slot's current last token; ctx_lens0 [S]: tokens
    already in cache; active [S] bool; block_tables [S, NB] preallocated
    for the full horizon.  Returns (sampled [n_steps, S], ctx_lens',
    cache_k', cache_v').  Slot s's row in ``sampled`` is garbage where
    ``active[s]`` is False.  ``state``: a mixer's recurrent slots, as
    ``ragged_forward`` takes them (returned fifth); an inactive row is
    the garbage slot's.
    """
    if cfg.mixed is not None:
        raise NotImplementedError(
            "the fused decode loop keeps one block table for its whole "
            "horizon: this model's window layers free their pages behind "
            "the window on the host, a step at a time (InferenceEngineV2."
            "step)")
    s_rows = block_tables.shape[0]
    slots = jnp.arange(s_rows, dtype=jnp.int32)
    act_i = active.astype(jnp.int32)
    state_slot = None
    if state is not None:
        # the padding rows' slot, the last of every kind of slot state
        state_slot = jnp.where(
            active, slots, (state["ssm"] if "ssm" in state
                            else jax.tree.leaves(state)[0]).shape[1] - 1)

    def step(carry, step_key):
        tokens, ctx_lens, ck, cv, st = carry
        pos = ctx_lens  # 0-based position of the incoming token
        with jax.named_scope("attn.append"):
            dest = block_tables[slots, pos // block_size] * block_size \
                + pos % block_size
            dest = jnp.where(active, dest, 0)  # inactive → garbage page 0
            ctx_after = ctx_lens + act_i
        logits, ck, cv, *st = ragged_forward(
            params, ck, cv, tokens, slots, pos, dest, block_tables,
            ctx_after, slots, st, cfg=cfg, block_size=block_size,
            state_slot=state_slot)
        with jax.named_scope("head"):
            nxt = sample_tokens(logits, step_key, temperature, greedy, top_k,
                                top_p)
            nxt = jnp.where(active, nxt, 0)
        return (nxt, ctx_after, ck, cv, st[0] if st else None), nxt

    keys = jax.random.split(key, n_steps)
    (tokens, ctx_lens, cache_k, cache_v, state), sampled = lax.scan(
        step, (tokens0, ctx_lens0, cache_k, cache_v, state), keys)
    if state is not None:
        return sampled, ctx_lens, cache_k, cache_v, state
    return sampled, ctx_lens, cache_k, cache_v
