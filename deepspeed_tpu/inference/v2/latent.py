"""Ragged step of a latent-attention model (``TransformerConfig.mla``; HF
``dots3_note``, ``glm_moe_dsa``): the trunk
``inference/v2/model.py:_ragged_trunk`` hands such a model to, and the
multi-token-prediction module a self-drafting step runs after it
(:func:`mtp_rows`).  Imported where a latent model is built or traced,
never on the package's import path.

What a sequence keeps, and where:

* **Full layers** write ONE row a token, ``[c_kv | k_r]`` (the normed key
  latent and the rotary key every head shares), into pages of
  ``cache_k`` ``[full layers, P, row]``, and the indexer's key into the
  same page rows of ``cache_v`` ``[full layers, P, index dim]``: the
  engine's block tables, allocator and ``token_dest`` serve both.  A
  query reads the ``index_topk`` rows its indexer scores highest (all of
  them while the context is shorter): scores over the sequence's pages
  (:func:`deepspeed_tpu.ops.pallas.latent_index.index_scores` on a TPU,
  a per-row gather elsewhere), :func:`choose_keys`, then one of two reads
  (:func:`read_impl_name`, a step program carries one): a walk of the
  sequence's own pages under the choice as a mask, all heads on the one
  row (:func:`deepspeed_tpu.ops.pallas.latent_read.latent_read`, on a TPU
  where its rule says it is the cheaper), or :func:`select_keys` and a
  gather of the chosen rows, a block of queries at a time, never
  ``[T, context, row]``.
* **Window layers** keep the last ``sliding_window`` positions only: a
  ring of ``ring`` rows a sequence, ``state["win"]`` ``[window layers,
  max_seqs + 1, ring, row]``, in the per-sequence slots a state-space
  mixer's state takes (``engine.state``); position ``p`` lives at
  ``p % ring``.  With ``ring >= sliding_window + step budget`` a step
  writes its rows first and reads after: no row a query of the step may
  see has been overwritten by a later row of the same step.  Nothing is
  cleared when a slot is handed on: a position below 0 is masked.  What
  would need a copy of the rows at an earlier position (prefix adoption,
  verify and rewind, KV hand-off) is refused by the engine, by name.  A
  model whose layers are all full keeps no ring (``state`` is ``None``)
  and is refused none of it on that ground: a rejected draft row leaves
  a latent row and an index key at a position the next step overwrites
  before any row reads it.
* **A multi-token-prediction module** (``mla.mtp_layers``) is one more
  full layer: its rows and keys are the LAST layer of ``cache_k`` and
  ``cache_v``, in the same pages under the same tables.  Its caller
  wraps it in the ``mtp`` stage; the layer inside keeps a full layer's
  stage names beneath it.

Attention is the ABSORBED form for prefill chunks and decode rows alike:
``q~_h = q_nope,h W_kb,h`` (``kv_lora_rank`` wide), score ``q~_h . c_kv +
q_rope,h . k_r``, value ``(sum p c_kv) W_vb,h``: every head reads the one
shared row, and no per-head key or value is ever formed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.inference.v2.modules import (register_module, resolve,
                                                resolve_name)
from deepspeed_tpu.models.transformer import (LatentWidths,
                                              TransformerConfig, _mlp_block,
                                              _norm)
from deepspeed_tpu.moe.sharded_moe import moe_forward_held
from deepspeed_tpu.utils.platform import on_tpu

NEG_INF = -1e30
# elements of gathered rows one block of queries may hold at once
_GATHER_ELEMENTS = 48 * 1024 * 1024
INDEX_NORM_EPS = 1e-6


def stored_width(row_dim: int) -> int:
    """Lanes a cache row takes: whole 128-lane tiles.  Under the (8, 128)
    tiling a 576-wide row occupies 640 lanes of HBM whatever is declared;
    declared 576, the TPU's compiler turns the pool rows-minor for the
    gather and copies it whole, there and back, in every layer (read in
    the compiled text for a described v5e).  The tail is zeros, in the
    rows and in the queries."""
    return -(-row_dim // 128) * 128


def ring_rows(cfg: TransformerConfig, token_budget: int) -> int:
    """Rows of a sequence's ring in every window layer: the window and
    one step's rows, in whole lane tiles."""
    return -(-(cfg.mla.sliding_window + token_budget) // 128) * 128


def new_cache(cfg: TransformerConfig, pool_rows: int, max_seqs: int,
              token_budget: int, zeros=jnp.zeros, dtype=None):
    """``(cache_k, cache_v, state)`` of a latent model, zeroed: the full
    layers' (and a module's) latent rows and index keys in pages, the
    window layers' rings in slots (slot ``max_seqs`` is the padding
    rows'); ``state`` is ``None`` where no layer is a window layer."""
    m, dtype = cfg.mla, dtype or cfg.dtype
    n_cache = m.cache_layers(cfg.num_layers)
    n_win = sum(1 for full, _ in m.kinds(cfg.num_layers) if not full)
    return (zeros((n_cache, pool_rows, stored_width(m.full.row_dim)), dtype),
            zeros((n_cache, pool_rows, m.index_head_dim), dtype),
            {"win": zeros((n_win, max_seqs + 1,
                           ring_rows(cfg, token_budget),
                           stored_width(m.window.row_dim)), dtype)}
            if n_win else None)


def latent_step_counts(items, cfg: TransformerConfig, row_bucket: int = 0,
                       context_bucket: int = 0) -> dict:
    """What one ragged step asks of a latent model, from its ``(cached,
    n_new)`` items and the step program's buckets (rows and context
    positions; 0: no program is named): further arguments of
    ``v2.schedule``, each for ONE layer of its kind.  ``latent_rows``:
    rows appended (a full layer's page rows, a window layer's ring rows);
    ``index_pairs``: (row, key) pairs the indexer scores, every causally
    visible key of every row; ``selected_keys``: rows the full layers'
    attention then reads, at most ``index_topk`` a query;
    ``walked_pairs``: (row, key) pairs that read multiplies where the
    step's program walks the pages under the selection as a mask
    (``read_impl_name``): ``index_pairs``, and 0 where it gathers;
    ``window_keys``: rows a window layer reads; ``expert_rows``: (row,
    held expert) products an expert layer expects under even routing, rows
    x experts per token x held / routed.  A module's layer is one more
    full layer with experts: the same counts hold for it."""
    m = cfg.mla

    def seen(cached, end, limit):
        """Keys rows ``cached .. end`` read when each reads at most
        ``limit``: the row at position p sees ``min(p + 1, limit)``."""
        full = min(max(cached, limit), end)
        return ((full * (full + 1) - cached * (cached + 1)) // 2
                + (end - full) * limit)

    rows = sum(n for _, n in items)
    pairs = sum(seen(c, c + n, c + n) for c, n in items)
    chosen = sum(seen(c, c + n, m.index_topk) for c, n in items)
    window = sum(seen(c, c + n, m.sliding_window) for c, n in items)
    walked = row_bucket > 0 and read_impl_name(
        cfg, row_bucket, context_bucket) == "latent_read_walk"
    return {"latent_rows": rows, "index_pairs": pairs,
            "selected_keys": chosen, "walked_pairs": pairs if walked else 0,
            "window_keys": window,
            "expert_rows": rows * m.num_experts_per_tok * m.experts_held[1]
            / m.n_routed_experts}


# -- pieces ------------------------------------------------------------
def _rms(x, scale, cfg: TransformerConfig):
    """RMSNorm at the model's eps, a bare gain for the params."""
    return _norm(x, {"scale": scale}, cfg)


def _rope(x, pos, theta, interleaved: bool = False):
    """Rotary over ALL dims of x [T, ..., n] at positions ``pos`` [T]:
    pair ``i`` is dims ``(i, i + n/2)``, or with ``interleaved`` the
    neighbours ``(2i, 2i + 1)``.  The result is laid out ``[first
    members | second members]`` either way (as HF's ``rope_interleave``
    leaves it): queries and keys come out in one order, and a dot
    product does not care which."""
    n = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos.astype(jnp.float32)[:, None] * inv              # [T, n/2]
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (n // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = xf[..., :n // 2], xf[..., n // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _map_blocks(fn, arrays, n: int):
    """``fn`` over blocks of ``n`` rows of ``arrays`` (a tuple of [T, ...]),
    results laid end to end: one call where the rows are one block or do
    not divide, else ``lax.map``."""
    t = arrays[0].shape[0]
    if t <= n or t % n:
        return fn(arrays)
    out = lax.map(fn, tuple(a.reshape((t // n, n) + a.shape[1:])
                            for a in arrays))
    return out.reshape((t,) + out.shape[2:])


def _at(tree, i):
    """Layer ``i`` of weights stacked on axis 0."""
    return jax.tree.map(lambda a: a[i], tree)


def _head_product(x, w):
    """``x @ w`` [T, heads x dim] as a value of its own, rows-major, for a
    product whose result is cut into heads on the spot.  With the heads'
    reshape folded into the product the TPU's compiler wants the weight
    ``[out][in]``: it slices the layer's matrix out of the stack,
    transposes the copy and only then multiplies (``wq_b`` of GLM-5: 67 MB
    moved twice a layer before it is read once, a quarter of a decode
    step: PERF.md, PR 49).  Behind the barrier the product streams the stack from
    HBM as it lies, as ``model._ragged_layer``'s q, k and v do, and the
    relayout falls on the step's rows.  Rounded to the rows' dtype here, as
    the folded product was."""
    return lax.optimization_barrier(jnp.matmul(x, w.astype(x.dtype)))


def _project(h, p, w: LatentWidths, pos, cfg: TransformerConfig):
    """What both kinds of layer make of the normed input h [T, H]:
    ``(c_q, q [T, heads, stored] absorbed, row [T, stored])``, query and
    row ``[latent | rope | zeros]`` at the row's stored width."""
    m, dt = cfg.mla, h.dtype
    t = h.shape[0]
    q_mul = math.sqrt(cfg.hidden_size / w.q_lora_rank) if m.lora_rescale else 1
    kv_mul = (math.sqrt(cfg.hidden_size / w.kv_lora_rank)
              if m.lora_rescale else 1)
    c_q = _rms(h @ p["wq_a"].astype(dt), p["q_norm"], cfg) * q_mul
    q = _head_product(c_q, p["wq_b"]).reshape(t, w.num_heads, w.qk_head_dim)
    q_rope = _rope(q[..., w.qk_nope_head_dim:], pos, w.rope_theta,
                   m.rope_interleaved)
    q_abs = jnp.einsum("thn,hnr->thr", q[..., :w.qk_nope_head_dim],
                       p["wk_b"].astype(dt))
    kv = h @ p["wkv_a"].astype(dt)
    c_kv = _rms(kv[:, :w.kv_lora_rank], p["kv_norm"], cfg) * kv_mul
    k_r = _rope(kv[:, w.kv_lora_rank:], pos, w.rope_theta,
                m.rope_interleaved)
    pad = stored_width(w.row_dim) - w.row_dim
    return (c_q,
            jnp.concatenate([q_abs, q_rope, jnp.zeros(
                (t, w.num_heads, pad), dt)], -1),
            jnp.concatenate([c_kv, k_r, jnp.zeros((t, pad), dt)], -1))


def _attend(q, gather, idx, ok, w: LatentWidths):
    """Absorbed attention of q [T, heads, row] over the rows ``gather(idx
    [n, K]) -> [n, K, row]`` gives each query, ``ok`` [T, K] masking; a
    block of queries at a time.  Returns the attended latents
    [T, heads, rank]."""
    t, k = ok.shape
    scale = 1.0 / math.sqrt(w.qk_head_dim)
    rank = w.kv_lora_rank

    def block(args):
        qb, ib, mb = args
        with jax.named_scope("latent.gather"):
            rows = gather(ib)                               # [n, K, row]
        s = jnp.einsum("nhd,nkd->nhk", qb, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mb[:, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(qb.dtype)
        # over the whole stored row, the latent cut out of the small
        # result: cut out of the gathered rows it is a copy of them all
        return jnp.einsum("nhk,nkd->nhd", p, rows)[..., :rank]

    n = 16
    while n < t and 2 * n * k * q.shape[-1] <= _GATHER_ELEMENTS:
        n *= 2
    return _map_blocks(block, (q, idx, ok), n)


def _finish(x, h, ctx, p, w: LatentWidths):
    """Up-project the attended latents, gate each head where the model
    has a gate (its params a ``wg``), project out."""
    dt = x.dtype
    out = jnp.einsum("thr,hrv->thv", ctx, p["wv_b"].astype(dt))
    if "wg" in p:
        gate = jax.nn.sigmoid((h @ p["wg"].astype(dt)).astype(jnp.float32))
        out = out * gate.astype(dt)[..., None]
    return x + out.reshape(out.shape[0], -1) @ p["wo"].astype(dt)


# -- the indexer -------------------------------------------------------
def index_scores_xla(q_i, w_i, pool, layer, tables, token_slot, token_pos,
                     token_ctx_len, block_size: int):
    """``I[t, c] = sum_j w_i[t, j] relu(q_i[t, j] . k[c])`` over the
    context positions ``c`` of row t's own sequence, ``-inf`` where ``c``
    is not causally visible: a gather of every row's keys
    ``[T, C, d]`` (the CPU's, and a test's: too large at a long
    context).  pool: every full layer's index keys [L, P, d]."""
    c = jnp.arange(tables.shape[1] * block_size, dtype=jnp.int32)
    rows = tables[token_slot][:, c // block_size] * block_size \
        + c % block_size                                       # [T, C]
    k = pool[layer, rows]                                      # [T, C, d]
    s = jnp.einsum("tjd,tcd->tjc", q_i, k,
                   preferred_element_type=jnp.float32)
    scores = jnp.einsum("tj,tjc->tc", w_i, jax.nn.relu(s))
    seen = (c[None] <= token_pos[:, None]) & (c[None] < token_ctx_len[:, None])
    return jnp.where(seen, scores, -jnp.inf)


@register_module("indexer", "indexer_pallas",
                 default_for=lambda on_tpu=False, **_: on_tpu)
def _indexer_pallas(*args, block_size):
    from deepspeed_tpu.ops.pallas.latent_index import index_scores

    return index_scores(*args, block_size=block_size)


@register_module("indexer", "indexer_xla")
def _indexer_xla(*args, block_size):
    return index_scores_xla(*args, block_size=block_size)


def indexer_impl_name(cfg: TransformerConfig) -> str:
    """The indexer's scores: the paged kernel on a TPU, the gather
    elsewhere; ``cfg.v2_modules`` pins a name."""
    name = dict(cfg.v2_modules or ()).get("indexer", "auto")
    return resolve_name("indexer", name, on_tpu=on_tpu())


def _index_inputs(h, c_q, p, token_pos, cfg: TransformerConfig):
    """The indexer's queries [T, heads, d], keys [T, d] and head weights
    [T, heads] (float32) of rows with normed input h and query latent
    c_q: rotary on the first ``index_rope_dim`` dims of both."""
    m, dt, t = cfg.mla, h.dtype, h.shape[0]
    rot, theta = m.index_rope_dim, m.full.rope_theta
    q_i = _head_product(c_q, p["idx_wq"]).reshape(t, m.index_heads,
                                                  m.index_head_dim)
    q_i = jnp.concatenate([_rope(q_i[..., :rot], token_pos, theta,
                                 m.rope_interleaved), q_i[..., rot:]], -1)
    k_i = (h @ p["idx_wk"].astype(dt)).astype(jnp.float32)
    mean = k_i.mean(-1, keepdims=True)
    var = jnp.square(k_i - mean).mean(-1, keepdims=True)
    k_i = ((k_i - mean) * lax.rsqrt(var + INDEX_NORM_EPS)
           * p["idx_k_norm"]["scale"].astype(jnp.float32)
           + p["idx_k_norm"]["bias"].astype(jnp.float32)).astype(dt)
    k_i = jnp.concatenate([_rope(k_i[:, :rot], token_pos, theta,
                                 m.rope_interleaved), k_i[:, rot:]], -1)
    w_i = (h @ p["idx_ww"].astype(dt)).astype(jnp.float32) \
        * (m.index_heads ** -0.5 * m.index_head_dim ** -0.5)
    return q_i, k_i, w_i


SELECT_BLOCK = 128      # context positions one place of the compaction spans
_SELECT_ROWS = 64       # rows of a step compacted at once


def choose_keys(scores, topk: int):
    """``chosen`` [T, C] bool: the ``topk`` best-scored context positions
    of every row, exactly (every visible one where a row sees fewer);
    among equal scores the lower position wins (as a stable descending
    sort has it).  scores: [T, C] float32, ``-inf`` where a key is not
    visible.

    No sort.  ``lax.top_k`` is a full sort of ``[T, C]`` on the TPU: 70 ms
    for a 1024-row chunk at a 32k context, a third of the chip's time in
    the first traced run (PERF.md, PR 34).  Instead: the k-th largest
    score of every row by bisection on the scores' bits, 32 counting
    passes, and of the scores that tie with it the first few."""
    t, c = scores.shape
    if c <= topk:
        # every visible key is chosen
        return scores > -jnp.inf
    i32, u32 = jnp.int32, jnp.uint32
    # -0.0 and 0.0 are one score
    bits = lax.bitcast_convert_type(jnp.where(scores == 0, 0.0, scores), i32)
    # order-preserving: a larger float is a larger unsigned number
    key = lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits | i32(-2 ** 31)), u32)

    def narrow(j, thr):
        cand = thr | (u32(1) << (u32(31) - j.astype(u32)))
        enough = jnp.sum((key >= cand[:, None]).astype(i32), axis=1) >= topk
        return jnp.where(enough, cand, thr)

    # the largest value that at least topk scores of the row reach
    thr = lax.fori_loop(0, 32, narrow, jnp.zeros((t,), u32))[:, None]
    above = key > thr
    tied = key == thr
    need = topk - jnp.sum(above.astype(i32), axis=1, keepdims=True)
    return (scores > -jnp.inf) & (
        above | (tied & (jnp.cumsum(tied.astype(i32), axis=1) <= need)))


def select_keys(scores, topk: int):
    """:func:`choose_keys` as a list, ``(positions [T, k], ok [T, k])``
    with ``k = min(topk, C)``: ``ok`` is False where a row sees fewer keys
    than ``k``.  The positions come in rising order, not by score:
    attention does not care.

    The chosen places are compacted into the list by counting: places per
    ``SELECT_BLOCK`` positions, the block of the i-th chosen place from
    the blocks' running counts, and its place inside the block from the
    block's own running count, which a one-hot product on the matrix unit
    fetches (small whole numbers, exact in bf16)."""
    t, c = scores.shape
    k = min(topk, c)
    chosen = choose_keys(scores, topk)
    if c <= topk:
        return (jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (t, c)),
                chosen)
    i32 = jnp.int32
    b = min(SELECT_BLOCK, c)
    nblk = c // b
    tri = (jnp.arange(b)[:, None] <= jnp.arange(b)[None, :]
           ).astype(jnp.bfloat16)
    # running count inside every block (at most b: exact in bf16)
    local = jnp.dot(chosen.reshape(t * nblk, b).astype(jnp.bfloat16), tri,
                    preferred_element_type=jnp.float32
                    ).reshape(t, nblk, b)
    count = local[:, :, -1].astype(i32)                       # [T, nblk]
    upto = jnp.cumsum(count, axis=1)
    place = jnp.arange(k, dtype=i32)

    def compact(args):
        local_, count_, upto_ = args
        before = upto_[:, None, :] <= place[None, :, None]    # [n, k, nblk]
        block = jnp.sum(before.astype(i32), axis=-1)          # [n, k]
        rank = place[None] - jnp.sum(jnp.where(before, count_[:, None, :],
                                               0), axis=-1)
        hot = (block[..., None] == jnp.arange(nblk, dtype=i32)
               ).astype(jnp.bfloat16)
        mine = jnp.einsum("nkj,njb->nkb", hot, local_.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        inside = jnp.sum((mine <= rank[..., None].astype(jnp.float32)
                          ).astype(i32), axis=-1)
        return jnp.minimum(block, nblk - 1) * b + jnp.minimum(inside, b - 1)

    positions = _map_blocks(compact, (local, count, upto), _SELECT_ROWS)
    return positions, place[None] < upto[:, -1:]


def _page_rows(tables, positions, block_size: int):
    """Pool rows of context ``positions`` [T, K] under each row's own
    page table ``tables`` [T, NB]: a compare-and-add over the table's
    entries, which fuses into one pass (as an element gather it took 16
    ms for a 1024-row chunk, more than the scores: PERF.md, PR 34)."""
    j = jnp.arange(tables.shape[1], dtype=jnp.int32)
    hit = (positions // block_size)[..., None] == j
    pages = jnp.sum(jnp.where(hit, tables[:, None, :], 0), axis=-1)
    return pages * block_size + positions % block_size


# -- the full layers' read of their selected rows -----------------------
@register_module("latent_read", "latent_read_walk",
                 default_for=lambda on_tpu=False, walks=False, **_:
                 on_tpu and walks)
def _read_walk(q, scores, cache_k, layer, meta, cfg: TransformerConfig):
    """The selection as a mask over the sequence's own pages, walked once
    a run of rows (``ops/pallas/latent_read.py``)."""
    from deepspeed_tpu.ops.pallas.latent_read import latent_read

    token_pos, _, token_slot, _, block_tables, ctx_lens, block_size = meta
    w = cfg.mla.full
    with jax.named_scope("latent.select"):
        chosen = choose_keys(scores, cfg.mla.index_topk)
    with jax.named_scope("latent.read"):
        return latent_read(
            q, chosen, cache_k, layer, block_tables, token_slot, token_pos,
            ctx_lens[token_slot], block_size=block_size, rank=w.kv_lora_rank,
            scale=1.0 / math.sqrt(w.qk_head_dim))


@register_module("latent_read", "latent_read_gather")
def _read_gather(q, scores, cache_k, layer, meta, cfg: TransformerConfig):
    """The selection as a list of pool rows, gathered for every query."""
    _, _, token_slot, _, block_tables, _, block_size = meta
    with jax.named_scope("latent.select"):
        sel, ok = select_keys(scores, cfg.mla.index_topk)
    with jax.named_scope("latent.gather"):
        rows = _page_rows(block_tables[token_slot], sel, block_size)
    with jax.named_scope("latent.read"):
        return _attend(q, lambda i: cache_k[layer, i], rows, ok, cfg.mla.full)


def read_impl_name(cfg: TransformerConfig, rows: int, context: int) -> str:
    """The full layers' read in a step program of ``rows`` rows at a
    context bucket of ``context`` positions: the walk on a TPU where
    ``latent_read.walks`` says it is the cheaper of the two, the gather
    elsewhere; ``cfg.v2_modules`` pins a name.  One program, one read."""
    from deepspeed_tpu.ops.pallas.latent_read import walks

    name = dict(cfg.v2_modules or ()).get("latent_read", "auto")
    return resolve_name(
        "latent_read", name, on_tpu=on_tpu(),
        walks=walks(rows, context, cfg.mla.index_topk,
                    cfg.mla.full.num_heads))


# -- layers ------------------------------------------------------------
def _full_layer(x, ln1, p, cache_k, cache_v, layer, meta,
                cfg: TransformerConfig):
    (token_pos, token_dest, token_slot, _, block_tables, ctx_lens,
     block_size) = meta
    w = cfg.mla.full
    with jax.named_scope("latent.down"):
        h = _rms(x, ln1, cfg)
        c_q, q, row = _project(h, p, w, token_pos, cfg)
    with jax.named_scope("attn.append"):
        cache_k = cache_k.at[layer, token_dest].set(
            row.astype(cache_k.dtype))

    with jax.named_scope("latent.index"):
        q_i, k_i, w_i = _index_inputs(h, c_q, p, token_pos, cfg)
    with jax.named_scope("attn.append"):
        cache_v = cache_v.at[layer, token_dest].set(
            k_i.astype(cache_v.dtype))

    with jax.named_scope("latent.index"):
        scores = resolve("indexer", indexer_impl_name(cfg))(
            q_i, w_i, cache_v, layer, block_tables, token_slot, token_pos,
            ctx_lens[token_slot], block_size=block_size)
    ctx = resolve("latent_read", read_impl_name(cfg, *scores.shape))(
        q, scores, cache_k, layer, meta, cfg)
    with jax.named_scope("attn.out"):
        return _finish(x, h, ctx, p, w), cache_k, cache_v


WINDOW_BLOCK = 128      # rows of a step that may share one read of the ring


def _window_layer(x, ln1, p, ring, layer, meta, cfg: TransformerConfig):
    """A window latent layer over the step's rows.  Consecutive rows of
    one sequence see nearly the same keys: a block of ``WINDOW_BLOCK``
    such rows reads the ``window - 1 + block`` ring rows they span ONCE
    and masks the band (``lax.cond`` on what the block's rows are);
    any other block (decode rows of different sequences, a run's ends,
    padding) gathers each row's own ``window`` rows."""
    token_pos, _, _, ring_slot = meta[:4]
    w, window = cfg.mla.window, cfg.mla.sliding_window
    size, t = ring.shape[2], x.shape[0]
    with jax.named_scope("latent.down"):
        h = _rms(x, ln1, cfg)
        _, q, row = _project(h, p, w, token_pos, cfg)
    with jax.named_scope("latent.window"):
        ring = ring.at[layer, ring_slot, token_pos % size].set(
            row.astype(ring.dtype))
    scale = 1.0 / math.sqrt(w.qk_head_dim)
    rank = w.kv_lora_rank
    n = min(WINDOW_BLOCK, t)
    # a row's own keys, oldest last; padded to whole sublane tiles
    back = jnp.arange(-(-window // 8) * 8, dtype=jnp.int32)
    span = jnp.arange(window - 1 + n, dtype=jnp.int32) - (window - 1)

    def attend(qb, rows, seen):
        sc = jnp.einsum("nhd,nkd->nhk" if rows.ndim == 3 else "nhd,kd->nhk",
                        qb, rows, preferred_element_type=jnp.float32) * scale
        pr = jax.nn.softmax(jnp.where(seen[:, None, :], sc, NEG_INF),
                            axis=-1).astype(qb.dtype)
        return jnp.einsum("nhk,nkd->nhd" if rows.ndim == 3 else "nhk,kd->nhd",
                          pr, rows)[..., :rank]

    def one_run(args):
        qb, slot, pos = args
        at = pos[0] + span                                   # [window-1+n]
        rows = ring[layer, slot[0], at % size]
        d = pos[:, None] - at[None, :]
        return attend(qb, rows, (d >= 0) & (d < window) & (at[None] >= 0))

    def each_row(args):
        qb, slot, pos = args
        at = pos[:, None] - back[None, :]                    # [n, window~]
        rows = ring[layer, slot[:, None], at % size]
        return attend(qb, rows, (at >= 0) & (back[None] < window))

    def block(args):
        _, slot, pos = args
        together = jnp.all(slot == slot[0]) & jnp.all(
            pos == pos[0] + jnp.arange(n, dtype=jnp.int32))
        return lax.cond(together, one_run, each_row, args)

    with jax.named_scope("latent.window"):
        ctx = _map_blocks(block, (q, ring_slot, token_pos), n)
    with jax.named_scope("attn.out"):
        return _finish(x, h, ctx, p, w), ring


def _feed_forward(x, ln2, stack, i, has_experts: bool,
                  cfg: TransformerConfig):
    """The ``i``-th feed-forward of its kind, ``stack`` holding the kind's
    layers: dense, or this program's routed experts and the shared one."""
    m = cfg.mla
    if not has_experts:
        with jax.named_scope("mlp"):
            return x + _mlp_block(_rms(x, ln2, cfg), _at(stack, i), cfg)
    with jax.named_scope("moe.router"):
        h = _rms(x, ln2, cfg)
    routed = moe_forward_held(
        h, stack, i, top_k=m.num_experts_per_tok, first=m.experts_held[0],
        scale=m.routed_scaling_factor)
    with jax.named_scope("moe.combine"):
        x = x + routed
    with jax.named_scope("moe.shared"):
        return x + _mlp_block(h, _at(stack["shared"], i), cfg)


def latent_trunk(params, cache_k, cache_v, token_ids, token_slot, token_pos,
                 token_dest, block_tables, ctx_lens, state,
                 cfg: TransformerConfig, block_size: int, state_slot=None):
    """Embedding, every block and the final norm over a step's flat rows,
    as ``model._ragged_trunk`` for a model without latent attention:
    ``(x [T, H], cache_k', cache_v', state')``.  Consecutive layers of one
    kind are one ``lax.scan`` (a period's three window layers); the pools
    and the rings ride its carry whole and are updated in place.
    ``state`` is ``None``, coming and going, for a model without a
    window layer."""
    rings = cfg.mla.has_window(cfg.num_layers)
    if rings and state is None:
        raise ValueError(
            "this model's window latent layers keep their rows in "
            "per-sequence rings (state=latent.new_cache(...)[2]); a caller "
            "that keeps none (inference.kv_generate) cannot run it")
    if cache_k.ndim != 3:
        raise ValueError(
            "a latent model's pools hold one latent row and one index key "
            "a token and layer, [layers, rows, width] "
            "(latent.new_cache(...)); a caller that makes per-head pages "
            "(inference.kv_generate) cannot run it")
    layers = params["layers"]
    with jax.named_scope("embed"):
        x = params["embed"]["tokens"].astype(cfg.dtype)[token_ids]
    meta = (token_pos, token_dest, token_slot,
            token_slot if state_slot is None else state_slot, block_tables,
            ctx_lens, block_size)

    def block(carry, i, start, kind):
        x, ck, cv, ring = carry
        is_full, has_experts = kind
        at = {name: n + i for name, n in start.items()}
        ln1 = layers["ln1"]["scale"][at["layer"]]
        if is_full:
            x, ck, cv = _full_layer(x, ln1, _at(layers["full"], at["full"]),
                                    ck, cv, at["full"], meta, cfg)
        else:
            x, ring = _window_layer(x, ln1,
                                    _at(layers["window"], at["window"]),
                                    ring, at["window"], meta, cfg)
        ffn = "moe" if has_experts else "mlp"
        x = _feed_forward(x, layers["ln2"]["scale"][at["layer"]],
                          layers[ffn], at[ffn], has_experts, cfg)
        return (x, ck, cv, ring), None

    kinds = cfg.mla.kinds(cfg.num_layers)
    carry = (x, cache_k, cache_v, state["win"] if rings else None)
    count = {"layer": 0, "full": 0, "window": 0, "mlp": 0, "moe": 0}
    with jax.named_scope("layers"):
        i = 0
        while i < len(kinds):
            n = 1
            while i + n < len(kinds) and kinds[i + n] == kinds[i]:
                n += 1
            start, kind = dict(count), kinds[i]
            if n == 1:
                carry, _ = block(carry, 0, start, kind)
            else:
                carry, _ = lax.scan(
                    lambda c, j, s=start, k=kind: block(c, j, s, k), carry,
                    jnp.arange(n, dtype=jnp.int32))
            count["layer"] += n
            count["full" if kind[0] else "window"] += n
            count["moe" if kind[1] else "mlp"] += n
            i += n
    x, cache_k, cache_v, ring = carry
    with jax.named_scope("head"):
        x = _rms(x, params["final_norm"]["scale"], cfg)
    return x, cache_k, cache_v, {"win": ring} if rings else None


def mtp_rows(params, x, next_ids, cache_k, cache_v, token_slot, token_pos,
             token_dest, block_tables, ctx_lens, cfg: TransformerConfig,
             block_size: int):
    """The multi-token-prediction module over a step's rows: ``x`` [T, H]
    is the trunk's output (after its final norm), ``next_ids`` [T] the
    token that FOLLOWS each row.  ``u = [rms_e(Emb(next)) ; rms_h(x)]
    W_eh``, one full layer with experts whose latent rows and index keys
    are the caches' last layer (same pages, same destinations as the
    trunk's rows of the step), the module's norm: ``(hidden [T, H],
    cache_k', cache_v')``; the trunk's head makes of ``hidden[i]`` the
    logits of the token after ``next_ids[i]``."""
    mp, dt = params["mtp"], cfg.dtype
    with jax.named_scope("embed"):
        emb = params["embed"]["tokens"].astype(dt)[next_ids]
        u = jnp.concatenate([_rms(emb, mp["enorm"]["scale"], cfg),
                             _rms(x, mp["hnorm"]["scale"], cfg)],
                            -1) @ mp["eh_proj"].astype(dt)
    meta = (token_pos, token_dest, token_slot, token_slot, block_tables,
            ctx_lens, block_size)
    u, cache_k, cache_v = _full_layer(
        u, mp["attn_norm"]["scale"], mp["full"], cache_k, cache_v,
        cache_k.shape[0] - 1, meta, cfg)
    u = _feed_forward(u, mp["ffn_norm"]["scale"], mp["moe"], 0, True, cfg)
    with jax.named_scope("head"):
        return _rms(u, mp["norm"]["scale"], cfg), cache_k, cache_v
