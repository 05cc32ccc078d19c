"""Mixture-of-Experts: top-k gating with einsum- and sort-based dispatch,
plus an explicit expert-parallel (shard_map + all_to_all) path.

Re-design of ``deepspeed/moe/sharded_moe.py`` (TopKGate :452, top1/top2/topk
gating :183/:290/:374, capacity :161, ``_AllToAll`` dispatch :96).  Three
formulations, one capacity/FCFS semantics:

* **einsum dispatch** (GShard-style): dispatch/combine are [T, E, C] one-hot
  einsums that XLA fuses.  Ideal for small E·C; memory is O(T·E·C).
* **sort dispatch**: flatten the (token, choice) pairs choice-major, stable
  argsort by expert, rank-within-expert via an exclusive-cumsum of counts,
  then a gather into the [E, C, H] expert buffer (and its transpose-gather
  for combine).  Memory is O(T·k + E·C·H) — no [T, E, C] one-hot ever
  materialises — matching the reference's einsum→sort evolution
  (sharded_moe.py:374 uses one-hots; the ragged-ops kernels in
  inference/v2 sort).  Identical drop order to the einsum path: experts
  fill first-come-first-served, first-choice assignments before second.
* **moe_forward_ep**: the expert mesh axis is made *manual* with
  ``shard_map(axis_names={"expert"})`` so the dispatch/return exchanges
  are explicit ``lax.all_to_all`` over ICI — the TPU-native `_AllToAll`
  (ref sharded_moe.py:96) — instead of relying on the automatic SPMD
  partitioner, which involuntarily replicates the dispatch einsum
  (observed in the round-2 multichip dryrun).  Other mesh axes (data,
  tensor, seq) stay automatic.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import EXPERT_AXIS, get_topology
from deepspeed_tpu.utils.jax_compat import get_abstract_mesh, shard_map

# Above this many one-hot elements (T·E·C) "auto" dispatch switches from the
# einsum formulation to the sort-based one (the one-hot would dominate HBM
# traffic; the sorted path is O(T·k)).
_SORT_DISPATCH_THRESHOLD = 1 << 22


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float, k: int,
              min_capacity: int = 4) -> int:
    """Ref: moe/sharded_moe.py:161 — tokens per expert budget."""
    cap = int(capacity_factor * k * num_tokens / num_experts)
    return max(cap, min_capacity)


def top_k_gating(logits: jnp.ndarray, k: int, capacity_factor: float,
                 min_capacity: int = 4, norm_topk: bool = False,
                 select_logits: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k gating with capacity. ``logits``: [T, E] (fp32).

    Returns (l_aux, combine_weights [T, E, C], dispatch_mask [T, E, C]).
    Implements the same load-balancing auxiliary loss as the reference
    (mean(token-fraction-per-expert · router-prob-per-expert) · E).
    ``select_logits``: when given (RSample noisy gating), expert CHOICE
    uses these noisy logits while gate values and the aux loss stay on
    the clean ``logits`` — the reference's split (sharded_moe.py:202).
    """
    t, e = logits.shape
    c = _capacity(t, e, capacity_factor, k, min_capacity)
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]

    # Iteratively pick top-k experts per token (static k, unrolled).
    masked = jax.nn.softmax(select_logits, axis=-1) \
        if select_logits is not None else probs
    combine = jnp.zeros((t, e, c), dtype=logits.dtype)
    dispatch = jnp.zeros((t, e, c), dtype=bool)
    # occupancy[e] tracked via cumsum of one-hot selections across tokens
    occupancy = jnp.zeros((e,), dtype=jnp.int32)
    l_aux = jnp.zeros((), dtype=logits.dtype)

    for i in range(k):
        idx = jnp.argmax(masked, axis=-1)  # [T]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)  # [T, E]
        if i == 0:
            # aux loss uses the first-choice assignment (ref top2gating)
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(onehot.astype(logits.dtype), axis=0)
            l_aux = jnp.sum(me * ce) * e
        # position of each token within its chosen expert's queue
        pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot + occupancy[None, :]  # [T, E]
        pos = jnp.sum(pos_in_expert * onehot, axis=-1)  # [T]
        keep = pos < c
        gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0] * keep
        pos_onehot = jax.nn.one_hot(jnp.where(keep, pos, c), c + 1, dtype=logits.dtype)[:, :c]
        combine = combine + gate[:, None, None] * onehot[:, :, None] * pos_onehot[:, None, :]
        dispatch = dispatch | ((onehot[:, :, None] * pos_onehot[:, None, :]) > 0)
        occupancy = occupancy + jnp.sum(onehot * keep[:, None], axis=0)
        masked = masked * (1 - onehot)

    # renormalise combine weights over selected experts: norm_topk (HF
    # mixtral norm_topk_prob) always sums kept weights to 1; the default
    # is the drop-aware top2gating scaling (ref top2gating denom)
    if k > 1:
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        if norm_topk:
            combine = combine / jnp.maximum(denom, 1e-9)
        else:
            combine = combine / jnp.maximum(denom, 1e-9) \
                * jnp.minimum(denom, 1.0)
    return l_aux, combine, dispatch


def top_k_gating_sorted(logits: jnp.ndarray, k: int, capacity_factor: float,
                        min_capacity: int = 4, norm_topk: bool = False,
                        select_logits: Optional[jnp.ndarray] = None):
    """Sort-based top-k gating: no [T, E, C] one-hot.

    Returns (l_aux, slot [T·k] int32 in [0, E·C] with E·C = dropped,
    gate [T·k] fp, c).  Flat entries are **choice-major** (entry
    ``i`` is choice ``i // T`` of token ``i % T``) so that, after the
    stable sort by expert, first-choice assignments fill an expert's
    queue before second choices — the exact FCFS drop order of the
    iterative einsum path above.
    """
    t, e = logits.shape
    c = _capacity(t, e, capacity_factor, k, min_capacity)
    probs = jax.nn.softmax(logits, axis=-1)

    if select_logits is not None:
        # RSample: choose experts by the noisy logits, keep clean gates
        _, top_i = jax.lax.top_k(select_logits, k)   # [T, k]
        top_p = jnp.take_along_axis(probs, top_i, axis=-1)
    else:
        top_p, top_i = jax.lax.top_k(probs, k)       # [T, k]
    # aux loss from the first-choice assignment, via scatter-add counts
    # (no [T, E] one-hot)
    counts0 = jnp.zeros((e,), probs.dtype).at[top_i[:, 0]].add(1.0)
    l_aux = jnp.sum(jnp.mean(probs, axis=0) * (counts0 / t)) * e

    e_flat = top_i.swapaxes(0, 1).reshape(-1)        # [k·T] choice-major
    g_flat = top_p.swapaxes(0, 1).reshape(-1)
    n = e_flat.shape[0]

    perm = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[perm]
    counts = jnp.zeros((e,), jnp.int32).at[e_flat].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - offsets[sorted_e]
    slot_sorted = jnp.where(rank_sorted < c, sorted_e * c + rank_sorted, e * c)
    slot = jnp.zeros((n,), jnp.int32).at[perm].set(slot_sorted)

    kept = slot < e * c
    gate = g_flat * kept
    if k > 1:
        # renormalise over a token's kept choices (ref top2gating denom;
        # norm_topk = HF mixtral norm_topk_prob semantics)
        per_tok = gate.reshape(k, t)
        denom = jnp.sum(per_tok, axis=0, keepdims=True)
        if norm_topk:
            per_tok = per_tok / jnp.maximum(denom, 1e-9)
        else:
            per_tok = per_tok / jnp.maximum(denom, 1e-9) \
                * jnp.minimum(denom, 1.0)
        gate = per_tok.reshape(-1)
    return l_aux, slot, gate, c


def _expert_ffn(dispatched: jnp.ndarray, p: Dict[str, jnp.ndarray], dt):
    """Batched expert FFN: [E, C, H] → [E, C, H] (one big MXU batch)."""
    if "wg" in p:
        gate = jax.nn.silu(jnp.einsum("ech,ehf->ecf", dispatched, p["wg"].astype(dt)))
        up = jnp.einsum("ech,ehf->ecf", dispatched, p["wi"].astype(dt))
        hidden = gate * up
    else:
        hidden = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", dispatched, p["wi"].astype(dt)),
                             approximate=True)
    return jnp.einsum("ecf,efh->ech", hidden, p["wo"].astype(dt))


def _resolve_dispatch(cfg, t: int, e: int, c: int) -> str:
    mode = getattr(cfg, "moe_dispatch", "auto")
    if mode == "auto":
        return "sorted" if t * e * c > _SORT_DISPATCH_THRESHOLD else "einsum"
    if mode not in _DISPATCHERS:
        raise ValueError(f"moe_dispatch={mode!r}: expected 'auto', "
                         f"{' or '.join(map(repr, _DISPATCHERS))}")
    return mode


def _dispatch_combine_einsum(tokens, logits, cfg, dt, select_logits=None):
    """Einsum formulation: returns (dispatched [E,C,H], combine_fn, aux)."""
    with jax.named_scope("moe.router"):
        l_aux, combine, dispatch = top_k_gating(
            logits, cfg.top_k, cfg.capacity_factor,
            norm_topk=getattr(cfg, "moe_norm_topk", False),
            select_logits=select_logits)
    dispatched = jnp.einsum("tec,th->ech", dispatch.astype(dt), tokens)

    def combine_fn(expert_out):
        return jnp.einsum("tec,ech->th", combine.astype(dt), expert_out)

    return dispatched, combine_fn, l_aux


def _dispatch_combine_sorted(tokens, logits, cfg, dt, select_logits=None):
    """Sort formulation: gather into [E,C,H] and its transpose for combine."""
    t, h = tokens.shape
    e = logits.shape[1]
    k = cfg.top_k
    with jax.named_scope("moe.router"):
        l_aux, slot, gate, c = top_k_gating_sorted(
            logits, k, cfg.capacity_factor,
            norm_topk=getattr(cfg, "moe_norm_topk", False),
            select_logits=select_logits)
    token_of = jnp.tile(jnp.arange(t, dtype=jnp.int32), k)     # choice-major
    # slot → source token (E·C+1 wide so the trash slot can't clip-corrupt;
    # empty slots keep the out-of-range sentinel t, gathered as zeros below)
    slot_token = jnp.full((e * c + 1,), t, jnp.int32).at[slot].set(token_of)[:e * c]
    dispatched = jnp.take(tokens, slot_token, axis=0, mode="fill",
                          fill_value=0).reshape(e, c, h)

    def combine_fn(expert_out):
        flat = expert_out.reshape(e * c, h)
        # dropped entries carry the out-of-range slot e*c → zero fill
        contrib = gate.astype(dt)[:, None] * jnp.take(
            flat, slot, axis=0, mode="fill", fill_value=0)     # [k·T, H]
        return jnp.sum(contrib.reshape(k, t, h), axis=0)

    return dispatched, combine_fn, l_aux


_DISPATCHERS = {"einsum": _dispatch_combine_einsum,
                "sorted": _dispatch_combine_sorted}


def _validate_noisy_policy(cfg) -> Optional[str]:
    """Reference noisy_gate_policy (sharded_moe.py:193-202) — one
    validation point for every gating path."""
    policy = getattr(cfg, "moe_noisy_gate_policy", None)
    if policy not in (None, "RSample", "Jitter"):
        raise ValueError(f"noisy_gate_policy={policy!r}: expected "
                         "'RSample', 'Jitter', or None")
    return policy


def _jitter_tokens(tokens, key):
    """'Jitter': multiply the ROUTER's input by uniform(1±1e-2); experts
    still see the clean tokens."""
    eps = 1e-2
    jit = jax.random.uniform(key, tokens.shape,
                             minval=1.0 - eps, maxval=1.0 + eps)
    return tokens * jit.astype(tokens.dtype)


def _rsample_logits(logits, key):
    """'RSample': gumbel-noised logits for expert CHOICE only (gates and
    the aux loss stay on the clean logits)."""
    return logits + jax.random.gumbel(key, logits.shape)


def moe_forward(x: jnp.ndarray, p: Dict[str, jnp.ndarray], cfg,
                noise_key=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN over [B, S, H] activations (single expert group / no manual
    expert axis — expert weights may still be auto-sharded by the mesh).

    Ref call stack: MoE layer → TopKGate → dispatch → Experts → combine
    (deepspeed/moe/layer.py:17, sharded_moe.py:96).
    """
    b, s, h = x.shape
    dt = x.dtype
    tokens = x.reshape(b * s, h)
    # router defaults to fp32 (routing decisions are precision-sensitive;
    # the reference keeps gate logits fp32 too, sharded_moe.py:452) —
    # overridable through the autocast policy's fp32_ops
    from deepspeed_tpu.models.transformer import op_fp32

    rt = jnp.float32 if op_fp32(cfg, "router") else dt
    policy = _validate_noisy_policy(cfg)
    with jax.named_scope("moe.router"):
        gate_in = _jitter_tokens(tokens, noise_key) \
            if noise_key is not None and policy == "Jitter" else tokens
        logits = (gate_in.astype(rt)
                  @ p["router"].astype(rt)).astype(jnp.float32)
        select = _rsample_logits(logits, noise_key) \
            if noise_key is not None and policy == "RSample" else None
    t, e = logits.shape
    c = _capacity(t, e, cfg.capacity_factor, cfg.top_k)
    mode = _resolve_dispatch(cfg, t, e, c)
    with jax.named_scope("moe.dispatch"):
        dispatched, combine_fn, l_aux = _DISPATCHERS[mode](
            tokens, logits, cfg, dt, select)
    with jax.named_scope("moe.experts"):
        expert_out = _expert_ffn(dispatched, p, dt)
    with jax.named_scope("moe.combine"):
        out = combine_fn(expert_out)
    with jax.named_scope("moe.shared"):
        out = _residual_mix(tokens, out, p, dt)
        out = out + _shared_expert_out(tokens, p, dt)
    return out.reshape(b, s, h), l_aux.astype(jnp.float32)


def _residual_mix(tokens: jnp.ndarray, routed: jnp.ndarray,
                  p: Dict[str, jnp.ndarray], dt):
    """Residual MoE (PR-MoE, ref moe/layer.py:124-135 use_residual /
    arXiv:2201.05596): a dense expert-shaped MLP runs every token and
    ``softmax(x @ coef)`` mixes it with the routed output —
    ``routed·c₀ + mlp·c₁``.  Identity when params carry no 'residual'."""
    if "residual" not in p:
        return routed
    rp = p["residual"]
    if "wg" in rp:
        hdn = jax.nn.silu(tokens @ rp["wg"].astype(dt)) \
            * (tokens @ rp["wi"].astype(dt))
    else:
        hdn = jax.nn.gelu(tokens @ rp["wi"].astype(dt), approximate=True)
    mlp_out = hdn @ rp["wo"].astype(dt)
    # the 2-way mixing head is tiny and decision-like — fp32, as with the
    # router/shared gates
    coef = jax.nn.softmax(
        tokens.astype(jnp.float32) @ p["coef_w"].astype(jnp.float32)
        + p["coef_b"].astype(jnp.float32), axis=-1).astype(dt)
    return routed * coef[:, 0:1] + mlp_out * coef[:, 1:2]


def _shared_expert_out(tokens: jnp.ndarray, p: Dict[str, jnp.ndarray], dt):
    """Qwen2-MoE shared expert: a dense FFN over every token, scaled by
    sigmoid(x @ shared_gate) and added to the routed output (HF
    Qwen2MoeSparseMoeBlock).  Zero when the params carry no 'shared'."""
    if "shared" not in p:
        return jnp.zeros((), dt)
    sp = p["shared"]
    if "wg" in sp:
        hdn = jax.nn.silu(tokens @ sp["wg"].astype(dt)) \
            * (tokens @ sp["wi"].astype(dt))
    else:
        hdn = jax.nn.gelu(tokens @ sp["wi"].astype(dt))
    y = hdn @ sp["wo"].astype(dt)
    gate = jax.nn.sigmoid(
        tokens.astype(jnp.float32) @ p["shared_gate"].astype(jnp.float32))
    return y * gate.astype(dt)


def moe_forward_ep(x: jnp.ndarray, p: Dict[str, jnp.ndarray], cfg,
                   topo=None, noise_key=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE with explicit all-to-all over the "expert" mesh
    axis (manual shard_map axis; data/tensor/seq stay automatic).

    Per shard: route the local tokens to all E experts, exchange the
    [E, C_loc, H] dispatch buffer so each shard holds its E/ep experts'
    tokens from every peer ([E/ep, ep·C_loc, H]), run the local expert FFN,
    exchange back, combine locally.  This is the reference's `_AllToAll`
    dispatch (sharded_moe.py:96) compiled onto ICI, and it removes the
    automatic partitioner's involuntary replication of the dispatch einsum.
    """
    topo = topo or get_topology()
    ep = topo.ep_size
    b, s, h = x.shape
    dt = x.dtype
    e_total = p["wi"].shape[0]
    if e_total % ep:
        raise ValueError(f"num_experts={e_total} not divisible by the "
                         f"expert mesh axis ({ep})")
    if b % ep:
        raise ValueError(f"batch={b} not divisible by the expert mesh axis "
                         f"({ep}); the expert axis is part of the data-"
                         "parallel product")

    def body(xs, ps):
        bl = xs.shape[0]
        tokens = xs.reshape(bl * s, h)
        # per-shard decorrelated noise key (tokens differ per shard)
        nk = jax.random.fold_in(noise_key, lax.axis_index(EXPERT_AXIS)) \
            if noise_key is not None else None
        policy = _validate_noisy_policy(cfg)
        with jax.named_scope("moe.router"):
            gate_in = _jitter_tokens(tokens, nk) \
                if nk is not None and policy == "Jitter" else tokens
            # fp32 router matmul: routing precision, and the replicated
            # router's backward psum must not be bf16 (XLA CPU's
            # AllReducePromotion aborts on the bf16 all-reduce that
            # shard_map's transpose of a replicated input otherwise emits)
            logits = gate_in.astype(jnp.float32) \
                @ ps["router"].astype(jnp.float32)
            select = _rsample_logits(logits, nk) \
                if nk is not None and policy == "RSample" else None
        t, e = logits.shape
        c = _capacity(t, e, cfg.capacity_factor, cfg.top_k)
        mode = _resolve_dispatch(cfg, t, e, c)
        with jax.named_scope("moe.dispatch"):
            dispatched, combine_fn, l_aux = _DISPATCHERS[mode](
                tokens, logits, cfg, dt, select)
            # [E, C_loc, H] → [E/ep, ep·C_loc, H]: shard i keeps experts
            # [i·E/ep, (i+1)·E/ep) and receives their queues from every peer
            dispatched = lax.all_to_all(dispatched, EXPERT_AXIS,
                                        split_axis=0, concat_axis=1,
                                        tiled=True)
        with jax.named_scope("moe.experts"):
            expert_out = _expert_ffn(dispatched, ps, dt)
        with jax.named_scope("moe.combine"):
            expert_out = lax.all_to_all(expert_out, EXPERT_AXIS,
                                        split_axis=1, concat_axis=0,
                                        tiled=True)
            out = combine_fn(expert_out)
        with jax.named_scope("moe.router"):
            l_aux = lax.pmean(l_aux, EXPERT_AXIS)
        return out.reshape(bl, s, h), l_aux.astype(jnp.float32)

    # tokens' batch dim is sharded over the expert axis (it is part of the
    # data-parallel product); expert weights over their leading expert dim;
    # the router is replicated.  The shared expert (dense, every token) is
    # computed outside the manual region under the auto partitioner.
    routed_p = {k: v for k, v in p.items()
                if k not in ("shared", "shared_gate", "residual",
                             "coef_w", "coef_b")}
    p_specs = {key: P(EXPERT_AXIS) if key != "router" else P()
               for key in routed_p}
    # inside another shard_map (e.g. the pipeline's manual "pipe" axis) the
    # inner shard_map must be built on the *context* mesh, whose outer axes
    # are already marked Manual — passing the raw device mesh is rejected
    ctx = get_abstract_mesh()
    mesh = topo.mesh if ctx.empty else ctx
    mapped = shard_map(
        body, mesh=mesh, axis_names={EXPERT_AXIS},
        in_specs=(P(EXPERT_AXIS), p_specs),
        out_specs=(P(EXPERT_AXIS), P()))
    out, l_aux = mapped(x, routed_p)
    # dense-per-token branches (PR-MoE residual mix, qwen2-moe shared
    # expert) run outside the manual region under the auto partitioner
    with jax.named_scope("moe.shared"):
        if "residual" in p:
            out = _residual_mix(x.reshape(b * s, h), out.reshape(b * s, h),
                                p, dt).reshape(x.shape)
        if "shared" in p:
            out = out + _shared_expert_out(x.reshape(b * s, h), p,
                                           dt).reshape(x.shape)
    return out, l_aux


# rows of a tile of (row, held expert) pairs in moe_forward_held
HELD_TILE = 128


def route_sigmoid(x, router, bias, top_k: int):
    """Sigmoid routing without capacity (DeepSeek-V3 ``noaux_tc`` without
    groups): scores ``s = sigmoid(x W_r)`` in float32; the ``top_k``
    experts with the largest ``s + bias`` are CHOSEN, and weighted by
    ``s`` alone, normalised over the chosen.  x: [T, H].  Returns
    ``(chosen [T, k] int32, weights [T, k] float32)``: every token keeps
    all its ``top_k`` experts, whatever the batch."""
    s = jax.nn.sigmoid(jnp.dot(x, router.astype(x.dtype),
                               preferred_element_type=jnp.float32))
    _, chosen = lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


def moe_forward_held(x, p, layer, *, top_k: int, first: int,
                     scale: float = 1.0):
    """The part of a sigmoid-routed expert layer that THIS program's
    experts give.  Every leaf of ``p`` holds the expert layers stacked on
    axis 0 and ``layer`` (a traced scalar will do) says which: ``p["wg"]``
    / ``["wi"]`` ``[L, held, H, F]`` and ``p["wo"]`` ``[L, held, F, H]``
    are experts ``first .. first + held`` of the ``p["router"]``'s
    ``[L, H, E]``; routing is over all ``E`` (:func:`route_sigmoid`), and
    ``y[t] = scale * sum over t's chosen experts that are held of w *
    SwiGLU_e(x[t])`` (``scale``: HF ``routed_scaling_factor``): what the
    other ranks of an expert-parallel layer
    would add is theirs to compute, and a shared expert is the caller's.
    Without a ``p["wg"]`` the experts have TWO matrices and no gate,
    ``relu(x W_u^T)^2 W_o`` (HF ``nemotron_h``), the up matrix stored as
    ``p["wu"]`` ``[L, held, F, H]``, a hidden unit's weights a row: the
    chip lays an array out by its shape, lanes along a dim that fills
    them, and at a width that is no multiple of 128 (1856) an ``[H, F]``
    stack came in lanes along ``H`` and was copied whole, 2.5 GB a step,
    into the order the product reads.
    x: [T, H] -> [T, H].  The tile loop reads ``[layer, expert]`` out of
    the stack itself: a layer's experts sliced out first are a
    loop-invariant value the compiler copies whole, 0.5 GB a matrix at
    published widths.

    No capacity and no dropped token.  The (row, held expert) pairs are
    sorted by expert and laid out in tiles of ``HELD_TILE`` rows, an
    expert's pairs starting on a tile's edge; a loop over the tiles IN USE
    (a traced count: at most ``T * top_k / tile + held``, which is where
    every row chose held experts only) multiplies a tile's rows by its
    expert's three matrices and adds the weighted result to the rows it
    came from.  An expert no row chose is never read: a decode step reads
    the experts its few rows chose, a prefill chunk each expert once a
    tile."""
    t, h = x.shape
    held = p["wo"].shape[1]
    gated = "wg" in p
    dt, f32 = x.dtype, jnp.float32
    tm = min(HELD_TILE, max(8, t))
    with jax.named_scope("moe.router"):
        chosen, w = route_sigmoid(x, p["router"][layer], p["bias"][layer],
                                  top_k)
        if scale != 1.0:
            w = w * scale
    with jax.named_scope("moe.dispatch"):
        local = (chosen - first).reshape(-1)                   # [T * k]
        local = jnp.where((local >= 0) & (local < held), local, held)
        n = t * top_k
        order = jnp.argsort(local)
        e_s = local[order]
        r_s = (order // top_k).astype(jnp.int32)
        w_s = w.reshape(-1)[order]

        counts = jnp.sum(jax.nn.one_hot(local, held + 1, dtype=jnp.int32),
                         axis=0)
        tiles_e = (counts[:held] + tm - 1) // tm
        tile_end = jnp.cumsum(tiles_e)
        first_pair = jnp.cumsum(counts) - counts               # [held + 1]
        n_tiles = n // tm + held
        rank = jnp.arange(n, dtype=jnp.int32) - first_pair[e_s]
        dest = jnp.where(
            e_s < held,
            (tile_end - tiles_e)[jnp.minimum(e_s, held - 1)] * tm + rank,
            n_tiles * tm)                                      # the dump
        # row T is a row of zeros that takes every unused place of a tile
        src = jnp.full((n_tiles * tm + 1,), t, jnp.int32).at[dest].set(r_s)
        gate = jnp.zeros((n_tiles * tm + 1,), f32).at[dest].set(w_s)
        tile_expert = jnp.minimum(
            jnp.sum(jnp.arange(n_tiles, dtype=jnp.int32)[:, None]
                    >= tile_end[None, :], axis=1), held - 1)
        x_pad = jnp.concatenate([x, jnp.zeros((1, h), dt)])

    def one_tile(i, y):
        with jax.named_scope("moe.dispatch"):
            e = tile_expert[i]
            rows = lax.dynamic_slice(src, (i * tm,), (tm,))
            g = lax.dynamic_slice(gate, (i * tm,), (tm,))
            xt = x_pad[rows]
        with jax.named_scope("moe.experts"):
            if gated:
                act = jax.nn.silu(xt @ p["wg"][layer, e].astype(dt)) \
                    * (xt @ p["wi"][layer, e].astype(dt))
            else:
                act = jnp.square(jax.nn.relu(lax.dot_general(
                    xt, p["wu"][layer, e].astype(dt),
                    (((1,), (1,)), ((), ())))))
            out = jnp.dot(act * g[:, None].astype(dt),
                          p["wo"][layer, e].astype(dt),
                          preferred_element_type=f32)
        with jax.named_scope("moe.combine"):
            return y.at[rows].add(out)

    with jax.named_scope("moe.experts"):
        y = lax.fori_loop(0, tile_end[-1], one_tile,
                          jnp.zeros((t + 1, h), f32))
    with jax.named_scope("moe.combine"):
        return y[:t].astype(dt)
