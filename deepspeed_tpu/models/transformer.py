"""Functional transformer model family (GPT-2 and Llama class).

TPU-first design notes (vs the reference's per-module eager torch models):

* Parameters are a plain pytree (nested dicts of jnp arrays); the per-layer
  params are **stacked along a leading layer axis** and the forward is a
  ``lax.scan`` over layers — one compiled layer body regardless of depth,
  which is the idiomatic XLA replacement for DeepSpeed's per-module hook
  machinery (SURVEY §7 hard part (a)).
* Activation checkpointing is ``jax.checkpoint`` with a configurable policy
  (ref: runtime/activation_checkpointing/checkpointing.py:948 — here the
  compiler does the re-materialisation).
* Compute runs in ``config.dtype`` (bf16 by default), master params stay in
  ``param_dtype`` (fp32) — the engine's mixed-precision contract.
* Param paths are stable strings (e.g. ``layers/attn/wq``) so parallelism
  sharding rules can be expressed as path-pattern → PartitionSpec maps
  (AutoTP-equivalent, ref module_inject/auto_tp.py:193).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]


@dataclass(frozen=True)
class SSMConfig:
    """A Mamba-2 mixer beside the attention heads of every block, and the
    muP multipliers of a block that has one (HF ``falcon_h1``).  The mixer
    reads the block's normed input, as attention does; the two outputs are
    scaled and added.  ``TransformerConfig.ssm`` is ``None`` for a model
    without a mixer."""
    num_heads: int
    head_dim: int
    state_size: int
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 128
    conv_bias: bool = True
    # multipliers (all 1.0 = a plain Mamba-2 hybrid)
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    # over the five parts of in_proj's output: gate z, x, B, C, dt
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    # inside the gate's activation, and on the down projection
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)

    @property
    def d_ssm(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels through the depthwise convolution: x, B and C."""
        return self.d_ssm + 2 * self.n_groups * self.state_size

    @property
    def proj_dim(self) -> int:
        """in_proj's output: z, then x, B, C, then dt."""
        return self.d_ssm + self.conv_dim + self.num_heads

    def part_sizes(self) -> Tuple[int, int, int, int, int]:
        gn = self.n_groups * self.state_size
        return (self.d_ssm, self.d_ssm, gn, gn, self.num_heads)


@dataclass(frozen=True)
class LatentWidths:
    """One kind of latent-attention (MLA) layer's sizes: queries and keys
    go through low-rank latents, a head's key is ``nope`` dims made from
    the key latent plus ``rope`` rotary dims shared by every head, and
    the cache holds ONE row a token, ``[c_kv | k_r]``."""
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float

    @property
    def row_dim(self) -> int:
        """Width of a cache row: the key latent and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class LatentConfig:
    """Latent attention in every block, of two kinds chosen per layer by
    ``layer_types`` (HF ``dots3_note``, ``glm_moe_dsa``):
    ``"full_attention"`` layers read the ``index_topk`` keys a learned
    indexer scores highest, ``"sliding_attention"`` layers the last
    ``sliding_window`` positions at widths of their own (``window`` is
    ``None`` and ``sliding_window`` 0 for a model whose layers are all
    full: it keeps no rings); ``gate``: a headwise sigmoid gate on every
    head's output (dots3-note has one, GLM-5 none); ``first_k_dense``
    leading blocks with a dense feed-forward, the others with
    sigmoid-routed experts of which this program holds ``experts_held``
    (first, count): it routes over all ``n_routed_experts`` and computes
    its own experts' part (weights normalised over the chosen, times
    ``routed_scaling_factor``).  ``rope_interleaved``: rotary pairs are
    neighbours ``(2i, 2i + 1)``, in attention and in the indexer, and not
    the halves ``(i, i + n/2)``.  ``mtp_layers`` (0 or 1): a
    multi-token-prediction module after the trunk (DeepSeek-V3's; HF
    ``num_nextn_predict_layers``): one more full layer with experts, on
    ``[RMSNorm(Emb(next token)) ; RMSNorm(trunk output)] W_eh``, through
    its own norm into the trunk's head; a serving engine drafts with it
    (``inference/v2``: ``self_draft``).
    ``TransformerConfig.mla`` is ``None`` for a model without latent
    attention.  Served by inference/v2 only."""
    full: LatentWidths
    window: Optional[LatentWidths]
    layer_types: Tuple[str, ...]
    sliding_window: int
    index_heads: int
    index_head_dim: int
    index_topk: int
    index_rope_dim: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int = 1
    first_k_dense: int = 1
    lora_rescale: bool = True
    rope_interleaved: bool = False
    gate: bool = True
    routed_scaling_factor: float = 1.0
    mtp_layers: int = 0

    def kinds(self, num_layers: int) -> Tuple[Tuple[bool, bool], ...]:
        """(is a full layer, has experts) for each of the first
        ``num_layers`` layers (the trunk's: a module is none of them)."""
        return tuple((t == "full_attention", i >= self.first_k_dense)
                     for i, t in enumerate(self.layer_types[:num_layers]))

    def has_window(self, num_layers: int) -> bool:
        """Whether any of the first ``num_layers`` layers keeps a ring."""
        return not all(full for full, _ in self.kinds(num_layers))

    def cache_layers(self, num_layers: int) -> int:
        """Layers that keep latent rows and index keys in the pages: the
        trunk's full layers and the module's one."""
        return sum(1 for full, _ in self.kinds(num_layers) if full) \
            + self.mtp_layers


@dataclass(frozen=True)
class MixedAttentionConfig:
    """Plain (grouped-query) attention of two kinds chosen per layer by
    ``layer_types`` (HF ``afmoe``, ``mimo_v2_flash``):
    ``"sliding_attention"`` layers see the token and the
    ``sliding_window - 1`` before it and carry rotary,
    ``"full_attention"`` layers are causal and carry rotary only with
    ``rope_full``.  ``qk_norm``: an RMSNorm over the dims of a head on
    queries and on keys, one gain vector each for all heads, before
    rotary; ``gate``: the attention output times ``sigmoid(a W_g)``,
    element by element, ``a`` the normed input, before the out
    projection; ``sandwich_norm``: an RMSNorm AFTER attention and AFTER
    the feed-forward, each before its residual add;
    ``embed_multiplier``: the embedding rows' muP scale.

    **What a kind of layer may have of its own** (each None or False
    leaves both kinds what the model's own fields say, which is HF
    ``afmoe`` and the program it always got): ``window_kv_heads`` (the
    window layers' KV heads; the full layers keep ``num_kv_heads``),
    ``window_rope_theta`` (the window layers' rotary base; the full
    layers keep ``rope_theta``), ``window_sink`` / ``full_sink`` (one
    learned logit a query head, ``attn/sink`` ``[heads]``, that joins the
    softmax's denominator and adds no value).  For both kinds alike:
    ``v_head_dim`` (the width of a value row and of a head's output where
    it is not the key's ``head_dim``; ``wo`` is then ``[heads x
    v_head_dim, hidden]``), ``value_scale`` (values times it before the
    product); the rotary share is the model's ``rotary_pct``.  Where the
    kinds' attention weights differ in shape or in what they hold
    (:attr:`attn_by_kind`) they are stacked a kind, ``layers/attn_full``
    and ``layers/attn_window``, else over every layer, ``layers/attn``.

    ``num_dense_layers`` leading blocks have a dense feed-forward, the
    others sigmoid-routed experts of which this program holds
    ``experts_held`` (first, count): it routes over all
    ``n_routed_experts`` and computes its own experts' part (weights
    normalised over the chosen, times ``route_scale``) and the shared
    expert whole (none with ``n_shared_experts`` 0).
    ``TransformerConfig.mixed`` is ``None`` for any other
    model.  Served by inference/v2 only: the window layers' rows live in
    a page pool of their own that frees pages behind the window."""
    layer_types: Tuple[str, ...]
    sliding_window: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_tok: int
    moe_intermediate_size: int
    num_dense_layers: int = 1
    n_shared_experts: int = 1
    route_scale: float = 1.0
    rope_full: bool = False
    qk_norm: bool = True
    gate: bool = True
    sandwich_norm: bool = True
    embed_multiplier: float = 1.0
    window_kv_heads: Optional[int] = None
    window_rope_theta: Optional[float] = None
    window_sink: bool = False
    full_sink: bool = False
    v_head_dim: Optional[int] = None
    value_scale: float = 1.0

    def kinds(self, num_layers: int) -> Tuple[Tuple[bool, bool], ...]:
        """(is a full layer, has experts) for each of the first
        ``num_layers`` layers."""
        return tuple((t == "full_attention", i >= self.num_dense_layers)
                     for i, t in enumerate(self.layer_types[:num_layers]))

    def window_layers(self, num_layers: int) -> int:
        return sum(1 for full, _ in self.kinds(num_layers) if not full)

    @property
    def attn_by_kind(self) -> bool:
        """Whether the kinds' attention weights are stacked apart."""
        return (self.window_kv_heads is not None
                or self.window_sink != self.full_sink)

    def sink(self, is_full: bool) -> bool:
        return self.full_sink if is_full else self.window_sink


@dataclass(frozen=True)
class HybridConfig:
    """ONE mixer a layer, chosen per layer by ``pattern`` (HF
    ``nemotron_h``'s ``hybrid_override_pattern``): ``"M"`` a Mamba-2 mixer
    (its sizes in ``TransformerConfig.ssm``, every multiplier 1), ``"*"``
    grouped-query attention with NO rotary and no other position signal,
    ``"E"`` sigmoid-routed experts of TWO matrices each, ``relu(u W_up)^2
    W_down`` without a gate, beside a shared one of
    ``shared_intermediate_size``.  A block is ``x + f(RMSNorm(x))`` with
    that one ``f``; there is no feed-forward beside a mixer.  This program
    holds ``experts_held`` (first, count) of the ``n_routed_experts``: it
    routes over all of them and computes its own experts' part (weights
    normalised over the chosen, times ``route_scale``) and the shared
    expert whole.  The recurrent slots are sized by the ``"M"`` layers and
    the KV pools by the ``"*"`` layers, not by ``num_layers``.
    ``TransformerConfig.hybrid`` is ``None`` for any other model.  Served
    by inference/v2 only."""
    pattern: str
    n_routed_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_intermediate_size: int
    route_scale: float = 1.0

    def kinds(self, num_layers: int) -> Tuple[str, ...]:
        """``"M"``, ``"*"`` or ``"E"`` for each of the first
        ``num_layers`` layers."""
        return tuple(self.pattern[:num_layers])


@dataclass(frozen=True)
class TransformerConfig:
    """Architecture hyperparameters covering GPT-2 and Llama families."""
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # < num_heads → GQA (Llama-3)
    head_dim: Optional[int] = None
    max_seq_len: int = 1024
    # architecture switches
    arch: str = "gpt2"  # "gpt2" | "llama" | "opt" | "mistral" | "qwen2" | "falcon" | "phi"
    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    activation: str = "gelu"  # "gelu" | "swiglu" | "relu"
    use_rope: bool = False
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0  # Phi-style partial rotary (fraction of head dim)
    tie_embeddings: bool = True
    # family features (ref inference/v2/model_implementations/{opt,phi,qwen,
    # falcon,mistral}): learned absolute positions, projection biases,
    # sliding-window attention, parallel attn+MLP residual blocks
    learned_positions: Optional[bool] = None  # None → arch == "gpt2"/"opt"
    use_bias: Optional[bool] = None  # all proj biases; None → gpt2/opt
    qkv_bias: bool = False  # qkv-only bias (Qwen2)
    sliding_window: Optional[int] = None  # Mistral
    # GPT-Neo attention_types: ODD global layer indices use
    # sliding_window ("local"), even ones attend globally.  Realized by
    # scanning layer PAIRS with a static per-member config — no dynamic
    # masks (ref module_inject/containers/gptneo.py)
    alt_window: bool = False
    # attention score scale; None → 1/sqrt(head_dim).  GPT-Neo famously
    # omits the sqrt(d) scaling (scale = 1.0)
    attn_scale: Optional[float] = None
    # ALiBi positional bias (Bloom): score += slope[h] · key_position —
    # used instead of rope/learned positions
    use_alibi: bool = False
    # GPT-J rotary layout: dims pair as (2i, 2i+1) ("rotate every two")
    # instead of the llama/neox half-split
    rope_interleaved: bool = False
    # MLP bias independent of attention bias (GPT-J: biasless attention,
    # biased MLP); None → follows has_bias
    mlp_bias: Optional[bool] = None
    # attention OUT-projection bias independent of q/k/v bias (GPT-Neo:
    # biasless q/k/v, biased out_proj); None → follows has_bias
    attn_out_bias: Optional[bool] = None
    # False = bidirectional (encoder/BERT-class) attention.  The reference
    # trains encoders through its fused transformer kernel
    # (ops/transformer/transformer.py:296 DeepSpeedTransformerLayer) and
    # serves bert/distilbert via v1 injection containers.
    causal: bool = True
    # "pre" (GPT/llama) | "post" (BERT: residual-add then LayerNorm; the
    # final norm is per-layer, so no final_norm is applied)
    norm_position: str = "pre"
    # BERT segment embeddings: 0 = none; batch may carry "token_type_ids"
    type_vocab_size: int = 0
    # BERT: LayerNorm (+dropout) applied to the summed embeddings
    embed_norm: bool = False
    # BERT MLM head: LN(gelu(h @ W + b)) @ embed.T + bias instead of the
    # plain lm_head matmul (HF BertLMPredictionHead)
    mlm_head: bool = False
    # vocab-size output bias added to the logits (GPT-J ships a nonzero
    # lm_head.bias; HF applies it, so serving parity requires it too)
    lm_head_bias: bool = False
    parallel_block: bool = False  # Falcon/Phi: x + attn(n) + mlp(n)
    # Falcon new_decoder_architecture (40B/180B, num_ln_in_parallel_attn=2):
    # the parallel block gets separate input norms — attn uses ln1 (HF
    # ln_attn) and the MLP uses ln2 (HF ln_mlp) on the same residual input.
    parallel_norms: bool = False
    # MoE (0 ⇒ dense; ref deepspeed/moe)
    num_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    # routed-expert FFN width when it differs from the dense layers'
    # intermediate_size (HF qwen2_moe moe_intermediate_size); None → same
    moe_intermediate_size: Optional[int] = None
    # Qwen2-MoE shared expert: a dense FFN of this width added to the
    # routed output, gated by sigmoid(x @ shared_gate); 0 = none
    moe_shared_expert_size: int = 0
    # True: renormalize top-k weights to sum to 1 (HF mixtral
    # norm_topk_prob); False: deepspeed top2gating drop-aware scaling
    moe_norm_topk: bool = False
    # Residual MoE (PR-MoE, ref moe/layer.py:29 use_residual /
    # arXiv:2201.05596): a dense expert-shaped MLP runs every token and a
    # learned 2-way coefficient softmax mixes it with the routed output
    moe_use_residual: bool = False
    # "auto" | "einsum" | "sorted": [T,E,C] one-hot einsum dispatch vs
    # argsort-by-expert gather dispatch (auto switches on one-hot size)
    moe_dispatch: str = "auto"
    # "1f1b" (training loss runs the interleaved schedule with O(pp) live
    # microbatches, ref runtime/pipe/schedule.py:189) | "gpipe" (fill-drain
    # forward scan differentiated by AD)
    pipeline_schedule: str = "1f1b"
    # ZeRO-Infinity: stacked layer params live in pinned host memory and
    # stream one layer at a time through the scan, fwd and bwd
    # (runtime/infinity.py; set by the engine from offload_param config)
    param_stream: bool = False
    moe_layer_freq: int = 2  # every Nth layer is MoE, matching ref PR-MoE style
    # pipeline parallelism: microbatches per forward call, i.e. per
    # gradient-accumulation micro-step (0 → pp size); must divide the
    # per-call batch dim
    pipeline_microbatches: int = 0
    # random-LTD (ref data_routing/basic_layer.py): a band of middle layers
    # [ltd_start, ltd_end) runs on ltd_kept random tokens; 0 = disabled.
    # ltd_kept is static per compile — the engine re-jits when the
    # schedule raises it (same recompile cadence as the reference's
    # shape changes).
    ltd_kept: int = 0
    ltd_start: int = 1
    ltd_end: Optional[int] = None
    # reference noisy gating (TopKGate noisy_gate_policy): 'RSample' |
    # 'Jitter' | None; active only while training threads a dropout/noise
    # key through the batch
    moe_noisy_gate_policy: Optional[str] = None
    # sequence-tiled logits+loss (ALST, sequence/alst.py): never
    # materialises [B, S, V]; 0 = full logits
    loss_tiles: int = 0
    # sequence-parallel attention form over the "seq" mesh axis:
    # "ulysses" (all-to-all head exchange; needs heads % (tp·sp) == 0) |
    # "ring" (K/V blocks rotate the ring with online softmax; no head
    # divisibility requirement — sequence/ring.py)
    seq_impl: str = "ulysses"
    # ring attention block placement over the seq mesh: "contiguous"
    # (shard r owns rows [r·S_l, (r+1)·S_l)) | "striped" (shard r owns
    # rows r, r+sp, … — Striped Attention causal load balancing: every
    # hop is ~half-masked on every rank, so the flash kernel's tile skip
    # halves causal compute uniformly instead of idling early ranks).
    # Striped feeds require stripe-permuted ids/labels; the engine
    # applies the permutation host-side and forward() derives matching
    # positions, so training is turnkey (sequence/ring.py helpers).
    ring_placement: str = "contiguous"
    # ring hop/compute interleave depth (step_schedule.ring_interleave;
    # sequence/ring.py): 1 = attend then rotate, 2 = rotate-ahead (next
    # hop's ppermute issued before the current hop's attend so the
    # transfer overlaps the hop's kernels)
    ring_interleave: int = 1
    # ring rotation wire dtype (comm_quantization.ring_rotation; set by
    # the engine): "fp32" | "int8" | "fp8" — quantized payloads + fp32
    # per-row scales travel every ring hop, dequantized in the consuming
    # flash kernel's epilogue (sequence/ring.py)
    ring_wire_dtype: str = "fp32"
    # layer-scan unroll factor (XLA overlaps across unrolled iterations)
    scan_unroll: int = 1
    # ZeRO-3 fused gather-matmul (step_schedule.fused_gather_matmul;
    # ops/pallas/gather_matmul.py): the MLP matmuls run inside an
    # explicit shard_map over `fused_gather_axes` that issues the
    # following matmul's param all-gather ahead of the current one.  Set
    # by the engine after it verifies the MLP weights actually carry the
    # expected fsdp sharding pattern.
    fused_gather_matmul: bool = False
    fused_gather_axes: Tuple[str, ...] = ()
    # residual/embedding dropout rate (GPT-2/BERT-class training; llama
    # pretraining leaves it 0).  Applied when the engine threads a
    # per-step PRNG key through the batch ("dropout_key"); inference and
    # eval paths pass no key, so dropout is identically off there.
    # Attention-probability dropout is folded into the residual drops
    # (the flash kernel keeps its probabilities in VMEM).  Under remat,
    # explicit keys make the recompute bitwise-identical — the property
    # the reference's CudaRNGStatesTracker exists to enforce.
    dropout: float = 0.0
    # numerics
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.float32  # master dtype
    layernorm_eps: float = 1e-5
    # per-op autocast policy (ref runtime/torch_autocast.py): which op
    # classes stay fp32 regardless of the compute dtype.  None → the safe
    # default below.  Configured via the "torch_autocast" config block
    # ("fp32_ops"); dropping entries is the aggressive full-low-precision
    # mode.  NOTE: the Pallas flash kernels always accumulate softmax in
    # fp32 (hardware-right on TPU) — "softmax" here gates the XLA path.
    fp32_ops: Optional[Tuple[str, ...]] = None
    # module classes allowed to run in the low compute dtype; None → all.
    # Modules NOT listed are promoted to fp32 (the torch autocast
    # "lower_precision_safe_modules" contract).
    autocast_safe_modules: Optional[Tuple[str, ...]] = None
    # remat policy name: none|full|nothing_saveable|dots_saveable|dots_with_no_batch_dims_saveable
    remat_policy: str = "nothing_saveable"
    attn_impl: str = "auto"  # "auto" | "xla" | "pallas_flash" | "sparse"
    # block-sparse attention config (ref ops/sparse_attention sparsity
    # configs): {"mode": "fixed"|"bigbird"|"bslongformer"|"variable",
    # "block": 16, ...mode kwargs}; selected when attn_impl == "sparse"
    sparse_attention: Optional[Any] = None
    # inference-v2 module overrides as (kind, name) pairs — resolved via
    # inference/v2/modules.py (ref inference/v2/modules/heuristics.py)
    v2_modules: Optional[Tuple[Tuple[str, str], ...]] = None
    # state-space mixer beside attention in every block (Falcon-H1);
    # None: the block has attention alone.  Served by inference/v2 only
    ssm: Optional[SSMConfig] = None
    # latent (MLA) attention of two kinds by layer, a learned indexer and
    # routed experts held in part (dots3-note); None: plain attention.
    # Served by inference/v2 only
    mla: Optional[LatentConfig] = None
    # plain attention of two kinds by layer (window layers in a page pool
    # that frees behind the window), q/k norms, an attention gate,
    # sandwich norms and held sigmoid-routed experts (Trinity, HF afmoe);
    # None: one kind of layer.  Served by inference/v2 only
    mixed: Optional[MixedAttentionConfig] = None
    # one mixer a layer by a pattern: a Mamba-2 scan (``ssm`` has its
    # sizes), attention without rotary, or held ungated relu^2 experts
    # (Nemotron-H, HF nemotron_h); None: every block alike.  Served by
    # inference/v2 only
    hybrid: Optional[HybridConfig] = None

    @property
    def _held(self):
        """The nested configuration that says which experts are held."""
        return self.mla or self.mixed or self.hybrid

    # a latent model's sizes by flat names (0 without one), as the
    # mixer's below
    @property
    def latent_row(self) -> int:
        return self.mla.full.row_dim if self.mla else 0

    @property
    def window_row(self) -> int:
        return (self.mla.window.row_dim
                if self.mla and self.mla.window else 0)

    @property
    def index_topk(self) -> int:
        return self.mla.index_topk if self.mla else 0

    @property
    def n_routed_experts(self) -> int:
        return self._held.n_routed_experts if self._held else 0

    @property
    def experts_held(self) -> int:
        return self._held.experts_held[1] if self._held else 0

    @property
    def mtp_layers(self) -> int:
        return self.mla.mtp_layers if self.mla else 0

    @property
    def first_k_dense(self) -> int:
        return self.mla.first_k_dense if self.mla else 0

    @property
    def routed_scaling_factor(self) -> float:
        return self.mla.routed_scaling_factor if self.mla else 0.0

    # a mixed-attention model's sizes by flat names (0 without one)
    @property
    def num_dense_layers(self) -> int:
        return self.mixed.num_dense_layers if self.mixed else 0

    @property
    def route_scale(self) -> float:
        held = self.mixed or self.hybrid
        return held.route_scale if held else 0.0

    @property
    def window_layers(self) -> int:
        return self.mixed.window_layers(self.num_layers) if self.mixed else 0

    @property
    def layer_window(self) -> int:
        return self.mixed.sliding_window if self.mixed else 0

    @property
    def experts_per_tok(self) -> int:
        return self._held.num_experts_per_tok if self._held else 0

    @property
    def expert_width(self) -> int:
        return self._held.moe_intermediate_size if self._held else 0

    @property
    def embed_multiplier(self) -> float:
        return self.mixed.embed_multiplier if self.mixed else 1.0

    @property
    def window_kv_heads(self) -> int:
        """KV heads of a mixed-attention model's window layers."""
        if not self.mixed:
            return 0
        return self.mixed.window_kv_heads or self.kv_heads

    @property
    def value_width(self) -> int:
        """Dims of a value row and of a head's output."""
        return (self.mixed and self.mixed.v_head_dim) or self.dim_per_head

    @property
    def sink_layers(self) -> int:
        """Layers whose softmax carries a learned sink a head."""
        if not self.mixed:
            return 0
        return sum(self.mixed.sink(full)
                   for full, _ in self.mixed.kinds(self.num_layers))

    def mixed_kind(self, is_full: bool) -> "TransformerConfig":
        """A mixed-attention model's configuration as ONE kind of layer
        reads it: the kind's window, rotary (and its base) and KV heads
        in the model's own fields."""
        mx = self.mixed
        if is_full:
            return self.replace(sliding_window=None, use_rope=mx.rope_full)
        return self.replace(
            sliding_window=mx.sliding_window, use_rope=True,
            num_kv_heads=self.window_kv_heads,
            rope_theta=mx.window_rope_theta or self.rope_theta)

    # a one-mixer-a-layer model's sizes by flat names ("" / 0 without one)
    @property
    def layer_kinds(self) -> str:
        return "".join(self.hybrid.kinds(self.num_layers)) \
            if self.hybrid else ""

    @property
    def shared_width(self) -> int:
        return self.hybrid.shared_intermediate_size if self.hybrid else 0

    @property
    def expert_layers(self) -> int:
        return self.layer_kinds.count("E")

    @property
    def ssm_layers(self) -> int:
        """Layers that keep a recurrent slot a sequence."""
        if self.hybrid:
            return self.layer_kinds.count("M")
        return self.num_layers if self.ssm else 0

    @property
    def attn_layers(self) -> int:
        """Layers of the plain KV pools ``[layers, kv_heads, rows, d]``."""
        if self.hybrid:
            return self.layer_kinds.count("*")
        return self.num_layers - self.window_layers

    # the mixer's sizes by flat names (0 without one), for callers that
    # hold a configuration to a file by ``getattr``
    @property
    def ssm_heads(self) -> int:
        return self.ssm.num_heads if self.ssm else 0

    @property
    def ssm_head_dim(self) -> int:
        return self.ssm.head_dim if self.ssm else 0

    @property
    def ssm_state(self) -> int:
        return self.ssm.state_size if self.ssm else 0

    @property
    def ssm_groups(self) -> int:
        return self.ssm.n_groups if self.ssm else 0

    @property
    def ssm_conv(self) -> int:
        return self.ssm.conv_kernel if self.ssm else 0

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def dim_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def has_learned_positions(self) -> bool:
        if self.learned_positions is not None:
            return self.learned_positions
        return self.arch in ("gpt2", "opt", "bert", "distilbert")

    @property
    def has_bias(self) -> bool:
        if self.use_bias is not None:
            return self.use_bias
        return self.arch in ("gpt2", "opt", "phi", "bert", "distilbert")

    @property
    def has_mlp_bias(self) -> bool:
        return self.has_bias if self.mlp_bias is None else self.mlp_bias

    @property
    def has_attn_out_bias(self) -> bool:
        return (self.has_bias if self.attn_out_bias is None
                else self.attn_out_bias)

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)


# ----------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------
def _dense_init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_layer_params(cfg: TransformerConfig, key) -> Params:
    """One transformer block's params (unstacked)."""
    h, ffn = cfg.hidden_size, cfg.intermediate_size
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
    keys = jax.random.split(key, 8)
    scale = 1.0 / math.sqrt(h)
    out_scale = scale / math.sqrt(2 * cfg.num_layers)  # GPT-2 style residual scaling
    pd = cfg.param_dtype

    attn = {
        "wq": _dense_init(keys[0], (h, nh * hd), scale, pd),
        "wk": _dense_init(keys[1], (h, nkv * hd), scale, pd),
        "wv": _dense_init(keys[2], (h, nkv * hd), scale, pd),
        "wo": _dense_init(keys[3], (nh * hd, h), out_scale, pd),
    }
    if cfg.has_bias or cfg.qkv_bias:
        attn["bq"] = jnp.zeros((nh * hd,), pd)
        attn["bk"] = jnp.zeros((nkv * hd,), pd)
        attn["bv"] = jnp.zeros((nkv * hd,), pd)
    if cfg.has_attn_out_bias:
        attn["bo"] = jnp.zeros((h,), pd)

    def mlp_params(k1, k2, k3):
        if cfg.activation == "swiglu":
            return {
                "wi": _dense_init(k1, (h, ffn), scale, pd),
                "wg": _dense_init(k2, (h, ffn), scale, pd),
                "wo": _dense_init(k3, (ffn, h), out_scale, pd),
            }
        mlp = {
            "wi": _dense_init(k1, (h, ffn), scale, pd),
            "wo": _dense_init(k3, (ffn, h), out_scale, pd),
        }
        if cfg.has_mlp_bias:
            mlp["bi"] = jnp.zeros((ffn,), pd)
            mlp["bo"] = jnp.zeros((h,), pd)
        return mlp

    block: Params = {"attn": attn}
    if not (cfg.is_moe and cfg.moe_layer_freq == 1):
        # all-MoE stacks (freq 1, mixtral/qwen2moe style) carry no dense
        # FFN at all — a zero/random filler would cost real HBM and
        # optimizer state (e.g. ~22GB of dead fp32 on mixtral-8x7b)
        block["mlp"] = mlp_params(keys[4], keys[5], keys[6])

    if cfg.is_moe:
        # Expert weights stacked on a leading expert axis (sharded over the
        # "expert" mesh axis); router is replicated. Ref: moe/experts.py +
        # sharded_moe.py TopKGate.
        ek = jax.random.split(keys[7], 12)
        e = cfg.num_experts
        mffn = cfg.moe_intermediate_size or ffn
        block["moe"] = {
            "router": _dense_init(ek[0], (h, e), scale, pd),
            "wi": _dense_init(ek[1], (e, h, mffn), scale, pd),
            "wg": _dense_init(ek[2], (e, h, mffn), scale, pd) if cfg.activation == "swiglu" else None,
            "wo": _dense_init(ek[3], (e, mffn, h), out_scale, pd),
        }
        if cfg.moe_use_residual:
            # PR-MoE (ref moe/layer.py:83-86): the residual branch is an
            # expert-shaped dense MLP plus a Linear(h, 2) mixing head
            block["moe"]["residual"] = {
                k: v for k, v in {
                    "wi": _dense_init(ek[8], (h, mffn), scale, pd),
                    "wg": _dense_init(ek[9], (h, mffn), scale, pd)
                    if cfg.activation == "swiglu" else None,
                    "wo": _dense_init(ek[10], (mffn, h), out_scale, pd),
                }.items() if v is not None}
            block["moe"]["coef_w"] = _dense_init(ek[11], (h, 2), scale, pd)
            block["moe"]["coef_b"] = jnp.zeros((2,), pd)
        if cfg.moe_shared_expert_size:
            sf = cfg.moe_shared_expert_size
            block["moe"]["shared"] = {
                "wi": _dense_init(ek[4], (h, sf), scale, pd),
                "wg": _dense_init(ek[5], (h, sf), scale, pd)
                if cfg.activation == "swiglu" else None,
                "wo": _dense_init(ek[6], (sf, h), out_scale, pd),
            }
            block["moe"]["shared"] = {k: v for k, v
                                      in block["moe"]["shared"].items()
                                      if v is not None}
            block["moe"]["shared_gate"] = _dense_init(ek[7], (h, 1), scale,
                                                      pd)
        block["moe"] = {k: v for k, v in block["moe"].items() if v is not None}

    def norm_params():
        p = {"scale": jnp.ones((h,), pd)}
        if cfg.norm == "layernorm":
            p["bias"] = jnp.zeros((h,), pd)
        return p

    block["ln1"] = norm_params()
    block["ln2"] = norm_params()
    if cfg.ssm is not None:
        block["ssm"] = init_ssm_params(cfg, jax.random.fold_in(key, 0x55D))
    return block


def init_ssm_params(cfg: TransformerConfig, key) -> Params:
    """One block's Mamba-2 mixer, by the Mamba-2 convention: ``A_log`` the
    log of uniform 1..16, ``dt_bias`` the inverse softplus of a step
    log-uniform in 1e-3..1e-1, ``D`` ones, the depthwise convolution
    uniform in +-1/sqrt(kernel) (torch's Conv1d default), the gated
    norm's scale ones."""
    m, h, pd = cfg.ssm, cfg.hidden_size, cfg.param_dtype
    k = jax.random.split(key, 6)
    scale = 1.0 / math.sqrt(h)
    bound = 1.0 / math.sqrt(m.conv_kernel)
    dt = jnp.exp(jax.random.uniform(k[2], (m.num_heads,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    p = {
        "in_proj": _dense_init(k[0], (h, m.proj_dim), scale, pd),
        "conv_w": jax.random.uniform(k[1], (m.conv_dim, m.conv_kernel),
                                     jnp.float32, -bound, bound).astype(pd),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        "A_log": jnp.log(jax.random.uniform(k[3], (m.num_heads,),
                                            jnp.float32, 1.0, 16.0)
                         ).astype(pd),
        "D": jnp.ones((m.num_heads,), pd),
        "norm": jnp.ones((m.d_ssm,), pd),
        "out_proj": _dense_init(
            k[4], (m.d_ssm, h),
            1.0 / math.sqrt(m.d_ssm) / math.sqrt(2 * cfg.num_layers), pd),
    }
    if m.conv_bias:
        p["conv_b"] = jax.random.uniform(k[5], (m.conv_dim,), jnp.float32,
                                         -bound, bound).astype(pd)
    return p


def refuse_ssm(cfg: TransformerConfig, what: str) -> None:
    """The dense forward has no state-space scan (and no backward for
    one), and no latent attention: a configuration with either is served
    by inference/v2 only."""
    if cfg.mla is not None:
        raise NotImplementedError(
            f"{what}: this configuration has latent (MLA) attention with a "
            f"learned top-{cfg.mla.index_topk} indexer"
            + (f" and window-{cfg.mla.sliding_window} latent layers"
               if cfg.mla.window else "")
            + "; models/transformer.py "
            "has neither and would run plain attention under its name. "
            "Serve it through inference.v2.InferenceEngineV2")
    if cfg.mixed is not None:
        raise NotImplementedError(
            f"{what}: this configuration mixes window-"
            f"{cfg.mixed.sliding_window} and full attention by layer "
            "(each kind with what MixedAttentionConfig gives it: q/k "
            "norms, a gate, sandwich norms, KV heads or a rotary base of "
            "its own, a sink in the softmax, a value width) over held "
            "sigmoid-routed experts; models/transformer.py has none of "
            "them and would run a plain block under its name. Serve it "
            "through inference.v2.InferenceEngineV2")
    if cfg.hybrid is not None:
        raise NotImplementedError(
            f"{what}: this configuration has ONE mixer a layer "
            f"({cfg.layer_kinds}: M a Mamba-2 scan, * attention without "
            "rotary, E ungated relu^2 experts held in part); "
            "models/transformer.py has no state-space scan, no layer "
            "without a feed-forward and no held experts, and would run a "
            "plain block under its name. Serve it through "
            "inference.v2.InferenceEngineV2")
    if cfg.ssm is not None:
        raise NotImplementedError(
            f"{what}: this configuration has a Mamba-2 SSM mixer beside "
            f"attention ({cfg.ssm.num_heads} heads of {cfg.ssm.head_dim}, "
            f"state {cfg.ssm.state_size}); models/transformer.py has no "
            "state-space scan and would run the block without it. Serve "
            "it through inference.v2.InferenceEngineV2")


# a latent model's seeded routing (init_latent_params has the reason)
EMBED_COMMON = 0.07
RESIDUAL_RMS = 0.5
ROUTER_LOGIT_SD = 4.0
ROUTER_LOGIT_MEAN = 3.5
# a model with a multi-token-prediction module: its seeded successor
# structure (init_latent_params has the reason)
SUCCESSOR_EMBED_NORM = 12.0
SUCCESSOR_HEAD_GAIN = 0.15
SUCCESSOR_SHARE = 0.8


def init_latent_params(cfg: TransformerConfig, key) -> Params:
    """A latent-attention model's params.  Layers of one kind are stacked
    on axis 0 under the kind's name: ``layers/full`` and
    ``layers/window`` (attention), ``layers/mlp`` (the leading dense
    feed-forwards) and ``layers/moe`` (router over ALL experts, selection
    bias, the held experts' weights ``[n, held, ...]``, the shared
    expert); ``layers/ln1`` and ``ln2`` over every layer.  The up
    projection of the key latent is kept as its two halves, ``wk_b``
    ``[heads, nope, rank]`` and ``wv_b`` ``[heads, rank, v]``: the
    absorbed form multiplies queries by the first and the attended latent
    by the second.  The selection bias is zeros (a trained value is not
    public); seeded normal initialisation otherwise.

    **The routing's initialisation.**  With zero-mean router logits the
    eight chosen scores all lie near one end of the sigmoid and each gets
    an eighth of the weight: a near-tie between the eighth and the ninth
    score, which any rounding upstream flips in one row of seven, swaps an
    eighth of the routed output, and a comparison with a float32
    reference then reads the flips and not the arithmetic (PERF.md §6, PR
    34).  A trained ``noaux_tc`` router's scores lie in the sigmoid's
    LOWER tail (a few experts near one, the rest near zero), so the
    marginal expert of the eight carries little weight and a flip there
    moves little.  Seeded weights get there as a trained model does, by a
    direction every hidden state shares: every embedding element has the
    offset ``EMBED_COMMON`` (beside a token-specific part of norm one) and
    every router element the matching negative part, so that the logits
    have standard deviation ``ROUTER_LOGIT_SD`` about a mean
    ``ROUTER_LOGIT_MEAN`` standard deviations below zero where the
    stream's token-specific part has grown to an rms of ``RESIDUAL_RMS``,
    which the output projections' ``1 / sqrt(2 layers)`` makes of it by
    the last blocks whatever the depth; earlier blocks sit lower still.
    Down there the eight weights are a softmax of the eight logits.

    **A module's initialisation** (``mtp_layers``: ``params["mtp"]`` holds
    ``enorm``, ``hnorm``, ``eh_proj`` ``[2 hidden, hidden]`` (the
    embedding's half first), one full layer with experts (``attn_norm``,
    ``full``, ``ffn_norm``, ``moe`` stacked ``[1, ...]``) and ``norm``; the
    embedding and the head are the trunk's).  A trained module's draft
    is the trunk's own next token for 85-90 % of its drafts (DeepSeek-V3's
    report); on plain seeded weights two argmaxes over the vocabulary
    agree once in ``vocab_size`` tries, and a server that always refuses
    its drafts measures what no user sees.  So a model with a module is
    given what a trained one has, a next token that follows from the
    last and a module that mostly knows it: a token's own part of its
    embedding row has norm ``SUCCESSOR_EMBED_NORM`` (not one) and lies in
    the first half of the hidden dims for a seeded ``SUCCESSOR_SHARE`` of
    the tokens and in the second half for the others; the head's column
    of token ``pi(v)`` carries ``SUCCESSOR_HEAD_GAIN`` times that part of
    the row of ``v`` beside its seeded normal part (``pi`` a seeded
    permutation of the vocabulary); and ``eh_proj``'s embedding half
    carries the identity ON THE FIRST HALF of the dims beside its
    normal part.  The trunk's greedy successor of
    ``v`` is then ``pi(v)`` by a wide margin over what the layers wrote
    into the stream (a logit near 27 against a largest other near 9; at
    half the norm and twice the gain, 13 against 9, one token in two
    hundred lost to the token the layers favour, every stream then
    restarted from that ONE token, and the share followed the few
    hundred tokens after it: 0.74-0.84 over three seeds), so a
    greedy stream walks a cycle of ``pi``, thousands of tokens long, and
    never the short loops greedy decoding of seeded weights falls into;
    the module is given the embedding row of the token it is to follow
    and says ``pi`` of it where it can see that row's own part, the
    ``SUCCESSOR_SHARE``, and something else where it cannot.  So the
    share of drafts accepted is about ``SUCCESSOR_SHARE`` whatever the
    seed.  What did NOT hold still (read on the chip, PERF.md, PR 39): one
    gain for every token at the margin of what the layers write (0.06 of
    the drafts accepted at a gain of 0.10, 0.88 at 0.13), and a gain for
    a share of the tokens only (a stream then falls into one short loop,
    all accepted or none: 0.96, 1.00 and 0.08 on three seeds).  Nothing
    is forced: a draft is the module's argmax, accepted where it equals
    the trunk's."""
    m, h, pd, nl = cfg.mla, cfg.hidden_size, cfg.param_dtype, cfg.num_layers
    kinds = m.kinds(nl)
    if len(kinds) != nl:
        raise ValueError(f"layer_types names {len(kinds)} layers, the "
                         f"model has {nl}")
    out_scale = 1.0 / math.sqrt(2 * nl)

    def dense(k, shape, fan_in, out=False):
        return _dense_init(k, shape, (out_scale if out else 1.0)
                           / math.sqrt(fan_in), pd)

    def attn(k, w: LatentWidths, indexer: bool):
        k = jax.random.split(k, 10)
        nh = w.num_heads
        # what reads a rescaled latent (rms sqrt(hidden / rank)) divides
        # its fan-in by that, so queries, keys and values start at unit
        # variance and attention's scores at about one: sharper scores
        # would be a property of the seed, not of the architecture
        q_in = w.q_lora_rank * (h / w.q_lora_rank if m.lora_rescale else 1)
        kv_in = w.kv_lora_rank * (h / w.kv_lora_rank if m.lora_rescale else 1)
        p = {
            "wq_a": dense(k[0], (h, w.q_lora_rank), h),
            "q_norm": jnp.ones((w.q_lora_rank,), pd),
            "wq_b": dense(k[1], (w.q_lora_rank, nh * w.qk_head_dim), q_in),
            "wkv_a": dense(k[2], (h, w.row_dim), h),
            "kv_norm": jnp.ones((w.kv_lora_rank,), pd),
            "wk_b": dense(k[3], (nh, w.qk_nope_head_dim, w.kv_lora_rank),
                          kv_in),
            "wv_b": dense(k[4], (nh, w.kv_lora_rank, w.v_head_dim), kv_in),
            "wo": dense(k[5], (nh * w.v_head_dim, h), nh * w.v_head_dim,
                        out=True),
        }
        if m.gate:
            p["wg"] = dense(k[6], (h, nh), h)
        if indexer:
            p["idx_wq"] = dense(k[7], (w.q_lora_rank,
                                       m.index_heads * m.index_head_dim),
                                q_in)
            p["idx_wk"] = dense(k[8], (h, m.index_head_dim), h)
            p["idx_k_norm"] = {"scale": jnp.ones((m.index_head_dim,), pd),
                               "bias": jnp.zeros((m.index_head_dim,), pd)}
            p["idx_ww"] = dense(k[9], (h, m.index_heads), h)
        return p

    def swiglu(k, lead, width):
        k = jax.random.split(k, 3)
        return {"wg": dense(k[0], lead + (h, width), h),
                "wi": dense(k[1], lead + (h, width), h),
                "wo": dense(k[2], lead + (width, h), width, out=True)}

    # a normed hidden state x has x . ones = h * c / sqrt(c^2 + r^2), r
    # the rms of the stream's token-specific part: each router element's
    # common part is the wanted mean logit over that, at the last blocks' r
    router_common = (ROUTER_LOGIT_MEAN * ROUTER_LOGIT_SD
                     * math.hypot(EMBED_COMMON, RESIDUAL_RMS)
                     / (h * EMBED_COMMON))

    def moe(k):
        k = jax.random.split(k, 3)
        f = m.moe_intermediate_size
        return dict(swiglu(k[0], (m.experts_held[1],), f),
                    router=(jax.random.normal(k[1], (h, m.n_routed_experts))
                            * (ROUTER_LOGIT_SD / math.sqrt(h))
                            - router_common).astype(pd),
                    bias=jnp.zeros((m.n_routed_experts,), pd),
                    shared=swiglu(k[2], (), f * m.n_shared_experts))

    keys = jax.random.split(key, nl + 2 + 2 * m.mtp_layers)
    groups: Dict[str, list] = {"full": [], "window": [], "mlp": [], "moe": []}
    for i, (is_full, has_experts) in enumerate(kinds):
        ka, kf = jax.random.split(keys[i])
        groups["full" if is_full else "window"].append(
            attn(ka, m.full if is_full else m.window, is_full))
        groups["moe" if has_experts else "mlp"].append(
            moe(kf) if has_experts else swiglu(kf, (), cfg.intermediate_size))
    layers = {name: jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *ps)
              for name, ps in groups.items() if ps}
    layers["ln1"] = {"scale": jnp.ones((nl, h), pd)}
    layers["ln2"] = {"scale": jnp.ones((nl, h), pd)}
    own = jax.random.normal(keys[nl], (cfg.vocab_size, h)) / math.sqrt(h)
    head = dense(keys[nl + 1], (h, cfg.vocab_size), h)
    params = {"layers": layers,
              "final_norm": {"scale": jnp.ones((h,), pd)}}
    if m.mtp_layers:
        if m.mtp_layers != 1:
            raise NotImplementedError(
                f"{m.mtp_layers} multi-token-prediction modules: one is "
                "built (it drafts one token)")
        ka, kf, ke, kp, ks = jax.random.split(keys[nl + 2], 5)
        first_half = jnp.arange(h) < h // 2
        seen = jax.random.uniform(ks, (cfg.vocab_size, 1)) < SUCCESSOR_SHARE
        # the same norm in half the dims
        own = jnp.where(seen == first_half, own, 0.0) \
            * (SUCCESSOR_EMBED_NORM * math.sqrt(2.0))
        successor = jax.random.permutation(kp, cfg.vocab_size)
        head = (head.astype(jnp.float32).T.at[successor].add(
            SUCCESSOR_HEAD_GAIN * own)).T.astype(pd)
        eh = jax.random.normal(ke, (2 * h, h)) / math.sqrt(2 * h)
        params["mtp"] = {
            "enorm": {"scale": jnp.ones((h,), pd)},
            "hnorm": {"scale": jnp.ones((h,), pd)},
            "eh_proj": eh.at[jnp.arange(h // 2), jnp.arange(h // 2)].add(
                1.0).astype(pd),
            "attn_norm": {"scale": jnp.ones((h,), pd)},
            "full": attn(ka, m.full, True),
            "ffn_norm": {"scale": jnp.ones((h,), pd)},
            "moe": jax.tree.map(lambda a: a[None], moe(kf)),
            "norm": {"scale": jnp.ones((h,), pd)},
        }
    params["embed"] = {"tokens": (own + EMBED_COMMON).astype(pd)}
    params["lm_head"] = head
    return params


# a mixed-attention model's seeded routing (init_mixed_params has the
# reason); the logits' spread and mean are the latent models'
MIXED_EMBED_COMMON = 0.5
MIXED_COMMON_NORM = 16.0
MIXED_ROUTER_LOGIT_SD = 8.0


def init_mixed_params(cfg: TransformerConfig, key) -> Params:
    """A mixed-attention model's params (``cfg.mixed``).  Attention and
    the four norms of a block are stacked over EVERY layer
    (``layers/attn``: ``wq``, ``wk``, ``wv``, ``wg`` (the gate), ``wo``,
    and the heads' ``q_norm`` / ``k_norm`` gains ``[L, head_dim]``;
    ``layers/ln1``, ``post_attn``, ``ln2``, ``post_mlp``); where the kinds
    of layer differ in their attention weights
    (``MixedAttentionConfig.attn_by_kind``: KV heads of their own, a
    ``sink`` ``[heads]`` in one kind alone) attention is stacked a kind,
    in layer order, ``layers/attn_full`` and ``layers/attn_window``, as
    the two page pools are.  The leading
    dense feed-forwards under ``layers/mlp`` and the expert layers under
    ``layers/moe`` (router over ALL experts, the selection ``bias``, the
    held experts' weights ``[n, held, ...]``, the ``shared`` expert), as a
    latent model's are.  Seeded normal, ``1 / sqrt(fan_in)``; every gain
    ones (a published "depth-scaled" gain is a trained value's start, and
    every block's output passes a norm here whatever its scale).

    **The routing's initialisation.**  Two things are wanted of seeded
    weights that a trained model has.  (1) The chosen scores lie in the
    sigmoid's LOWER tail, so that the last of a row's experts carries a
    twentieth of the weight and not a quarter: a near-tie between the
    last chosen and the first not chosen, which any rounding upstream
    flips in one row of forty, then moves little, and a comparison with a
    float32 reference reads the arithmetic and not the flips
    (``init_latent_params``, PERF.md section 6 PR 34).  (2) The rows'
    choices are spread evenly over the experts, from every row and at
    every context length (PERF.md section 7, PR 39: a few favoured experts
    made a cell spread 4.3-4.6 % over seeds).  A lower tail needs a
    direction every hidden state shares: each element of the scaled
    embedding has the offset ``MIXED_EMBED_COMMON`` (more at a narrow
    width, so that the offset's norm is ``MIXED_COMMON_NORM`` at the
    least: a router reads it beside the chance part of a row's own part
    along the same direction, which at 64 dims is as large, and a logit
    that lands below -87 is a score of zero in float32 and a weight of 0
    / 0) beside a part of its own of variance one, and each router element the matching negative
    part, sized for the layer's depth (a block adds two normed outputs of
    variance one to the stream), so that the logits have standard
    deviation ``ROUTER_LOGIT_SD`` about a mean ``ROUTER_LOGIT_MEAN``
    standard deviations below zero.  The spread here is
    ``MIXED_ROUTER_LOGIT_SD``, twice the latent models': at their 4 the
    last of four experts still carried 8 % of the weight (0.19 of an
    expert's output after ``route_scale``), and on the chip one weight
    seed in a dozen read 3.9 % against the float32 reference where the
    others read 0.9-1.4 %, one position 14 % off: a swapped expert, with
    every weight through int8 reading 8 % (PERF.md section 6, PR 46); at
    8 the four weights are 0.77, 0.15, 0.055 and 0.026 in the mean
    (counted at the published widths) and a swap at the edge of the
    choice moves a third of what it moved; the readings' tail did not go
    (3.0 % once in nine), so not every outlier is such a swap: section 7.
    What made the latent models' routing
    uneven is that NOTHING ELSE may see that direction: values that share
    a part make attention's output over a long context that part and
    little else, the norm after attention scales it back to one, and every
    later router then adds the same per-expert offset to every row.  So
    every matrix that reads the stream other than a router (``wq``,
    ``wk``, ``wv``, ``wg``, the feed-forwards' ``wg`` and ``wi``) has its
    columns centred (each sums to zero over the hidden dims), as has the
    routers' seeded part: the shared direction shifts all of a row's
    logits alike and does nothing else.  What would still be uneven is
    the router columns' norms (an expert whose column is 1 % longer is
    chosen 5 % more often: 8 % between experts at these widths), so each
    column's seeded part is scaled to the same norm: the experts are then
    alike but for their directions, and over 32,768 seeded rows at the
    published widths their loads spread as a Poisson count does (4.2 %
    against 4.4 %).  The published model reaches the same end with its
    selection ``bias``; the bias-update rule, tried here over a seeded
    batch (sign steps, 8 or 16 rounds, 4,096 to 32,768 rows), chased the
    batch's own noise and left the loads 1.5 to 4 times LESS even than it
    found them, so the ``bias`` is zeros in seeded weights (a trained
    value is not public; the tests seed a non-zero one: it moves the
    choice only, never a weight).  Tried on the chip and taken out
    (PERF.md section 6, PR 46): a head whose column of token ``pi(v)``
    carries the embedding row of ``v``, so that greedy streams walk a long
    cycle instead of the short loop seeded weights fall into; the time a
    token then spread 4.7 % over six weight seeds where the loops' spread
    0.9 % without the farthest run."""
    m, h, pd, nl = cfg.mixed, cfg.hidden_size, cfg.param_dtype, cfg.num_layers
    kinds = m.kinds(nl)
    if len(kinds) != nl:
        raise ValueError(f"layer_types names {len(kinds)} layers, the "
                         f"model has {nl}")
    nh, hd, vd = cfg.num_heads, cfg.dim_per_head, cfg.value_width
    c = max(MIXED_EMBED_COMMON, MIXED_COMMON_NORM / math.sqrt(h))

    def dense(k, shape, fan_in, centred=False):
        w = jax.random.normal(k, shape) / math.sqrt(fan_in)
        if centred:     # over the hidden dims, the axis before the last
            w = w - w.mean(axis=-2, keepdims=True)
        return w.astype(pd)

    def swiglu(k, lead, width):
        k = jax.random.split(k, 3)
        return {"wg": dense(k[0], lead + (h, width), h, True),
                "wi": dense(k[1], lead + (h, width), h, True),
                "wo": dense(k[2], lead + (width, h), width)}

    def attn(key, is_full=True):
        k = jax.random.split(key, 5)
        nkv = cfg.kv_heads if is_full else cfg.window_kv_heads
        p = {"wq": dense(k[0], (h, nh * hd), h, True),
             "wk": dense(k[1], (h, nkv * hd), h, True),
             "wv": dense(k[2], (h, nkv * vd), h, True),
             "wo": dense(k[3], (nh * vd, h), nh * vd)}
        if m.gate:
            p["wg"] = dense(k[4], (h, nh * vd), h, True)
        if m.qk_norm:
            p["q_norm"] = jnp.ones((hd,), pd)
            p["k_norm"] = jnp.ones((hd,), pd)
        if m.sink(is_full):
            # normal(0, 1): zeros would hide a sign error (a trained
            # value is not public)
            p["sink"] = jax.random.normal(jax.random.fold_in(key, 5),
                                          (nh,)).astype(pd)
        return p

    def router(k, layer):
        e = m.n_routed_experts
        # the stream at this block's second norm: the embedding's own
        # part, the offset, and 2 * layer + 1 normed outputs
        own = 2.0 * layer + 2.0
        rms = math.sqrt(own + c * c)
        seeded = jax.random.normal(k, (h, e))
        seeded = seeded - seeded.mean(axis=0, keepdims=True)
        seeded = seeded * lax.rsqrt(jnp.mean(jnp.square(seeded), axis=0,
                                             keepdims=True))
        return (seeded * (MIXED_ROUTER_LOGIT_SD / math.sqrt(h) * rms
                          / math.sqrt(own))
                - ROUTER_LOGIT_MEAN * MIXED_ROUTER_LOGIT_SD * rms / (h * c)
                ).astype(pd)

    def stacked(fn, ks):
        return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0),
                            *[fn(k) for k in ks])

    keys = jax.random.split(key, nl + 2)
    sub = [jax.random.split(keys[i], 4) for i in range(nl)]
    dense_at = [i for i, (_, has_experts) in enumerate(kinds)
                if not has_experts]
    moe_at = [i for i, (_, has_experts) in enumerate(kinds) if has_experts]
    if m.attn_by_kind:
        layers = {name: stacked(partial(attn, is_full=full),
                                [sub[i][0] for i in range(nl)
                                 if kinds[i][0] == full])
                  for name, full in (("attn_full", True),
                                     ("attn_window", False))
                  if any(k[0] == full for k in kinds)}
    else:
        layers = {"attn": stacked(attn, [sub[i][0] for i in range(nl)])}
    if dense_at:
        layers["mlp"] = stacked(
            lambda k: swiglu(k, (), cfg.intermediate_size),
            [sub[i][1] for i in dense_at])
    if moe_at:
        f = m.moe_intermediate_size
        # a layer's held experts at a time, written into their place in
        # the stack (a loop, not a stack of whole layers: at the published
        # widths the stack's copy beside its parts is 14 GB of a chip's 16)
        layers["moe"] = dict(
            lax.map(lambda k: swiglu(k, (m.experts_held[1],), f),
                    jnp.stack([sub[i][1] for i in moe_at])),
            router=jnp.stack([router(sub[i][2], i) for i in moe_at]),
            bias=jnp.zeros((len(moe_at), m.n_routed_experts), pd))
        if m.n_shared_experts:
            layers["moe"]["shared"] = stacked(
                lambda k: swiglu(k, (), f * m.n_shared_experts),
                [sub[i][3] for i in moe_at])
    for name in ("ln1", "ln2") + (("post_attn", "post_mlp")
                                  if m.sandwich_norm else ()):
        layers[name] = {"scale": jnp.ones((nl, h), pd)}
    own = jax.random.normal(keys[nl], (cfg.vocab_size, h))
    return {"embed": {"tokens": ((own + c) / m.embed_multiplier).astype(pd)},
            "layers": layers,
            "final_norm": {"scale": jnp.ones((h,), pd)},
            "lm_head": dense(keys[nl + 1], (h, cfg.vocab_size), h)}


# a one-mixer-a-layer model's seeded routing: what a layer of each kind
# adds to the variance of the stream's own part under init_hybrid_params,
# counted over seeded rows at hidden 64 and 512: a mixer one, an attention
# layer a context's mean of centred values (next to nothing), an expert
# layer the shared expert's 0.3 and, of the routed experts' 1.2, the
# share that is held
HYBRID_OWN_GAIN = {"M": 1.0, "*": 0.05, "E": (0.3, 1.2)}
HYBRID_DOWN_SCALE = 0.5


def init_hybrid_params(cfg: TransformerConfig, key) -> Params:
    """A one-mixer-a-layer model's params (``cfg.hybrid``).  Layers of one
    kind are stacked on axis 0 under the kind's name, in layer order:
    ``layers/ssm`` ``[M layers, ...]`` (:func:`init_ssm_params`' leaves),
    ``layers/attn`` ``[* layers, ...]`` (``wq``, ``wk``, ``wv``, ``wo``)
    and ``layers/moe`` ``[E layers, ...]`` (``router`` over ALL experts,
    the selection ``bias``, the held experts' TWO matrices ``wu`` and
    ``wo``, both ``[n, held, F, H]`` (the up matrix a hidden unit's
    weights a row: ``moe_forward_held`` has the reason), the ``shared``
    expert's ``wi`` ``[n, H, F]`` and ``wo``); ``layers/norm`` is every
    layer's one pre-norm gain ``[L, H]``.
    Seeded normal, ``1 / sqrt(fan_in)``, no bias but the convolution's;
    ``A_log``, ``dt_bias``, ``D``, the convolution and the mixer's gain as
    :func:`init_ssm_params` seeds them.

    The routing is seeded as :func:`init_mixed_params` seeds it, for the
    reasons written there: each embedding element has the offset
    ``MIXED_EMBED_COMMON`` beside a part of its own of variance one, the
    router's columns have a seeded part of zero sum and equal norm and the
    matching negative part, so that the logits have standard deviation
    ``MIXED_ROUTER_LOGIT_SD`` about a mean ``ROUTER_LOGIT_MEAN`` standard
    deviations below zero, and every matrix that READS the stream other
    than a router has its columns centred, so the shared direction moves
    nothing but all of a row's logits alike.  Two things differ.  A block
    has no norm after its mixer, so the variance of the stream's own part
    at a router is counted from ``HYBRID_OWN_GAIN`` a layer before it, and
    the experts' down matrices are seeded at ``HYBRID_DOWN_SCALE / sqrt(
    fan_in)``, so that an expert layer adds to the stream about what a
    mixer adds (at ``1 / sqrt(fan_in)`` six weighted ``relu^2`` experts
    and the shared one added six times a mixer's variance, layer after
    layer, and the mixers' part of the logits was a few percent).
    And ``relu^2`` is never negative: an expert's hidden units have a mean
    of a half, which through ``W_down`` would be ONE vector added to
    every row, a per-expert offset on every later router's logits for
    every row alike (the uneven loads PERF.md section 7 tells of); so
    the matrices that WRITE the stream (``wo``, ``out_proj``) are centred
    over their input dims and pass a uniform mean on as nothing.  The
    selection ``bias`` is zeros."""
    m, s, h, pd = cfg.hybrid, cfg.ssm, cfg.hidden_size, cfg.param_dtype
    nl = cfg.num_layers
    kinds = m.kinds(nl)
    if len(kinds) != nl or set(kinds) - set("M*E") or s is None:
        raise ValueError(f"pattern {m.pattern!r} names {len(kinds)} layers "
                         f"of kinds M, * and E (the model has {nl}), and "
                         "the M layers' sizes are cfg.ssm")
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
    c = max(MIXED_EMBED_COMMON, MIXED_COMMON_NORM / math.sqrt(h))

    def dense(k, shape, fan_in):
        """Centred over the axis before the last: a matrix that reads
        the stream sees nothing of its shared direction, one that writes
        it passes no uniform mean on."""
        w = jax.random.normal(k, shape) / math.sqrt(fan_in)
        return (w - w.mean(axis=-2, keepdims=True)).astype(pd)

    def relu2(k, lead, width):
        k = jax.random.split(k)
        up = dense(k[0], lead + (h, width), h)
        down = dense(k[1], lead + (width, h), width / HYBRID_DOWN_SCALE ** 2)
        return ({"wu": up.swapaxes(-1, -2), "wo": down} if lead
                else {"wi": up, "wo": down})

    def mixer(k):
        k = jax.random.split(k, 3)
        return dict(init_ssm_params(cfg, k[0]),
                    in_proj=dense(k[1], (h, s.proj_dim), h),
                    out_proj=dense(k[2], (s.d_ssm, h), s.d_ssm))

    def attn(k):
        k = jax.random.split(k, 4)
        return {"wq": dense(k[0], (h, nh * hd), h),
                "wk": dense(k[1], (h, nkv * hd), h),
                "wv": dense(k[2], (h, nkv * hd), h),
                "wo": dense(k[3], (nh * hd, h), nh * hd)}

    shared_gain, routed_gain = HYBRID_OWN_GAIN["E"]
    gain = dict(HYBRID_OWN_GAIN, E=shared_gain + routed_gain
                * m.experts_held[1] / m.n_routed_experts)

    def router(k, layer):
        # the stream's own part at this layer's norm: the embedding's
        # and what each layer before it added
        own = 1.0 + sum(gain[kind] for kind in kinds[:layer])
        rms = math.sqrt(own + c * c)
        seeded = jax.random.normal(k, (h, m.n_routed_experts))
        seeded = seeded - seeded.mean(axis=0, keepdims=True)
        seeded = seeded * lax.rsqrt(jnp.mean(jnp.square(seeded), axis=0,
                                             keepdims=True))
        return (seeded * (MIXED_ROUTER_LOGIT_SD / math.sqrt(h) * rms
                          / math.sqrt(own))
                - ROUTER_LOGIT_MEAN * MIXED_ROUTER_LOGIT_SD * rms / (h * c)
                ).astype(pd)

    def stacked(fn, ks):
        return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0),
                            *[fn(k) for k in ks])

    keys = jax.random.split(key, nl + 2)
    sub = [jax.random.split(keys[i], 3) for i in range(nl)]
    at = {kind: [i for i, k in enumerate(kinds) if k == kind]
          for kind in "M*E"}
    layers = {"norm": {"scale": jnp.ones((nl, h), pd)}}
    if at["M"]:
        layers["ssm"] = stacked(mixer, [sub[i][0] for i in at["M"]])
    if at["*"]:
        layers["attn"] = stacked(attn, [sub[i][0] for i in at["*"]])
    if at["E"]:
        # a layer's held experts at a time (init_mixed_params: a stack of
        # whole layers would be copied beside its parts)
        layers["moe"] = dict(
            lax.map(lambda k: relu2(k, (m.experts_held[1],),
                                    m.moe_intermediate_size),
                    jnp.stack([sub[i][0] for i in at["E"]])),
            router=jnp.stack([router(sub[i][1], i) for i in at["E"]]),
            bias=jnp.zeros((len(at["E"]), m.n_routed_experts), pd),
            shared=stacked(lambda k: relu2(k, (), m.shared_intermediate_size),
                           [sub[i][2] for i in at["E"]]))
    own = jax.random.normal(keys[nl], (cfg.vocab_size, h))
    return {"embed": {"tokens": (own + c).astype(pd)},
            "layers": layers,
            "final_norm": {"scale": jnp.ones((h,), pd)},
            "lm_head": (jax.random.normal(keys[nl + 1], (h, cfg.vocab_size))
                        / math.sqrt(h)).astype(pd)}


def init_params(cfg: TransformerConfig, key) -> Params:
    """Full model params with per-layer params stacked on axis 0."""
    # nl+5 keys: rows are counter-derived, so rows nl..nl+2 keep the same
    # values the old nl+3 split produced (init stays bit-stable for
    # existing archs); the encoder-only params use the two new rows.
    if cfg.mla is not None:
        return init_latent_params(cfg, key)
    if cfg.mixed is not None:
        return init_mixed_params(cfg, key)
    if cfg.hybrid is not None:
        return init_hybrid_params(cfg, key)
    nl = cfg.num_layers
    keys = jax.random.split(key, nl + 5)
    scale = 1.0 / math.sqrt(cfg.hidden_size)
    pd = cfg.param_dtype
    h = cfg.hidden_size

    layer_list = [init_layer_params(cfg, keys[i]) for i in range(nl)]
    layers = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *layer_list)

    params: Params = {
        "embed": {"tokens": _dense_init(keys[nl], (cfg.vocab_size, h), scale, pd)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((h,), pd)},
    }
    if cfg.norm == "layernorm":
        params["final_norm"]["bias"] = jnp.zeros((h,), pd)
    if cfg.has_learned_positions:
        params["embed"]["positions"] = _dense_init(
            keys[nl + 1], (cfg.max_seq_len, h), scale, pd)
    if cfg.type_vocab_size:
        params["embed"]["token_types"] = _dense_init(
            keys[nl + 3], (cfg.type_vocab_size, h), scale, pd)
    if cfg.embed_norm:
        params["embed"]["norm"] = {"scale": jnp.ones((h,), pd),
                                   "bias": jnp.zeros((h,), pd)}
    if cfg.mlm_head:
        # BERT MLM head (HF BertLMPredictionHead): transform dense + LN,
        # decoder tied to the token embeddings, per-vocab output bias
        params["mlm_head"] = {
            "w": _dense_init(keys[nl + 4], (h, h), scale, pd),
            "b": jnp.zeros((h,), pd),
            "ln": {"scale": jnp.ones((h,), pd), "bias": jnp.zeros((h,), pd)},
            "bias": jnp.zeros((cfg.vocab_size,), pd),
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(keys[nl + 2], (h, cfg.vocab_size), scale, pd)
    if cfg.lm_head_bias and not cfg.mlm_head:
        params["lm_head_bias"] = jnp.zeros((cfg.vocab_size,), pd)
    return params


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ----------------------------------------------------------------------
# Forward pieces
# ----------------------------------------------------------------------
_DEFAULT_FP32_OPS = ("layernorm", "softmax", "rope", "router", "loss")


def op_fp32(cfg, op: str) -> bool:
    """Whether op class ``op`` runs in fp32 under the autocast policy.
    getattr: callers (moe/sharded_moe) pass duck-typed configs in tests."""
    ops = getattr(cfg, "fp32_ops", None)
    return op in (ops if ops is not None else _DEFAULT_FP32_OPS)


def _module_dtype(cfg: TransformerConfig, name: str, default_dt):
    """Compute dtype for module class ``name``: safe-listed (or no list →
    everything) runs in the low dtype, the rest is promoted to fp32."""
    if cfg.autocast_safe_modules is None:
        return default_dt
    if any(pat in name for pat in cfg.autocast_safe_modules):
        return default_dt
    return jnp.float32


def _norm(x, p, cfg: TransformerConfig):
    dt = x.dtype
    ct = jnp.float32 if op_fp32(cfg, "layernorm") else dt
    xc = x.astype(ct)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
        out = xc * lax.rsqrt(var + cfg.layernorm_eps) * p["scale"].astype(ct)
    else:
        mean = jnp.mean(xc, axis=-1, keepdims=True)
        var = jnp.var(xc, axis=-1, keepdims=True)
        out = (xc - mean) * lax.rsqrt(var + cfg.layernorm_eps)
        out = out * p["scale"].astype(ct) + p["bias"].astype(ct)
    return out.astype(dt)


def _rope(q, k, positions, cfg: TransformerConfig):
    """Rotary embeddings (Llama). q,k: [B, S, H, D].  ``rotary_pct`` < 1
    rotates only the leading fraction of the head dim (Phi partial rotary,
    ref inference/v2 phi containers)."""
    d = cfg.dim_per_head
    rot_d = d if cfg.rotary_pct >= 1.0 else max(2, int(d * cfg.rotary_pct) // 2 * 2)
    freqs = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot_d, 2, dtype=jnp.float32) / rot_d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, rot_d/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]

    ct = jnp.float32 if op_fp32(cfg, "rope") else q.dtype
    cos, sin = cos.astype(ct), sin.astype(ct)

    def rot(x):
        xf = x.astype(ct)
        xr, x_pass = xf[..., :rot_d], xf[..., rot_d:]
        if cfg.rope_interleaved:
            # GPT-J "rotate every two": dims pair as (2i, 2i+1)
            x1, x2 = xr[..., 0::2], xr[..., 1::2]
            xr = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).reshape(xr.shape)
        else:
            x1, x2 = jnp.split(xr, 2, axis=-1)
            xr = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                                 axis=-1)
        return jnp.concatenate([xr, x_pass], axis=-1)

    return rot(q).astype(q.dtype), rot(k).astype(k.dtype)


def alibi_slopes(nh: int) -> jnp.ndarray:
    """ALiBi head slopes (Press et al.; HF build_alibi_tensor semantics,
    including the non-power-of-two head interleave)."""
    cp2 = 2 ** math.floor(math.log2(nh))
    base = 2.0 ** (-(2.0 ** -(math.log2(cp2) - 3)))
    slopes = [base ** (i + 1) for i in range(cp2)]
    if cp2 != nh:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * cp2) - 3)))
        slopes += [extra_base ** (i + 1)
                   for i in range(0, 2 * (nh - cp2), 2)]
    return jnp.asarray(slopes, jnp.float32)


def _attention_scores(q, k, v, cfg: TransformerConfig, segment_pos=None,
                      attention_mask=None):
    """MHA/GQA over [B, S, H, D] via XLA einsums (MXU-friendly) — causal
    or bidirectional per ``cfg.causal``.  ``attention_mask``: [B, S] 1 =
    attend / 0 = padding key (HF convention).  Pallas flash attention is
    selected by the engine when attn_impl allows."""
    b, s, nh, d = q.shape
    nkv = k.shape[2]
    if nkv != nh:  # GQA: repeat kv heads
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = cfg.attn_scale if cfg.attn_scale is not None else 1 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if cfg.use_alibi:
        # Bloom ALiBi: slope[h] · key_position added to the scores (HF's
        # key-position form — per-query-row softmax shift makes it
        # equivalent to the distance form)
        if attention_mask is not None:
            # HF build_alibi_tensor derives key positions from the padding
            # mask (cumsum - 1 over the kept keys), so LEFT-padded batches
            # bias by the token's position within the real sequence, not
            # its slot index.  Padding slots get position 0; their scores
            # are masked below anyway.
            am = attention_mask.astype(jnp.float32)
            kpos = (jnp.cumsum(am, axis=-1) - 1.0) * am      # [B, S]
            scores = scores + (alibi_slopes(nh)[None, :, None, None]
                               * kpos[:, None, None, :]).astype(scores.dtype)
        else:
            kpos = jnp.arange(s, dtype=jnp.float32)
            scores = scores + (alibi_slopes(nh)[:, None, None]
                               * kpos[None, None, :]).astype(scores.dtype)
    if cfg.causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        if cfg.sliding_window:
            # Mistral sliding-window: key within the last `window` positions
            qpos = lax.broadcasted_iota(jnp.int32, (s, s), 0)
            kpos = lax.broadcasted_iota(jnp.int32, (s, s), 1)
            mask = mask & (qpos - kpos < cfg.sliding_window)
        mask = mask[None, None, :, :]
    else:
        mask = jnp.ones((1, 1, s, s), dtype=bool)
    if attention_mask is not None:
        mask = mask & attention_mask[:, None, None, :].astype(bool)
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    ct = jnp.float32 if op_fp32(cfg, "softmax") else scores.dtype
    probs = jax.nn.softmax(scores.astype(ct), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _sparse_attn(q, k, v, cfg: TransformerConfig):
    """Block-sparse attention path (ref ops/sparse_attention configs);
    causal composes with the layout."""
    from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig,
                                                    BSLongformerSparsityConfig,
                                                    DenseSparsityConfig,
                                                    FixedSparsityConfig,
                                                    VariableSparsityConfig,
                                                    sparse_attention)

    sc = dict(cfg.sparse_attention or {})
    mode = sc.pop("mode", "fixed")
    cls = {"fixed": FixedSparsityConfig, "bigbird": BigBirdSparsityConfig,
           "bslongformer": BSLongformerSparsityConfig,
           "variable": VariableSparsityConfig,
           "dense": DenseSparsityConfig}[mode]
    sparsity = cls(num_heads=q.shape[2], **sc)
    return sparse_attention(q, k, v, sparsity, causal=cfg.causal)


def _attn_block(x, p, positions, cfg: TransformerConfig,
                attention_mask=None):
    b, s, h = x.shape
    nh, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
    dt0 = x.dtype  # residual-stream dtype: restored at the block boundary
    dt = _module_dtype(cfg, "attn", dt0)
    x = x.astype(dt)

    def proj(w, b_, out_dim):
        y = x @ w.astype(dt)
        if b_ is not None:
            y = y + b_.astype(dt)
        return y

    q = proj(p["wq"], p.get("bq"), nh * d).reshape(b, s, nh, d)
    k = proj(p["wk"], p.get("bk"), nkv * d).reshape(b, s, nkv, d)
    v = proj(p["wv"], p.get("bv"), nkv * d).reshape(b, s, nkv, d)
    if cfg.use_rope:
        q, k = _rope(q, k, positions, cfg)

    from deepspeed_tpu.parallel.topology import get_topology

    topo = get_topology()
    if cfg.seq_impl not in ("ulysses", "ring"):
        raise ValueError(f"seq_impl={cfg.seq_impl!r}: expected 'ulysses' "
                         "or 'ring'")
    if (topo is not None and topo.sp_size > 1 and cfg.seq_impl == "ring"):
        # Ring attention: K/V blocks rotate the seq ring (nearest-
        # neighbour ppermute + online softmax) — no heads % sp
        # requirement, unlike the Ulysses all-to-all below.
        if attention_mask is not None:
            raise NotImplementedError(
                "attention_mask + ring sequence parallelism not supported")
        if cfg.use_alibi:
            raise NotImplementedError(
                "alibi + ring sequence parallelism not supported (the "
                "ring hop has no score-bias lane yet)")
        if cfg.attn_impl == "sparse":
            raise NotImplementedError(
                "attn_impl='sparse' + ring sequence parallelism not "
                "supported (dense ring hops would silently replace the "
                "block-sparse layout's semantics)")
        from deepspeed_tpu.sequence.ring import ring_attention

        out = ring_attention(q, k, v, topo, causal=cfg.causal,
                             sm_scale=cfg.attn_scale,
                             window=cfg.sliding_window or None,
                             placement=cfg.ring_placement,
                             interleave=cfg.ring_interleave,
                             wire_dtype=cfg.ring_wire_dtype)
        out = out.reshape(b, s, nh * d)
        out = out @ p["wo"].astype(dt)
        if p.get("bo") is not None:
            out = out + p["bo"].astype(dt)
        return out.astype(dt0)

    # Ulysses SP: re-shard seq-sharded q/k/v to head-sharded (XLA lowers the
    # layout switch to all-to-all over ICI; ref sequence/layer.py:331).
    from deepspeed_tpu.sequence.layer import (ulysses_output_constraint,
                                              ulysses_qkv_constraint)

    q, k, v = ulysses_qkv_constraint(q, k, v)

    if attention_mask is not None or cfg.use_alibi:
        if cfg.attn_impl == "sparse":
            raise NotImplementedError(
                "attention_mask/alibi + attn_impl='sparse' not supported "
                "(the padding mask would silently replace the block-sparse "
                "layout's semantics)")
        # key-padding masks and the ALiBi score bias thread only through
        # the XLA scores path (the flash kernel has neither lane; padded
        # serving is the encoder case, alibi the bloom family)
        out = _attention_scores(q, k, v, cfg, attention_mask=attention_mask)
    elif cfg.attn_impl == "sparse":
        out = _sparse_attn(q, k, v, cfg)
    elif cfg.attn_impl in ("pallas_flash", "auto"):
        # flash_attention dispatches: Pallas kernel on TPU (tiled online
        # softmax, no [S,S] materialisation; sliding windows skip dead
        # tiles at the grid level), equivalent XLA math elsewhere.
        from deepspeed_tpu.ops.flash_attention import flash_attention

        out = flash_attention(q, k, v, causal=cfg.causal,
                              sm_scale=cfg.attn_scale,
                              window=cfg.sliding_window or None)
    else:
        out = _attention_scores(q, k, v, cfg)
    out = ulysses_output_constraint(out.reshape(b, s, nh * d))
    out = out @ p["wo"].astype(dt)
    if p.get("bo") is not None:
        out = out + p["bo"].astype(dt)
    return out.astype(dt0)


def _mlp_block(x, p, cfg: TransformerConfig):
    dt0 = x.dtype
    dt = _module_dtype(cfg, "mlp", dt0)
    x = x.astype(dt)
    if cfg.fused_gather_matmul and cfg.fused_gather_axes:
        # ZeRO-3 fused gather-matmul (step_schedule.fused_gather_matmul;
        # ops/pallas/gather_matmul.py): explicit shard_map over the fsdp
        # axes — the following matmul's param all-gather issues inside
        # the current matmul's epilogue region instead of wherever GSPMD
        # scheduled it.  The engine verified the weight sharding pattern
        # before setting the flag; the tiny output bias stays on the
        # implicit path (bi rides the fused region — it must add before
        # the activation).
        from deepspeed_tpu.ops.pallas.gather_matmul import fused_gather_mlp

        y = fused_gather_mlp(x, p, cfg)
        if p.get("bo") is not None:
            y = y + p["bo"].astype(dt)
        return y.astype(dt0)
    if cfg.activation == "swiglu":
        if cfg.ssm is not None:
            # falcon_h1 muP: inside the gate's activation, and on the way out
            m_gate, m_down = cfg.ssm.mlp_multipliers
            gate = jax.nn.silu((x @ p["wg"].astype(dt)) * m_gate)
            up = x @ p["wi"].astype(dt)
            return (((gate * up) @ p["wo"].astype(dt)) * m_down).astype(dt0)
        gate = jax.nn.silu(x @ p["wg"].astype(dt))
        up = x @ p["wi"].astype(dt)
        return ((gate * up) @ p["wo"].astype(dt)).astype(dt0)
    y = x @ p["wi"].astype(dt)
    if p.get("bi") is not None:
        y = y + p["bi"].astype(dt)
    # "gelu_exact" = erf gelu (HF BERT's hidden_act="gelu"); "gelu" keeps
    # the tanh approximation the decoder families use
    y = jax.nn.relu(y) if cfg.activation == "relu" \
        else jax.nn.gelu(y, approximate=cfg.activation != "gelu_exact")
    y = y @ p["wo"].astype(dt)
    if p.get("bo") is not None:
        y = y + p["bo"].astype(dt)
    return y.astype(dt0)


def _moe_block(x, p, cfg: TransformerConfig, allow_ep: bool = True,
               noise_key=None):
    """MoE block used inside the scan.  With an expert mesh axis of size
    > 1 the explicit shard_map + all_to_all expert-parallel path runs
    (deepspeed_tpu/moe/sharded_moe.moe_forward_ep — the reference's
    `_AllToAll` dispatch on ICI); otherwise the single-group path.

    ``allow_ep=False`` is passed from ``lax.cond`` call sites: a shard_map
    collective inside a cond branch crashes XLA's backward pass, so traced
    MoE-vs-dense selection keeps the auto-partitioned formulation (the
    grouped scan in :func:`forward` makes the selection static precisely
    so the EP path applies on aligned configs)."""
    from deepspeed_tpu.moe.sharded_moe import moe_forward, moe_forward_ep
    from deepspeed_tpu.parallel.topology import get_topology

    topo = get_topology()
    if allow_ep and topo is not None and topo.ep_size > 1:
        return moe_forward_ep(x, p, cfg, topo, noise_key=noise_key)
    return moe_forward(x, p, cfg, noise_key=noise_key)


def _select_ffn(h, layer_params, cfg: TransformerConfig, layer_is_moe,
                noise_key=None):
    """MoE-vs-dense FFN selection on normed input ``h`` → (y, aux).

    A static ``layer_is_moe`` keeps the choice out of the compiled graph
    (and lets the expert-parallel shard_map path apply); a traced one
    lowers to ``lax.cond`` with the auto-partitioned MoE (a shard_map
    collective under cond crashes XLA backward)."""
    def dense_branch(h):
        return _mlp_block(h, layer_params["mlp"], cfg), jnp.zeros((), jnp.float32)

    if "moe" not in layer_params:
        return dense_branch(h)
    if isinstance(layer_is_moe, bool):
        return (_moe_block(h, layer_params["moe"], cfg, noise_key=noise_key)
                if layer_is_moe else dense_branch(h))

    def moe_branch(h):
        return _moe_block(h, layer_params["moe"], cfg, allow_ep=False,
                          noise_key=noise_key)

    return lax.cond(layer_is_moe, moe_branch, dense_branch, h)


def _dropout(x, rate: float, key):
    """Inverted dropout; identity when no key is threaded (eval/serve)."""
    if key is None or rate <= 0.0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def transformer_layer(x, layer_params, positions, cfg: TransformerConfig,
                      layer_is_moe=False, dropout_key=None,
                      attention_mask=None):
    """One transformer block (pre- or post-norm). Returns (x, moe_aux_loss).

    ``layer_is_moe`` may be a traced bool (layer index inside a scan): the
    MoE-vs-dense choice then lowers to ``lax.cond``, which is how the
    reference's per-layer MoE placement (PR-MoE, moe_layer_freq) maps onto a
    uniform scan-over-layers body.  ``dropout_key``: this layer's PRNG key
    for residual dropout (None → off).  ``attention_mask``: [B, S] key
    padding mask (encoder serving).
    """
    refuse_ssm(cfg, "transformer_layer()")
    dk = (lambda i: jax.random.fold_in(dropout_key, i)) \
        if dropout_key is not None else (lambda i: None)
    if cfg.parallel_block:
        # Falcon/Phi residual form: shared (or, with parallel_norms, per-
        # branch) input norms feed attention and MLP in parallel (ref
        # falcon/phi v2 containers).
        n = _norm(x, layer_params["ln1"], cfg)
        n_mlp = _norm(x, layer_params["ln2"], cfg) if cfg.parallel_norms else n
        attn_out = _attn_block(n, layer_params["attn"], positions, cfg,
                               attention_mask=attention_mask)
        y, aux = _select_ffn(n_mlp, layer_params, cfg, layer_is_moe,
                             noise_key=dk(2))
        return x + _dropout(attn_out, cfg.dropout, dk(0)) \
            + _dropout(y, cfg.dropout, dk(1)), aux
    if cfg.norm_position == "post":
        # BERT-class post-LN (HF BertLayer): residual add THEN LayerNorm —
        # ln1 is attention.output.LayerNorm, ln2 is output.LayerNorm
        attn_out = _attn_block(x, layer_params["attn"], positions, cfg,
                               attention_mask=attention_mask)
        x = _norm(x + _dropout(attn_out, cfg.dropout, dk(0)),
                  layer_params["ln1"], cfg)
        y, aux = _select_ffn(x, layer_params, cfg, layer_is_moe,
                             noise_key=dk(2))
        return _norm(x + _dropout(y, cfg.dropout, dk(1)),
                     layer_params["ln2"], cfg), aux
    attn_out = _attn_block(_norm(x, layer_params["ln1"], cfg),
                           layer_params["attn"], positions, cfg,
                           attention_mask=attention_mask)
    x = x + _dropout(attn_out, cfg.dropout, dk(0))
    h = _norm(x, layer_params["ln2"], cfg)
    y, aux = _select_ffn(h, layer_params, cfg, layer_is_moe,
                         noise_key=dk(2))
    return x + _dropout(y, cfg.dropout, dk(1)), aux


_REMAT_POLICIES = {
    "none": None,
    "full": None,
    "nothing_saveable": "nothing_saveable",
    "dots_saveable": "dots_saveable",
    # dots + the repo flash kernel's named residuals (flash_out/flash_lse):
    # the backward then never re-runs the attention forward kernel.
    "dots_flash_saveable": "dots_flash_saveable",
    # ONLY the flash residuals: at long sequence the per-layer matmul
    # outputs dots_saveable keeps are O(S·ffn) and dominate HBM (seq 32k:
    # ~640MB/layer); saving just flash_out/flash_lse keeps the backward
    # from re-running the attention kernel while everything else remats.
    "flash_saveable": "flash_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
    # CPU activation checkpointing (ref checkpointing.py:474): matmul
    # outputs are saved to pinned host memory instead of rematerialised —
    # trades PCIe/DMA bandwidth for recompute, like the reference's
    # cpu_checkpointing flag.
    "offload_dots": "offload_dot_with_no_batch_dims",
}


def _maybe_remat(fn, cfg: TransformerConfig):
    if cfg.remat_policy in ("none",):
        return fn
    policy = None
    name = _REMAT_POLICIES.get(cfg.remat_policy)
    if name == "offload_dot_with_no_batch_dims":
        # factory: activations saved to pinned host instead of recomputed
        policy = jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
    elif name == "dots_flash_saveable":
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"))
    elif name == "flash_saveable":
        policy = jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse")
    elif name:
        policy = getattr(jax.checkpoint_policies, name)
    return jax.checkpoint(fn, policy=policy, prevent_cse=False)


def make_pipeline_stage_fn(cfg: TransformerConfig, topo):
    """Per-stage layer applier for the SPMD pipeline: scans this stage's
    ``L/pp`` stacked layers, returns ``(h, aux)``.

    MoE placement must be static inside the pipe shard_map (the stage
    index is a traced ``axis_index``, so a global-layer-index predicate
    would put the MoE collective under a traced cond — see
    :func:`_select_ffn`): with ``layers_per_stage % moe_layer_freq == 0``
    every stage has the same local pattern — groups of f layers whose last
    member is MoE.  Ref: MoE+PP composition, utils/groups.py:384.
    """
    pp = topo.pp_size
    if cfg.num_layers % pp:
        raise ValueError(f"num_layers={cfg.num_layers} not divisible by "
                         f"pipeline stages ({pp})")
    if cfg.alt_window:
        raise NotImplementedError(
            "alt_window (GPT-Neo alternating local attention) + pipeline "
            "parallelism not supported (stage fns scan a uniform body)")
    lp_count = cfg.num_layers // pp
    f = max(1, cfg.moe_layer_freq) if cfg.is_moe else 1
    if cfg.is_moe and lp_count % f != 0:
        raise NotImplementedError(
            f"MoE + pipeline requires layers_per_stage ({lp_count}) "
            f"divisible by moe_layer_freq ({f}) so expert placement is "
            "static per stage")

    def stage_fn(stage_params, h, extras_mb):
        # extras carry (positions, per-microbatch dropout key rows) when
        # training threads randomness — the key rides the same per-
        # microbatch slicing as positions, so the 1F1B backward tick
        # replays the identical mask (remat-bit-exact, like the dense
        # path's keyed dropout).  Bare positions = no randomness.
        pos_mb, keys_mb = (extras_mb if isinstance(extras_mb, tuple)
                           else (extras_mb, None))
        mb_key = keys_mb[0] if keys_mb is not None else None
        from deepspeed_tpu.parallel.topology import PIPE_AXIS
        stage0 = lax.axis_index(PIPE_AXIS) * lp_count

        def layer_key(li):
            # fold the GLOBAL layer index so stages draw distinct masks,
            # mirroring the dense path's fold_in(key, layer_idx)
            return jax.random.fold_in(mb_key, stage0 + li) \
                if mb_key is not None else None

        zero = jnp.zeros((), jnp.float32)
        if f > 1:
            steps = lp_count // f

            def body(carry, xs):
                h, aux_acc = carry
                glp, g = xs
                for j in range(f):
                    lp = jax.tree.map(lambda p, j=j: p[j], glp)
                    h, aux = transformer_layer(h, lp, pos_mb, cfg,
                                               layer_is_moe=(j == f - 1),
                                               dropout_key=layer_key(g * f + j))
                    aux_acc = aux_acc + aux
                return (h, aux_acc), None

            body = _maybe_remat(body, cfg)
            grouped = jax.tree.map(
                lambda p: p.reshape((steps, f) + p.shape[1:]), stage_params)
            (h, aux), _ = lax.scan(body, (h, zero),
                                   (grouped, jnp.arange(steps)))
        else:
            def body(carry, xs):
                h, aux_acc = carry
                lp, li = xs
                h, aux = transformer_layer(h, lp, pos_mb, cfg,
                                           layer_is_moe=cfg.is_moe,
                                           dropout_key=layer_key(li))
                return (h, aux_acc + aux), None

            body = _maybe_remat(body, cfg)
            (h, aux), _ = lax.scan(body, (h, zero),
                                   (stage_params, jnp.arange(lp_count)))
        return h, aux

    return stage_fn


def _pipeline_key_rows(dropout_key, b: int, n_micro: int):
    """Expand a per-step PRNG key into per-example rows [B, 2] where every
    row of microbatch ``m`` holds ``fold_in(step_key, m)`` — the shape the
    pipeline's per-microbatch extras slicing expects (row 0 of a microbatch
    slice is its key)."""
    mb = b // n_micro
    mb_keys = jax.vmap(lambda m: jax.random.fold_in(dropout_key, m))(
        jnp.arange(n_micro))
    return jnp.repeat(mb_keys, mb, axis=0)


def forward(params: Params, input_ids, cfg: TransformerConfig,
            positions=None, pld_theta=None,
            return_hidden: bool = False, token_embeds=None,
            dropout_key=None, token_type_ids=None,
            attention_mask=None) -> jnp.ndarray:
    """Token ids [B, S] → logits [B, S, V]. lax.scan over stacked layers.
    ``pld_theta``: progressive-layer-drop keep prob (traced scalar or None).
    ``return_hidden``: final-norm hidden states instead of logits (tiled
    loss path).  ``dropout_key``: per-step PRNG key enabling
    ``cfg.dropout`` (None → dropout off, the eval/serve contract).
    ``token_type_ids``/``attention_mask``: encoder (BERT-class) segment
    ids and [B, S] key-padding mask."""
    refuse_ssm(cfg, "forward()")
    b, s = input_ids.shape
    dt = cfg.dtype
    if positions is None:
        pos_row = jnp.arange(s, dtype=jnp.int32)
        if cfg.seq_impl == "ring" and cfg.ring_placement == "striped":
            from deepspeed_tpu.parallel.topology import get_topology as _gt
            from deepspeed_tpu.sequence.ring import ring_position_map

            topo_ = _gt()
            if topo_ is not None and topo_.sp_size > 1:
                # striped ring: the engine feeds stripe-permuted ids, so
                # slot j of shard r holds token r + sp*j — positions must
                # follow (RoPE/learned embeddings stay exact)
                pos_row = ring_position_map(s, topo_.sp_size, "striped")
        positions = jnp.broadcast_to(pos_row[None, :], (b, s))
    if dropout_key is not None and cfg.param_stream:
        raise NotImplementedError(
            "dropout / noisy MoE gating + param streaming not supported "
            "(the streamed scan's custom VJP does not thread per-layer "
            "keys)")
    if attention_mask is not None and cfg.param_stream:
        raise NotImplementedError(
            "attention_mask + param streaming not supported (the streamed "
            "scan does not thread the mask)")
    if attention_mask is not None and 0 < cfg.ltd_kept < s:
        raise NotImplementedError(
            "attention_mask + random-LTD not supported (the LTD band's "
            "reduced token subset would need the mask gathered by the "
            "kept indices)")

    x = _embed(params, input_ids, positions, cfg, token_embeds,
               token_type_ids=token_type_ids)
    if dropout_key is not None and cfg.dropout > 0:
        x = _dropout(x, cfg.dropout, jax.random.fold_in(dropout_key, 10_000))

    moe_every = max(1, cfg.moe_layer_freq)

    from deepspeed_tpu.parallel.topology import get_topology

    topo = get_topology()
    moe_aux = jnp.zeros((), jnp.float32)
    if topo is not None and topo.pp_size > 1:
        # Pipeline path: layers circulate microbatches over the "pipe" axis
        # (ref runtime/pipe/engine.py TrainSchedule → spmd_pipeline here).
        if pld_theta is not None:
            raise NotImplementedError(
                "progressive layer drop + pipeline parallelism not supported")
        if 0 < cfg.ltd_kept < s:
            raise NotImplementedError(
                "random-LTD + pipeline parallelism not supported")
        if cfg.param_stream:
            raise NotImplementedError(
                "param streaming + pipeline parallelism not supported "
                "(the pipe axis already partitions layers pp-ways)")
        if attention_mask is not None:
            raise NotImplementedError(
                "attention_mask + pipeline parallelism not supported "
                "(masks do not ride the pipeline extras yet)")
        from deepspeed_tpu.parallel.pipeline import spmd_pipeline

        stage_fn = make_pipeline_stage_fn(cfg, topo)
        n_micro = cfg.pipeline_microbatches or topo.pp_size
        extras = positions
        if dropout_key is not None:
            # per-microbatch keys ride the extras so every stage/layer/
            # microbatch draws a distinct, replay-stable mask
            extras = (positions, _pipeline_key_rows(dropout_key, b, n_micro))
        x, moe_aux = spmd_pipeline(stage_fn, params["layers"], x, topo=topo,
                                   n_micro=n_micro, extras=extras)
    else:
        def scan_segment(x, pos, layers_slice, idx0, n_layers):
            """Scan a contiguous slice of the stacked layers.

            MoE placement is kept **static** so the expert-parallel
            shard_map path applies: with moe_layer_freq f, the f-aligned
            middle of the segment scans *groups* of f layers whose last
            member is statically MoE (no lax.cond in the scan body — a
            shard_map collective under a traced cond crashes XLA
            backward), and the unaligned head/tail layers (e.g. where a
            random-LTD band cuts through a group) run unrolled with their
            static global indices.
            """
            if cfg.alt_window:
                # GPT-Neo alternating global/local attention: scan layer
                # PAIRS so each member's window is STATIC (even global
                # index → global, odd → cfg.sliding_window)
                if cfg.is_moe:
                    raise NotImplementedError(
                        "alt_window + MoE not supported")
                f = 2
            else:
                f = moe_every if cfg.is_moe else 1
            if n_layers == 0:
                return x, jnp.zeros((), jnp.float32)

            def member_cfg(parity: int):
                """Per-layer static config: alt_window strips the local
                window from even global indices."""
                if not cfg.alt_window or parity % 2:
                    return cfg
                return cfg.replace(sliding_window=None)

            def apply_layer(h, aux_acc, lp, layer_idx, is_moe_layer,
                            lcfg=cfg):
                # keys serve dropout AND noisy MoE gating — thread whenever
                # one is present (each consumer no-ops when its rate/policy
                # is off)
                lk = jax.random.fold_in(dropout_key, layer_idx) \
                    if dropout_key is not None else None
                h2, aux = transformer_layer(h, lp, pos, lcfg,
                                            layer_is_moe=is_moe_layer,
                                            dropout_key=lk,
                                            attention_mask=attention_mask)
                if pld_theta is not None:
                    # progressive layer drop (ref progressive_layer_drop.py
                    # + stochastic depth): deeper layers drop more; batch
                    # content seeds the per-step coin so the step stays a
                    # single compile.
                    key = jax.random.fold_in(
                        jax.random.PRNGKey(17),
                        (jnp.sum(input_ids) % 100003).astype(jnp.int32)
                        * 1000 + layer_idx)
                    depth_frac = (layer_idx + 1) / cfg.num_layers
                    p_keep = 1.0 - (1.0 - pld_theta) * depth_frac
                    coin = jax.random.bernoulli(key, p_keep)
                    h2 = jnp.where(coin, h2, h)
                return h2, aux_acc + aux

            aux0 = jnp.zeros((), jnp.float32)
            head = min((-idx0) % f, n_layers)
            mid = (n_layers - head) // f * f

            if cfg.param_stream:
                # ZeRO-Infinity: layer slices stream host→device inside the
                # scan; the custom VJP (runtime/infinity.streamed_scan)
                # parks each layer's gradient back to a host accumulator so
                # neither params nor their grads are ever device-resident in
                # full. Placement must be static end to end.
                if head or mid != n_layers:
                    raise NotImplementedError(
                        "param streaming requires moe_layer_freq-aligned "
                        "segments (no random-LTD bands)")
                if pld_theta is not None:
                    raise NotImplementedError(
                        "param streaming + progressive layer drop "
                        "not supported")
                from deepspeed_tpu.runtime.infinity import streamed_scan

                if f > 1:
                    steps = n_layers // f
                    stacked = jax.tree.map(
                        lambda p: p.reshape((steps, f) + p.shape[1:]),
                        layers_slice)
                else:
                    stacked = layers_slice

                def step_fn(lp, h, pos_, i):
                    aux_acc = jnp.zeros((), jnp.float32)
                    if f > 1:
                        for j in range(f):
                            sub = jax.tree.map(lambda p, j=j: p[j], lp)
                            h, aux = transformer_layer(
                                h, sub, pos_, member_cfg(j % 2),
                                layer_is_moe=(cfg.is_moe and j == f - 1))
                            aux_acc = aux_acc + aux
                    else:
                        h, aux = transformer_layer(
                            h, lp, pos_, cfg, layer_is_moe=cfg.is_moe)
                        aux_acc = aux_acc + aux
                    return h, aux_acc

                return streamed_scan(step_fn, stacked, x, extras=pos)
            # head/tail: static global indices → static MoE placement
            def run_unrolled(x, aux, lo, hi):
                for j in range(lo, hi):
                    lp = jax.tree.map(lambda p, j=j: p[j], layers_slice)
                    is_moe = cfg.is_moe and ((idx0 + j) % f == f - 1)
                    lcfg = member_cfg((idx0 + j) % 2)
                    step = _maybe_remat(
                        lambda h, a, lp, j=j, m=is_moe, c=lcfg:
                        apply_layer(h, a, lp, idx0 + j, m, lcfg=c), cfg)
                    x, aux = step(x, aux, lp)
                return x, aux

            x, aux0 = run_unrolled(x, aux0, 0, head)
            if mid > 0:
                grouped = f > 1

                def body(carry, scanned):
                    h, aux_acc = carry
                    layer_params, i = scanned
                    if grouped:
                        for j in range(f):
                            lp = jax.tree.map(lambda p, j=j: p[j],
                                              layer_params)
                            # group starts are ≡ 0 mod f, so the member's
                            # global parity is j's — static
                            h, aux_acc = apply_layer(
                                h, aux_acc, lp, i * f + j,
                                cfg.is_moe and j == f - 1,
                                lcfg=member_cfg(j % 2))
                    else:
                        h, aux_acc = apply_layer(h, aux_acc, layer_params, i,
                                                 cfg.is_moe and f == 1)
                    return (h, aux_acc), None

                body = _maybe_remat(body, cfg)
                mid_slice = jax.tree.map(lambda p: p[head:head + mid],
                                         layers_slice)
                if grouped:
                    steps = mid // f
                    layers_scan = jax.tree.map(
                        lambda p: p.reshape((steps, f) + p.shape[1:]),
                        mid_slice)
                    idxs = jnp.arange((idx0 + head) // f,
                                      (idx0 + head) // f + steps)
                else:
                    steps = mid
                    layers_scan = mid_slice
                    idxs = jnp.arange(idx0 + head, idx0 + head + mid)
                unroll = max(1, cfg.scan_unroll)
                if steps % unroll != 0:
                    unroll = 1
                (x, aux_mid), _ = lax.scan(
                    body, (x, jnp.zeros((), jnp.float32)),
                    (layers_scan, idxs), unroll=unroll)
                aux0 = aux0 + aux_mid
            x, aux0 = run_unrolled(x, aux0, head + mid, n_layers)
            return x, aux0

        def layer_slice(a, b_):
            return jax.tree.map(lambda p: p[a:b_], params["layers"])

        ltd_on = 0 < cfg.ltd_kept < s
        if ltd_on:
            # random-LTD: middle band runs on a random token subset
            # (ref RandomLayerTokenDrop; gather/scatter = csrc/random_ltd)
            from deepspeed_tpu.runtime.data_pipeline.data_routing import (
                random_ltd_drop, random_ltd_indices, random_ltd_restore)

            a = max(0, min(cfg.ltd_start, cfg.num_layers))
            z = cfg.ltd_end if cfg.ltd_end is not None else cfg.num_layers - 1
            z = max(a, min(z, cfg.num_layers))
            x, aux0 = scan_segment(x, positions, layer_slice(0, a), 0, a)
            key = jax.random.fold_in(jax.random.PRNGKey(23),
                                     jnp.sum(input_ids[:, :1]).astype(jnp.int32))
            idx = random_ltd_indices(key, s, cfg.ltd_kept, b)
            x_kept = random_ltd_drop(x, idx)
            pos_kept = jnp.take_along_axis(positions, idx, axis=1)
            x_kept, aux1 = scan_segment(x_kept, pos_kept, layer_slice(a, z),
                                        a, z - a)
            x = random_ltd_restore(x, x_kept, idx)
            x, aux2 = scan_segment(x, positions, layer_slice(z, cfg.num_layers),
                                   z, cfg.num_layers - z)
            moe_aux = aux0 + aux1 + aux2
        else:
            x, moe_aux = scan_segment(x, positions, params["layers"], 0,
                                      cfg.num_layers)

    if cfg.norm_position != "post":
        # post-LN stacks (BERT) normalise inside every layer — no final norm
        x = _norm(x, params["final_norm"], cfg)
    if return_hidden:
        return (x, moe_aux) if cfg.is_moe else x
    # honor the autocast safe-module list for the output head: an unlisted
    # lm_head is promoted to fp32 like any other module class.
    ht = _module_dtype(cfg, "lm_head", dt)
    if cfg.mlm_head:
        # BERT MLM head: LN(gelu(h W + b)) @ embed.T + vocab bias (HF
        # BertLMPredictionHead; decoder tied to the token embeddings)
        mh = params["mlm_head"]
        t = x.astype(ht) @ mh["w"].astype(ht) + mh["b"].astype(ht)
        # transform activation follows cfg.activation like the MLP blocks
        # (HF BertPredictionHeadTransform uses config.hidden_act)
        t = jax.nn.relu(t) if cfg.activation == "relu" else \
            jax.nn.gelu(t, approximate=cfg.activation != "gelu_exact")
        t = _norm(t, mh["ln"], cfg)
        logits = t.astype(ht) @ params["embed"]["tokens"].astype(ht).T \
            + mh["bias"].astype(ht)
    elif cfg.tie_embeddings:
        logits = x.astype(ht) @ params["embed"]["tokens"].astype(ht).T
    else:
        logits = x.astype(ht) @ params["lm_head"].astype(ht)
    if not cfg.mlm_head and params.get("lm_head_bias") is not None:
        # GPT-J-style per-vocab output bias (HF applies lm_head.bias)
        logits = logits + params["lm_head_bias"].astype(ht)
    if cfg.is_moe:
        # stash aux loss on the fwd for the engine loss fn via closure return
        return logits, moe_aux
    return logits


MOE_AUX_COEF = 0.01


def _nll_sum(logits32, labels_mb):
    """Summed token NLL with -100 = ignore (HF convention)."""
    m = labels_mb != -100
    safe = jnp.where(m, labels_mb, 0)
    logz = jax.nn.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(logits32, safe[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * m)


def _embed(params: Params, input_ids, positions, cfg: TransformerConfig,
           token_embeds=None, token_type_ids=None):
    """Embedding prologue shared by forward() and the 1F1B loss path.
    ``token_embeds``: precomputed table rows [B,S,H] — the sparse-gradient
    path (runtime/sparse.py) hoists the lookup out of the differentiated
    function so the table cotangent stays (ids, values)-sparse.
    ``token_type_ids``: BERT segment ids (default segment 0)."""
    et = _module_dtype(cfg, "embed", cfg.dtype)
    x = (params["embed"]["tokens"].astype(et)[input_ids]
         if token_embeds is None else token_embeds.astype(et))
    if cfg.has_learned_positions:
        x = x + params["embed"]["positions"].astype(et)[positions]
    if cfg.type_vocab_size:
        tt = (token_type_ids if token_type_ids is not None
              else jnp.zeros_like(input_ids))
        x = x + params["embed"]["token_types"].astype(et)[tt]
    if cfg.embed_norm:
        x = _norm(x.astype(cfg.dtype), params["embed"]["norm"], cfg)
    return x.astype(cfg.dtype)


def _pipeline_1f1b_loss(params, batch, cfg: TransformerConfig, topo,
                        labels_eff, denom):
    """Training loss through the 1F1B pipeline schedule (the head + NLL run
    per microbatch on the last stage, ref runtime/pipe/engine.py:337)."""
    from deepspeed_tpu.parallel.pipeline import make_pipeline_train_loss

    input_ids = batch["input_ids"]
    b, s = input_ids.shape
    dt = cfg.dtype
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :],
                                 (b, s))

    def tail_fn(tp, h, labels_mb):
        h = _norm(h, tp["final_norm"], cfg)
        ht = _module_dtype(cfg, "lm_head", dt)
        w = tp["w"].astype(ht)
        logits = h.astype(ht) @ (w.T if cfg.tie_embeddings else w)
        lt = jnp.float32 if op_fp32(cfg, "loss") else logits.dtype
        return _nll_sum(logits.astype(lt), labels_mb)

    def embed_fn(ep, ids_mb, extras_mb):
        # runs inside the pipelined region: stage 0 embeds per microbatch
        # and its backward folds the input cotangent straight into these
        # tables (no O(batch) dx stash — see make_pipeline_train_loss)
        pos_mb, keys_mb = (extras_mb if isinstance(extras_mb, tuple)
                           else (extras_mb, None))
        x = _embed(ep, ids_mb, pos_mb, cfg)
        if keys_mb is not None and cfg.dropout > 0:
            # embedding dropout, keyed per microbatch (dense path uses
            # fold_in(step_key, 10_000) — same sentinel here)
            x = _dropout(x, cfg.dropout,
                         jax.random.fold_in(keys_mb[0], 10_000))
        return x

    tail_params = {"final_norm": params["final_norm"],
                   "w": params["embed"]["tokens"] if cfg.tie_embeddings
                   else params["lm_head"]}
    stage_fn = make_pipeline_stage_fn(cfg, topo)
    n_micro = cfg.pipeline_microbatches or topo.pp_size
    dropout_key = batch.get("dropout_key")
    extras = positions if dropout_key is None else (
        positions, _pipeline_key_rows(dropout_key, b, n_micro))
    f = make_pipeline_train_loss(
        stage_fn, tail_fn, topo, n_micro,
        aux_coef=MOE_AUX_COEF if cfg.is_moe else 0.0, embed_fn=embed_fn)
    return f(params["layers"], tail_params, {"embed": params["embed"]},
             input_ids, labels_eff, extras, denom)


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], cfg: TransformerConfig,
            token_embeds=None):
    """Causal LM cross-entropy. ``batch``: input_ids [B,S], labels [B,S]
    (-100 = ignore, HF convention), optional loss_mask, optional pld_theta
    (progressive layer drop keep prob, passed through the batch so the
    schedule never forces a recompile).

    With ``cfg.loss_tiles`` set (and dividing S), the loss is computed in
    sequence tiles (ALST, sequence/alst.py) so [B, S, V] logits are never
    materialised.
    """
    labels = batch["labels"]
    mask = (labels != -100)
    if "loss_mask" in batch:
        mask = mask & (batch["loss_mask"] > 0)

    s = batch["input_ids"].shape[1]
    tiled = cfg.loss_tiles and s % cfg.loss_tiles == 0
    if tiled and cfg.mlm_head:
        raise NotImplementedError(
            "loss_tiles + mlm_head not supported (the tiled loss computes "
            "logits directly against the embedding table, bypassing the "
            "MLM transform head); encoder sequences are short — drop "
            "loss_tiles")

    from deepspeed_tpu.parallel.topology import get_topology

    topo = get_topology()
    if (topo is not None and topo.pp_size > 1
            and cfg.pipeline_schedule == "1f1b" and not tiled
            and not cfg.param_stream   # forward() raises for pp+streaming
            and batch.get("pld_theta") is None
            and not (0 < cfg.ltd_kept < s)      # forward() raises for pp+LTD
            # encoder stacks: the 1F1B tail applies final_norm + the plain
            # tied head — post-LN/MLM-head models keep the AD GPipe path
            and not cfg.mlm_head and cfg.norm_position != "post"
            and batch.get("attention_mask") is None
            # fp16 needs the dynamic loss scale inside the backward, but the
            # 1F1B custom VJP computes grads in its forward before the scale
            # cotangent exists — fp16 stays on the AD-differentiated GPipe
            # path (bf16 shares f32's exponent range; no scaling needed)
            and cfg.dtype != jnp.float16):
        labels_eff = jnp.where(mask, labels, -100)
        denom = jnp.maximum(mask.sum(), 1).astype(jnp.float32)
        return _pipeline_1f1b_loss(params, batch, cfg, topo, labels_eff,
                                   denom)
    out = forward(params, batch["input_ids"], cfg,
                  pld_theta=batch.get("pld_theta"), return_hidden=bool(tiled),
                  token_embeds=token_embeds,
                  dropout_key=batch.get("dropout_key"),
                  token_type_ids=batch.get("token_type_ids"),
                  attention_mask=batch.get("attention_mask"))
    moe_aux = jnp.zeros((), jnp.float32)
    if isinstance(out, tuple):
        out, moe_aux = out

    if tiled:
        from deepspeed_tpu.sequence.alst import tiled_logits_loss

        w = params["embed"]["tokens"] if cfg.tie_embeddings \
            else params["lm_head"].T
        loss, _ = tiled_logits_loss(out, w.astype(cfg.dtype),
                                    jnp.where(mask, labels, -100),
                                    cfg.loss_tiles)
    else:
        lt = jnp.float32 if op_fp32(cfg, "loss") else out.dtype
        loss = _nll_sum(out.astype(lt),
                        jnp.where(mask, labels, -100)) \
            / jnp.maximum(mask.sum(), 1)
    if cfg.is_moe:
        loss = loss + MOE_AUX_COEF * moe_aux
    return loss
