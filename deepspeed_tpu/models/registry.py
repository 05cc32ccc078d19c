"""Model presets (flagship + test-scale configs)."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from deepspeed_tpu.models.transformer import (HybridConfig, LatentConfig,
                                              LatentWidths,
                                              MixedAttentionConfig,
                                              SSMConfig, TransformerConfig)

_REGISTRY = {}


def register(name: str, cfg: TransformerConfig) -> TransformerConfig:
    _REGISTRY[name] = cfg
    return cfg


def get_model_config(name: str, **overrides) -> TransformerConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model '{name}'; known: {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]
    for nested in ("mla", "mixed"):
        # what a latent or mixed-attention model keeps in its nested
        # configuration alone (its leading dense layers, say: a cut of
        # the depth takes fewer of them with it) is overridden there
        inner = getattr(cfg, nested)
        if inner is None:
            continue
        own = ({f.name for f in dataclasses.fields(inner)}
               - {f.name for f in dataclasses.fields(cfg)})
        sub = {k: overrides.pop(k) for k in own & set(overrides)}
        if sub:
            cfg = cfg.replace(**{nested: dataclasses.replace(inner, **sub)})
    return cfg.replace(**overrides) if overrides else cfg


def list_models():
    return sorted(_REGISTRY)


# -- GPT-2 family ------------------------------------------------------
register("gpt2-125m", TransformerConfig(
    vocab_size=50304,  # padded to 128 multiple for MXU tiling
    hidden_size=768, intermediate_size=3072, num_layers=12, num_heads=12,
    max_seq_len=1024, arch="gpt2", norm="layernorm", activation="gelu"))

register("gpt2-350m", TransformerConfig(
    vocab_size=50304, hidden_size=1024, intermediate_size=4096, num_layers=24,
    num_heads=16, max_seq_len=1024, arch="gpt2"))

register("gpt2-1.3b", TransformerConfig(
    vocab_size=50304, hidden_size=2048, intermediate_size=8192, num_layers=24,
    num_heads=32, max_seq_len=2048, arch="gpt2"))

# GPT-3 6.7B-class geometry — the peak_params ladder's chunked-offload
# rung builds this shape from gpt2-1.3b overrides; registered so the
# plan compiler (tools/plan.py --model gpt2-6.7b) can name it directly
register("gpt2-6.7b", TransformerConfig(
    vocab_size=50304, hidden_size=4096, intermediate_size=16384,
    num_layers=32, num_heads=32, max_seq_len=2048, arch="gpt2"))

# ~1B-total MoE with 8 routed experts: the planner's expert-parallel
# sight-unseen target (moe_1b_ep8) — experts dominate the param count,
# so expert-parallel meshes beat replicated-expert DP on wire bytes
register("moe-1b-ep8", TransformerConfig(
    vocab_size=32000, hidden_size=1024, intermediate_size=2816,
    num_layers=12, num_heads=16, num_kv_heads=8, max_seq_len=2048,
    arch="llama", norm="rmsnorm", activation="swiglu", use_rope=True,
    tie_embeddings=False, num_experts=8, top_k=2, moe_layer_freq=1))

# -- Llama family ------------------------------------------------------
_llama = dict(arch="llama", norm="rmsnorm", activation="swiglu", use_rope=True,
              tie_embeddings=False, rope_theta=500000.0)

register("llama3-8b", TransformerConfig(
    vocab_size=128256, hidden_size=4096, intermediate_size=14336, num_layers=32,
    num_heads=32, num_kv_heads=8, max_seq_len=8192, **_llama))

register("llama3-70b", TransformerConfig(
    vocab_size=128256, hidden_size=8192, intermediate_size=28672, num_layers=80,
    num_heads=64, num_kv_heads=8, max_seq_len=8192, **_llama))

register("llama-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
    num_heads=4, num_kv_heads=2, max_seq_len=256, arch="llama", norm="rmsnorm",
    activation="swiglu", use_rope=True, tie_embeddings=False, rope_theta=10000.0))

# -- Mixtral-style MoE -------------------------------------------------
register("mixtral-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
    num_heads=4, num_kv_heads=2, max_seq_len=256, arch="llama", norm="rmsnorm",
    activation="swiglu", use_rope=True, tie_embeddings=False,
    num_experts=4, top_k=2, moe_layer_freq=1))

# Qwen2-MoE style: narrower routed experts + a gated shared expert
register("qwen2moe-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
    num_heads=4, num_kv_heads=2, max_seq_len=256, arch="llama",
    norm="rmsnorm", activation="swiglu", use_rope=True,
    tie_embeddings=False, qkv_bias=True, num_experts=4, top_k=2,
    moe_layer_freq=1, moe_intermediate_size=64, moe_shared_expert_size=128))

register("qwen2moe-a14b", TransformerConfig(  # Qwen2-57B-A14B geometry
    vocab_size=151936, hidden_size=3584, intermediate_size=18944,
    num_layers=28, num_heads=28, num_kv_heads=4, max_seq_len=32768,
    arch="llama", norm="rmsnorm", activation="swiglu", use_rope=True,
    tie_embeddings=False, qkv_bias=True, num_experts=64, top_k=8,
    moe_layer_freq=1, moe_intermediate_size=2560,
    moe_shared_expert_size=20480))

register("mixtral-8x7b", TransformerConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=14336, num_layers=32,
    num_heads=32, num_kv_heads=8, max_seq_len=8192, arch="llama", norm="rmsnorm",
    activation="swiglu", use_rope=True, tie_embeddings=False, rope_theta=1e6,
    num_experts=8, top_k=2, moe_layer_freq=1))

# -- OPT family (ref inference/v2/model_implementations/opt) -----------
_opt = dict(arch="opt", norm="layernorm", activation="relu",
            learned_positions=True, use_bias=True, tie_embeddings=True)

register("opt-125m", TransformerConfig(
    vocab_size=50272, hidden_size=768, intermediate_size=3072, num_layers=12,
    num_heads=12, max_seq_len=2048, **_opt))

register("opt-1.3b", TransformerConfig(
    vocab_size=50272, hidden_size=2048, intermediate_size=8192, num_layers=24,
    num_heads=32, max_seq_len=2048, **_opt))

# -- Mistral (ref v2 mistral: llama + sliding window) ------------------
register("mistral-7b", TransformerConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=14336, num_layers=32,
    num_heads=32, num_kv_heads=8, max_seq_len=8192, arch="mistral",
    norm="rmsnorm", activation="swiglu", use_rope=True, tie_embeddings=False,
    rope_theta=10000.0, sliding_window=4096))

# -- Qwen2 (ref v2 qwen_v2: llama + qkv bias) --------------------------
register("qwen2-7b", TransformerConfig(
    vocab_size=152064, hidden_size=3584, intermediate_size=18944,
    num_layers=28, num_heads=28, num_kv_heads=4, max_seq_len=8192,
    arch="qwen2", norm="rmsnorm", activation="swiglu", use_rope=True,
    tie_embeddings=False, rope_theta=1e6, qkv_bias=True))

# -- Falcon (ref v2 falcon: multi-query + parallel block) --------------
register("falcon-7b", TransformerConfig(
    vocab_size=65024, hidden_size=4544, intermediate_size=18176,
    num_layers=32, num_heads=71, num_kv_heads=1, max_seq_len=2048,
    arch="falcon", norm="layernorm", activation="gelu", use_rope=True,
    tie_embeddings=True, parallel_block=True, use_bias=False))

# -- Falcon-H1 (HF falcon_h1): Mamba-2 heads beside attention heads in
# every block, muP multipliers; tiiuae/Falcon-H1-34B-Instruct config.json
_falcon_h1 = dict(arch="falcon_h1", norm="rmsnorm", activation="swiglu",
                  use_rope=True, tie_embeddings=False, use_bias=False)

register("falcon-h1-34b", TransformerConfig(
    vocab_size=261120, hidden_size=5120, intermediate_size=21504,
    num_layers=72, num_heads=20, num_kv_heads=4, head_dim=128,
    max_seq_len=262144, rope_theta=1e11, layernorm_eps=1e-5,
    ssm=SSMConfig(
        num_heads=32, head_dim=128, state_size=256, n_groups=2,
        conv_kernel=4, chunk_size=128, conv_bias=True,
        embedding_multiplier=5.656854249492381,
        lm_head_multiplier=0.0078125,
        attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804,
        ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284)),
    **_falcon_h1))

register("falcon-h1-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=32, max_seq_len=256,
    rope_theta=1e6,
    ssm=SSMConfig(
        num_heads=4, head_dim=32, state_size=16, n_groups=2, conv_kernel=4,
        chunk_size=16, conv_bias=True, embedding_multiplier=2.5,
        lm_head_multiplier=0.5, attention_in_multiplier=0.9,
        attention_out_multiplier=0.7, key_multiplier=0.6,
        ssm_in_multiplier=0.8, ssm_out_multiplier=1.3,
        ssm_multipliers=(0.7, 1.2, 0.9, 1.1, 0.8),
        mlp_multipliers=(1.4, 0.75)),
    **_falcon_h1))

# -- dots3-note (HF dots3_note: latent attention of two kinds by layer, a
# learned top-k indexer in the full layers, sigmoid-routed experts) ----
_dots3 = dict(arch="dots3_note", norm="rmsnorm", activation="swiglu",
              use_rope=True, tie_embeddings=False, use_bias=False)
# full at 0, 1, 5, 9, ...: a leading full layer, then periods of
# (full, window, window, window)
_dots3_types = ("full_attention",) + ("full_attention", "sliding_attention",
                                      "sliding_attention",
                                      "sliding_attention") * 11 \
    + ("full_attention",)


def _dots3_latent(experts_held):
    return LatentConfig(
        full=LatentWidths(num_heads=128, q_lora_rank=1024, kv_lora_rank=512,
                          qk_nope_head_dim=128, qk_rope_head_dim=64,
                          v_head_dim=128, rope_theta=8e7),
        window=LatentWidths(num_heads=64, q_lora_rank=1024,
                            kv_lora_rank=1024, qk_nope_head_dim=192,
                            qk_rope_head_dim=64, v_head_dim=128,
                            rope_theta=5e4),
        layer_types=_dots3_types, sliding_window=513, index_heads=64,
        index_head_dim=128, index_topk=2048, index_rope_dim=64,
        n_routed_experts=256, experts_held=experts_held,
        num_experts_per_tok=8, moe_intermediate_size=1536)


register("dots3-note-prev", TransformerConfig(
    vocab_size=152064, hidden_size=5120, intermediate_size=13824,
    num_layers=46, num_heads=128, max_seq_len=524288, rope_theta=8e7,
    layernorm_eps=1e-5, mla=_dots3_latent((0, 256)), **_dots3))

# one chip's share of a layer divided over eight: experts 0-31 of the 256
# (routing over all of them) and an eighth of the vocabulary; attention
# and the shared expert whole
register("dots3-note-prev-ep8", TransformerConfig(
    vocab_size=19008, hidden_size=5120, intermediate_size=13824,
    num_layers=46, num_heads=128, max_seq_len=524288, rope_theta=8e7,
    layernorm_eps=1e-5, mla=_dots3_latent((0, 32)), **_dots3))

register("dots3-note-tiny", TransformerConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=5,
    num_heads=4, max_seq_len=256, rope_theta=1e4, layernorm_eps=1e-5,
    mla=LatentConfig(
        full=LatentWidths(num_heads=4, q_lora_rank=32, kv_lora_rank=24,
                          qk_nope_head_dim=16, qk_rope_head_dim=8,
                          v_head_dim=16, rope_theta=1e4),
        window=LatentWidths(num_heads=2, q_lora_rank=32, kv_lora_rank=40,
                            qk_nope_head_dim=24, qk_rope_head_dim=8,
                            v_head_dim=16, rope_theta=5e2),
        layer_types=_dots3_types, sliding_window=5, index_heads=4,
        index_head_dim=16, index_topk=8, index_rope_dim=8,
        n_routed_experts=16, experts_held=(4, 4), num_experts_per_tok=4,
        moe_intermediate_size=32),
    **_dots3))

# -- GLM-5 (HF glm_moe_dsa: latent attention with a learned top-k
# indexer in EVERY layer, no gate, no window layer, interleaved rotary
# pairs, sigmoid-routed experts scaled by 2.5 after three dense layers,
# one multi-token-prediction module) ------------------------------------
_glm5 = dict(arch="glm_moe_dsa", norm="rmsnorm", activation="swiglu",
             use_rope=True, tie_embeddings=False, use_bias=False)


def _glm5_latent(experts_held):
    return LatentConfig(
        full=LatentWidths(num_heads=64, q_lora_rank=2048, kv_lora_rank=512,
                          qk_nope_head_dim=192, qk_rope_head_dim=64,
                          v_head_dim=256, rope_theta=1e6),
        window=None, layer_types=("full_attention",) * 78,
        sliding_window=0, index_heads=32, index_head_dim=128,
        index_topk=2048, index_rope_dim=64, n_routed_experts=256,
        experts_held=experts_held, num_experts_per_tok=8,
        moe_intermediate_size=2048, first_k_dense=3, lora_rescale=False,
        rope_interleaved=True, gate=False, routed_scaling_factor=2.5,
        mtp_layers=1)


register("glm-5", TransformerConfig(
    vocab_size=154880, hidden_size=6144, intermediate_size=12288,
    num_layers=78, num_heads=64, max_seq_len=202752, rope_theta=1e6,
    layernorm_eps=1e-5, mla=_glm5_latent((0, 256)), **_glm5))

# one chip's share of a layer divided over sixteen: experts 0-15 of the
# 256 (routing over all of them) and a sixteenth of the vocabulary;
# attention, the dense feed-forwards and the shared expert whole
register("glm-5-ep16", TransformerConfig(
    vocab_size=19360, hidden_size=6144, intermediate_size=12288,
    num_layers=78, num_heads=64, max_seq_len=202752, rope_theta=1e6,
    layernorm_eps=1e-5, mla=_glm5_latent((0, 16)), **_glm5))

register("glm-5-tiny", TransformerConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=4,
    num_heads=4, max_seq_len=256, rope_theta=1e4, layernorm_eps=1e-5,
    mla=LatentConfig(
        full=LatentWidths(num_heads=4, q_lora_rank=32, kv_lora_rank=24,
                          qk_nope_head_dim=16, qk_rope_head_dim=8,
                          v_head_dim=16, rope_theta=1e4),
        window=None, layer_types=("full_attention",) * 4, sliding_window=0,
        index_heads=4, index_head_dim=16, index_topk=8, index_rope_dim=8,
        n_routed_experts=16, experts_held=(4, 4), num_experts_per_tok=4,
        moe_intermediate_size=32, first_k_dense=2, lora_rescale=False,
        rope_interleaved=True, gate=False, routed_scaling_factor=2.5,
        mtp_layers=1),
    **_glm5))

# -- Trinity (HF afmoe: grouped-query attention of two kinds by layer,
# three sliding-4096 layers with rotary then a full one without, q/k
# norms, a gated attention output, sandwich norms, a muP embedding scale,
# six dense layers, then 256 sigmoid-routed experts, top 4 scaled by
# 2.448, and a shared one); arcee-ai/Trinity-Large-Preview config.json
_trinity = dict(arch="afmoe", norm="rmsnorm", activation="swiglu",
                use_rope=True, tie_embeddings=False, use_bias=False)
_trinity_types = ("sliding_attention",) * 3 + ("full_attention",)


def _trinity_mixed(experts_held):
    return MixedAttentionConfig(
        layer_types=_trinity_types * 15, sliding_window=4096,
        n_routed_experts=256, experts_held=experts_held,
        num_experts_per_tok=4, moe_intermediate_size=3072,
        num_dense_layers=6, route_scale=2.448,
        embed_multiplier=3072 ** 0.5)


register("trinity-large-preview", TransformerConfig(
    vocab_size=200192, hidden_size=3072, intermediate_size=12288,
    num_layers=60, num_heads=48, num_kv_heads=8, head_dim=128,
    max_seq_len=262144, rope_theta=1e4, layernorm_eps=1e-5,
    mixed=_trinity_mixed((0, 256)), **_trinity))

# one chip's share of a layer divided over eight: experts 0-31 of the 256
# (routing over all of them) and an eighth of the vocabulary; attention,
# the dense feed-forwards and the shared expert whole
register("trinity-large-preview-ep8", TransformerConfig(
    vocab_size=25024, hidden_size=3072, intermediate_size=12288,
    num_layers=60, num_heads=48, num_kv_heads=8, head_dim=128,
    max_seq_len=262144, rope_theta=1e4, layernorm_eps=1e-5,
    mixed=_trinity_mixed((0, 32)), **_trinity))

# two periods (of up to four), two layers of them dense; a window of
# three pages of 8
register("trinity-tiny", TransformerConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=8,
    num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256,
    rope_theta=1e4, layernorm_eps=1e-5,
    mixed=MixedAttentionConfig(
        layer_types=_trinity_types * 4, sliding_window=24,
        n_routed_experts=16, experts_held=(4, 4), num_experts_per_tok=4,
        moe_intermediate_size=32, num_dense_layers=2, route_scale=2.448,
        embed_multiplier=8.0),
    **_trinity))

# -- MiMo-V2-Flash (HF mimo_v2_flash: window-128 layers with a learned
# sink a head in the softmax beside full layers, 8 and 4 KV heads, keys 192
# wide of which 64 carry rotary (base 1e4 and 5e6) and values 128 times
# 0.707; one dense layer, then 256 sigmoid-routed experts top 8, no shared
# one); XiaomiMiMo/MiMo-V2-Flash config.json.  Trinity's q/k norms, gate,
# sandwich norms and muP embedding are all off
_mimo = dict(arch="mimo_v2_flash", norm="rmsnorm", activation="swiglu",
             use_rope=True, rotary_pct=0.334, tie_embeddings=False,
             use_bias=False)
_W, _F = "sliding_attention", "full_attention"
# hybrid_layer_pattern: full at 0, 5, 11, 17, ..., 47
_mimo_types = (_F,) + (_W,) * 4 + ((_F,) + (_W,) * 5) * 7 + (_F,)


def _mimo_mixed(experts_held, layer_types=_mimo_types):
    return MixedAttentionConfig(
        layer_types=layer_types, sliding_window=128,
        n_routed_experts=256, experts_held=experts_held,
        num_experts_per_tok=8, moe_intermediate_size=2048,
        num_dense_layers=1, n_shared_experts=0, rope_full=True,
        qk_norm=False, gate=False, sandwich_norm=False,
        window_kv_heads=8, window_rope_theta=1e4, window_sink=True,
        v_head_dim=128, value_scale=0.707)


# whole: for ``describe`` and shape tests only (no chip holds a layer)
register("mimo-v2-flash", TransformerConfig(
    vocab_size=152576, hidden_size=4096, intermediate_size=16384,
    num_layers=48, num_heads=64, num_kv_heads=4, head_dim=192,
    max_seq_len=262144, rope_theta=5e6, layernorm_eps=1e-5,
    mixed=_mimo_mixed((0, 256)), **_mimo))

# one chip's share of a layer divided over sixteen: experts 0-15 of the
# 256 (routing over all of them) and an eighth of the vocabulary;
# attention and the dense feed-forward whole.  Depth as cut: layer 0
# (full, dense) and one whole period, layers 6-11 (window x 5, full)
register("mimo-v2-flash-ep16", TransformerConfig(
    vocab_size=19072, hidden_size=4096, intermediate_size=16384,
    num_layers=7, num_heads=64, num_kv_heads=4, head_dim=192,
    max_seq_len=262144, rope_theta=5e6, layernorm_eps=1e-5,
    mixed=_mimo_mixed((0, 16), (_F,) + (_W,) * 5 + (_F,)), **_mimo))

# a dense full layer, two window layers and a full one; a window of 24
# on pages of 8 or 16 rows, so that tiny prompts pass it
register("mimo-tiny", TransformerConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=4,
    num_heads=4, num_kv_heads=2, head_dim=24, max_seq_len=256,
    rope_theta=5e6, layernorm_eps=1e-5,
    mixed=dataclasses.replace(
        _mimo_mixed((4, 4), (_F, _W, _W, _F)), sliding_window=24,
        n_routed_experts=16, num_experts_per_tok=2,
        moe_intermediate_size=32, window_kv_heads=4, v_head_dim=16),
    **_mimo))

# -- Nemotron-H (HF nemotron_h: ONE mixer a layer by a pattern, M a
# Mamba-2 scan of 64 heads of 64 in 8 groups at state 128, * grouped-query
# attention 32:2 at head 128 WITHOUT rotary, E 128 sigmoid-routed experts
# of two matrices, relu^2 without a gate, top 6 scaled by 2.5, and a
# shared one of twice the width; one RMSNorm a layer);
# nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json
_nemotron = dict(arch="nemotron_h", norm="rmsnorm", activation="relu2",
                 use_rope=False, tie_embeddings=False, use_bias=False)
_nemotron_pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _nemotron_3_nano(vocab_size, experts_held):
    return TransformerConfig(
        vocab_size=vocab_size, hidden_size=2688, intermediate_size=1856,
        num_layers=52, num_heads=32, num_kv_heads=2, head_dim=128,
        max_seq_len=262144, rope_theta=1e4, layernorm_eps=1e-5,
        ssm=SSMConfig(num_heads=64, head_dim=64, state_size=128, n_groups=8,
                      conv_kernel=4, chunk_size=128, conv_bias=True),
        hybrid=HybridConfig(
            pattern=_nemotron_pattern, n_routed_experts=128,
            experts_held=experts_held, num_experts_per_tok=6,
            moe_intermediate_size=1856, shared_intermediate_size=3712,
            route_scale=2.5),
        **_nemotron)


register("nemotron-3-nano-30b-a3b", _nemotron_3_nano(131072, (0, 128)))

# one chip's share of a layer divided over two: experts 0-63 of the 128
# (routing over all of them) and half of the vocabulary; the mixers, the
# attention and the shared expert whole
register("nemotron-3-nano-30b-a3b-ep2", _nemotron_3_nano(65536, (0, 64)))

# the first nine layers' pattern, experts 8-15 of 16
register("nemotron-h-tiny", TransformerConfig(
    vocab_size=512, hidden_size=64, intermediate_size=32, num_layers=9,
    num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256,
    rope_theta=1e4, layernorm_eps=1e-5,
    ssm=SSMConfig(num_heads=8, head_dim=8, state_size=16, n_groups=2,
                  conv_kernel=4, chunk_size=16, conv_bias=True),
    hybrid=HybridConfig(
        pattern=_nemotron_pattern[:9], n_routed_experts=16,
        experts_held=(8, 8), num_experts_per_tok=3,
        moe_intermediate_size=32, shared_intermediate_size=64,
        route_scale=2.5),
    **_nemotron))

# -- Phi (ref v2 phi: parallel block + partial rotary + biases) --------
register("phi-2", TransformerConfig(
    vocab_size=51200, hidden_size=2560, intermediate_size=10240,
    num_layers=32, num_heads=32, max_seq_len=2048, arch="phi",
    norm="layernorm", activation="gelu", use_rope=True, rotary_pct=0.4,
    tie_embeddings=False, parallel_block=True, use_bias=True))

# -- test-scale --------------------------------------------------------
register("gpt2-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=512, num_layers=2,
    num_heads=4, max_seq_len=256, arch="gpt2"))

register("opt-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=512, num_layers=2,
    num_heads=4, max_seq_len=256, **_opt))

register("mistral-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
    num_heads=4, num_kv_heads=2, max_seq_len=256, arch="mistral",
    norm="rmsnorm", activation="swiglu", use_rope=True, tie_embeddings=False,
    sliding_window=32))

register("qwen2-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
    num_heads=4, num_kv_heads=2, max_seq_len=256, arch="qwen2",
    norm="rmsnorm", activation="swiglu", use_rope=True, tie_embeddings=False,
    qkv_bias=True))

register("falcon-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=512, num_layers=2,
    num_heads=8, num_kv_heads=1, max_seq_len=256, arch="falcon",
    norm="layernorm", activation="gelu", use_rope=True, tie_embeddings=True,
    parallel_block=True, use_bias=False))

register("phi-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=512, num_layers=2,
    num_heads=4, max_seq_len=256, arch="phi", norm="layernorm",
    activation="gelu", use_rope=True, rotary_pct=0.5, tie_embeddings=False,
    parallel_block=True, use_bias=True))


# -- Encoder (BERT-class) family ---------------------------------------
# Ref: the reference trains these through its fused transformer kernel
# (ops/transformer/transformer.py:296) and serves them via the
# bert/distil_bert v1 injection containers (module_inject/containers).
_bert = dict(arch="bert", norm="layernorm", activation="gelu_exact",
             causal=False, norm_position="post", embed_norm=True,
             mlm_head=True, tie_embeddings=True, layernorm_eps=1e-12)

register("bert-base-uncased", TransformerConfig(
    vocab_size=30522, hidden_size=768, intermediate_size=3072,
    num_layers=12, num_heads=12, max_seq_len=512, type_vocab_size=2,
    dropout=0.1, **_bert))

register("bert-large-uncased", TransformerConfig(
    vocab_size=30522, hidden_size=1024, intermediate_size=4096,
    num_layers=24, num_heads=16, max_seq_len=512, type_vocab_size=2,
    dropout=0.1, **_bert))

register("bert-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=512, num_layers=2,
    num_heads=4, max_seq_len=256, type_vocab_size=2, **_bert))

register("distilbert-base-uncased", TransformerConfig(
    vocab_size=30522, hidden_size=768, intermediate_size=3072,
    num_layers=6, num_heads=12, max_seq_len=512, dropout=0.1,
    **{**_bert, "arch": "distilbert"}))

register("distilbert-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=512, num_layers=2,
    num_heads=4, max_seq_len=256, **{**_bert, "arch": "distilbert"}))


# -- Bloom / GPT-J / GPT-NeoX (v1 injection breadth) -------------------
# Ref containers: module_inject/containers/{bloom,gptj,gptneox}.py
register("bloom-560m", TransformerConfig(
    vocab_size=250880, hidden_size=1024, intermediate_size=4096,
    num_layers=24, num_heads=16, max_seq_len=2048, arch="bloom",
    norm="layernorm", activation="gelu", use_alibi=True, embed_norm=True,
    use_bias=True, tie_embeddings=True))

register("bloom-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=512, num_layers=2,
    num_heads=4, max_seq_len=256, arch="bloom", norm="layernorm",
    activation="gelu", use_alibi=True, embed_norm=True, use_bias=True,
    tie_embeddings=True))

register("gptj-6b", TransformerConfig(
    vocab_size=50400, hidden_size=4096, intermediate_size=16384,
    num_layers=28, num_heads=16, max_seq_len=2048, arch="gptj",
    norm="layernorm", activation="gelu", use_rope=True,
    rope_interleaved=True, rotary_pct=64 / 256, parallel_block=True,
    use_bias=False, mlp_bias=True, tie_embeddings=False))

register("gptj-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=512, num_layers=2,
    num_heads=4, max_seq_len=256, arch="gptj", norm="layernorm",
    activation="gelu", use_rope=True, rope_interleaved=True,
    rotary_pct=0.5, parallel_block=True, use_bias=False, mlp_bias=True,
    tie_embeddings=False))

register("gptneox-20b", TransformerConfig(
    vocab_size=50432, hidden_size=6144, intermediate_size=24576,
    num_layers=44, num_heads=64, max_seq_len=2048, arch="gptneox",
    norm="layernorm", activation="gelu_exact", use_rope=True,
    rotary_pct=0.25, parallel_block=True, parallel_norms=True,
    use_bias=True, tie_embeddings=False))

register("gptneox-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=512, num_layers=2,
    num_heads=4, max_seq_len=256, arch="gptneox", norm="layernorm",
    activation="gelu_exact", use_rope=True, rotary_pct=0.25,
    parallel_block=True, parallel_norms=True, use_bias=True,
    tie_embeddings=False))


register("gptneo-1.3b", TransformerConfig(
    vocab_size=50257, hidden_size=2048, intermediate_size=8192,
    num_layers=24, num_heads=16, max_seq_len=2048, arch="gptneo",
    norm="layernorm", activation="gelu", learned_positions=True,
    use_bias=False, mlp_bias=True, attn_out_bias=True, alt_window=True,
    sliding_window=256,
    attn_scale=1.0, tie_embeddings=True))

register("gptneo-tiny", TransformerConfig(
    vocab_size=512, hidden_size=128, intermediate_size=512, num_layers=2,
    num_heads=4, max_seq_len=256, arch="gptneo", norm="layernorm",
    activation="gelu", learned_positions=True, use_bias=False,
    mlp_bias=True, attn_out_bias=True, alt_window=True,
    sliding_window=16, attn_scale=1.0,
    tie_embeddings=True))
