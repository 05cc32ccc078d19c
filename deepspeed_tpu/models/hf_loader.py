"""Hugging Face checkpoint import.

The reference consumes HF models directly (module_inject/replace_module.py
kernel injection, inference/v2/model_implementations per-arch containers +
``flat_model_helpers``).  Here the equivalent surface is a *weight
converter*: ``config_from_hf`` maps an HF config to a
:class:`TransformerConfig` and ``params_from_hf`` maps an HF state dict to
the stacked functional param tree, after which every subsystem (engine,
AutoTP, ZeRO, inference v1/v2) consumes the model like any other.

Supported families: gpt2, llama, mistral, falcon_h1, qwen, qwen2, mixtral, qwen2_moe,
opt, falcon, phi, phi3 — the same set as the reference's v2 model
implementations (MoE included) — plus the v1-injection families
bloom (ALiBi), gptj (interleaved rotary), gpt_neox, and the encoder
family bert/distilbert (ref module_inject/containers/);
:func:`register_converter` adds new families without touching this module
(the analog of the v2 registry).

Conventions handled per family:
* HF ``nn.Linear`` stores [out, in] → transposed to our [in, out];
  GPT-2's Conv1D already stores [in, out].
* Fused projections are split (GPT-2 ``c_attn`` 3-way; Falcon
  ``query_key_value`` MQA layout [(nh + 2·nkv)·d, h]).
* OPT's learned positions carry a +2 row offset.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.utils.logging import logger


def _np(t) -> np.ndarray:
    if hasattr(t, "detach"):
        t = t.detach().cpu().float().numpy()
    return np.asarray(t, np.float32)


def _map_hf_activation(mt: str, act_name) -> str:
    """HF activation names → the functional vocabulary ("gelu" in HF
    BERT/NeoX is the exact erf form; gelu_new/_fast/_tanh are the tanh
    approximation the decoder families use)."""
    table = {"gelu": "gelu_exact", "gelu_new": "gelu",
             "gelu_fast": "gelu", "gelu_pytorch_tanh": "gelu",
             "relu": "relu"}
    name = str(act_name)
    if name not in table:
        raise ValueError(f"{mt}: unsupported hidden_act {name!r} "
                         f"(supported: {sorted(table)})")
    return table[name]


def config_from_hf(hf_config) -> TransformerConfig:
    """HF PretrainedConfig → TransformerConfig (ref engine_factory arch
    dispatch, inference/v2/engine_factory.py:69)."""
    mt = getattr(hf_config, "model_type", "")
    if mt == "gpt2":
        return TransformerConfig(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.n_embd,
            intermediate_size=4 * hf_config.n_embd,
            num_layers=hf_config.n_layer, num_heads=hf_config.n_head,
            max_seq_len=hf_config.n_positions, arch="gpt2",
            norm="layernorm", activation="gelu",
            layernorm_eps=hf_config.layer_norm_epsilon)
    if mt in ("llama", "mistral", "qwen2", "mixtral", "qwen2_moe"):
        # one llama-family block; MoE variants add routing fields.
        # Dropless capacity: C = cf*k*T/E = T exactly at cf = E/k (HF MoE
        # blocks never drop tokens; larger cf inflates [E,C,H] buffers).
        moe_kw = {}
        if mt == "mixtral":
            e, k = hf_config.num_local_experts, hf_config.num_experts_per_tok
            moe_kw = dict(num_experts=e, top_k=k, moe_layer_freq=1,
                          moe_norm_topk=True, capacity_factor=float(e / k))
        elif mt == "qwen2_moe":
            e, k = hf_config.num_experts, hf_config.num_experts_per_tok
            moe_kw = dict(
                num_experts=e, top_k=k, capacity_factor=float(e / k),
                moe_layer_freq=int(getattr(hf_config, "decoder_sparse_step",
                                           1) or 1),
                moe_norm_topk=bool(getattr(hf_config, "norm_topk_prob",
                                           False)),
                moe_intermediate_size=hf_config.moe_intermediate_size,
                moe_shared_expert_size=getattr(
                    hf_config, "shared_expert_intermediate_size", 0))
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None),
            max_seq_len=hf_config.max_position_embeddings,
            arch="llama" if mt in ("mixtral", "qwen2_moe") else mt,
            norm="rmsnorm", activation="swiglu", use_rope=True,
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
            qkv_bias=(mt in ("qwen2", "qwen2_moe")),
            sliding_window=getattr(hf_config, "sliding_window", None)
            if mt == "mistral" else None,
            layernorm_eps=hf_config.rms_norm_eps, **moe_kw)
    if mt == "falcon_h1":
        # a Mamba-2 mixer beside attention in every block + muP
        # multipliers (transformers models/falcon_h1)
        from deepspeed_tpu.models.transformer import SSMConfig

        c = hf_config
        if getattr(c, "mamba_proj_bias", False) or getattr(
                c, "projectors_bias", False) or getattr(
                    c, "attention_bias", False) or getattr(c, "mlp_bias",
                                                           False):
            raise NotImplementedError("falcon_h1 with projection biases")
        if not getattr(c, "mamba_rms_norm", True) or getattr(
                c, "mamba_norm_before_gate", False):
            raise NotImplementedError(
                "falcon_h1: only the gated RMSNorm with the gate first "
                "(mamba_rms_norm, not mamba_norm_before_gate) is served")
        d_ssm = c.mamba_d_ssm or int(c.mamba_expand * c.hidden_size)
        return TransformerConfig(
            vocab_size=c.vocab_size, hidden_size=c.hidden_size,
            intermediate_size=c.intermediate_size,
            num_layers=c.num_hidden_layers,
            num_heads=c.num_attention_heads,
            num_kv_heads=c.num_key_value_heads,
            head_dim=getattr(c, "head_dim", None),
            max_seq_len=c.max_position_embeddings, arch="falcon_h1",
            norm="rmsnorm", activation="swiglu", use_rope=True,
            use_bias=False, rope_theta=float(c.rope_theta),
            tie_embeddings=bool(c.tie_word_embeddings),
            layernorm_eps=c.rms_norm_eps,
            ssm=SSMConfig(
                num_heads=c.mamba_n_heads, head_dim=d_ssm // c.mamba_n_heads,
                state_size=c.mamba_d_state, n_groups=c.mamba_n_groups,
                conv_kernel=c.mamba_d_conv, chunk_size=c.mamba_chunk_size,
                conv_bias=bool(c.mamba_conv_bias),
                embedding_multiplier=float(c.embedding_multiplier),
                lm_head_multiplier=float(c.lm_head_multiplier),
                attention_in_multiplier=float(c.attention_in_multiplier),
                attention_out_multiplier=float(c.attention_out_multiplier),
                key_multiplier=float(c.key_multiplier),
                ssm_in_multiplier=float(c.ssm_in_multiplier),
                ssm_out_multiplier=float(c.ssm_out_multiplier),
                ssm_multipliers=tuple(float(v) for v in c.ssm_multipliers),
                mlp_multipliers=tuple(float(v) for v in c.mlp_multipliers)))
    if mt == "phi3":
        # llama-family numerics with fused qkv_proj / gate_up_proj weights
        # (ref inference/v2/model_implementations/phi3)
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None),
            max_seq_len=hf_config.max_position_embeddings,
            arch="phi3", norm="rmsnorm", activation="swiglu", use_rope=True,
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings",
                                        False)),
            sliding_window=getattr(hf_config, "sliding_window", None),
            layernorm_eps=hf_config.rms_norm_eps)
    if mt == "qwen":
        # Qwen v1 (remote-code modeling_qwen.py; ref
        # inference/v2/model_implementations/qwen): fused biased c_attn,
        # RMSNorm, SwiGLU where w2 gates and the HF intermediate_size is
        # 2x the actual FFN width (the modeling code splits it in half)
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size // 2,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            max_seq_len=getattr(hf_config, "seq_length", 2048),
            arch="qwen", norm="rmsnorm", activation="swiglu", use_rope=True,
            rope_theta=getattr(hf_config, "rotary_emb_base", 10000.0),
            qkv_bias=True, tie_embeddings=False,
            layernorm_eps=getattr(hf_config, "layer_norm_epsilon", 1e-6))
    if mt == "opt":
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.ffn_dim,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            max_seq_len=hf_config.max_position_embeddings,
            arch="opt", norm="layernorm", activation="relu",
            learned_positions=True, use_bias=True, tie_embeddings=True)
    if mt == "falcon":
        # HF falcon precedence (modeling_falcon): new_decoder_architecture
        # reads num_kv_heads; legacy multi_query means exactly 1 KV head.
        if getattr(hf_config, "new_decoder_architecture", False):
            nkv = getattr(hf_config, "num_kv_heads", None) \
                or hf_config.num_attention_heads
        elif getattr(hf_config, "multi_query", True):
            nkv = 1
        else:
            nkv = hf_config.num_attention_heads
        new_arch = bool(getattr(hf_config, "new_decoder_architecture", False))
        n_ln = getattr(hf_config, "num_ln_in_parallel_attn", None)
        if n_ln is None and new_arch:
            n_ln = 2  # HF FalconDecoderLayer default for the new arch
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=4 * hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads, num_kv_heads=nkv,
            max_seq_len=getattr(hf_config, "max_position_embeddings", 2048),
            arch="falcon", norm="layernorm", activation="gelu",
            use_rope=getattr(hf_config, "rotary", True),
            parallel_block=bool(getattr(hf_config, "parallel_attn", True)),
            parallel_norms=(new_arch and n_ln == 2),
            use_bias=bool(getattr(hf_config, "bias", False)),
            tie_embeddings=True,
            layernorm_eps=getattr(hf_config, "layer_norm_epsilon", 1e-5))
    if mt == "bloom":
        # ALiBi attention, embedding LayerNorm, BloomGelu = tanh approx
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=4 * hf_config.hidden_size,
            num_layers=hf_config.n_layer, num_heads=hf_config.n_head,
            max_seq_len=getattr(hf_config, "seq_length", 2048),
            arch="bloom", norm="layernorm", activation="gelu",
            use_alibi=True, embed_norm=True, use_bias=True,
            tie_embeddings=True,
            layernorm_eps=getattr(hf_config, "layer_norm_epsilon", 1e-5))
    if mt == "gptj":
        # interleaved partial rotary, parallel block with ONE shared norm,
        # biasless attention + biased MLP, gelu_new = tanh approx
        d = hf_config.n_embd // hf_config.n_head
        return TransformerConfig(
            vocab_size=hf_config.vocab_size, hidden_size=hf_config.n_embd,
            intermediate_size=(hf_config.n_inner
                               or 4 * hf_config.n_embd),
            num_layers=hf_config.n_layer, num_heads=hf_config.n_head,
            max_seq_len=hf_config.n_positions, arch="gptj",
            norm="layernorm", activation="gelu", use_rope=True,
            rope_interleaved=True,
            # rotary_dim=None = full-head rotary (HF GPTJAttention)
            rotary_pct=(hf_config.rotary_dim or d) / d,
            parallel_block=True, use_bias=False, mlp_bias=True,
            tie_embeddings=False, lm_head_bias=True,
            layernorm_eps=getattr(hf_config, "layer_norm_epsilon", 1e-5))
    if mt == "gpt_neo":
        # alternating global/local attention, learned positions, NO
        # sqrt(d) score scaling, biasless q/k/v with biased out/mlp
        layers = list(getattr(hf_config, "attention_layers", []))
        alt = (len(layers) == hf_config.num_layers and all(
            p == ("global" if i % 2 == 0 else "local")
            for i, p in enumerate(layers)))
        all_global = all(p == "global" for p in layers)
        if not (alt or all_global):
            raise ValueError(
                f"gpt_neo: unsupported attention_layers pattern {layers} "
                "(supported: all-global, or alternating global/local)")
        if alt and hf_config.num_layers % 2:
            raise ValueError(
                "gpt_neo: alternating attention needs an even layer count "
                f"(got {hf_config.num_layers}) — the alt-window paths scan "
                "layer pairs")
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=(hf_config.intermediate_size
                               or 4 * hf_config.hidden_size),
            num_layers=hf_config.num_layers,
            num_heads=hf_config.num_heads,
            max_seq_len=hf_config.max_position_embeddings, arch="gptneo",
            norm="layernorm",
            activation=_map_hf_activation(
                mt, getattr(hf_config, "activation_function", "gelu_new")),
            learned_positions=True, use_bias=False, mlp_bias=True,
            attn_out_bias=True, alt_window=alt,
            sliding_window=(hf_config.window_size if alt else None),
            attn_scale=1.0, tie_embeddings=True,
            layernorm_eps=getattr(hf_config, "layer_norm_epsilon", 1e-5))
    if mt == "gpt_neox":
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            max_seq_len=hf_config.max_position_embeddings, arch="gptneox",
            norm="layernorm",
            activation=_map_hf_activation(mt, hf_config.hidden_act),
            use_rope=True, rotary_pct=hf_config.rotary_pct,
            rope_theta=float(getattr(hf_config, "rope_theta", None)
                             or getattr(hf_config, "rotary_emb_base",
                                        10000.0)),
            parallel_block=bool(getattr(hf_config, "use_parallel_residual",
                                        True)),
            parallel_norms=bool(getattr(hf_config, "use_parallel_residual",
                                        True)),
            use_bias=True, tie_embeddings=False,
            layernorm_eps=getattr(hf_config, "layer_norm_eps", 1e-5))
    if mt in ("bert", "distilbert"):
        # map HF activation names onto the functional vocabulary ("gelu"
        # in HF BERT is the exact erf form; gelu_new/_tanh are the tanh
        # approximation the decoder families use)
        act_name = (getattr(hf_config, "hidden_act", None)
                    or getattr(hf_config, "activation", "gelu"))
        enc_kw = dict(
            arch=mt, norm="layernorm",
            activation=_map_hf_activation(mt, act_name),
            causal=False, norm_position="post", embed_norm=True,
            mlm_head=True, tie_embeddings=True)
        if mt == "bert":
            return TransformerConfig(
                vocab_size=hf_config.vocab_size,
                hidden_size=hf_config.hidden_size,
                intermediate_size=hf_config.intermediate_size,
                num_layers=hf_config.num_hidden_layers,
                num_heads=hf_config.num_attention_heads,
                max_seq_len=hf_config.max_position_embeddings,
                type_vocab_size=getattr(hf_config, "type_vocab_size", 2),
                dropout=getattr(hf_config, "hidden_dropout_prob", 0.1),
                layernorm_eps=getattr(hf_config, "layer_norm_eps", 1e-12),
                **enc_kw)
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.dim,
            intermediate_size=hf_config.hidden_dim,
            num_layers=hf_config.n_layers,
            num_heads=hf_config.n_heads,
            max_seq_len=hf_config.max_position_embeddings,
            dropout=getattr(hf_config, "dropout", 0.1),
            layernorm_eps=1e-12, **enc_kw)
    if mt == "phi":
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            max_seq_len=hf_config.max_position_embeddings,
            arch="phi", norm="layernorm", activation="gelu", use_rope=True,
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rotary_pct=getattr(hf_config, "partial_rotary_factor", 0.5),
            parallel_block=True, use_bias=True, tie_embeddings=False,
            layernorm_eps=getattr(hf_config, "layer_norm_eps", 1e-5))
    raise ValueError(f"unsupported HF model_type {mt!r}")


# ----------------------------------------------------------------------
#: arch → converter registry (the analog of inference/v2's pluggable
#: model-implementation registry, engine_factory.py:69 — register a new
#: family without touching this module)
_CONVERTERS: Dict[str, Any] = {}


def register_converter(arch: str, fn) -> None:
    """Register ``fn(state_dict, cfg) -> param tree`` for ``cfg.arch``."""
    _CONVERTERS[arch] = fn


def params_from_hf(model_or_state_dict, cfg: TransformerConfig,
                   dtype=None) -> Dict[str, Any]:
    """HF model / state dict → stacked functional param tree."""
    sd = (model_or_state_dict if isinstance(model_or_state_dict, dict)
          else model_or_state_dict.state_dict())
    sd = {k: _np(v) for k, v in sd.items()}
    dt = dtype or cfg.param_dtype
    if cfg.arch not in _CONVERTERS:
        raise KeyError(f"no converter for arch {cfg.arch!r}; known: "
                       f"{sorted(_CONVERTERS)} (register_converter to add)")
    params = _CONVERTERS[cfg.arch](sd, cfg)
    return {k: _cast_tree(v, dt) for k, v in params.items()}


def _cast_tree(x, dt):
    if isinstance(x, dict):
        return {k: _cast_tree(v, dt) for k, v in x.items()}
    return jnp.asarray(x, dt)


def _stack(layer_dicts):
    out: Dict[str, Any] = {}
    for key in layer_dicts[0]:
        if isinstance(layer_dicts[0][key], dict):
            out[key] = _stack([ld[key] for ld in layer_dicts])
        else:
            out[key] = np.stack([ld[key] for ld in layer_dicts], axis=0)
    return out


def _convert_gpt2(sd, cfg):
    h = cfg.hidden_size
    layers = []
    for i in range(cfg.num_layers):
        p = f"transformer.h.{i}."
        ca_w = sd[p + "attn.c_attn.weight"]  # Conv1D: [in, 3h]
        ca_b = sd[p + "attn.c_attn.bias"]
        wq, wk, wv = np.split(ca_w, 3, axis=1)
        bq, bk, bv = np.split(ca_b, 3, axis=0)
        layers.append({
            "attn": {"wq": wq, "wk": wk, "wv": wv,
                     "wo": sd[p + "attn.c_proj.weight"],
                     "bq": bq, "bk": bk, "bv": bv,
                     "bo": sd[p + "attn.c_proj.bias"]},
            "mlp": {"wi": sd[p + "mlp.c_fc.weight"],
                    "bi": sd[p + "mlp.c_fc.bias"],
                    "wo": sd[p + "mlp.c_proj.weight"],
                    "bo": sd[p + "mlp.c_proj.bias"]},
            "ln1": {"scale": sd[p + "ln_1.weight"], "bias": sd[p + "ln_1.bias"]},
            "ln2": {"scale": sd[p + "ln_2.weight"], "bias": sd[p + "ln_2.bias"]},
        })
    return {
        "embed": {"tokens": sd["transformer.wte.weight"],
                  "positions": sd["transformer.wpe.weight"]},
        "layers": _stack(layers),
        "final_norm": {"scale": sd["transformer.ln_f.weight"],
                       "bias": sd["transformer.ln_f.bias"]},
    }


def _convert_llama(sd, cfg):
    layers = []
    qkv_b = cfg.qkv_bias
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        attn = {"wq": sd[p + "self_attn.q_proj.weight"].T,
                "wk": sd[p + "self_attn.k_proj.weight"].T,
                "wv": sd[p + "self_attn.v_proj.weight"].T,
                "wo": sd[p + "self_attn.o_proj.weight"].T}
        if qkv_b:
            attn["bq"] = sd[p + "self_attn.q_proj.bias"]
            attn["bk"] = sd[p + "self_attn.k_proj.bias"]
            attn["bv"] = sd[p + "self_attn.v_proj.bias"]
        block = {
            "attn": attn,
            "ln1": {"scale": sd[p + "input_layernorm.weight"]},
            "ln2": {"scale": sd[p + "post_attention_layernorm.weight"]},
        }
        if p + "block_sparse_moe.gate.weight" in sd:
            # Mixtral: w1=gate, w3=up, w2=down per expert (ref
            # inference/v2/model_implementations/mixtral)
            ep = p + "block_sparse_moe.experts."
            e = cfg.num_experts
            block["moe"] = {
                "router": sd[p + "block_sparse_moe.gate.weight"].T,
                "wg": np.stack([sd[f"{ep}{j}.w1.weight"].T
                                for j in range(e)]),
                "wi": np.stack([sd[f"{ep}{j}.w3.weight"].T
                                for j in range(e)]),
                "wo": np.stack([sd[f"{ep}{j}.w2.weight"].T
                                for j in range(e)]),
            }
        elif p + "mlp.gate.weight" in sd:
            # Qwen2-MoE: routed experts + gated shared expert
            ep = p + "mlp.experts."
            e = cfg.num_experts
            block["moe"] = {
                "router": sd[p + "mlp.gate.weight"].T,
                "wg": np.stack([sd[f"{ep}{j}.gate_proj.weight"].T
                                for j in range(e)]),
                "wi": np.stack([sd[f"{ep}{j}.up_proj.weight"].T
                                for j in range(e)]),
                "wo": np.stack([sd[f"{ep}{j}.down_proj.weight"].T
                                for j in range(e)]),
                "shared": {
                    "wg": sd[p + "mlp.shared_expert.gate_proj.weight"].T,
                    "wi": sd[p + "mlp.shared_expert.up_proj.weight"].T,
                    "wo": sd[p + "mlp.shared_expert.down_proj.weight"].T},
                "shared_gate": sd[p + "mlp.shared_expert_gate.weight"].T,
            }
        else:
            block["mlp"] = {"wg": sd[p + "mlp.gate_proj.weight"].T,
                            "wi": sd[p + "mlp.up_proj.weight"].T,
                            "wo": sd[p + "mlp.down_proj.weight"].T}
        layers.append(block)
    if cfg.is_moe and any("moe" not in b for b in layers):
        raise NotImplementedError(
            "mixed dense/MoE layer stacks (decoder_sparse_step > 1 or "
            "mlp_only_layers) are not supported by the stacked-layer scan")
    out = {"embed": {"tokens": sd["model.embed_tokens.weight"]},
           "layers": _stack(layers),
           "final_norm": {"scale": sd["model.norm.weight"]}}
    if not cfg.tie_embeddings:
        lm = sd.get("lm_head.weight", sd["model.embed_tokens.weight"])
        out["lm_head"] = lm.T
    return out


def _convert_falcon_h1(sd, cfg):
    """HF ``falcon_h1``: ``mamba.*`` beside ``self_attn.*`` in every
    layer, ``feed_forward.*``, ``input_layernorm`` / ``pre_ff_layernorm``,
    ``model.final_layernorm``."""
    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        ssm = {"in_proj": sd[p + "mamba.in_proj.weight"].T,
               "conv_w": sd[p + "mamba.conv1d.weight"][:, 0, :],
               "dt_bias": sd[p + "mamba.dt_bias"],
               "A_log": sd[p + "mamba.A_log"],
               "D": sd[p + "mamba.D"],
               "norm": sd[p + "mamba.norm.weight"],
               "out_proj": sd[p + "mamba.out_proj.weight"].T}
        if cfg.ssm.conv_bias:
            ssm["conv_b"] = sd[p + "mamba.conv1d.bias"]
        layers.append({
            "attn": {"wq": sd[p + "self_attn.q_proj.weight"].T,
                     "wk": sd[p + "self_attn.k_proj.weight"].T,
                     "wv": sd[p + "self_attn.v_proj.weight"].T,
                     "wo": sd[p + "self_attn.o_proj.weight"].T},
            "ssm": ssm,
            "mlp": {"wg": sd[p + "feed_forward.gate_proj.weight"].T,
                    "wi": sd[p + "feed_forward.up_proj.weight"].T,
                    "wo": sd[p + "feed_forward.down_proj.weight"].T},
            "ln1": {"scale": sd[p + "input_layernorm.weight"]},
            "ln2": {"scale": sd[p + "pre_ff_layernorm.weight"]},
        })
    out = {"embed": {"tokens": sd["model.embed_tokens.weight"]},
           "layers": _stack(layers),
           "final_norm": {"scale": sd["model.final_layernorm.weight"]}}
    if not cfg.tie_embeddings:
        out["lm_head"] = sd["lm_head.weight"].T
    return out


def _convert_opt(sd, cfg):
    layers = []
    for i in range(cfg.num_layers):
        p = f"model.decoder.layers.{i}."
        layers.append({
            "attn": {"wq": sd[p + "self_attn.q_proj.weight"].T,
                     "wk": sd[p + "self_attn.k_proj.weight"].T,
                     "wv": sd[p + "self_attn.v_proj.weight"].T,
                     "wo": sd[p + "self_attn.out_proj.weight"].T,
                     "bq": sd[p + "self_attn.q_proj.bias"],
                     "bk": sd[p + "self_attn.k_proj.bias"],
                     "bv": sd[p + "self_attn.v_proj.bias"],
                     "bo": sd[p + "self_attn.out_proj.bias"]},
            "mlp": {"wi": sd[p + "fc1.weight"].T, "bi": sd[p + "fc1.bias"],
                    "wo": sd[p + "fc2.weight"].T, "bo": sd[p + "fc2.bias"]},
            "ln1": {"scale": sd[p + "self_attn_layer_norm.weight"],
                    "bias": sd[p + "self_attn_layer_norm.bias"]},
            "ln2": {"scale": sd[p + "final_layer_norm.weight"],
                    "bias": sd[p + "final_layer_norm.bias"]},
        })
    # OPT's learned positions skip the first 2 rows (padding offset)
    pos = sd["model.decoder.embed_positions.weight"][2:]
    return {
        "embed": {"tokens": sd["model.decoder.embed_tokens.weight"],
                  "positions": pos},
        "layers": _stack(layers),
        "final_norm": {"scale": sd["model.decoder.final_layer_norm.weight"],
                       "bias": sd["model.decoder.final_layer_norm.bias"]},
    }


def _convert_falcon(sd, cfg):
    nh, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
    ln_attn = "transformer.h.0.ln_attn.weight" in sd
    if ln_attn:
        ln2_key = "ln_mlp"
    elif "transformer.h.0.post_attention_layernorm.weight" in sd:
        ln2_key = "post_attention_layernorm"  # parallel_attn=False layout
    else:
        ln2_key = "input_layernorm"
    layers = []
    for i in range(cfg.num_layers):
        p = f"transformer.h.{i}."
        qkv = sd[p + "self_attention.query_key_value.weight"].T  # [h, (nh+2nkv)d]
        # HF Falcon's fused layout is per-KV-group in every variant:
        # nkv groups of (nh/nkv query heads, one k, one v).  nkv==nh reduces
        # to per-head [q,k,v] interleave (Falcon-RW), nkv==1 to [all-q, k, v]
        # (7B multi-query), and 1<nkv<nh is the new_decoder_architecture
        # interleave (40B/180B — the reference handles it via
        # GQAMegatronQKVParameter, module_inject/layers.py).
        hdim = qkv.shape[0]
        qkv = qkv.reshape(hdim, nkv, nh // nkv + 2, d)
        wq = qkv[:, :, :-2, :].reshape(hdim, nh * d)
        wk = qkv[:, :, -2, :].reshape(hdim, nkv * d)
        wv = qkv[:, :, -1, :].reshape(hdim, nkv * d)
        layers.append({
            "attn": {"wq": wq, "wk": wk, "wv": wv,
                     "wo": sd[p + "self_attention.dense.weight"].T},
            "mlp": {"wi": sd[p + "mlp.dense_h_to_4h.weight"].T,
                    "wo": sd[p + "mlp.dense_4h_to_h.weight"].T},
            # new_decoder_architecture: separate ln_attn/ln_mlp parallel
            # norms; legacy sequential (parallel_attn=False): ln2 is the
            # post-attention norm; legacy parallel: one shared input norm
            # (ln2 mirrors it so the tree keeps the slot).
            "ln1": {"scale": sd[p + ("ln_attn.weight" if ln_attn
                                     else "input_layernorm.weight")],
                    "bias": sd[p + ("ln_attn.bias" if ln_attn
                                    else "input_layernorm.bias")]},
            "ln2": {"scale": sd[p + ln2_key + ".weight"],
                    "bias": sd[p + ln2_key + ".bias"]},
        })
    return {
        "embed": {"tokens": sd["transformer.word_embeddings.weight"]},
        "layers": _stack(layers),
        "final_norm": {"scale": sd["transformer.ln_f.weight"],
                       "bias": sd["transformer.ln_f.bias"]},
    }


def _convert_phi(sd, cfg):
    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        layers.append({
            "attn": {"wq": sd[p + "self_attn.q_proj.weight"].T,
                     "wk": sd[p + "self_attn.k_proj.weight"].T,
                     "wv": sd[p + "self_attn.v_proj.weight"].T,
                     "wo": sd[p + "self_attn.dense.weight"].T,
                     "bq": sd[p + "self_attn.q_proj.bias"],
                     "bk": sd[p + "self_attn.k_proj.bias"],
                     "bv": sd[p + "self_attn.v_proj.bias"],
                     "bo": sd[p + "self_attn.dense.bias"]},
            "mlp": {"wi": sd[p + "mlp.fc1.weight"].T,
                    "bi": sd[p + "mlp.fc1.bias"],
                    "wo": sd[p + "mlp.fc2.weight"].T,
                    "bo": sd[p + "mlp.fc2.bias"]},
            "ln1": {"scale": sd[p + "input_layernorm.weight"],
                    "bias": sd[p + "input_layernorm.bias"]},
            "ln2": {"scale": sd[p + "input_layernorm.weight"],
                    "bias": sd[p + "input_layernorm.bias"]},
        })
    out = {"embed": {"tokens": sd["model.embed_tokens.weight"]},
           "layers": _stack(layers),
           "final_norm": {"scale": sd["model.final_layernorm.weight"],
                          "bias": sd["model.final_layernorm.bias"]},
           "lm_head": sd["lm_head.weight"].T}
    if "lm_head.bias" in sd and np.abs(sd["lm_head.bias"]).max() > 0:
        logger.warning("phi lm_head bias dropped (functional head has no "
                       "output bias)")
    return out


def _convert_phi3(sd, cfg):
    """Phi-3: fused qkv_proj ([q;k;v] rows) and gate_up_proj ([gate;up])
    split into the functional layout (ref phi3 layer containers)."""
    nh, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
    ffn = cfg.intermediate_size
    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        qkv = sd[p + "self_attn.qkv_proj.weight"]        # [(nh+2nkv)d, h]
        wq = qkv[:nh * d].T
        wk = qkv[nh * d:nh * d + nkv * d].T
        wv = qkv[nh * d + nkv * d:].T
        gu = sd[p + "mlp.gate_up_proj.weight"]           # [2*ffn, h]
        layers.append({
            "attn": {"wq": wq, "wk": wk, "wv": wv,
                     "wo": sd[p + "self_attn.o_proj.weight"].T},
            "mlp": {"wg": gu[:ffn].T, "wi": gu[ffn:].T,
                    "wo": sd[p + "mlp.down_proj.weight"].T},
            "ln1": {"scale": sd[p + "input_layernorm.weight"]},
            "ln2": {"scale": sd[p + "post_attention_layernorm.weight"]},
        })
    out = {"embed": {"tokens": sd["model.embed_tokens.weight"]},
           "layers": _stack(layers),
           "final_norm": {"scale": sd["model.norm.weight"]}}
    if not cfg.tie_embeddings:
        out["lm_head"] = sd.get("lm_head.weight",
                                sd["model.embed_tokens.weight"]).T
    return out


def _convert_qwen(sd, cfg):
    """Qwen v1 (remote-code modeling_qwen.py layout): transformer.h.*,
    fused biased c_attn, and the w1/w2/c_proj MLP where out =
    c_proj(w1(x) * silu(w2(x))) — w2 is the gate, w1 the up projection."""
    h = cfg.hidden_size
    layers = []
    for i in range(cfg.num_layers):
        p = f"transformer.h.{i}."
        ca_w = sd[p + "attn.c_attn.weight"].T            # [h, 3h]
        ca_b = sd[p + "attn.c_attn.bias"]
        wq, wk, wv = np.split(ca_w, 3, axis=1)
        bq, bk, bv = np.split(ca_b, 3, axis=0)
        layers.append({
            "attn": {"wq": wq, "wk": wk, "wv": wv,
                     "bq": bq, "bk": bk, "bv": bv,
                     "wo": sd[p + "attn.c_proj.weight"].T},
            "mlp": {"wg": sd[p + "mlp.w2.weight"].T,
                    "wi": sd[p + "mlp.w1.weight"].T,
                    "wo": sd[p + "mlp.c_proj.weight"].T},
            "ln1": {"scale": sd[p + "ln_1.weight"]},
            "ln2": {"scale": sd[p + "ln_2.weight"]},
        })
    return {"embed": {"tokens": sd["transformer.wte.weight"]},
            "layers": _stack(layers),
            "final_norm": {"scale": sd["transformer.ln_f.weight"]},
            "lm_head": sd["lm_head.weight"].T}


def _split_headwise_qkv(w, b, nh, d):
    """Bloom/GPT-NeoX fused query_key_value: rows are grouped PER HEAD as
    [nh, (q|k|v), d] (ref GQAMegatronQKVParameter, module_inject/layers.py).
    Returns ((wq, wk, wv), (bq, bk, bv)) in the functional [in, out]
    layout."""
    h_in = w.shape[1]
    wg = w.reshape(nh, 3, d, h_in)
    ws = tuple(wg[:, j].reshape(nh * d, h_in).T for j in range(3))
    if b is None:
        return ws, (None, None, None)
    bg = b.reshape(nh, 3, d)
    return ws, tuple(bg[:, j].reshape(nh * d) for j in range(3))


def _convert_bloom(sd, cfg):
    """HF BloomForCausalLM → functional tree (ref
    module_inject/containers/bloom.py)."""
    nh, d = cfg.num_heads, cfg.dim_per_head
    layers = []
    for i in range(cfg.num_layers):
        p = f"transformer.h.{i}."
        (wq, wk, wv), (bq, bk, bv) = _split_headwise_qkv(
            sd[p + "self_attention.query_key_value.weight"],
            sd[p + "self_attention.query_key_value.bias"], nh, d)
        layers.append({
            "attn": {"wq": wq, "wk": wk, "wv": wv,
                     "bq": bq, "bk": bk, "bv": bv,
                     "wo": sd[p + "self_attention.dense.weight"].T,
                     "bo": sd[p + "self_attention.dense.bias"]},
            "mlp": {"wi": sd[p + "mlp.dense_h_to_4h.weight"].T,
                    "bi": sd[p + "mlp.dense_h_to_4h.bias"],
                    "wo": sd[p + "mlp.dense_4h_to_h.weight"].T,
                    "bo": sd[p + "mlp.dense_4h_to_h.bias"]},
            "ln1": {"scale": sd[p + "input_layernorm.weight"],
                    "bias": sd[p + "input_layernorm.bias"]},
            "ln2": {"scale": sd[p + "post_attention_layernorm.weight"],
                    "bias": sd[p + "post_attention_layernorm.bias"]},
        })
    return {
        "embed": {
            "tokens": sd["transformer.word_embeddings.weight"],
            "norm": {
                "scale": sd["transformer.word_embeddings_layernorm.weight"],
                "bias": sd["transformer.word_embeddings_layernorm.bias"]}},
        "layers": _stack(layers),
        "final_norm": {"scale": sd["transformer.ln_f.weight"],
                       "bias": sd["transformer.ln_f.bias"]},
    }


def _convert_gptj(sd, cfg):
    """HF GPTJForCausalLM → functional tree (ref
    module_inject/containers/gptj.py).  The checkpoint's lm_head.bias
    (nonzero in the released EleutherAI weights) maps to the functional
    head's optional vocab-size output bias — served logits match HF
    per-token."""
    layers = []
    for i in range(cfg.num_layers):
        p = f"transformer.h.{i}."
        ln1 = {"scale": sd[p + "ln_1.weight"], "bias": sd[p + "ln_1.bias"]}
        layers.append({
            "attn": {"wq": sd[p + "attn.q_proj.weight"].T,
                     "wk": sd[p + "attn.k_proj.weight"].T,
                     "wv": sd[p + "attn.v_proj.weight"].T,
                     "wo": sd[p + "attn.out_proj.weight"].T},
            "mlp": {"wi": sd[p + "mlp.fc_in.weight"].T,
                    "bi": sd[p + "mlp.fc_in.bias"],
                    "wo": sd[p + "mlp.fc_out.weight"].T,
                    "bo": sd[p + "mlp.fc_out.bias"]},
            # one shared input norm (parallel_norms=False): ln2 mirrors
            # ln1 to keep the stacked tree shape
            "ln1": ln1, "ln2": dict(ln1),
        })
    out = {"embed": {"tokens": sd["transformer.wte.weight"]},
           "layers": _stack(layers),
           "final_norm": {"scale": sd["transformer.ln_f.weight"],
                          "bias": sd["transformer.ln_f.bias"]},
           "lm_head": sd["lm_head.weight"].T}
    if "lm_head.bias" in sd:
        out["lm_head_bias"] = sd["lm_head.bias"]
    return out


def _convert_gptneo(sd, cfg):
    """HF GPTNeoForCausalLM → functional tree (ref
    module_inject/containers/gptneo.py).  q/k/v carry no bias; out_proj
    and the MLP do."""
    layers = []
    for i in range(cfg.num_layers):
        p = f"transformer.h.{i}."
        layers.append({
            "attn": {"wq": sd[p + "attn.attention.q_proj.weight"].T,
                     "wk": sd[p + "attn.attention.k_proj.weight"].T,
                     "wv": sd[p + "attn.attention.v_proj.weight"].T,
                     "wo": sd[p + "attn.attention.out_proj.weight"].T,
                     "bo": sd[p + "attn.attention.out_proj.bias"]},
            "mlp": {"wi": sd[p + "mlp.c_fc.weight"].T,
                    "bi": sd[p + "mlp.c_fc.bias"],
                    "wo": sd[p + "mlp.c_proj.weight"].T,
                    "bo": sd[p + "mlp.c_proj.bias"]},
            "ln1": {"scale": sd[p + "ln_1.weight"],
                    "bias": sd[p + "ln_1.bias"]},
            "ln2": {"scale": sd[p + "ln_2.weight"],
                    "bias": sd[p + "ln_2.bias"]},
        })
    return {
        "embed": {"tokens": sd["transformer.wte.weight"],
                  "positions": sd["transformer.wpe.weight"]},
        "layers": _stack(layers),
        "final_norm": {"scale": sd["transformer.ln_f.weight"],
                       "bias": sd["transformer.ln_f.bias"]},
    }


def _convert_gptneox(sd, cfg):
    """HF GPTNeoXForCausalLM → functional tree (ref
    module_inject/containers/gptneox.py)."""
    nh, d = cfg.num_heads, cfg.dim_per_head
    layers = []
    for i in range(cfg.num_layers):
        p = f"gpt_neox.layers.{i}."
        (wq, wk, wv), (bq, bk, bv) = _split_headwise_qkv(
            sd[p + "attention.query_key_value.weight"],
            sd.get(p + "attention.query_key_value.bias"), nh, d)
        attn = {"wq": wq, "wk": wk, "wv": wv,
                "wo": sd[p + "attention.dense.weight"].T}
        if bq is not None:
            attn.update(bq=bq, bk=bk, bv=bv,
                        bo=sd[p + "attention.dense.bias"])
        layers.append({
            "attn": attn,
            "mlp": {"wi": sd[p + "mlp.dense_h_to_4h.weight"].T,
                    "bi": sd[p + "mlp.dense_h_to_4h.bias"],
                    "wo": sd[p + "mlp.dense_4h_to_h.weight"].T,
                    "bo": sd[p + "mlp.dense_4h_to_h.bias"]},
            "ln1": {"scale": sd[p + "input_layernorm.weight"],
                    "bias": sd[p + "input_layernorm.bias"]},
            "ln2": {"scale": sd[p + "post_attention_layernorm.weight"],
                    "bias": sd[p + "post_attention_layernorm.bias"]},
        })
    return {"embed": {"tokens": sd["gpt_neox.embed_in.weight"]},
            "layers": _stack(layers),
            "final_norm": {"scale": sd["gpt_neox.final_layer_norm.weight"],
                           "bias": sd["gpt_neox.final_layer_norm.bias"]},
            "lm_head": sd["embed_out.weight"].T}


def _convert_bert(sd, cfg):
    """HF BertForMaskedLM → functional tree (ref v1 injection
    module_inject/containers/bert.py; post-LN handled by norm_position)."""
    h = cfg.hidden_size
    layers = []
    for i in range(cfg.num_layers):
        p = f"bert.encoder.layer.{i}."
        layers.append({
            "attn": {"wq": sd[p + "attention.self.query.weight"].T,
                     "bq": sd[p + "attention.self.query.bias"],
                     "wk": sd[p + "attention.self.key.weight"].T,
                     "bk": sd[p + "attention.self.key.bias"],
                     "wv": sd[p + "attention.self.value.weight"].T,
                     "bv": sd[p + "attention.self.value.bias"],
                     "wo": sd[p + "attention.output.dense.weight"].T,
                     "bo": sd[p + "attention.output.dense.bias"]},
            "mlp": {"wi": sd[p + "intermediate.dense.weight"].T,
                    "bi": sd[p + "intermediate.dense.bias"],
                    "wo": sd[p + "output.dense.weight"].T,
                    "bo": sd[p + "output.dense.bias"]},
            # post-LN: ln1 = attention.output.LayerNorm, ln2 = output.LayerNorm
            "ln1": {"scale": sd[p + "attention.output.LayerNorm.weight"],
                    "bias": sd[p + "attention.output.LayerNorm.bias"]},
            "ln2": {"scale": sd[p + "output.LayerNorm.weight"],
                    "bias": sd[p + "output.LayerNorm.bias"]},
        })
    out = {
        "embed": {
            "tokens": sd["bert.embeddings.word_embeddings.weight"],
            "positions": sd["bert.embeddings.position_embeddings.weight"],
            "token_types": sd["bert.embeddings.token_type_embeddings.weight"],
            "norm": {"scale": sd["bert.embeddings.LayerNorm.weight"],
                     "bias": sd["bert.embeddings.LayerNorm.bias"]}},
        "layers": _stack(layers),
        # post-LN stacks never apply final_norm; identity keeps the tree
        # shape every subsystem (sharding, checkpoints) expects
        "final_norm": {"scale": np.ones((h,), np.float32),
                       "bias": np.zeros((h,), np.float32)},
    }
    # classification checkpoints (BertForSequenceClassification) carry a
    # pooler + classifier instead of the MLM head; convert them so
    # models.encoder_heads.bert_pooled_classify can serve the logits
    if "bert.pooler.dense.weight" in sd:
        out["pooler"] = {"w": sd["bert.pooler.dense.weight"].T,
                         "b": sd["bert.pooler.dense.bias"]}
    if "classifier.weight" in sd:
        out["classifier"] = {"w": sd["classifier.weight"].T,
                             "b": sd["classifier.bias"]}
    if not cfg.mlm_head:
        return out  # headless encoder (hidden states / classification)
    if "cls.predictions.transform.dense.weight" not in sd:
        raise KeyError(
            "bert checkpoint carries no MLM head (cls.predictions.*): "
            "convert a BertForMaskedLM model, or build the config with "
            "mlm_head=False for headless encoders")
    out["mlm_head"] = {
        "w": sd["cls.predictions.transform.dense.weight"].T,
        "b": sd["cls.predictions.transform.dense.bias"],
        "ln": {"scale": sd["cls.predictions.transform.LayerNorm.weight"],
               "bias": sd["cls.predictions.transform.LayerNorm.bias"]},
        "bias": sd["cls.predictions.bias"]}
    return out


def _convert_distilbert(sd, cfg):
    """HF DistilBertForMaskedLM → functional tree (ref
    module_inject/containers/distil_bert.py).  No token-type table; the
    vocab_projector weight is tied to the embeddings."""
    h = cfg.hidden_size
    layers = []
    for i in range(cfg.num_layers):
        p = f"distilbert.transformer.layer.{i}."
        layers.append({
            "attn": {"wq": sd[p + "attention.q_lin.weight"].T,
                     "bq": sd[p + "attention.q_lin.bias"],
                     "wk": sd[p + "attention.k_lin.weight"].T,
                     "bk": sd[p + "attention.k_lin.bias"],
                     "wv": sd[p + "attention.v_lin.weight"].T,
                     "bv": sd[p + "attention.v_lin.bias"],
                     "wo": sd[p + "attention.out_lin.weight"].T,
                     "bo": sd[p + "attention.out_lin.bias"]},
            "mlp": {"wi": sd[p + "ffn.lin1.weight"].T,
                    "bi": sd[p + "ffn.lin1.bias"],
                    "wo": sd[p + "ffn.lin2.weight"].T,
                    "bo": sd[p + "ffn.lin2.bias"]},
            "ln1": {"scale": sd[p + "sa_layer_norm.weight"],
                    "bias": sd[p + "sa_layer_norm.bias"]},
            "ln2": {"scale": sd[p + "output_layer_norm.weight"],
                    "bias": sd[p + "output_layer_norm.bias"]},
        })
    return {
        "embed": {
            "tokens": sd["distilbert.embeddings.word_embeddings.weight"],
            "positions": sd["distilbert.embeddings.position_embeddings.weight"],
            "norm": {"scale": sd["distilbert.embeddings.LayerNorm.weight"],
                     "bias": sd["distilbert.embeddings.LayerNorm.bias"]}},
        "layers": _stack(layers),
        "final_norm": {"scale": np.ones((h,), np.float32),
                       "bias": np.zeros((h,), np.float32)},
        "mlm_head": {
            "w": sd["vocab_transform.weight"].T,
            "b": sd["vocab_transform.bias"],
            "ln": {"scale": sd["vocab_layer_norm.weight"],
                   "bias": sd["vocab_layer_norm.bias"]},
            "bias": sd["vocab_projector.bias"]},
    }


def load_hf_model(name_or_model, dtype=None):
    """AutoModel / checkpoint path → (TransformerConfig, params).  The
    one-call porting path for reference users (ref build_hf_engine)."""
    if isinstance(name_or_model, str):
        from transformers import AutoConfig

        conf = AutoConfig.from_pretrained(name_or_model)
        if getattr(conf, "model_type", "") in ("bert", "distilbert"):
            from transformers import AutoModelForMaskedLM as Auto
        else:
            from transformers import AutoModelForCausalLM as Auto
        model = Auto.from_pretrained(name_or_model)
    else:
        model = name_or_model
    cfg = config_from_hf(model.config)
    return cfg, params_from_hf(model, cfg, dtype=dtype)


for _arch, _fn in (("gpt2", _convert_gpt2), ("llama", _convert_llama),
                   ("mistral", _convert_llama), ("qwen2", _convert_llama),
                   ("opt", _convert_opt), ("falcon", _convert_falcon),
                   ("falcon_h1", _convert_falcon_h1),
                   ("phi", _convert_phi), ("phi3", _convert_phi3),
                   ("qwen", _convert_qwen), ("bert", _convert_bert),
                   ("distilbert", _convert_distilbert),
                   ("bloom", _convert_bloom), ("gptj", _convert_gptj),
                   ("gptneox", _convert_gptneox),
                   ("gptneo", _convert_gptneo)):
    register_converter(_arch, _fn)
