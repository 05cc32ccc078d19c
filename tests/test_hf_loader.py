"""HF checkpoint import: logits parity vs transformers reference models.

The strongest conversion test: build a tiny randomly-initialized HF model
per family, convert weights with params_from_hf, and require near-equal
logits between the torch forward and our functional forward."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.models import transformer as tf  # noqa: E402
from deepspeed_tpu.models.hf_loader import (config_from_hf,  # noqa: E402
                                            params_from_hf)


def _compare(hf_model, atol=2e-3, zero_lm_head_bias=False):
    hf_model.eval()
    if zero_lm_head_bias and getattr(hf_model, "lm_head", None) is not None \
            and getattr(hf_model.lm_head, "bias", None) is not None:
        with torch.no_grad():
            hf_model.lm_head.bias.zero_()
    cfg = config_from_hf(hf_model.config).replace(dtype=jnp.float32)
    params = params_from_hf(hf_model, cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 12), dtype=np.int64)
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids)).logits.float().numpy()
    out = tf.forward(params, jnp.asarray(ids, jnp.int32), cfg)
    if isinstance(out, tuple):
        out = out[0]
    out = np.asarray(out, np.float32)
    np.testing.assert_allclose(out, ref, atol=atol, rtol=1e-3)


def test_gpt2_parity():
    from transformers import GPT2Config, GPT2LMHeadModel

    torch.manual_seed(0)
    m = GPT2LMHeadModel(GPT2Config(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64))
    _compare(m)


def test_llama_parity():
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False))
    _compare(m)


def test_mistral_parity():
    from transformers import MistralConfig, MistralForCausalLM

    torch.manual_seed(0)
    m = MistralForCausalLM(MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=8,
        tie_word_embeddings=False))
    _compare(m)


def test_qwen2_parity():
    from transformers import Qwen2Config, Qwen2ForCausalLM

    torch.manual_seed(0)
    m = Qwen2ForCausalLM(Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False))
    _compare(m)


def test_opt_parity():
    from transformers import OPTConfig, OPTForCausalLM

    torch.manual_seed(0)
    m = OPTForCausalLM(OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        do_layer_norm_before=True, word_embed_proj_dim=64))
    _compare(m)


def test_falcon_parity():
    from transformers import FalconConfig, FalconForCausalLM

    torch.manual_seed(0)
    m = FalconForCausalLM(FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True,
        new_decoder_architecture=False, parallel_attn=True, bias=False,
        alibi=False))
    _compare(m)


def test_falcon_sequential_parity():
    """parallel_attn=False (Falcon-RW sequential residual): ln2 must load
    from post_attention_layernorm, not input_layernorm."""
    from transformers import FalconConfig, FalconForCausalLM

    torch.manual_seed(0)
    m = FalconForCausalLM(FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=False,
        new_decoder_architecture=False, parallel_attn=False, bias=False,
        alibi=False))
    _compare(m)


def test_falcon_gqa_new_arch_parity():
    """Falcon-40B/180B layout: new_decoder_architecture with 1 < nkv < nh
    interleaves the fused QKV per KV group and uses ln_attn/ln_mlp parallel
    norms (ref GQAMegatronQKVParameter, module_inject/layers.py)."""
    from transformers import FalconConfig, FalconForCausalLM

    torch.manual_seed(0)
    m = FalconForCausalLM(FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_kv_heads=2, multi_query=False,
        new_decoder_architecture=True, parallel_attn=True, bias=False,
        alibi=False))
    _compare(m)


def test_phi_parity():
    from transformers import PhiConfig, PhiForCausalLM

    torch.manual_seed(0)
    m = PhiForCausalLM(PhiConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, partial_rotary_factor=0.5))
    _compare(m, zero_lm_head_bias=True)


def test_phi3_parity():
    from transformers import Phi3Config, Phi3ForCausalLM

    torch.manual_seed(0)
    m = Phi3ForCausalLM(Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False,
        pad_token_id=0))
    _compare(m)


def test_qwen_v1_parity():
    """Qwen v1 is a remote-code model (no transformers class), but its
    math is Qwen2's (rmsnorm + biased-qkv + swiglu, no GQA) in a
    different state-dict layout: fused transformer.h.*.attn.c_attn,
    mlp.w1 (up) / w2 (gate) / c_proj, intermediate_size doubled.  Re-lay a
    tiny Qwen2 checkpoint into the v1 layout and require logits parity
    against the torch forward — this pins the converter's fused splits
    and gate/up mapping against real numerics."""
    from types import SimpleNamespace

    from transformers import Qwen2Config, Qwen2ForCausalLM

    torch.manual_seed(0)
    m = Qwen2ForCausalLM(Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=64, tie_word_embeddings=False))
    m.eval()
    sd2 = {k: v for k, v in m.state_dict().items()}
    sd1 = {"transformer.wte.weight": sd2["model.embed_tokens.weight"],
           "transformer.ln_f.weight": sd2["model.norm.weight"],
           "lm_head.weight": sd2["lm_head.weight"]}
    for i in range(2):
        p2, p1 = f"model.layers.{i}.", f"transformer.h.{i}."
        sd1[p1 + "attn.c_attn.weight"] = torch.cat(
            [sd2[p2 + "self_attn.q_proj.weight"],
             sd2[p2 + "self_attn.k_proj.weight"],
             sd2[p2 + "self_attn.v_proj.weight"]], dim=0)
        sd1[p1 + "attn.c_attn.bias"] = torch.cat(
            [sd2[p2 + "self_attn.q_proj.bias"],
             sd2[p2 + "self_attn.k_proj.bias"],
             sd2[p2 + "self_attn.v_proj.bias"]], dim=0)
        sd1[p1 + "attn.c_proj.weight"] = sd2[p2 + "self_attn.o_proj.weight"]
        sd1[p1 + "mlp.w2.weight"] = sd2[p2 + "mlp.gate_proj.weight"]
        sd1[p1 + "mlp.w1.weight"] = sd2[p2 + "mlp.up_proj.weight"]
        sd1[p1 + "mlp.c_proj.weight"] = sd2[p2 + "mlp.down_proj.weight"]
        sd1[p1 + "ln_1.weight"] = sd2[p2 + "input_layernorm.weight"]
        sd1[p1 + "ln_2.weight"] = sd2[p2 + "post_attention_layernorm.weight"]
    hf_cfg = SimpleNamespace(model_type="qwen", vocab_size=128,
                             hidden_size=64, intermediate_size=256,
                             num_hidden_layers=2, num_attention_heads=4,
                             seq_length=64, rotary_emb_base=10000.0,
                             layer_norm_epsilon=1e-6)
    cfg = config_from_hf(hf_cfg).replace(dtype=jnp.float32)
    params = params_from_hf(sd1, cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 12), dtype=np.int64)
    with torch.no_grad():
        ref = m(torch.tensor(ids)).logits.float().numpy()
    out = tf.forward(params, jnp.asarray(ids, jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               atol=2e-3, rtol=1e-3)


def test_converted_model_trains():
    """End-to-end: HF GPT-2 weights → engine → loss decreases."""
    from transformers import GPT2Config, GPT2LMHeadModel

    import deepspeed_tpu as ds

    torch.manual_seed(0)
    m = GPT2LMHeadModel(GPT2Config(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64))
    cfg = config_from_hf(m.config)
    params = params_from_hf(m, cfg)
    engine, _, _, _ = ds.initialize(
        model=cfg, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 4,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 5e-3}},
                "mesh": {"data": 1}})
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(4, 17), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    losses = [float(np.asarray(engine.train_batch(batch))) for _ in range(6)]
    assert losses[-1] < losses[0]
    from deepspeed_tpu.parallel import topology

    topology._GLOBAL_TOPOLOGY = None


def test_mixtral_parity():
    from transformers import MixtralConfig, MixtralForCausalLM

    torch.manual_seed(0)
    m = MixtralForCausalLM(MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_local_experts=4,
        num_experts_per_tok=2, tie_word_embeddings=False))
    # ample capacity (set by config_from_hf) ⇒ no token drops ⇒ exact
    # top-2 routing parity with HF's dropless block
    _compare(m, atol=4e-3)


def test_qwen2_moe_parity():
    from transformers import Qwen2MoeConfig, Qwen2MoeForCausalLM

    torch.manual_seed(0)
    m = Qwen2MoeForCausalLM(Qwen2MoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=48, shared_expert_intermediate_size=96,
        decoder_sparse_step=1, norm_topk_prob=False,
        tie_word_embeddings=False))
    _compare(m, atol=4e-3)


def test_bert_parity():
    """Encoder family: bidirectional post-LN stack + MLM head logits must
    match HF BertForMaskedLM (ref module_inject/containers/bert.py)."""
    from transformers import BertConfig, BertForMaskedLM

    torch.manual_seed(0)
    m = BertForMaskedLM(BertConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, type_vocab_size=2))
    m.eval()
    cfg = config_from_hf(m.config).replace(dtype=jnp.float32)
    assert not cfg.causal and cfg.norm_position == "post" and cfg.mlm_head
    params = params_from_hf(m, cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 12), dtype=np.int64)
    tt = rng.integers(0, 2, size=(2, 12), dtype=np.int64)
    with torch.no_grad():
        ref = m(torch.tensor(ids),
                token_type_ids=torch.tensor(tt)).logits.float().numpy()
    out = np.asarray(tf.forward(params, jnp.asarray(ids, jnp.int32), cfg,
                                token_type_ids=jnp.asarray(tt, jnp.int32)),
                     np.float32)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=1e-3)


def test_bert_attention_mask_parity():
    """Key-padding mask: padded positions must not influence kept tokens'
    logits (matches HF attention_mask semantics)."""
    from transformers import BertConfig, BertForMaskedLM

    torch.manual_seed(1)
    m = BertForMaskedLM(BertConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, type_vocab_size=2))
    m.eval()
    cfg = config_from_hf(m.config).replace(dtype=jnp.float32)
    params = params_from_hf(m, cfg)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 12), dtype=np.int64)
    mask = np.ones((2, 12), np.int64)
    mask[:, 9:] = 0  # right padding
    with torch.no_grad():
        ref = m(torch.tensor(ids),
                attention_mask=torch.tensor(mask)).logits.float().numpy()
    out = np.asarray(
        tf.forward(params, jnp.asarray(ids, jnp.int32), cfg,
                   attention_mask=jnp.asarray(mask, jnp.int32)), np.float32)
    np.testing.assert_allclose(out[:, :9], ref[:, :9], atol=2e-3, rtol=1e-3)


def test_distilbert_parity():
    from transformers import DistilBertConfig, DistilBertForMaskedLM

    torch.manual_seed(0)
    m = DistilBertForMaskedLM(DistilBertConfig(
        vocab_size=128, dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        max_position_embeddings=64))
    m.eval()
    cfg = config_from_hf(m.config).replace(dtype=jnp.float32)
    assert cfg.arch == "distilbert" and cfg.type_vocab_size == 0
    params = params_from_hf(m, cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 12), dtype=np.int64)
    with torch.no_grad():
        ref = m(torch.tensor(ids)).logits.float().numpy()
    out = np.asarray(tf.forward(params, jnp.asarray(ids, jnp.int32), cfg),
                     np.float32)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=1e-3)


def test_bloom_parity():
    """ALiBi attention + embedding LayerNorm + headwise-fused qkv (ref
    module_inject/containers/bloom.py)."""
    from transformers import BloomConfig, BloomForCausalLM

    torch.manual_seed(0)
    m = BloomForCausalLM(BloomConfig(
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4))
    _compare(m)


def test_bloom_left_padded_alibi_matches_hf():
    """LEFT-padded batches: HF build_alibi_tensor derives key positions
    from attention_mask.cumsum — the bias must shift by the padding
    offset per row, not use absolute slot indices."""
    from transformers import BloomConfig, BloomForCausalLM

    torch.manual_seed(0)
    m = BloomForCausalLM(BloomConfig(
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4))
    m.eval()
    cfg = config_from_hf(m.config).replace(dtype=jnp.float32)
    params = params_from_hf(m, cfg)
    rng = np.random.default_rng(11)
    ids = rng.integers(3, cfg.vocab_size, size=(2, 12), dtype=np.int64)
    mask = np.ones((2, 12), np.int64)
    mask[0, :4] = 0   # row 0 left-padded by 4
    mask[1, :1] = 0
    with torch.no_grad():
        ref = m(torch.tensor(ids),
                attention_mask=torch.tensor(mask)).logits.float().numpy()
    out = tf.forward(params, jnp.asarray(ids, jnp.int32), cfg,
                     attention_mask=jnp.asarray(mask, jnp.int32))
    out = np.asarray(out, np.float32)
    keep = mask.astype(bool)
    np.testing.assert_allclose(out[keep], ref[keep], atol=2e-3, rtol=1e-3)


def test_gptj_parity():
    """Interleaved partial rotary + parallel block with one shared norm +
    biasless attention / biased MLP (ref containers/gptj.py).  The HF
    lm_head.bias is NOT zeroed: the converter carries it into the
    functional head's vocab-size output bias, so logits must match with a
    nonzero bias applied (the released EleutherAI weights ship one)."""
    from transformers import GPTJConfig, GPTJForCausalLM

    torch.manual_seed(0)
    m = GPTJForCausalLM(GPTJConfig(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64,
        rotary_dim=8))
    with torch.no_grad():
        # the random init leaves it zero — make the parity check prove the
        # bias actually reaches the logits
        m.lm_head.bias.uniform_(-0.5, 0.5)
    _compare(m)


@pytest.mark.parametrize("parallel", [True, False])
def test_gptneox_parity(parallel):
    """Partial rotate-half rotary + parallel residual with separate norms
    (and the sequential use_parallel_residual=False variant); headwise
    fused qkv (ref containers/gptneox.py)."""
    from transformers import GPTNeoXConfig, GPTNeoXForCausalLM

    torch.manual_seed(0)
    m = GPTNeoXForCausalLM(GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=0.25,
        use_parallel_residual=parallel))
    _compare(m)


def test_bloom_gptj_neox_generate_matches_hf():
    """The new v1-injection families serve through the KV-cached generate
    path: greedy continuations must match HF transformers' generate."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import topology
    from transformers import (BloomConfig, BloomForCausalLM, GPTJConfig,
                              GPTJForCausalLM, GPTNeoXConfig,
                              GPTNeoXForCausalLM)

    cases = [
        BloomForCausalLM(BloomConfig(vocab_size=128, hidden_size=64,
                                     n_layer=2, n_head=4)),
        GPTJForCausalLM(GPTJConfig(vocab_size=128, n_embd=64, n_layer=2,
                                   n_head=4, n_positions=64, rotary_dim=8)),
        GPTNeoXForCausalLM(GPTNeoXConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64, rotary_pct=0.25)),
    ]
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, size=(1, 10), dtype=np.int64)
    for m in cases:
        torch.manual_seed(0)
        m.eval()
        with torch.no_grad():
            # fresh LayerNorms are weight=1/bias=0, which makes ln1 == ln2
            # numerically and would mask norm-routing bugs (e.g. the v2
            # parallel_norms path) — randomize them
            for name, p in m.named_parameters():
                if "layernorm" in name.lower() or "ln_" in name.lower():
                    p.add_(torch.randn_like(p) * 0.1)
        if getattr(getattr(m, "lm_head", None), "bias", None) is not None:
            with torch.no_grad():
                m.lm_head.bias.zero_()
        cfg = config_from_hf(m.config).replace(dtype=jnp.float32)
        params = params_from_hf(m, cfg)
        with torch.no_grad():
            ref = m.generate(torch.tensor(ids), max_new_tokens=6,
                             do_sample=False).numpy()[0, 10:]
        eng = ds.init_inference(model=cfg, model_params=params,
                                dtype="float32")
        out = np.asarray(eng.generate(ids.astype(np.int32),
                                      max_new_tokens=6))[0, 10:]
        np.testing.assert_array_equal(out, ref, err_msg=cfg.arch)
        topology._GLOBAL_TOPOLOGY = None


def test_bert_sequence_classification_parity():
    """Classification checkpoints: pooler + classifier convert, and
    pooled logits match HF BertForSequenceClassification (eval mode)."""
    from transformers import BertConfig, BertForSequenceClassification

    from deepspeed_tpu.models.encoder_heads import bert_pooled_classify

    torch.manual_seed(2)
    m = BertForSequenceClassification(BertConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, type_vocab_size=2, num_labels=3))
    m.eval()
    cfg = config_from_hf(m.config).replace(dtype=jnp.float32,
                                           mlm_head=False)
    params = params_from_hf(m, cfg)
    assert "pooler" in params and "classifier" in params
    rng = np.random.default_rng(2)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 12), dtype=np.int64)
    with torch.no_grad():
        ref = m(torch.tensor(ids)).logits.float().numpy()
    hidden = tf.forward(params, jnp.asarray(ids, jnp.int32), cfg,
                        return_hidden=True)
    out = np.asarray(bert_pooled_classify(params, hidden), np.float32)
    assert out.shape == (2, 3)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=1e-3)


def test_gptneo_parity():
    """GPT-Neo: alternating global/local attention (layer pairs with a
    static per-member window), learned positions, unscaled scores, and
    biasless q/k/v with biased out/mlp (ref containers/gptneo.py).
    window_size=8 < seq=12 so the local layer's mask is live."""
    from transformers import GPTNeoConfig, GPTNeoForCausalLM

    torch.manual_seed(0)
    m = GPTNeoForCausalLM(GPTNeoConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        attention_types=[[["global", "local"], 1]], window_size=8,
        max_position_embeddings=64, intermediate_size=128))
    cfg = config_from_hf(m.config).replace(dtype=jnp.float32)
    assert cfg.alt_window and cfg.sliding_window == 8
    assert cfg.attn_scale == 1.0
    _compare(m)


def test_gptneo_generate_matches_hf():
    """GPT-Neo serves through the paged ragged path (paired alt-window
    scan + learned positions): greedy continuation equals HF generate."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import topology
    from transformers import GPTNeoConfig, GPTNeoForCausalLM

    torch.manual_seed(1)
    m = GPTNeoForCausalLM(GPTNeoConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        attention_types=[[["global", "local"], 1]], window_size=8,
        max_position_embeddings=64, intermediate_size=128)).eval()
    cfg = config_from_hf(m.config).replace(dtype=jnp.float32)
    params = params_from_hf(m, cfg)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 128, size=(1, 12), dtype=np.int64)
    with torch.no_grad():
        ref = m.generate(torch.tensor(ids), max_new_tokens=6,
                         do_sample=False).numpy()[0, 12:]
    eng = ds.init_inference(model=cfg, model_params=params,
                            dtype="float32")
    out = np.asarray(eng.generate(ids.astype(np.int32),
                                  max_new_tokens=6))[0, 12:]
    np.testing.assert_array_equal(out, ref)
    topology._GLOBAL_TOPOLOGY = None


def test_opt_generate_matches_hf():
    """Regression: the ragged embed path used to gate learned positions
    on arch == 'gpt2', silently dropping OPT's position embeddings in
    paged serving."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import topology
    from transformers import OPTConfig, OPTForCausalLM

    torch.manual_seed(2)
    m = OPTForCausalLM(OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        do_layer_norm_before=True, word_embed_proj_dim=64)).eval()
    cfg = config_from_hf(m.config).replace(dtype=jnp.float32)
    params = params_from_hf(m, cfg)
    rng = np.random.default_rng(2)
    ids = rng.integers(4, 128, size=(1, 10), dtype=np.int64)
    with torch.no_grad():
        ref = m.generate(torch.tensor(ids), max_new_tokens=6,
                         do_sample=False).numpy()[0, 10:]
    eng = ds.init_inference(model=cfg, model_params=params,
                            dtype="float32")
    out = np.asarray(eng.generate(ids.astype(np.int32),
                                  max_new_tokens=6))[0, 10:]
    np.testing.assert_array_equal(out, ref)
    topology._GLOBAL_TOPOLOGY = None
