"""A mixed-attention model (``TransformerConfig.mixed``: Trinity, HF
``afmoe``) through ``InferenceEngineV2`` at the tiny preset, float32,
against the plain reference ``benchmark/reference/afmoe.py``: chunked
prefill then decode THROUGH the window's edge and through freed window
pages (also with every free page of the window pool poisoned), the shares
of an expert layer against the uncut layer, rotary on the window layers
alone, what is refused by name, and the step programs of the models the
benchmark had, held to the parent commit's."""

import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import afmoe  # noqa: E402
from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.v2 import model as v2_model  # noqa: E402
from deepspeed_tpu.inference.v2.engine_v2 import \
    RecurrentStateUnsupported  # noqa: E402
from deepspeed_tpu.models import get_model_config  # noqa: E402
from deepspeed_tpu.models import transformer as tf_model  # noqa: E402
from deepspeed_tpu.moe.sharded_moe import moe_forward_held  # noqa: E402

# window 24 = three pages of 8; a step of 16 rows; a context of 20 pages
ENGINE = {"dtype": "float32",
          "memory_config": {"num_blocks": 48, "window_blocks": 16,
                            "block_size": 8},
          "max_context": 160,
          "state_manager": {"max_tracked_sequences": 4,
                            "max_ragged_batch_size": 16}}
# float32 arithmetic on both sides: what is left is the order of the sums
# (the paged gather against the dense mask, the experts' tiles against an
# expert at a time): 1e-5 of the logits' rms with room, where one wrong
# key, a rotated full layer or a freed page read reads 1e-1 or NaN
TOLERANCE = 2e-4


def reference_config(model) -> dict:
    """The published names ``reference/afmoe.py`` reads, from a model."""
    mx = model.mixed
    return {"hidden_size": model.hidden_size,
            "num_hidden_layers": model.num_layers,
            "num_attention_heads": model.num_heads,
            "num_key_value_heads": model.kv_heads,
            "head_dim": model.dim_per_head,
            "rms_norm_eps": model.layernorm_eps,
            "rope_theta": model.rope_theta,
            "sliding_window": mx.sliding_window,
            "layer_types": list(mx.layer_types),
            "num_dense_layers": mx.num_dense_layers,
            "num_experts": mx.experts_held[1],
            "experts_held_first": mx.experts_held[0],
            "num_experts_per_tok": mx.num_experts_per_tok,
            "route_scale": mx.route_scale, "route_norm": True,
            "mup_enabled": True}


def seeded_bias(params, seed=5):
    """A non-zero selection bias (the seeded weights' is zeros), of the
    size of the scores at the edge of the choice: it must move choices."""
    bias = params["layers"]["moe"]["bias"]
    noise = jax.random.normal(jax.random.PRNGKey(seed), bias.shape) * 0.05
    return {**params, "layers": {**params["layers"], "moe": {
        **params["layers"]["moe"], "bias": noise.astype(bias.dtype)}}}


def build(layers=8, engine=None, **overrides):
    model = get_model_config("trinity-tiny", num_layers=layers,
                             param_dtype=jnp.float32, **overrides)
    params = seeded_bias(tf_model.init_params(
        model.replace(dtype=jnp.float32), jax.random.PRNGKey(3)))
    return InferenceEngineV2(model, dict(engine or ENGINE),
                             model_params=params), model


# not NaN: a page handed out again still holds the poison in the rows not
# yet written, which are masked, and a NaN in a masked key's VALUE row
# survives the product with a probability of exactly zero (in the XLA path
# and in the kernel alike); 1e30 does not, and read unmasked it is no
# smaller a fault
POISON = 1e30


def poison_free_window_pages(eng):
    """``POISON`` in every row of every page on the window pool's free
    list."""
    bs = eng.cfg.block_size
    free = np.asarray(eng.state_manager.window_allocator._free)
    if not len(free):
        return
    rows = (free[:, None] * bs + np.arange(bs)[None]).reshape(-1)
    eng.state = {k: a.at[:, :, rows].set(POISON)
                 for k, a in eng.state.items()}


def run_through_window(eng, prompt, decode, poison):
    """Logits of the prompt's last position and of ``decode`` greedy steps
    after it, through ``put``; the tokens; the most window pages held."""
    uid, rows, toks, held = 7, [], [], 0
    seq = None
    out = eng.put([uid], [prompt])
    while True:
        seq = eng.state_manager.get(uid)
        held = max(held, len(seq.window_blocks) - seq.window_freed)
        if poison:
            poison_free_window_pages(eng)
        if uid in out:
            rows.append(np.asarray(out[uid], np.float32))
            if len(rows) > decode:
                break
            toks.append(int(rows[-1].argmax()))
            eng.extend(uid, toks[-1])
        out = eng.put([], [])
    freed = seq.window_freed
    eng.flush(uid)
    return np.stack(rows), toks, held, freed


@pytest.mark.parametrize("layers,poison", [(8, False), (8, True),
                                           (12, False)])
def test_chunked_prefill_and_decode_through_the_window(layers, poison):
    """A 70-token prompt in chunks of 16, then 30 decoded tokens: the
    window (24) is passed inside the prompt, pages are freed behind it
    from the fourth chunk on, and every decode row reads across a page
    edge.  12 layers: the period of four is one scan of two repeats."""
    eng, model = build(layers)
    if layers == 12:
        assert (2, 4, 2) in v2_model.layer_segments(
            model.mixed.kinds(layers))
    prompt = np.random.default_rng(1).integers(0, 512, size=70).tolist()
    got, toks, held, freed = run_through_window(eng, prompt, 30, poison)
    ref = np.asarray(afmoe.logits(
        eng.params, np.asarray([prompt + toks]), reference_config(model),
        jax.devices()[0], last=31))[0]
    assert np.isfinite(got).all()
    err = np.sqrt(((got - ref) ** 2).mean() / (ref ** 2).mean())
    assert err < TOLERANCE, err
    assert (got.argmax(-1) == ref.argmax(-1)).all()
    # ceil((24 + 16) / 8) + 1 pages at the most, whatever the context;
    # (100 - 24) // 8 pages gone by the last step
    assert held <= 6 and freed == 9
    mgr = eng.state_manager
    assert mgr.window_allocator.free_blocks == 15
    assert mgr.allocator.free_blocks == 47


def test_a_page_freed_too_early_shows():
    """The comparison sees the mechanism: an allocator that frees two
    pages earlier than the window allows reads far off the reference."""
    eng, model = build()
    eng.state_manager.window -= 16
    prompt = np.random.default_rng(1).integers(0, 512, size=70).tolist()
    got, toks, *_ = run_through_window(eng, prompt, 4, True)
    ref = np.asarray(afmoe.logits(
        eng.params, np.asarray([prompt + toks]), reference_config(model),
        jax.devices()[0], last=5))[0]
    assert np.sqrt(((got - ref) ** 2).mean() / (ref ** 2).mean()) > 0.01


def test_rotary_on_the_window_layers_alone():
    """Full layers carry no rotary: a model whose layers are all full
    does not know ``rope_theta``, bit for bit; the mixed model does."""
    prompt = np.random.default_rng(2).integers(0, 512, size=40).tolist()

    def logits(theta, types=None):
        kw = {"layer_types": types} if types else {}
        eng, _ = build(rope_theta=theta, **kw)
        return run_through_window(eng, prompt, 2, False)[0]

    full = ("full_attention",) * 8
    assert np.array_equal(logits(1e4, full), logits(5e2, full))
    assert not np.allclose(logits(1e4), logits(5e2), atol=1e-3)


def test_shares_add_up_to_the_uncut_layer():
    """Section 4's shares test: the four shares of the tiny preset's 16
    experts, each through the PROGRAM's held layer with the router over
    all 16, and the shared expert counted once, add up to the uncut
    reference's layer."""
    model = get_model_config("trinity-tiny", experts_held=(0, 16),
                             param_dtype=jnp.float32,
                             dtype=jnp.float32)
    moe = seeded_bias(tf_model.init_params(
        model, jax.random.PRNGKey(4)))["layers"]["moe"]
    mx = model.mixed
    m = jax.random.normal(jax.random.PRNGKey(9), (48, model.hidden_size))
    m = m + 0.5                     # the stream's shared part, as seeded
    layer = 2
    total = tf_model._mlp_block(
        m, jax.tree.map(lambda a: a[layer], moe["shared"]), model)
    chosen_somewhere = 0
    for first in range(0, 16, 4):
        share = {**moe, **{n: moe[n][:, first:first + 4]
                           for n in ("wg", "wi", "wo")}}
        part = moe_forward_held(m, share, layer, first=first,
                                top_k=mx.num_experts_per_tok,
                                scale=mx.route_scale)
        chosen_somewhere += bool(jnp.abs(part).sum() > 0)
        total = total + part
    assert chosen_somewhere >= 2
    cfg = dict(reference_config(model), num_experts=16, experts_held_first=0)
    with jax.default_matmul_precision("highest"):
        ref = afmoe.expert_layer(cfg, jax.devices()[0])(m[None], moe,
                                                        layer)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


REFUSED = {
    "prefix adoption": lambda eng: eng.admit(
        9, list(range(20)), cached_blocks=[1], num_cached=8),
    "verify_step": lambda eng: eng.verify_step({7: [1, 2]}),
    "rewind": lambda eng: eng.rewind(7, [1, 2, 3], 2),
    "export": lambda eng: eng.export_kv_chain(7),
    "import": lambda eng: eng.import_kv_chain({"geom": (), "tokens": []}),
    "audit of the verify step": lambda eng: eng.audit_step_args("verify"),
    "server: prefix cache": lambda eng: _server(
        eng, {"prefix_cache": {"enabled": True}}),
    "server: spec decoder": lambda eng: _server(eng, {},
                                                spec_decoder=object()),
    "server: hand-off": lambda eng: _server(eng, {}).submit(
        [1, 2, 3], handoff=True),
}


def _server(eng, config, **kw):
    from deepspeed_tpu.serving import InferenceServer

    return InferenceServer(eng, config, **kw)



@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_needs_a_freed_page_is_refused_by_name(what):
    eng, _ = build()
    eng.admit(7, list(range(30)))
    eng.step()
    with pytest.raises(RecurrentStateUnsupported, match="page pool of"):
        REFUSED[what](eng)


def test_programs_that_keep_one_table_refuse_the_model():
    model = get_model_config("trinity-tiny")
    with pytest.raises(NotImplementedError, match="window"):
        v2_model.ragged_forward_verify(
            None, None, None, *([None] * 7), cfg=model, block_size=8)
    with pytest.raises(NotImplementedError, match="fused decode loop"):
        v2_model.ragged_decode_loop(
            None, None, None, None, None, None, None, None, None,
            cfg=model, block_size=8, n_steps=1, greedy=True)
    with pytest.raises(NotImplementedError, match="mixes window-24"):
        tf_model.forward(None, jnp.zeros((1, 4), jnp.int32), model)
    with pytest.raises(ValueError, match="page pool of their own"):
        v2_model.ragged_forward(
            {"layers": {}, "embed": {}}, jnp.zeros((1,)), None,
            *([None] * 7), cfg=model, block_size=8)


def test_generate_and_server_streams_agree():
    """The server's streams under load equal ``generate()``'s, greedy,
    with requests long enough to free pages while others are admitted."""
    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 512, size=n).tolist()
               for n in (50, 9, 33, 70, 21)]
    eng, _ = build()
    want = eng.generate(prompts, max_new_tokens=20)
    eng, _ = build()
    srv = InferenceServer(eng, {})
    srv.start()
    try:
        streams = [srv.submit(p, SamplingParams(max_new_tokens=20))
                   for p in prompts]
        got = [list(s) for s in streams]
    finally:
        srv.stop(drain=False, timeout=30)
    assert got == want
    assert eng.free_window_blocks == 15 and eng.free_blocks == 47


def test_the_step_names_the_stages_the_latent_path_uses():
    """No new stage name: the lowered step of the tiny preset names a
    plain block's stages and the held experts', and nothing else."""
    import re

    from deepspeed_tpu.utils import xplane

    eng, _ = build()
    fn, args = eng.audit_step_args("decode")
    stacks = set(re.findall(r'loc\("([^"]+)"',
                            fn.lower(*args).as_text(debug_info=True)))
    assert {xplane._stage_of(s) for s in stacks} - {xplane._UNSCOPED} == {
        "embed", "layers", "attn.qkv", "attn.append", "attn.read",
        "attn.out", "mlp", "head", "moe.router", "moe.dispatch",
        "moe.experts", "moe.combine", "moe.shared"}


# sha256 (16 hex digits) of the lowered decode-bucket step of each plain
# model the benchmark had, read on the parent commit (b5e58af) with this
# engine configuration: a model whose layers are all of one kind gets the
# program it got before.  (Read under this suite's conftest: eight virtual
# devices; a change of the JAX version moves them all, on both commits.
# The two latent models' are PR 49's: `wq_b`'s and `idx_wq`'s products went
# behind a barrier, `latent._head_product`, and nothing else moved.)
PARENT_STEP = {"mistral-tiny": "c9237077b6f347ef",
               "falcon-h1-tiny": "0a917515148bfc20",
               "gptneo-tiny": "04cea80fd7a5dfc2",
               "dots3-note-tiny": "771bfd007280ee70",
               "glm-5-tiny": "ae31c93635ea6b34"}
PARENT_ENGINE = {"dtype": "float32",
                 "memory_config": {"num_blocks": 32, "block_size": 8},
                 "max_context": 64,
                 "state_manager": {"max_tracked_sequences": 4,
                                   "max_ragged_batch_size": 32}}


@pytest.mark.parametrize("name", sorted(PARENT_STEP))
def test_other_models_step_programs_are_the_parents(name):
    eng = InferenceEngineV2(get_model_config(name), dict(PARENT_ENGINE))
    fn, args = eng.audit_step_args("decode")
    text = fn.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_STEP[name]
