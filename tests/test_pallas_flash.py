"""Parity tests for the repo-owned Pallas flash attention kernel
(deepspeed_tpu/ops/pallas/flash_mha.py) run through the Pallas interpreter
on the CPU mesh. Ref test model: tests/unit/ops/transformer/inference
attention parity in the reference suite."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the package re-exports the flash_mha *function* under the same name as the
# submodule; import the module itself for INTERPRET toggling
fm = importlib.import_module("deepspeed_tpu.ops.pallas.flash_mha")


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = fm.INTERPRET
    fm.INTERPRET = True
    yield
    fm.INTERPRET = old


def _ref_attn(q, k, v, causal, scale):
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        kf = jnp.repeat(kf, hq // hkv, axis=1)
        vf = jnp.repeat(vf, hq // hkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        S = q.shape[2]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf)


CASES = [
    # b, hq, hkv, s, d, causal
    (1, 2, 2, 256, 64, True),     # MHA
    (1, 4, 2, 256, 64, True),     # GQA 2x
    (1, 4, 1, 128, 64, True),     # MQA
    (1, 2, 2, 200, 64, True),     # odd length (pad + mask path)
    (1, 2, 2, 256, 64, False),    # non-causal
]


@pytest.mark.parametrize("b,hq,hkv,s,d,causal", CASES)
def test_forward_parity(b, hq, hkv, s, d, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    out = fm.flash_mha(q, k, v, causal)
    ref = _ref_attn(q, k, v, causal, 1.0 / np.sqrt(d))
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    assert err < 0.05, err


@pytest.mark.parametrize("b,hq,hkv,s,d,causal", [CASES[1], CASES[3]])
def test_grad_parity(b, hq, hkv, s, d, causal):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    w = jnp.linspace(0.0, 1.0, d)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    scale = 1.0 / np.sqrt(d)
    g1 = jax.grad(loss(lambda q, k, v: fm.flash_mha(q, k, v, causal)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: _ref_attn(q, k, v, causal, scale)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        a32 = a.astype(jnp.float32)
        b32 = b_.astype(jnp.float32)
        rel = float(jnp.linalg.norm((a32 - b32).ravel())
                    / (jnp.linalg.norm(b32.ravel()) + 1e-9))
        assert rel < 0.02, rel


def test_supports_budget():
    assert fm.supports(1024, 64)
    assert fm.supports(8192, 128)
    assert fm.supports(65536, 128)          # KV-blocked long-context path
    assert fm.supports(262144, 128)
    assert not fm.supports(1 << 20, 128)
    assert fm._supports_resident(1024, 64)
    assert fm._supports_resident(2048, 128)
    # past _RESIDENT_MAX_SEQ the blocked kernels are measured faster
    # (r04 crossover study) even though 8192x64 fits the VMEM budget
    assert not fm._supports_resident(8192, 64)
    assert not fm._supports_resident(16384, 128)
    # ...and plan() is what the calls run on: resident lengths run their
    # live part only under a mask and keep the one-shot kernels without one
    assert fm.plan(1024, 64).fwd.path == "live"
    assert fm.plan(2048, 128, group=4, window=4096).fwd.path == "live"
    assert fm.plan(1024, 64, causal=False).fwd.path == "oneshot"
    for s, d in ((8192, 64), (16384, 128)):
        p = fm.plan(s, d)
        assert {p.fwd.path, p.dq.path, p.dkv.path} == {"blocked"}
        assert (p.fwd.bq, p.fwd.bk) == (1024, 1024)   # r04's choice stands
    assert fm.plan(8192, 128, group=4).dkv[:3] == ("blocked", 512, 1024)
    with pytest.raises(ValueError, match="KV-blocked ceiling"):
        fm.plan(1 << 20, 128)


def test_resident_bwd_vmem_budget():
    """The grouped resident dkv kernel holds group× the q-side in VMEM;
    Llama-3 geometry (group=4, S=1024, D=128) measured 17.55M against the
    16M scoped-vmem limit on a real v5e (r04), so the backward must route
    to the KV-blocked path there while the r02-tuned MHA d=64 config
    keeps the resident fast path — and under a mask the live kernel's
    segment-sized intermediates let S=2048, d=64 (opt-1.3b) stay resident
    too, which the one-shot kernel's [s_pad, bq] ones did not."""
    assert not fm._resident_bwd_fits(1024, 128, 4, fm._choose_bq(1024))
    assert fm._resident_bwd_fits(1024, 64, 1, fm._choose_bq(1024))
    assert not fm._resident_bwd_fits(2048, 64, 1, fm._choose_bq(2048))
    llama, gpt2, opt = (fm.plan(1024, 128, group=4), fm.plan(1024, 64),
                        fm.plan(2048, 64))
    assert (llama.fwd.path, llama.dq.path, llama.dkv.path) == (
        "live", "blocked", "blocked")
    assert (llama.dkv.bq, llama.dkv.bk) == (512, 1024)
    for p in (gpt2, opt):
        assert (p.fwd.path, p.dq.path, p.dkv.path) == ("live",) * 3
    dense = fm.plan(2048, 64, causal=False)    # nothing to skip: as before
    assert (dense.fwd.path, dense.dq.path) == ("oneshot", "blocked")


@pytest.mark.parametrize("s,d", [(1024, 64), (2048, 64)])
def test_plan_executed_share(s, d):
    """The two train cells' shapes: under the causal mask forward and dq
    execute at most 0.65 of S² (1.0 before PR 47) and dkv, whose products
    need k blocks of 512 rows to pay for their weights (the block sweep:
    flash_mha._LIVE_EDGE_KV), at most 0.75 (1.0 before for gpt2, 0.75 for
    opt's blocked backward, now 0.625); never less than the pairs the
    mask keeps, and a dense call executes all of it."""
    p = fm.plan(s, d)
    assert p.live_pairs == s * (s + 1) // 2
    for kp, most in ((p.fwd, 0.65), (p.dq, 0.65), (p.dkv, 0.75)):
        assert p.live_pairs <= kp.executed_pairs <= most * s * s, kp
    assert 0.5 < p.executed_share_fwd <= 0.65
    assert 0.5 < p.executed_share_bwd <= 0.70
    dense = fm.plan(s, d, causal=False)
    assert dense.live_pairs == s * s
    assert dense.executed_share_fwd == dense.executed_share_bwd == 1.0
    # a window narrower than S cuts the far side as well
    w = fm.plan(s, d, window=256)
    assert w.live_pairs == sum(min(r + 1, 256) for r in range(s))
    assert w.live_pairs <= w.fwd.executed_pairs < p.fwd.executed_pairs
    assert w.live_pairs <= w.dkv.executed_pairs <= p.dkv.executed_pairs


LIVE_CASES = [
    # b, hq, hkv, s, d, window, backward's path: the skip ENGAGES (four q
    # blocks or more); the grouped dkv kernel's q side is what VMEM refuses
    pytest.param(1, 2, 2, 1024, 64, None, "live", id="mha-1k"),
    pytest.param(1, 1, 1, 2048, 64, None, "live", id="mha-2k-opt"),
    pytest.param(1, 4, 2, 1024, 64, None, "live", id="gqa2"),
    pytest.param(1, 2, 1, 1024, 64, None, "live", id="mqa"),
    pytest.param(1, 4, 1, 1024, 64, None, "blocked", id="mqa4-bwd-blocked"),
    pytest.param(1, 2, 2, 1000, 64, None, "live", id="odd-length"),
    pytest.param(1, 2, 2, 1024, 64, 300, "live", id="window-300"),
    pytest.param(1, 2, 2, 1100, 64, 200, "live", id="window-200-odd"),
    pytest.param(1, 2, 1, 1100, 64, 200, "blocked", id="window-200-odd-gqa"),
    pytest.param(1, 2, 2, 1024, 64, 64, "live", id="window-one-tile"),
    pytest.param(1, 4, 1, 1024, 128, 600, "blocked", id="d128-bwd-blocked"),
]


@pytest.mark.parametrize("b,hq,hkv,s,d,window,bwd", LIVE_CASES)
def test_live_part_parity(b, hq, hkv, s, d, window, bwd):
    """Forward and gradients against the dense reference at shapes where
    the resident kernels have dead chunks to leave out, interior chunks
    to run unmasked and edge chunks to mask: causal, causal +
    window, tail padding, GQA and MQA; ``window-one-tile``: every row's
    whole live range lies in one or two tiles."""
    p = fm.plan(s, d, hq // hkv, True, window)
    assert p.fwd.path == "live" and p.fwd.s_pad // p.fwd.bq >= 4
    assert p.fwd.executed_pairs < p.fwd.s_pad ** 2
    assert p.dq.path == p.dkv.path == bwd
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    w = jnp.linspace(0.0, 1.0, d)
    scale = 1.0 / np.sqrt(d)

    def ref(q, k, v):
        if window is None:
            return _ref_attn(q, k, v, True, scale)
        return _ref_attn_window(q, k, v, True, scale, window)

    def ours(q, k, v):
        return fm.flash_mha(q, k, v, True, None, window)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    assert float(jnp.max(jnp.abs(ours(q, k, v) - ref(q, k, v)))) < 5e-5
    g1 = jax.grad(loss(ours), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        rel = float(jnp.linalg.norm((a - b_).ravel())
                    / (jnp.linalg.norm(b_.ravel()) + 1e-9))
        assert rel < 1e-4, rel


def test_live_part_parity_bf16():
    """The train cells' precision (bf16 operands, fp32 scores) at the
    file's bf16 tolerances, four q blocks."""
    b, hq, hkv, s, d = 1, 2, 2, 1024, 64
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    w = jnp.linspace(0.0, 1.0, d)
    scale = 1.0 / np.sqrt(d)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    out = fm.flash_mha(q, k, v, True)
    ref = _ref_attn(q, k, v, True, scale)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < 0.05
    g1 = jax.grad(loss(lambda q, k, v: fm.flash_mha(q, k, v, True)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: _ref_attn(q, k, v, True, scale)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        a32, b32 = a.astype(jnp.float32), b_.astype(jnp.float32)
        rel = float(jnp.linalg.norm((a32 - b32).ravel())
                    / (jnp.linalg.norm(b32.ravel()) + 1e-9))
        assert rel < 0.02, rel


def test_gqa_d128_grad_parity_blocked_fallback():
    """Grad parity through the footprint-driven blocked-backward fallback
    (forward stays resident, backward goes KV-blocked): the exact
    llama3-8b head geometry that VMEM-OOMed on hardware in r04."""
    b, hq, hkv, s, d = 1, 8, 2, 1024, 128
    assert fm._supports_resident(s, d)  # fwd resident...
    assert not fm._resident_bwd_fits(   # ...bwd must fall back
        s, d, hq // hkv, fm._choose_bq(s))
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    w = jnp.linspace(0.0, 1.0, d)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    scale = 1.0 / np.sqrt(d)
    g1 = jax.grad(loss(lambda q, k, v: fm.flash_mha(q, k, v, True)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: _ref_attn(q, k, v, True, scale)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        a32, b32 = a.astype(jnp.float32), b_.astype(jnp.float32)
        rel = float(jnp.linalg.norm((a32 - b32).ravel())
                    / (jnp.linalg.norm(b32.ravel()) + 1e-9))
        assert rel < 0.02, rel


BLOCKED_CASES = [
    # b, hq, hkv, s, d, causal
    (1, 4, 2, 1024, 64, True),    # GQA, 2x2 blocks
    (1, 2, 2, 1280, 64, True),    # pad path (s_pad = 1536, ragged tail)
    (1, 4, 1, 1024, 64, False),   # MQA, non-causal
]


@pytest.fixture
def _force_blocked(monkeypatch):
    monkeypatch.setattr(fm, "_supports_resident", lambda s, d: False)


@pytest.mark.parametrize("b,hq,hkv,s,d,causal", BLOCKED_CASES)
def test_blocked_forward_parity(b, hq, hkv, s, d, causal, _force_blocked):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    out = fm.flash_mha(q, k, v, causal)
    ref = _ref_attn(q, k, v, causal, 1.0 / np.sqrt(d))
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    assert err < 0.05, err


@pytest.mark.parametrize("b,hq,hkv,s,d,causal",
                         [BLOCKED_CASES[0], BLOCKED_CASES[1]])
def test_blocked_grad_parity(b, hq, hkv, s, d, causal, _force_blocked):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    w = jnp.linspace(0.0, 1.0, d)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    scale = 1.0 / np.sqrt(d)
    g1 = jax.grad(loss(lambda q, k, v: fm.flash_mha(q, k, v, causal)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: _ref_attn(q, k, v, causal, scale)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        a32 = a.astype(jnp.float32)
        b32 = b_.astype(jnp.float32)
        rel = float(jnp.linalg.norm((a32 - b32).ravel())
                    / (jnp.linalg.norm(b32.ravel()) + 1e-9))
        assert rel < 0.02, rel


def test_long_context_16k_forward():
    """S=16K naturally routes to the KV-blocked path (resident budget is
    8K at d=128 / 128·s_pad score cap); oracle is the independently-written
    FPDT chunked online-softmax attention (O(chunk) memory — a full [S,S]
    reference would need multi-GB scores on the CPU runner)."""
    from deepspeed_tpu.sequence.fpdt import chunked_attention

    s, d = 16384, 64
    assert not fm._supports_resident(s, d)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 2, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 1, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 1, s, d), jnp.bfloat16)
    out = fm.flash_mha(q, k, v, True)
    ref = chunked_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                            v.swapaxes(1, 2), chunk_size=2048,
                            causal=True).swapaxes(1, 2)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < 0.05, err


def test_any_length_no_fallback(monkeypatch):
    """flash_attention dispatches s % 128 != 0 through the repo kernel
    (pad+mask), not the O(S²) XLA path — verified by pretending to be on
    TPU (interpret mode) and asserting the repo kernel actually ran."""
    from deepspeed_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    calls = {"n": 0}
    real = fm._fwd

    def counting_fwd(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(fm, "_fwd", counting_fwd)

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 200, 4, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 200, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 200, 2, 64), jnp.bfloat16)
    out = fa.flash_attention(q, k, v, causal=True, sm_scale=None,
                             impl="auto")
    assert calls["n"] == 1, "repo kernel was not used for s % 128 != 0"
    ref = _ref_attn(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                    True, 1.0 / np.sqrt(64)).swapaxes(1, 2)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < 0.05


def _ref_attn_window(q, k, v, causal, scale, window):
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        kf = jnp.repeat(kf, hq // hkv, axis=1)
        vf = jnp.repeat(vf, hq // hkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    S = q.shape[2]
    rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    mask = rows - cols < window
    if causal:
        mask &= cols <= rows
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf)


@pytest.mark.parametrize("b,hq,hkv,s,d,window",
                         [(1, 2, 2, 256, 64, 96),   # window < S
                          (1, 4, 2, 256, 64, 128),  # GQA
                          (1, 2, 2, 200, 64, 64)])  # ragged tail
def test_sliding_window_forward_parity(b, hq, hkv, s, d, window):
    """Mistral sliding-window masking in the resident kernel (ref
    transformer.py _attention_scores window semantics: q - k < window)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    out = fm.flash_mha(q, k, v, True, None, window)
    ref = _ref_attn_window(q, k, v, True, 1.0 / np.sqrt(d), window)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    assert err < 5e-5, err


def test_sliding_window_blocked_grads(_force_blocked):
    """Window masking + grid skip in the KV-blocked path, fwd and bwd
    (grid-level skip must not drop in-window tiles)."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    b, hq, hkv, s, d, window = 1, 2, 1, 1536, 64, 700
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    w = jnp.linspace(0.0, 1.0, d)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    scale = 1.0 / np.sqrt(d)
    out = fm.flash_mha(q, k, v, True, None, window)
    ref = _ref_attn_window(q, k, v, True, scale, window)
    assert float(jnp.max(jnp.abs(out - ref))) < 5e-5
    g1 = jax.grad(loss(lambda q, k, v: fm.flash_mha(q, k, v, True, None,
                                                    window)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: _ref_attn_window(q, k, v, True,
                                                        scale, window)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        rel = float(jnp.linalg.norm((a - b_).ravel())
                    / (jnp.linalg.norm(b_.ravel()) + 1e-9))
        assert rel < 1e-4, rel


def test_sliding_window_resident_grads():
    """Window gradients on the RESIDENT path (the default at training
    lengths) — fwd-only coverage there would ship untested dq/dkv
    masking."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    b, hq, hkv, s, d, window = 1, 2, 1, 256, 64, 96
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    assert fm._supports_resident(s, d)  # really the resident path
    w = jnp.linspace(0.0, 1.0, d)
    scale = 1.0 / np.sqrt(d)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    g1 = jax.grad(loss(lambda q, k, v: fm.flash_mha(q, k, v, True, None,
                                                    window)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: _ref_attn_window(q, k, v, True,
                                                        scale, window)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        rel = float(jnp.linalg.norm((a - b_).ravel())
                    / (jnp.linalg.norm(b_.ravel()) + 1e-9))
        assert rel < 1e-4, rel


@pytest.mark.parametrize("bq,bk", [(256, 512), (512, 256)])
def test_blocked_asymmetric_blocks_parity(bq, bk, _force_blocked,
                                          monkeypatch):
    """bq != bk exercises the generalized diagonal clamps
    (_clamped_kv_index / the dkv q-side clamp use block-unit division,
    not equality) — fwd and grads must match the dense reference."""
    monkeypatch.setattr(fm, "_BLK_Q", bq)
    monkeypatch.setattr(fm, "_BLK_K", bk)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    b, hq, hkv, s, d = 1, 2, 1, 1280, 64  # ragged tail vs 512-step pad
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    out = fm.flash_mha(q, k, v, True)
    ref = _ref_attn(q, k, v, True, 1.0 / np.sqrt(d))
    assert float(jnp.max(jnp.abs(out - ref))) < 5e-5
    w = jnp.linspace(0.0, 1.0, d)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    g1 = jax.grad(loss(lambda q, k, v: fm.flash_mha(q, k, v, True)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: _ref_attn(q, k, v, True,
                                                 1.0 / np.sqrt(d))),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        rel = float(jnp.linalg.norm((a - b_).ravel())
                    / (jnp.linalg.norm(b_.ravel()) + 1e-9))
        assert rel < 1e-4, rel


@pytest.mark.parametrize("bq,bk", [(256, 512), (512, 256)])
def test_blocked_asymmetric_window_parity(bq, bk, _force_blocked,
                                          monkeypatch):
    """Sliding window + asymmetric blocks: the window clamp's lo/hi block
    arithmetic must not drop live tiles."""
    monkeypatch.setattr(fm, "_BLK_Q", bq)
    monkeypatch.setattr(fm, "_BLK_K", bk)
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    b, hq, hkv, s, d, window = 1, 2, 1, 1536, 64, 700
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    out = fm.flash_mha(q, k, v, True, None, window)
    ref = _ref_attn_window(q, k, v, True, 1.0 / np.sqrt(d), window)
    assert float(jnp.max(jnp.abs(out - ref))) < 5e-5
    g = jax.grad(lambda q, k, v: fm.flash_mha(
        q, k, v, True, None, window).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: _ref_attn_window(
        q, k, v, True, 1.0 / np.sqrt(d), window)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        rel = float(jnp.linalg.norm((a - b_).ravel())
                    / (jnp.linalg.norm(b_.ravel()) + 1e-9))
        assert rel < 1e-4, rel


@pytest.mark.parametrize("causal,stride", [(True, 1), (False, 1), (True, 4)])
def test_carry_kernel_chains_to_full_attention(causal, stride):
    """flash_carry_block (the ring-hop kernel): chaining the online-softmax
    carry over key blocks fed in ARBITRARY hop order must reproduce dense
    attention.  stride=4 exercises the striped-placement position
    arithmetic (block positions off + stride*i)."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    b, h, s_l, d, hops = 1, 2, 128, 32, 4
    s = s_l * hops
    scale = 1.0 / np.sqrt(d)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)

    # reference over GLOBAL positions (identity layout: position == index)
    pos = np.arange(s)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    valid = np.ones((s, s), bool)
    if causal:
        valid = pos[:, None] >= pos[None, :]
    sc = jnp.where(jnp.asarray(valid)[None, None], sc, -1e30)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)

    # hop decomposition: block j holds positions off_j + stride*i.  For
    # stride=1 that is contiguous chunks; for stride=s_l... use the striped
    # interleave (off_j = j, stride = hops) and gather the matching rows.
    if stride == 1:
        blocks = [(j * s_l, k[:, :, j * s_l:(j + 1) * s_l],
                   v[:, :, j * s_l:(j + 1) * s_l]) for j in range(hops)]
        q_off, q_stride = 0, 1
        qk = q[:, :, :s_l]
        ref_rows = slice(0, s_l)
    else:
        blocks = [(j, k[:, :, j::hops], v[:, :, j::hops])
                  for j in range(hops)]
        q_off, q_stride = 0, hops
        qk = q[:, :, 0::hops]
        ref_rows = slice(0, s, hops)

    m = jnp.full((b, h, s_l, 128), -1e30, jnp.float32)
    l = jnp.zeros((b, h, s_l, 128), jnp.float32)
    acc = jnp.zeros((b, h, s_l, d), jnp.float32)
    for k_off, kc, vc in reversed(blocks):   # arbitrary order on purpose
        m, l, acc = fm.flash_carry_block(
            qk, kc, vc, m, l, acc, jnp.int32(q_off), jnp.int32(k_off),
            q_stride=q_stride, k_stride=stride if stride > 1 else 1,
            s_real=s_l, sm_scale=scale, causal=causal)
    out = acc / jnp.maximum(l[..., 0:1], 1e-20)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref[:, :, ref_rows]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,stride", [(True, 1), (False, 1), (True, 4)])
def test_ring_bwd_kernels_chain_to_reference_grads(causal, stride):
    """flash_ring_dq_block / flash_ring_dkv_block (the fused ring
    backward): accumulating per-block grads over key blocks fed in
    ARBITRARY hop order must reproduce the dense-attention gradients —
    dq for the local query shard (aliased accumulator across hops) and
    dk/dv per visiting block.  stride=4 exercises the striped-placement
    position arithmetic shared with the forward carry kernel."""
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    b, h, s_l, d, hops = 1, 2, 128, 32, 4
    s = s_l * hops
    scale = 1.0 / np.sqrt(d)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    qk = jax.random.normal(ks[0], (b, h, s_l, d), jnp.float32)
    do = jax.random.normal(ks[3], (b, h, s_l, d), jnp.float32)

    # one query shard at global positions q_stride*i against the FULL
    # key sequence (dq is row-independent; dk/dv from a single shard are
    # exactly this reference's dk/dv)
    if stride == 1:
        q_stride, qpos = 1, np.arange(s_l)
        blocks = [(j * s_l, k[:, :, j * s_l:(j + 1) * s_l],
                   v[:, :, j * s_l:(j + 1) * s_l]) for j in range(hops)]
        merge = lambda parts: jnp.concatenate(  # noqa: E731
            [p for _, p in sorted(parts.items())], axis=2)
    else:
        q_stride, qpos = hops, np.arange(0, s, hops)
        blocks = [(j, k[:, :, j::hops], v[:, :, j::hops])
                  for j in range(hops)]

        def merge(parts):
            out = np.zeros((b, h, s, d), np.float32)
            for j, p in parts.items():
                out[:, :, j::hops] = np.asarray(p)
            return jnp.asarray(out)

    kpos = np.arange(s)
    valid = np.ones((s_l, s), bool)
    if causal:
        valid = qpos[:, None] >= kpos[None, :]
    vmask = jnp.asarray(valid)[None, None]

    def ref_out(qk, k, v):
        sc = jnp.einsum("bhqd,bhkd->bhqk", qk, k) * scale
        sc = jnp.where(vmask, sc, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)

    dq_ref, dk_ref, dv_ref = jax.grad(
        lambda qk, k, v: jnp.sum(ref_out(qk, k, v) * do),
        argnums=(0, 1, 2))(qk, k, v)

    # the kernels consume the saved forward residuals: o, lse, delta
    sc = jnp.einsum("bhqd,bhkd->bhqk", qk, k) * scale
    sc = jnp.where(vmask, sc, -1e30)
    lse = jax.scipy.special.logsumexp(sc, axis=-1)        # [b, h, s_l]
    o = ref_out(qk, k, v)
    lsep, deltap = fm.bwd_lane_residuals(o, do, lse, s_l)

    dq = jnp.zeros((b, h, s_l, d), jnp.float32)
    dk_parts, dv_parts = {}, {}
    for k_off, kc, vc in reversed(blocks):   # arbitrary order on purpose
        kw = dict(q_stride=q_stride, k_stride=stride if stride > 1 else 1,
                  s_real=s_l, sm_scale=scale, causal=causal)
        dq = fm.flash_ring_dq_block(qk, kc, vc, do, lsep, deltap, dq,
                                    jnp.int32(0), jnp.int32(k_off), **kw)
        zk = jnp.zeros((b, h, s_l, d), jnp.float32)
        zv = jnp.zeros((b, h, s_l, d), jnp.float32)
        dk_b, dv_b = fm.flash_ring_dkv_block(
            qk, kc, vc, do, lsep, deltap, zk, zv,
            jnp.int32(0), jnp.int32(k_off), **kw)
        dk_parts[k_off], dv_parts[k_off] = dk_b, dv_b

    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(merge(dk_parts)),
                               np.asarray(dk_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(merge(dv_parts)),
                               np.asarray(dv_ref), rtol=2e-4, atol=2e-4)
