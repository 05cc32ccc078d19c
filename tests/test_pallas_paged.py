"""Parity tests for the repo-owned Pallas paged (block-table) decode
attention kernel (deepspeed_tpu/ops/pallas/paged_attention.py) run through
the Pallas interpreter on the CPU mesh, against the XLA gather fallback it
replaces on TPU. Ref kernel family: inference/v2/kernels/ragged_ops
(blocked flash over a KV block table) in the reference suite."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pm = importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention")


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pm.INTERPRET
    pm.INTERPRET = True
    yield
    pm.INTERPRET = old


def _decode_fn(*args, **kw):
    # bypass the jit wrapper so the INTERPRET toggle is honoured regardless
    # of any cached trace from a previous test
    return pm.paged_decode_attention.__wrapped__(*args, **kw)


def _ref_paged(q, k_pages, v_pages, pages, pos, clen, bs, scale):
    """Gather-based reference: materialises each token's [C, d] context."""
    t, nh, d = q.shape
    nkv = k_pages.shape[0]
    g = nh // nkv
    nb = pages.shape[1]
    c_idx = jnp.arange(nb * bs)
    rows = pages[:, c_idx // bs] * bs + (c_idx % bs)[None, :]      # [T, C]
    k_ctx = k_pages[:, rows].astype(jnp.float32)                   # [nkv,T,C,d]
    v_ctx = v_pages[:, rows].astype(jnp.float32)
    qg = q.reshape(t, nkv, g, d).astype(jnp.float32)
    s = jnp.einsum("tkgd,ktcd->tkgc", qg, k_ctx) * scale
    valid = (c_idx[None, :] <= pos[:, None]) & (c_idx[None, :] < clen[:, None])
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("tkgc,ktcd->tkgd", p, v_ctx)
    return out.reshape(t, nh, d)


def _make_case(key, t, nh, nkv, d, n_pages, nb, bs, poison=False):
    """Random tokens with ragged context lengths over a shared page pool.

    Each token gets `nb` block-table slots; slots beyond its context point
    at page 0 (shared garbage, like a real allocator's freed pages)."""
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (t, nh, d), jnp.bfloat16)
    P = n_pages * bs
    k_pages = jax.random.normal(ks[1], (nkv, P, d), jnp.bfloat16)
    v_pages = jax.random.normal(ks[2], (nkv, P, d), jnp.bfloat16)
    # ragged context lengths in [1, nb*bs]
    clen = jax.random.randint(ks[3], (t,), 1, nb * bs + 1)
    pos = clen - 1                                   # decode: last position
    # distinct pages per token where possible, wrapping over the pool;
    # table entries past the context are garbage (page 0)
    tbl = (np.arange(t)[:, None] * nb + np.arange(nb)[None, :]) % n_pages
    used = (np.asarray(clen)[:, None] > np.arange(nb)[None, :] * bs)
    tbl = np.where(used, tbl, 0)
    if poison:
        # huge finite values in page 0 must never leak through the masks
        k_pages = k_pages.at[:, :bs].set(1e3)
        v_pages = v_pages.at[:, :bs].set(1e3)
        tbl = np.where(used, tbl + 1, 0)             # keep page 0 pure garbage
        tbl = np.minimum(tbl, n_pages - 1)
    return q, k_pages, v_pages, jnp.asarray(tbl, jnp.int32), pos, clen


CASES = [
    # t, nh, nkv, d, n_pages, nb, bs
    (4, 4, 4, 64, 8, 2, 16),       # MHA, multi-page
    (5, 8, 2, 64, 16, 3, 16),      # GQA 4x, 3 pages
    (3, 4, 1, 64, 8, 2, 32),       # MQA, wider pages
    (2, 4, 2, 128, 8, 2, 8),       # d=128, minimal block size
]


@pytest.mark.parametrize("t,nh,nkv,d,n_pages,nb,bs", CASES)
def test_paged_parity(t, nh, nkv, d, n_pages, nb, bs):
    q, kp, vp, tbl, pos, clen = _make_case(
        jax.random.PRNGKey(0), t, nh, nkv, d, n_pages, nb, bs)
    scale = 1.0 / np.sqrt(d)
    out = _decode_fn(q, kp, vp, tbl, pos, clen, block_size=bs, sm_scale=scale)
    ref = _ref_paged(q, kp, vp, tbl, pos, clen, bs, scale)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    assert err < 0.05, err


def test_garbage_page_masking():
    """Block-table slots past a token's context point at a poison page of
    huge values; output must still match the masked reference."""
    q, kp, vp, tbl, pos, clen = _make_case(
        jax.random.PRNGKey(1), 5, 8, 2, 64, 16, 3, 16, poison=True)
    scale = 1.0 / np.sqrt(64)
    out = _decode_fn(q, kp, vp, tbl, pos, clen, block_size=16, sm_scale=scale)
    ref = _ref_paged(q, kp, vp, tbl, pos, clen, 16, scale)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    assert err < 0.05, err
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


def test_mid_sequence_positions():
    """pos < clen - 1 (e.g. SplitFuse chunked prefill): the causal frontier,
    not the context length, must bound attention."""
    t, nh, nkv, d, bs, nb = 4, 4, 2, 64, 16, 2
    q, kp, vp, tbl, _, _ = _make_case(
        jax.random.PRNGKey(2), t, nh, nkv, d, 8, nb, bs)
    clen = jnp.full((t,), nb * bs, jnp.int32)
    pos = jnp.asarray([0, 7, 16, nb * bs - 1], jnp.int32)
    scale = 1.0 / np.sqrt(d)
    out = _decode_fn(q, kp, vp, tbl, pos, clen, block_size=bs, sm_scale=scale)
    ref = _ref_paged(q, kp, vp, tbl, pos, clen, bs, scale)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    assert err < 0.05, err


@pytest.mark.parametrize("d,n_calls", [(128, 1), (64, 0)])
def test_dispatch_uses_pallas_kernel(monkeypatch, d, n_calls):
    """inference v2's _paged_attention routes through the repo kernel when
    block tables are available on TPU and the kernel's ``supports()``
    takes the shape (head_dim 128), and the kernel output matches the XLA
    gather path it replaces; head_dim 64 — which the chip's compiler
    refuses — resolves to the XLA path by name."""
    from deepspeed_tpu.inference.v2 import model as m2
    from deepspeed_tpu.models.transformer import TransformerConfig

    monkeypatch.setattr(m2, "on_tpu", lambda: True)
    calls = {"n": 0}
    real = pm.paged_decode_attention

    def counting(*a, **kw):
        calls["n"] += 1
        return real.__wrapped__(*a, **kw)

    # model.py binds the kernel at import — patch the consumer's name
    monkeypatch.setattr(m2, "paged_decode_attention", counting)

    t, nh, nkv, bs, nb = 3, 8, 2, 16, 2
    q, kp, vp, tbl, pos, clen = _make_case(
        jax.random.PRNGKey(3), t, nh, nkv, d, 8, nb, bs)
    cfg = TransformerConfig(num_heads=nh, num_kv_heads=nkv,
                            hidden_size=nh * d, use_rope=True, arch="llama")
    assert m2.attention_impl_name(cfg, bs) == (
        "paged_pallas" if n_calls else "paged_xla")
    # gather_idx for the XLA path: flat page-row index of each ctx position
    c_idx = jnp.arange(nb * bs)
    gather_idx = tbl[:, c_idx // bs] * bs + (c_idx % bs)[None, :]
    token_slot = jnp.arange(t, dtype=jnp.int32)
    out = m2._paged_attention(q, kp, vp, gather_idx, pos, clen, cfg,
                              block_tables=tbl, token_slot=token_slot,
                              block_size=bs)
    assert calls["n"] == n_calls, "wrong paged attention dispatch"
    ref = m2._paged_attention_xla(q, kp, vp, gather_idx, pos, clen, cfg)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < 0.05, err


def test_paged_sliding_window_parity():
    """Mistral sliding-window masking in the paged kernel (pages wholly
    before the window are grid-skipped; partial pages masked per-row)."""
    t, nh, nkv, d, n_pages, nb, bs, window = 5, 4, 2, 64, 16, 4, 16, 24
    q, kp, vp, tbl, pos, clen = _make_case(
        jax.random.PRNGKey(5), t, nh, nkv, d, n_pages, nb, bs)
    scale = 1.0 / np.sqrt(d)
    out = _decode_fn(q, kp, vp, tbl, pos, clen, block_size=bs,
                     sm_scale=scale, window=window)

    # reference with window mask
    nbk = tbl.shape[1]
    c_idx = jnp.arange(nbk * bs)
    rows = tbl[:, c_idx // bs] * bs + (c_idx % bs)[None, :]
    k_ctx = kp[:, rows].astype(jnp.float32)
    v_ctx = vp[:, rows].astype(jnp.float32)
    g = nh // nkv
    qg = q.reshape(t, nkv, g, d).astype(jnp.float32)
    s = jnp.einsum("tkgd,ktcd->tkgc", qg, k_ctx) * scale
    valid = ((c_idx[None, :] <= pos[:, None])
             & (c_idx[None, :] < clen[:, None])
             & (pos[:, None] - c_idx[None, :] < window))
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("tkgc,ktcd->tkgd", p, v_ctx).reshape(t, nh, d)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    assert err < 0.05, err


@pytest.mark.parametrize("t,nh,nkv,d,n_pages,nb,bs", CASES)
def test_paged_quantized_parity(t, nh, nkv, d, n_pages, nb, bs):
    """Int8-KV kernel variant: quantize the page pools per (head, row),
    run the quantized kernel, and compare against the float reference on
    the DEQUANTIZED pools (exact math parity) and against the original
    float pools (small quantization error)."""
    q, kp, vp, tbl, pos, clen = _make_case(
        jax.random.PRNGKey(1), t, nh, nkv, d, n_pages, nb, bs)
    scale = 1.0 / np.sqrt(d)

    def quantize(p):
        pf = p.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(pf), axis=-1), 1e-8) / 127.0
        q8 = jnp.clip(jnp.round(pf / s[..., None]), -127, 127)
        return q8.astype(jnp.int8), s

    kq, ks = quantize(kp)
    vq, vs = quantize(vp)
    out = _decode_fn(q, kq, vq, tbl, pos, clen, block_size=bs,
                     sm_scale=scale, k_scales=ks, v_scales=vs)
    deq = lambda q8, s: (q8.astype(jnp.float32) * s[..., None]).astype(jnp.bfloat16)
    ref_exact = _ref_paged(q, deq(kq, ks), deq(vq, vs), tbl, pos, clen, bs,
                           scale)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref_exact)))
    assert err < 0.05, err
    ref_float = _ref_paged(q, kp, vp, tbl, pos, clen, bs, scale)
    qerr = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref_float)))
    assert qerr < 0.15, qerr  # int8 per-row quantization noise bound
