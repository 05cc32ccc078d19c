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


def _ref_paged(q, k_pages, v_pages, pages, pos, clen, bs, scale,
               window=None):
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
    if window is not None:
        valid &= pos[:, None] - c_idx[None, :] < window
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

    ref = _ref_paged(q, kp, vp, tbl, pos, clen, bs, scale, window=window)
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


# -- every layer's pool and a layer index: the layer loop carries the pool ---
_N_LAYERS = 5


@pytest.mark.parametrize("layer", [0, 2, _N_LAYERS - 1],
                         ids=["first", "middle", "last"])
@pytest.mark.parametrize("kind", ["bf16", "window", "int8", "ragged_step"])
def test_layer_of_a_pool_matches_the_layers_own_pages(kind, layer):
    """``paged_decode_attention(pool4d, layer=l)`` reads ``pool4d[l]``
    out of the whole pool ``[L, nkv, P, d]`` and nothing else: the same
    bits as the call on that layer's pages alone (a traced ``l``, as the
    layer scan hands it over), with the other layers holding noise."""
    t, nh, nkv, d, n_pages, nb, bs = 5, 8, 2, 64, 16, 3, 16
    q, _, _, tbl, pos, clen = _make_case(
        jax.random.PRNGKey(7), t, nh, nkv, d, n_pages, nb, bs)
    kw = dict(block_size=bs, sm_scale=1.0 / np.sqrt(d))
    if kind == "ragged_step":
        q, _, _, tbl, slot, pos, clen, _ = _ragged_step(
            [(20, 1), (45, 1), (16, 40), (0, 30)], t=96, nb=8, bs=bs,
            nh=nh, nkv=nkv, d=d)
        kw["token_slot"] = slot
        n_pages = 12
    if kind == "window":
        kw["window"] = 24
    kk, kv = jax.random.split(jax.random.PRNGKey(8))
    shape = (_N_LAYERS, nkv, n_pages * bs, d)
    pools = [jax.random.normal(kk, shape, jnp.bfloat16),
             jax.random.normal(kv, shape, jnp.bfloat16)]
    scales = []
    if kind == "int8":
        for i, p in enumerate(pools):
            pf = p.astype(jnp.float32)
            sc = jnp.maximum(jnp.max(jnp.abs(pf), axis=-1), 1e-8) / 127.0
            pools[i] = jnp.clip(jnp.round(pf / sc[..., None]), -127,
                                127).astype(jnp.int8)
            scales.append(sc)

    def attend(pools, scales, **layer):
        sc = dict(zip(("k_scales", "v_scales"), scales))
        return _decode_fn(q, *pools, tbl, pos, clen, **sc, **layer, **kw)

    def own_pages(l):
        return attend([p[l] for p in pools], [sc[l] for sc in scales])

    got = jax.jit(lambda l: attend(pools, scales, layer=l))(jnp.int32(layer))
    assert got.shape == q.shape
    same = lambda a, b: np.array_equal(np.asarray(a.astype(jnp.float32)),
                                       np.asarray(b.astype(jnp.float32)))
    assert same(got, own_pages(layer))
    # and it is that layer: its neighbour's pages give another answer
    assert not same(got, own_pages((layer + 1) % _N_LAYERS))


# -- the ragged step: runs of one sequence's rows share a page walk ---------
def _ragged_step(items, t, nb, bs, nh=4, nkv=2, d=64, seed=0, poison=False):
    """A step as ``build_ragged_batch`` lays it out: each ``(cached,
    n_new)`` item is one sequence's run of rows in position order, then
    padding (slot = the last table row, no context).  Page 0 is the
    garbage page; every sequence owns the pages after it, in order."""
    n_seq = len(items)
    tables = np.zeros((n_seq + 1, nb), np.int32)
    slot = np.full((t,), n_seq, np.int32)
    pos = np.zeros((t,), np.int32)
    ctx = np.zeros((n_seq + 1,), np.int32)
    cursor, page = 0, 1
    for s, (cached, n) in enumerate(items):
        need = -(-(cached + n) // bs)
        tables[s, :need] = np.arange(page, page + need)
        page += need
        slot[cursor:cursor + n] = s
        pos[cursor:cursor + n] = np.arange(cached, cached + n)
        ctx[s] = cached + n
        cursor += n
    assert cursor <= t and max(ctx) <= nb * bs
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (t, nh, d), jnp.bfloat16)
    k_pages = jax.random.normal(ks[1], (nkv, page * bs, d), jnp.bfloat16)
    v_pages = jax.random.normal(ks[2], (nkv, page * bs, d), jnp.bfloat16)
    if poison:
        # huge finite values in page 0 must never leak through the masks
        k_pages = k_pages.at[:, :bs].set(1e3)
        v_pages = v_pages.at[:, :bs].set(1e3)
    return (q, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(slot),
            jnp.asarray(pos), jnp.asarray(ctx)[slot], cursor)


RAGGED_CASES = {
    # a decode-only step: every row its own sequence, then padding
    "decode_only": dict(items=[(c, 1) for c in (3, 17, 40, 95, 16, 31, 64,
                                                1, 77, 50, 12, 88, 9, 33,
                                                47, 5, 70, 23, 15, 60)],
                        t=32, nb=8),
    # one 256-row chunk that starts mid-page on a context already there
    "chunk_256_mid_page": dict(items=[(37, 256)], t=256, nb=20),
    # decode rows, two chunks whose seam falls inside a query block, padding
    "decode_then_two_chunks": dict(items=[(20, 1), (45, 1), (9, 1), (63, 1),
                                          (30, 1), (16, 40), (0, 30)],
                                   t=128, nb=8),
    # T below the query block; T above it and not a multiple of it
    "t_below_block": dict(items=[(11, 1), (0, 6), (25, 1)], t=9, nb=4),
    "t_not_a_multiple": dict(items=[(18, 1), (5, 41)], t=48, nb=4),
    # a window shorter than the context, its edge moving inside a block
    "window_edge_in_block": dict(items=[(7, 1), (100, 64)], t=96, nb=12,
                                 window=40),
    # the garbage page holds huge values
    "garbage_page_poisoned": dict(items=[(33, 1), (2, 1), (19, 50)], t=64,
                                  nb=8, poison=True),
    # serving widths: 4 query heads a KV head, head 128
    "group4_head128": dict(items=[(21, 1), (40, 45)], t=64, nb=8, nh=8,
                           nkv=2, d=128),
}


@pytest.mark.parametrize("case", list(RAGGED_CASES), ids=list(RAGGED_CASES))
def test_ragged_step_parity(case):
    """The query-blocked kernel against the XLA gather reference on whole
    ragged steps, block tables and ``token_slot`` as the model passes
    them."""
    kw = dict(RAGGED_CASES[case])
    window, bs = kw.pop("window", None), 16
    q, kp, vp, tables, slot, pos, clen, n_real = _ragged_step(bs=bs, **kw)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = _decode_fn(q, kp, vp, tables, pos, clen, block_size=bs,
                     sm_scale=scale, window=window, token_slot=slot)
    ref = _ref_paged(q, kp, vp, tables[slot], pos, clen, bs, scale,
                     window=window)
    assert out.shape == q.shape
    outf = out.astype(jnp.float32)
    assert bool(jnp.all(jnp.isfinite(outf)))
    err = float(jnp.max(jnp.abs(outf[:n_real] - ref[:n_real])))
    assert err < 0.05, err
    # a padded row belongs to no sequence and attends to nothing
    assert float(jnp.max(jnp.abs(outf[n_real:]), initial=0.0)) == 0.0


def test_rows_out_of_order_keep_their_own_masks():
    """``build_ragged_batch``'s row order makes the shared walks long but
    is not required: a sequence's rows with gaps and out of position
    order, interleaved with another's, give each row its own answer."""
    bs, nb = 16, 6
    q, kp, vp, tables, _, _, _, _ = _ragged_step(
        [(60, 1), (80, 1)], t=12, nb=nb, bs=bs)
    slot = jnp.asarray([0, 0, 1, 0, 0, 1, 1, 1, 0, 2, 2, 2], jnp.int32)
    pos = jnp.asarray([40, 3, 70, 59, 17, 2, 33, 80, 60, 0, 0, 0], jnp.int32)
    clen = jnp.asarray([61, 81, 0], jnp.int32)[slot]
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = _decode_fn(q, kp, vp, tables, pos, clen, block_size=bs,
                     sm_scale=scale, window=24, token_slot=slot)
    ref = _ref_paged(q, kp, vp, tables[slot], pos, clen, bs, scale, window=24)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)[:9] - ref[:9])))
    assert err < 0.05, err


_WALK_CASES = [
    ([1] * 20, 32, 0),                  # decode only
    ([256], 32, 256),                   # one chunk, eight blocks
    ([1] * 5 + [251], 32, 251),         # the seam block still shares a walk
    ([1] * 5 + [40, 30], 32, 70),
    ([1] * 31 + [2], 32, 0),            # a run cut in two rows of one
    ([3, 1], 32, 3),
    ([33], 32, 32),                     # its last row is alone in its block
]


@pytest.mark.parametrize("runs,qb,want", _WALK_CASES)
def test_shared_walk_rows(runs, qb, want):
    assert pm.shared_walk_rows(runs, qb) == want


@pytest.mark.parametrize("runs,qb,shared", _WALK_CASES)
def test_one_row_walks_are_the_rows_that_share_no_walk(runs, qb, shared):
    """``one_row_walks`` of the ``v2.schedule`` span: the step's pieces of
    one row under ``shared_walk_rows``' cut, which the kernel multiplies
    on the narrow window; 0 where the step does not run the kernel."""
    from deepspeed_tpu.inference.v2.engine_v2 import step_counts

    items = [(7, n) for n in runs]
    got = step_counts(items, query_block=qb)
    assert got["blocked_rows"] == shared
    assert got["one_row_walks"] == sum(runs) - shared
    assert step_counts(items)["one_row_walks"] == 0


# -- a run of ONE row is multiplied on a narrow window of its program's tile --
# group: KV heads, key width, value width, window, sink
_ONE_ROW_GROUPS = {
    4: (2, 128, 128, None, False),
    5: (2, 128, 128, None, False),
    6: (2, 128, 128, None, False),
    8: (2, 128, 128, 128, True),       # a window layer with a learned sink
    16: (2, 256, 128, None, False),    # keys of 192 in 256 lanes, values 128
}
_PAD = -2       # a row the CALLER padded: the last table row, no context


def _one_row_rows(scenario, rng):
    """``(t, rows, ctx)``: ``rows`` the step's ``(sequence, position)`` by
    row (``_PAD``: the caller's padding), ``ctx`` each sequence's context
    length.  Runs are laid as ``(cached, n_new)`` items unless a scenario
    says its rows one by one."""
    if scenario == "out_of_order":
        # three sequences' rows interleaved, no two neighbours of one
        # sequence, positions in no order: thirteen runs of one row
        ctx = [150, 90, 200]
        seqs = [0, 1, 0, 2, 1, 0, 2, 1, 0, 1, 2, 0, 1]
        return 16, [(s, int(rng.integers(0, ctx[s]))) for s in seqs] \
            + [_PAD] * 3, ctx
    t, runs = {
        # decode rows alone in the smallest bucket, then padding
        "alone_bucket_16": (16, [1] * 11),
        # one-row runs before, between and after multi-row runs of ONE
        # 32-row block, a padded row last
        "beside_runs_in_a_block": (32, [1] * 3 + [17] + [1] * 2 + [4]
                                   + [1] * 4),
        # rows 31..64 are one run: the block boundaries cut a piece of one
        # row off its head (row 31) and off its tail (row 64)
        "cut_by_a_block_boundary": (96, [1] * 2 + [28] + [1] + [34]
                                    + [1] * 3),
        # T no multiple of the block: the kernel pads rows of slot -1; row
        # 45 is the caller's padding, alone between a run and those
        "padded_tail": (46, [1] * 30 + [5] + [1] * 10),
    }[scenario]
    rows, ctx = [], []
    for s, n in enumerate(runs):
        cached = int(rng.integers(1, 200 - n))
        rows += [(s, cached + i) for i in range(n)]
        ctx.append(cached + n)
    return t, rows + [_PAD] * (t - len(rows)), ctx


@pytest.mark.parametrize("scenario", [
    "alone_bucket_16", "beside_runs_in_a_block", "cut_by_a_block_boundary",
    "out_of_order", "padded_tail"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group", list(_ONE_ROW_GROUPS))
def test_one_row_runs_on_the_narrow_window(monkeypatch, group, dtype,
                                           scenario):
    """A run of one row multiplies, soft-maxes and accumulates on
    ``narrow_rows`` rows of its program's tile, beside runs that keep the
    whole tile: against the XLA gather path, in float32 to rounding (a
    window's rows of OTHER runs are left exactly as they were), walks of
    several compute steps."""
    from deepspeed_tpu.inference.v2 import model as m2
    from deepspeed_tpu.models.transformer import TransformerConfig

    nkv, dk, dv, window, has_sink = _ONE_ROW_GROUPS[group]
    nh, bs, nb = nkv * group, 16, 13
    monkeypatch.setattr(pm, "_STEP_KEYS", 64)   # a walk is up to 4 steps
    rng = np.random.default_rng(group)
    t, rows, ctx = _one_row_rows(scenario, rng)
    assert pm.narrow_rows(group, min(t, pm.QUERY_BLOCK) * group) == (
        16 if 16 % group == 0 else 32)
    n_seq, n_real = len(ctx), sum(r != _PAD for r in rows)
    slot = jnp.asarray([n_seq if r == _PAD else r[0] for r in rows],
                       jnp.int32)
    pos = jnp.asarray([0 if r == _PAD else r[1] for r in rows], jnp.int32)
    clen = jnp.asarray(ctx + [0], jnp.int32)[slot]
    # page 0 is garbage; every sequence owns its pages, in no order
    n_pages = 1 + n_seq * nb
    tables = np.zeros((n_seq + 1, nb), np.int32)
    tables[:n_seq] = 1 + rng.permutation(n_seq * nb).reshape(n_seq, nb)
    tables = jnp.asarray(tables)
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(group), 4)
    q = jax.random.normal(ks[0], (t, nh, dk), dt)
    kp = jax.random.normal(ks[1], (nkv, n_pages * bs, dk), dt)
    vp = jax.random.normal(ks[2], (nkv, n_pages * bs, dv), dt)
    sink = 1.0 + jax.random.normal(ks[3], (nh,), jnp.float32) \
        if has_sink else None
    scale = 1.0 / np.sqrt(dk)
    out = _decode_fn(q, kp, vp, tables, pos, clen, block_size=bs,
                     sm_scale=scale, window=window, token_slot=slot,
                     sink=sink)
    assert out.shape == (t, nh, dv) and out.dtype == dt
    c_idx = jnp.arange(nb * bs)
    gather_idx = tables[slot][:, c_idx // bs] * bs + (c_idx % bs)[None, :]
    cfg = TransformerConfig(num_heads=nh, num_kv_heads=nkv,
                            hidden_size=nh * dk, use_rope=True, arch="llama",
                            attn_scale=scale, sliding_window=window or 0)
    ref = m2._paged_attention_xla(q, kp, vp, gather_idx, pos, clen, cfg, sink)
    outf, reff = out.astype(jnp.float32), ref.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(outf[:n_real] - reff[:n_real])))
    assert err < (2e-5 if dtype == "float32" else 0.05), err
    # a padded row belongs to no sequence and attends to nothing
    assert float(jnp.max(jnp.abs(outf[n_real:]), initial=0.0)) == 0.0


# -- the engine: a long prompt prefilled in chunks beside decoding rows ------
def _engine_logits(attention):
    """Two short sequences decode while a 150-token prompt prefills in
    three chunks of the 64-token budget; every step's logits, the
    ``v2.schedule`` spans' ``blocked_rows`` and the programs dispatched."""
    from deepspeed_tpu.inference.v2 import build_engine
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.telemetry.tracing import Tracer

    # head_dim 128: the narrowest head the paged kernel takes
    model = get_model_config("mistral-tiny", hidden_size=256, num_heads=2,
                             num_kv_heads=1, num_layers=2)
    eng = build_engine(
        model, {"dtype": "float32", "modules": {"attention": attention},
                "state_manager": {"max_tracked_sequences": 4,
                                  "max_ragged_batch_size": 64},
                "memory_config": {"num_blocks": 48, "block_size": 8},
                "max_context": 192}, seed=0)
    eng.tracer = Tracer(enabled=True)
    rng = np.random.default_rng(11)
    tok = lambda n: rng.integers(1, model.vocab_size, size=n).tolist()
    steps = [eng.put([1, 2], [tok(5), tok(9)])]
    eng.admit(3, tok(150))
    for i in range(4):
        # uid 3 has its first token after the third of these steps
        for uid in (1, 2, 3)[:3 if i == 3 else 2]:
            eng.extend(uid, tok(1)[0])
        steps.append(eng.step(return_logits=True))
    blocked = [e["args"]["blocked_rows"] for e in eng.tracer.snapshot()
               if e["ph"] == "X" and e["name"] == "v2.schedule"]
    return steps, blocked, set(eng._dispatched)


def test_engine_chunked_prefill_beside_decode_matches_xla():
    got, blocked, dispatched = _engine_logits("paged_pallas")
    want, blocked_xla, dispatched_xla = _engine_logits("paged_xla")
    # the prompt's last chunk comes in the third step after it was
    # admitted: uid 3 is sampled there, and decodes in the fourth
    assert [sorted(s) for s in got] == [[1, 2], [1, 2], [1, 2], [1, 2, 3],
                                        [1, 2, 3]]
    for step_got, step_want in zip(got, want):
        for uid, logits in step_got.items():
            err = float(np.max(np.abs(logits - step_want[uid])))
            assert err < 0.05, (uid, err)
    # runs of [5, 9]; [1, 1, 62] twice; [1, 1, 26]; three decode rows
    assert blocked == [14, 62, 62, 26, 0]
    assert blocked_xla == [0] * 5       # the gather path shares no walk
    # one program per (program, t_bucket, nb_bucket), as before
    assert dispatched == dispatched_xla == {
        ("ragged_step", 16, 2), ("ragged_step", 64, 8),
        ("ragged_step", 64, 16), ("ragged_step", 32, 24),
        ("ragged_step", 16, 24)}
