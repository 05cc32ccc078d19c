"""``latent_read_walk`` (interpreted) against the XLA gather path of the
full latent layers' read: the same softmax over the same chosen keys in
another order of summation.  Chunks, decode rows, verify runs, runs that
cross a query block, contexts below, at and above ``index_topk``, tied
scores; and what the mask or the frontier excludes never reaches the
result, whatever it holds."""

import math
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import latent
from deepspeed_tpu.ops.pallas import latent_read as lr

HEADS, ROW, RANK, LAYERS = 4, 32, 24, 2
WIDTHS = SimpleNamespace(kv_lora_rank=RANK, qk_head_dim=16)
SCALE = 1.0 / math.sqrt(WIDTHS.qk_head_dim)


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setattr(lr, "INTERPRET", True)
    monkeypatch.setattr(lr, "QUERY_BLOCK", 16)
    lr.latent_read.clear_cache()      # traced under other constants
    yield
    lr.latent_read.clear_cache()


# (slot, first position, rows) runs, then three padding rows of slot S
LAYOUTS = {
    "a chunk of one sequence": [(1, 0, 16)],
    "a chunk across query blocks": [(1, 3, 45)],
    "decode rows": [(3, 60, 1), (0, 0, 1), (2, 17, 1), (1, 33, 1)],
    "verify runs": [(2, 40, 2), (0, 7, 2), (3, 62, 2), (1, 15, 2),
                    (4, 30, 2), (5, 31, 2), (6, 1, 2), (7, 50, 2),
                    (8, 21, 2)],
    "mixed": [(0, 37, 1), (1, 10, 20), (2, 5, 2), (3, 0, 13)],
}


def _case(layout, bs, nb, seed=0, ties=False):
    rng = np.random.default_rng(seed)
    runs = LAYOUTS[layout]
    slots = 1 + max(s for s, _, _ in runs)
    slot = np.concatenate([np.full(n, s) for s, _, n in runs] + [[slots] * 3])
    pos = np.concatenate([np.arange(p, p + n) for _, p, n in runs] + [[0] * 3])
    clen = np.zeros(slots + 1, np.int32)
    for s, p, n in runs:
        clen[s] = p + n
    assert clen.max() <= bs * nb
    tables = np.zeros((slots + 1, nb), np.int32)
    tables[:slots] = 1 + rng.permutation(slots * nb).reshape(slots, nb)
    pool = rng.normal(size=(LAYERS, (slots * nb + 1) * bs, ROW))
    t, c = len(slot), np.arange(bs * nb)
    q = rng.normal(size=(t, HEADS, ROW))
    scores = rng.normal(size=(t, bs * nb))
    if ties:
        scores = np.round(scores)           # a handful of distinct values
    seen = (c[None] <= pos[:, None]) & (c[None] < clen[slot][:, None])
    scores = np.where(seen, scores, -np.inf)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    return SimpleNamespace(
        q=f32(q), scores=f32(scores), pool=f32(pool), tables=i32(tables),
        slot=i32(slot), pos=i32(pos), clen=i32(clen[slot]), bs=bs,
        real=slot != slots)


def _walk(case, topk, pool=None, layer=1):
    chosen = latent.choose_keys(case.scores, topk)
    return np.asarray(lr.latent_read(
        case.q, chosen, case.pool if pool is None else pool, layer,
        case.tables, case.slot, case.pos, case.clen, block_size=case.bs,
        rank=RANK, scale=SCALE))


def _gather(case, topk, layer=1):
    sel, ok = latent.select_keys(case.scores, topk)
    rows = latent._page_rows(case.tables[case.slot], sel, case.bs)
    return np.asarray(latent._attend(
        case.q, lambda i: case.pool[layer, i], rows, ok, WIDTHS))


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("bs,nb,step_keys,group_rows,topk", [
    (8, 8, 512, 128, 64),       # the context at the selection's size
    (8, 8, 512, 128, 128),      # below it: every visible key
    (4, 16, 16, 8, 8),          # above it; steps of four pages, two tokens
    (4, 16, 8, 4, 24),          # a product, a token; steps of two pages
])
def test_walk_is_the_gather(layout, bs, nb, step_keys, group_rows, topk,
                            monkeypatch):
    monkeypatch.setattr(lr, "_STEP_KEYS", step_keys)
    monkeypatch.setattr(lr, "_GROUP_ROWS", group_rows)
    monkeypatch.setattr(lr, "_CHUNK_GROUP_ROWS", 2 * group_rows)
    case = _case(layout, bs, nb)
    got, want = _walk(case, topk), _gather(case, topk)
    assert got.shape == want.shape == (len(case.real), HEADS, RANK)
    np.testing.assert_allclose(got[case.real], want[case.real], atol=2e-5)
    # a padding row holds no key
    assert (got[~case.real] == 0).all()


@pytest.mark.parametrize("layout", ["a chunk across query blocks", "mixed",
                                    "verify runs"])
def test_tied_scores_choose_the_lower_positions(layout, monkeypatch):
    monkeypatch.setattr(lr, "_STEP_KEYS", 16)
    case = _case(layout, 4, 16, seed=3, ties=True)
    got, want = _walk(case, 8), _gather(case, 8)
    np.testing.assert_allclose(got[case.real], want[case.real], atol=2e-5)


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("poison", [np.nan, np.inf])
def test_what_no_row_holds_never_reaches_the_result(layout, poison,
                                                    monkeypatch):
    """Every pool row no query of its sequence chose (unselected, past the
    frontier in a walked page, another layer's, nobody's) is poisoned: the
    result is the clean pool's, and finite."""
    monkeypatch.setattr(lr, "_STEP_KEYS", 16)
    monkeypatch.setattr(lr, "_GROUP_ROWS", 8)
    case, topk = _case(layout, 4, 16, seed=1), 8
    chosen = np.asarray(latent.choose_keys(case.scores, topk))
    tables, slot = np.asarray(case.tables), np.asarray(case.slot)
    held = np.zeros(case.pool.shape[:2], bool)
    for t in np.flatnonzero(case.real):
        c = np.flatnonzero(chosen[t])
        held[1, tables[slot[t], c // case.bs] * case.bs + c % case.bs] = True
    assert held.sum() and not held[0].any()
    bad = jnp.where(jnp.asarray(held)[..., None], case.pool, poison)
    clean, got = _walk(case, topk), _walk(case, topk, pool=bad)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


# the cells' corners: (rows, context bucket, heads) -> the walk?
CORNERS = [
    # GLM-5 (reason_open): every bucket it reaches, chunks and decode
    ((1024, 1024, 64), True), ((1024, 8192, 64), True),
    ((16, 8192, 64), True), ((32, 4096, 64), True),
    # dots3 (longctx_open): decode rows at any context
    ((16, 32768, 128), True), ((32, 16384, 128), True),
    # its chunks: up to the 16k bucket, and the tail of a prompt as a chunk
    ((1024, 4096, 128), True), ((1024, 16384, 128), True),
    ((1024, 32768, 128), False), ((64, 32768, 128), False),
    ((64, 16384, 128), True),
    # half the heads, twice the context
    ((1024, 32768, 64), True), ((1024, 65536, 64), False),
]


@pytest.mark.parametrize("shape,walk", CORNERS)
def test_the_rule_at_the_cells_corners(shape, walk, monkeypatch):
    monkeypatch.setattr(lr, "QUERY_BLOCK", 32)      # the kernel's own
    rows, context, heads = shape
    assert lr.walks(rows, context, 2048, heads) is walk
    # a context the selection covers whole is walked whatever the rest
    assert lr.walks(rows, 2048, 2048, 8 * heads)
