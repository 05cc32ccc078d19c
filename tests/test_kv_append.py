"""The page-granular KV append (``ops/pallas/kv_append.py``, interpreted
on the CPU) against the row scatter it replaces: every page but page 0,
the garbage page, bit for bit."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import model as m2
from deepspeed_tpu.ops.pallas import kv_append as ka

BS = 16


@pytest.fixture(autouse=True)
def interpreted_kernels():
    pm = importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention")
    old, pm.INTERPRET = pm.INTERPRET, True
    yield
    pm.INTERPRET = old


def _dest(t, runs, bs=BS):
    """``token_dest`` [t] of ``runs``: (pages, first position, rows), a
    sequence's block table and the consecutive positions it appends; the
    rows left over aim at flat row 0, as padding does."""
    dest = np.zeros((t,), np.int32)
    cursor = 0
    for pages, first, n in runs:
        pos = first + np.arange(n)
        dest[cursor:cursor + n] = np.asarray(pages)[pos // bs] * bs + pos % bs
        cursor += n
    assert cursor <= t
    return dest


def _prefill_256(t):
    # one chunk from position 0: sixteen whole pages, out of order
    return [(list(range(40, 20, -1)), 0, t)]


# name: (rows, kv heads, runs)
_CASES = {
    "chunk_256x8": (256, 8, _prefill_256(256)),
    "chunk_16x8": (16, 8, [([7], 0, 16)]),
    "chunk_256x4": (256, 4, _prefill_256(256)),
    # starts at row 11 of its first page and ends in row 5 of its third
    "mid_page_across_three": (64, 8, [([9, 3, 12, 5], 11, 27)]),
    "sixteen_decode_rows": (16, 8, [([30 + i, 50 - i], 16 + i, 1)
                                    for i in range(16)]),
    # one run ends in page 6, the next starts in page 7 (and the other
    # way round): neighbours in the pool, strangers in the step
    "neighbouring_pages": (32, 4, [([6], 3, 13), ([7], 0, 9),
                                   ([11], 15, 1), ([10], 2, 5)]),
    "all_padding": (16, 8, []),
    # two programs of 256 rows: the page that rows 251..266 fill is cut
    # between them, and a decode row and padding end the second
    "two_row_blocks": (512, 2, [(list(range(60, 20, -1)), 5, 500),
                                ([3], 9, 1)]),
    "chunk_decodes_padding": (64, 8, [([2, 4, 8], 5, 30), ([13], 15, 1),
                                      ([14, 15], 15, 2), ([1], 0, 1)]),
}


def _assert_pages_equal_the_scatters(name, t, nkv, runs, dtype, layer,
                                     bs=BS):
    n_layers, n_pages, d = 3, 64, 128
    rng = np.random.default_rng(sum(map(ord, name)) + layer)
    dt = getattr(jnp, dtype)
    dest = jnp.asarray(_dest(t, runs, bs))

    def pool():
        a = rng.normal(size=(n_layers, nkv, n_pages * bs, d))
        a[np.arange(n_layers) != layer] = np.nan
        return jnp.asarray(a, dt)

    ck, cv = pool(), pool()
    # float32 rows into a bfloat16 pool: rounded once, on the way in
    k, v = (jnp.asarray(rng.normal(size=(t, nkv, d)), jnp.float32)
            for _ in range(2))
    lyr = jnp.int32(layer)
    want = jax.jit(lambda ck, cv: (m2._kv_append(ck, k, dest, lyr),
                                   m2._kv_append(cv, v, dest, lyr)))(ck, cv)
    got = jax.jit(lambda ck, cv: ka.kv_append(
        ck, cv, k, v, ka.step_pages(ck, dest, bs), lyr, bs))(
        ck, cv)
    bits = lambda a: np.asarray(a).view(np.uint16 if dtype == "bfloat16"
                                        else np.uint32)
    for g, w, before in zip(got, want, (ck, cv)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(bits(g[layer, :, bs:]),
                                      bits(w[layer, :, bs:]))
        others = np.arange(n_layers) != layer
        np.testing.assert_array_equal(bits(g)[others], bits(before)[others])
        if runs:
            assert not np.array_equal(bits(g[layer]), bits(before[layer]))


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(_CASES))
def test_pages_equal_the_scatters(name, dtype, layer):
    """Both pools after the kernel and after ``_kv_append``'s scatter: the
    layer's pages from page 1 on hold the same bits, and every other
    layer's pages, NaN before, were not touched."""
    _assert_pages_equal_the_scatters(name, *_CASES[name], dtype, layer)


# pages of 64 rows under a VMEM budget that leaves a program sixteen rows
# and a handful of pages at once.  name: (rows, kv heads, runs)
_TIGHT = {
    # a page's rows are cut among four programs, each of which reads it as
    # the one before left it
    "pages_cut_among_programs": (128, 2, [([9, 3, 12], 37, 100),
                                          ([5, 6], 63, 2), ([7], 0, 9)]),
    # sixteen entries a program, in more groups than one
    "more_groups_than_one": (32, 2, [([30 + i, 63 - i], 64 + i, 1)
                                     for i in range(32)]),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(_TIGHT))
def test_pages_equal_the_scatters_in_little_vmem(name, dtype, monkeypatch):
    t, nkv, runs = _TIGHT[name]
    monkeypatch.setattr(ka, "_VMEM_BUDGET", 640 * 1024)
    rows, group = ka.fit(t, nkv, 128, 64, dtype)
    assert rows == 16 and group <= 5
    _assert_pages_equal_the_scatters(name, t, nkv, runs, dtype, 1, bs=64)


def _bytes_held(rows, group, nkv, d, bs, size):
    return 2 * (group * nkv * bs * d * size + 2 * rows * nkv * d * size
                + (rows + 2 * bs) * nkv * d * 4)


@pytest.mark.parametrize("shape,want", [
    # rows, kv heads, head, page, dtype: the three serving shapes whole
    ((256, 8, 128, 16, "bfloat16"), (256, 32)),
    ((16, 8, 128, 16, "bfloat16"), (16, 16)),
    ((256, 4, 128, 16, "bfloat16"), (256, 32)),
    # as many kv heads as heads; a page of 128 rows; both
    ((256, 32, 128, 16, "bfloat16"), (128, 32)),
    ((256, 8, 128, 128, "bfloat16"), (256, 24)),
    ((256, 32, 128, 128, "bfloat16"), (64, 6)),
    # not a page beside its room in the spread rows: no kernel
    ((256, 32, 256, 128, "float32"), None),
])
def test_fit_sizes_rows_and_pages_to_the_budget(shape, want):
    assert ka.fit(*shape) == want
    t, nkv, d, bs, dtype = shape
    pool = jax.ShapeDtypeStruct((2, nkv, 8 * bs, d), dtype)
    dest = jnp.zeros((t,), jnp.int32)
    if want is None:
        assert ka.step_pages(pool, dest, bs) is None
    else:
        assert _bytes_held(*want, nkv, d, bs,
                           jnp.dtype(dtype).itemsize) <= ka._VMEM_BUDGET
        assert ka.step_pages(pool, dest, bs)[0].shape == (-(-t // want[0]),)


def test_page_list_of_a_hand_worked_step():
    """A run from row 11 of page 9 through page 3 into row 5 of page 12,
    a decode row in page 5's last row, two padding rows."""
    dest = _dest(32, [([9, 3, 12], 11, 27), ([0, 5], 31, 1)])
    ends, row0, base, lo, hi = (np.asarray(a) for a in jax.jit(
        lambda d: ka.page_list(d, BS, ka.ROW_BLOCK))(jnp.asarray(dest)))
    # the four pages and the padding's entry, which writes page 0 alone
    assert ends.tolist() == [5] and row0[4] == 0
    assert row0[:4].tolist() == [9 * BS, 3 * BS, 12 * BS, 5 * BS]
    # the source row that lands in the page's row 0, a page of padding on
    assert (base[:4] - BS).tolist() == [-11, 5, 21, 27 - 15]
    assert lo[:4].tolist() == [11, 0, 0, 15]
    assert hi[:4].tolist() == [16, 16, 6, 16]


def test_append_pages_counted_by_hand():
    """``v2.schedule``'s ``append_pages``: 5 cached + 30 new rows touch
    pages 0, 1 and 2 of their sequence; a decode row at position 15 one;
    one at 16 one; a 16-row chunk from 16 one; from 17 two."""
    items = [(5, 30), (15, 1), (16, 1), (16, 16), (17, 16)]
    assert ka.append_pages(items, BS) == 3 + 1 + 1 + 1 + 2
    # the entries the program makes of such a step are as many
    runs, page = [], 1
    for cached, n in items:
        pages = list(range(page, page + 3))
        page += 3
        runs.append((pages, cached, n))
    n_entries = jax.jit(lambda d: ka.page_list(d, BS, ka.ROW_BLOCK)[0])(
        jnp.asarray(_dest(64, runs)))
    assert int(n_entries[0]) == 8
