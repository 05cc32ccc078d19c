"""``latent_index_scores`` (interpreted) against the XLA formulation of
the indexer's scores: decode rows, chunks that cross programs and pages,
padding, a second layer of the pool, and contexts that need more than one
compute step."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.latent import index_scores_xla
from deepspeed_tpu.ops.pallas import latent_index as li


@pytest.fixture(autouse=True)
def interpret():
    old, li.INTERPRET = li.INTERPRET, True
    yield
    li.INTERPRET = old


# (slot, first position, rows) runs, then padding rows of slot S
LAYOUTS = {
    "mixed": [(0, 37, 1), (1, 10, 20), (2, 5, 1), (3, 0, 13)],
    "decode rows": [(3, 60, 1), (0, 0, 1), (2, 17, 1), (1, 33, 1)],
    "a chunk across programs": [(1, 3, 45)],
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("bs,nb,step_keys", [(8, 8, 512), (4, 16, 16)])
def test_kernel_is_the_gather(layout, bs, nb, step_keys, monkeypatch):
    monkeypatch.setattr(li, "_STEP_KEYS", step_keys)
    monkeypatch.setattr(li, "QUERY_BLOCK", 16)
    rng = np.random.default_rng(0)
    runs, slots, heads, d, layers = LAYOUTS[layout], 4, 4, 16, 2
    slot = np.concatenate([np.full(n, s) for s, _, n in runs] + [[slots] * 3])
    pos = np.concatenate([np.arange(p, p + n) for _, p, n in runs] + [[0] * 3])
    clen = np.zeros(slots + 1, np.int32)
    for s, p, n in runs:
        clen[s] = p + n
    assert clen.max() <= bs * nb
    tables = np.zeros((slots + 1, nb), np.int32)
    tables[:slots] = 1 + rng.permutation(slots * nb).reshape(slots, nb)
    pool = jnp.asarray(rng.normal(size=(layers, (slots * nb + 1) * bs, d)),
                       jnp.float32)
    t = len(slot)
    q = jnp.asarray(rng.normal(size=(t, heads, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(t, heads)), jnp.float32)
    args = (q, w, pool, 1, jnp.asarray(tables), jnp.asarray(slot, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(clen)[slot])
    got = np.asarray(li.index_scores(*args, block_size=bs))
    want = np.asarray(index_scores_xla(*args, bs))
    real = slot != slots
    assert got.shape == want.shape == (t, bs * nb)
    assert (np.isinf(got) == np.isinf(want))[real].all()
    seen = np.isfinite(want) & real[:, None]
    np.testing.assert_allclose(got[seen], want[seen], atol=1e-5)
    # a padding row is walked by nobody
    assert np.isinf(got[~real]).all()


def test_supports_says_what_the_chip_takes():
    assert li.supports(128, 128) and li.supports(16, 128)
    assert not li.supports(4, 128) and not li.supports(128, 64)
    assert not li.supports(24, 128)
