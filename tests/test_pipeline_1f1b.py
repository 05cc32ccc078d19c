"""1F1B pipeline schedule: table invariants, grad parity vs the GPipe
scan, and the O(pp) live-activation bound (ref runtime/pipe/schedule.py:189
TrainSchedule)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.parallel.pipeline import _make_1f1b_schedule
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology


@pytest.mark.parametrize("pp,m", [(2, 2), (2, 8), (4, 4), (4, 8), (3, 5)])
def test_schedule_invariants(pp, m):
    wt, wm = _make_1f1b_schedule(pp, m)
    T = wt.shape[0]
    f_tick = {}
    b_tick = {}
    in_flight = np.zeros(pp, int)
    max_flight = np.zeros(pp, int)
    for t in range(T):
        for s in range(pp):
            if wt[t, s] == 1:
                o = wm[t, s]
                assert (s, o) not in f_tick, "duplicate forward"
                if s > 0:  # activation must have arrived (strictly earlier)
                    assert f_tick[(s - 1, o)] < t
                f_tick[(s, o)] = t
                in_flight[s] += 1
                max_flight[s] = max(max_flight[s], in_flight[s])
            elif wt[t, s] == 2:
                o = wm[t, s]
                assert (s, o) not in b_tick, "duplicate backward"
                assert (s, o) in f_tick and f_tick[(s, o)] < t or s == pp - 1
                if s == pp - 1:
                    assert f_tick[(s, o)] < t
                else:
                    assert b_tick[(s + 1, o)] < t
                b_tick[(s, o)] = t
                in_flight[s] -= 1
    # every (stage, microbatch) ran exactly one F and one B
    assert len(f_tick) == pp * m and len(b_tick) == pp * m
    # the defining 1F1B property: bounded stash
    assert max_flight.max() <= pp
    # utilisation sanity: ticks close to the ideal 2m + 2(pp-1)
    assert T <= 2 * m + 4 * pp


def _loss_and_grads(schedule, n_micro=8, pp=2):
    from deepspeed_tpu.models import init_params
    from deepspeed_tpu.models import transformer as tr
    from deepspeed_tpu.models.registry import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=4,
        num_heads=2, num_kv_heads=2, max_seq_len=16, arch="llama",
        norm="rmsnorm", activation="swiglu", use_rope=True,
        tie_embeddings=True, dtype=jnp.float32,
        pipeline_schedule=schedule, pipeline_microbatches=n_micro)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32)
    batch = {"input_ids": ids, "labels": ids}

    topo = MeshTopology({"pipe": pp, "data": 8 // pp})
    set_topology(topo)
    try:
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: tr.loss_fn(p, batch, cfg)))(params, )
    finally:
        set_topology(None)
    return float(loss), grads


@pytest.mark.parametrize("pp", [2, 4])
def test_1f1b_matches_gpipe_grads(pp):
    """pp=4 exercises true middle stages: multi-hop cotangent hand-off,
    left/right clip gating, and arr slot reuse over a >2 ring."""
    l1, g1 = _loss_and_grads("1f1b", pp=pp)
    l2, g2 = _loss_and_grads("gpipe", pp=pp)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    flat1 = jax.tree_util.tree_leaves(g1)
    flat2 = jax.tree_util.tree_leaves(g2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_1f1b_live_activation_bound():
    """The compiled 1F1B step's temporary memory must not grow with
    n_micro (O(pp) stash), unlike the AD-differentiated GPipe scan whose
    residual stash is O(n_micro)."""
    from deepspeed_tpu.models import init_params
    from deepspeed_tpu.models import transformer as tr
    from deepspeed_tpu.models.registry import TransformerConfig

    def temp_bytes(schedule, n_micro):
        cfg = TransformerConfig(
            vocab_size=64, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=2, num_kv_heads=2, max_seq_len=64,
            arch="llama", norm="rmsnorm", activation="swiglu", use_rope=True,
            tie_embeddings=True, dtype=jnp.float32,
            pipeline_schedule=schedule, pipeline_microbatches=n_micro,
            remat_policy="none")
        params = init_params(cfg, jax.random.PRNGKey(0))
        ids = jnp.zeros((n_micro, 64), jnp.int32)
        batch = {"input_ids": ids, "labels": ids}
        topo = MeshTopology({"pipe": 2, "data": 1})
        set_topology(topo)
        try:
            compiled = jax.jit(jax.grad(
                lambda p: tr.loss_fn(p, batch, cfg))).lower(params).compile()
            mem = compiled.memory_analysis()
            return mem.temp_size_in_bytes
        finally:
            set_topology(None)

    # per-microbatch work is constant (mb=1); only the stash should differ.
    small = temp_bytes("1f1b", 4)
    big = temp_bytes("1f1b", 16)
    # O(pp) bound: with the embedding inside the pipelined region the
    # input cotangent folds into O(vocab·H) embed grads per tick — no
    # O(n_micro) dx stash — so 4x more microbatches is near-flat (the
    # only O(B) growth left is the int32 ids/labels themselves)
    assert big < small * 1.15, (small, big)
    gpipe_big = temp_bytes("gpipe", 16)
    assert big < gpipe_big, (big, gpipe_big)


def test_pipe_sharded_init_matches_eager_init():
    """Regression: jitting init straight into P(pipe) stacked-layer
    out_shardings on a mesh with an unused data axis returned the
    pipe-sharded leaves scaled by the data-axis size (4x at data=4 on
    jax 0.4.37) — a silently-hot init that trained ~2x slower.  The
    engine now materializes unsharded and device_puts; a pipe-mesh
    engine's params must be bit-identical to the eager init."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models import transformer as tf

    model = get_model_config("gpt2-tiny")
    config = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "mesh": {"pipe": 2, "data": 4},
        "steps_per_print": 10_000,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config, seed=11)
    # eager run of the engine's own init fn — no jit, no shardings, so
    # XLA partitioning cannot touch the drawn values
    ref = engine._init_fn(jax.random.PRNGKey(11))
    got = jax.tree.map(lambda a: np.asarray(a, np.float32), engine.params)
    ref = jax.tree.map(lambda a: np.asarray(a, np.float32), ref)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_ref = jax.tree_util.tree_leaves_with_path(ref)
    assert len(flat_got) == len(flat_ref)
    for (path, a), (_, b) in zip(flat_got, flat_ref):
        # allclose, not array_equal: eager-vs-jit rng lowering may differ
        # in the last ulp — the bug being regressed is a 4x SCALE, which
        # no tolerance this tight lets through
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-7,
            err_msg=f"init drifted at {jax.tree_util.keystr(path)}")
