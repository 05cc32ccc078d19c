"""Fused compute-collective kernels (PR 12): quantize-into-ppermute,
gather-matmul, and the reduce-scatter grad-accumulator epilogue.

Covers the acceptance matrix:
* the wire codec the Pallas dequant epilogue applies is BITWISE the XLA
  codec (``comm/quantized.wire_decode_rows`` vs
  ``flash_mha.wire_dequant_rows``) — the two can never drift;
* quantized ring fwd+bwd parity on the 2×4 mesh, fused (interpreter
  Pallas) and XLA fallback paths, incl. exact fused-vs-XLA agreement;
* ≥3× collective-permute wire-byte reduction, census-verified;
* ``_rotate_together`` word packing survives odd-length buffers
  (satellite: no caller shape alignment);
* fused gather-matmul kernel + engine loss parity and warn-fallback;
* fused reduce-scatter engine loss parity;
* the overlap scheduler's ``fused_gather_matmul`` decision arm +
  pinned-config compatibility.
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

# the ops.pallas package re-exports the flash_mha FUNCTION under the
# same name as its submodule — resolve the module itself
_fm = importlib.import_module("deepspeed_tpu.ops.pallas.flash_mha")


@pytest.fixture
def seq_topo():
    topo = MeshTopology({"seq": 4, "data": 2})
    set_topology(topo)
    yield topo
    set_topology(None)


@pytest.fixture
def flash_interpret():
    old = _fm.INTERPRET
    _fm.INTERPRET = True
    yield
    _fm.INTERPRET = old


def _qkv(rng, b=2, s=64, nh=4, nkv=4, d=16, dtype=jnp.float32):
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, nh, d)), dtype)
    q = mk()
    k = jnp.asarray(rng.standard_normal((b, s, nkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, nkv, d)), dtype)
    return q, k, v


# ----------------------------------------------------------------------
# Codec parity: the kernel epilogue's dequant IS the XLA codec
# ----------------------------------------------------------------------
def test_wire_codec_kernel_parity_bitwise(rng):
    """flash_mha.wire_dequant_rows must reproduce
    comm/quantized.wire_decode_rows BIT-FOR-BIT on the same blocks —
    the shared-constants contract that keeps the Pallas and XLA wire
    codecs from drifting."""
    from deepspeed_tpu.comm.quantized import (wire_decode_rows,
                                              wire_encode_rows)
    from deepspeed_tpu.ops.pallas.flash_mha import wire_dequant_rows

    x = jnp.asarray(rng.standard_normal((6, 5, 32)), jnp.float32) * 3.7
    payload, scale = wire_encode_rows(x, "int8")
    ref = np.asarray(wire_decode_rows(payload, scale, "int8"))
    got = np.asarray(wire_dequant_rows(payload.reshape(-1, 32),
                                       scale.reshape(-1, 1))).reshape(
                                           ref.shape)
    assert got.dtype == np.float32
    assert np.array_equal(got, ref), "kernel dequant drifted from codec"
    # round trip bounded by the per-row symmetric int8 step
    err = np.abs(ref - np.asarray(x))
    bound = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / 127 * 0.51
    assert (err <= bound + 1e-7).all()


def test_flash_carry_quantized_matches_decoded_input(rng, flash_interpret):
    """flash_carry_block fed the int8 payload + scales must equal the
    same kernel fed the codec-decoded fp32 K/V exactly (the in-kernel
    dequant is the same arithmetic, then the same kernel body)."""
    from deepspeed_tpu.comm.quantized import (wire_decode_rows,
                                              wire_encode_rows)
    from deepspeed_tpu.ops.pallas.flash_mha import (flash_carry_block,
                                                    ring_carry_pad)

    b, h, s, d = 1, 2, 128, 32
    s_pad = ring_carry_pad(s)
    q = jnp.asarray(rng.standard_normal((b, h, s_pad, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s_pad, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s_pad, d)), jnp.float32)
    m = jnp.full((b, h, s_pad, 128), -1e30, jnp.float32)
    l = jnp.zeros((b, h, s_pad, 128), jnp.float32)
    acc = jnp.zeros((b, h, s_pad, d), jnp.float32)
    kp, ks = wire_encode_rows(k, "int8")
    vp, vs = wire_encode_rows(v, "int8")
    lanes = lambda x: jnp.broadcast_to(x, x.shape[:-1] + (128,))
    off = jnp.int32(0)
    out_q = flash_carry_block(q, kp, vp, m, l, acc, off, off, s_real=s,
                              k_scale=lanes(ks), v_scale=lanes(vs))
    out_f = flash_carry_block(
        q, wire_decode_rows(kp, ks, "int8"),
        wire_decode_rows(vp, vs, "int8"), m, l, acc, off, off, s_real=s)
    for a, b_ in zip(out_q, out_f):
        assert np.array_equal(np.asarray(a), np.asarray(b_))


# ----------------------------------------------------------------------
# Quantized ring parity (both gates, both wire dtypes)
# ----------------------------------------------------------------------
def _ring_loss_grads(topo, q, k, v, wire, interleave=1,
                     placement="contiguous"):
    from deepspeed_tpu.sequence.ring import ring_attention

    def loss(q, k, v):
        return ring_attention(q, k, v, topo, causal=True,
                              placement=placement, interleave=interleave,
                              wire_dtype=wire).astype(jnp.float32).sum()

    l, g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    return np.asarray(l), [np.asarray(x) for x in g]


@pytest.mark.parametrize("interleave", [1, 2])
@pytest.mark.parametrize("nkv", [4, 2])
def test_ring_quantized_wire_parity(seq_topo, rng, interleave, nkv):
    """int8 ring wire vs the fp32 wire: outputs and grads agree within
    the per-row int8 quantization budget on the XLA fallback path (the
    traveling K/V quantize once, dk/dv once per hop)."""
    q, k, v = _qkv(rng, nkv=nkv)
    l_f, g_f = _ring_loss_grads(seq_topo, q, k, v, "fp32",
                                interleave=interleave)
    l_q, g_q = _ring_loss_grads(seq_topo, q, k, v, "int8",
                                interleave=interleave)
    for a, b in zip(g_q, g_f):
        denom = np.abs(b).max() + 1e-9
        assert np.abs(a - b).max() / denom < 5e-2


def test_ring_quantized_fused_matches_xla_exactly(seq_topo, rng):
    """The fused path (int8 payload into the kernels, in-kernel dequant)
    must agree with the XLA fallback decoding the SAME payloads — both
    compute fp32 from identical decoded values."""
    q, k, v = _qkv(rng)
    old = _fm.INTERPRET
    try:
        _fm.INTERPRET = False
        l_x, g_x = _ring_loss_grads(seq_topo, q, k, v, "int8")
        _fm.INTERPRET = True
        l_p, g_p = _ring_loss_grads(seq_topo, q, k, v, "int8")
    finally:
        _fm.INTERPRET = old
    # the loss is an fp32 sum of ~5: hold it to 1e-5 RELATIVE (the order
    # of that sum follows how the shard_map is lowered)
    assert abs(l_x - l_p) < 1e-5 * max(1.0, abs(l_x))
    for a, b in zip(g_p, g_x):
        assert np.abs(a - b).max() < 1e-4, np.abs(a - b).max()


def test_ring_quantized_striped_flash(seq_topo, rng, flash_interpret):
    """Quantized wire composes with striped placement on the fused
    kernels: parity vs the fp32-wire striped ring."""
    q, k, v = _qkv(rng, nkv=2)
    l_f, g_f = _ring_loss_grads(seq_topo, q, k, v, "fp32",
                                placement="striped")
    l_q, g_q = _ring_loss_grads(seq_topo, q, k, v, "int8",
                                placement="striped")
    for a, b in zip(g_q, g_f):
        assert np.abs(a - b).max() / (np.abs(b).max() + 1e-9) < 5e-2


def test_ring_fp8_wire_runs(seq_topo, rng):
    """fp8 wire (payload bitcast to u8, XLA-side decode on both gates)
    stays within its coarser budget."""
    from deepspeed_tpu.comm.quantized import fp8_supported

    if not fp8_supported():
        pytest.skip("no float8_e4m3fn on this jax build")
    q, k, v = _qkv(rng)
    l_f, g_f = _ring_loss_grads(seq_topo, q, k, v, "fp32")
    l_q, g_q = _ring_loss_grads(seq_topo, q, k, v, "fp8")
    for a, b in zip(g_q, g_f):
        assert np.abs(a - b).max() / (np.abs(b).max() + 1e-9) < 2e-1


def test_ring_rejects_unknown_wire(seq_topo, rng):
    from deepspeed_tpu.sequence.ring import ring_attention

    q, k, v = _qkv(rng)
    with pytest.raises(ValueError, match="wire dtype"):
        jax.jit(lambda a, b, c: ring_attention(
            a, b, c, seq_topo, wire_dtype="int3"))(q, k, v)


# ----------------------------------------------------------------------
# Census: the quantized wire is statically visible and ≥3× smaller
# ----------------------------------------------------------------------
def test_ring_quant_census_byte_reduction(seq_topo, rng):
    """analysis.audit on the jitted ring fwd+bwd: the quantized rotation
    moves s8 payloads (the declared fused wire), the u32 word-packing is
    gone, and total collective-permute wire bytes shrink ≥3× vs the
    fp32 wire."""
    from deepspeed_tpu.analysis.auditor import audit
    from deepspeed_tpu.sequence.ring import ring_attention

    q, k, v = _qkv(rng)

    def permute_bytes(wire):
        def fwd_bwd(q, k, v):
            def loss(q, k, v):
                return ring_attention(q, k, v, seq_topo,
                                      wire_dtype=wire).astype(
                                          jnp.float32).sum()
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

        rep = audit(jax.jit(fwd_bwd), q, k, v, label=f"ring_{wire}")
        rows = [c for c in rep.census if c.kind == "collective-permute"]
        return rows, sum(c.wire_bytes for c in rows)

    rows_f, bytes_f = permute_bytes("fp32")
    rows_q, bytes_q = permute_bytes("int8")
    dtypes_q = {d for c in rows_q for d in c.dtype.split("+")}
    assert "s8" in dtypes_q, dtypes_q
    assert "u32" not in dtypes_q, dtypes_q
    assert bytes_f / bytes_q >= 3.0, (bytes_f, bytes_q)


def test_fused_collective_rollup_in_census_summary():
    """collective_census_engine attaches the fused_collective rollup so
    pinned static_census evidence distinguishes fused from scheduled
    hops (here: a quantized-ring engine declares ring_rotation)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.analysis.auditor import collective_census_engine
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.parallel import topology as topo_mod

    model = get_model_config("llama-tiny", max_seq_len=64, seq_impl="ring",
                             attn_impl="xla")
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2},
        "mesh": {"seq": 4},
        "comm_quantization": {"enabled": True, "ring_rotation": "int8"},
        "steps_per_print": 10_000,
    })
    try:
        summary = collective_census_engine(engine)
        fused = summary["fused_collective"]
        assert "ring_rotation" in fused
        assert fused["ring_rotation"]["wire"] == "int8"
        assert fused["ring_rotation"]["present"] is True
    finally:
        engine.destroy()
        topo_mod._GLOBAL_TOPOLOGY = None


# ----------------------------------------------------------------------
# _rotate_together word packing: arbitrary (odd) lengths
# ----------------------------------------------------------------------
@pytest.mark.parametrize("shape,dtype", [
    ((3, 17), jnp.bfloat16),       # odd element count, 2-byte dtype
    ((2, 5, 7), jnp.bfloat16),     # odd again, higher rank
    ((5, 3), jnp.int8),            # 1-byte dtype, non-multiple of 4
    ((4, 8), jnp.float32),         # word-aligned control
])
def test_rotate_together_odd_shapes(seq_topo, rng, shape, dtype):
    """The packed single-permute rotation pads sub-word tails instead of
    relying on callers to keep shapes pair-aligned (regression: an odd
    head_dim used to silently fall back to per-buffer permutes)."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.sequence.ring import _rotate_together
    from deepspeed_tpu.utils.jax_compat import shard_map

    sp = seq_topo.sp_size
    vals = rng.standard_normal((sp,) + shape) * 10
    odd = jnp.asarray(vals, dtype)
    extra = jnp.asarray(rng.standard_normal((sp, 4, 8)), jnp.float32)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def body(a, b):
        ra, rb = _rotate_together(perm, a, b)
        return ra, rb

    f = shard_map(body, mesh=seq_topo.mesh,
                  in_specs=(P("seq"), P("seq")),
                  out_specs=(P("seq"), P("seq")),
                  axis_names={"seq"}, check_vma=False)
    ra, rb = jax.jit(f)(odd, extra)
    # shard i receives shard i-1's buffer, byte-exact
    assert np.array_equal(np.asarray(ra), np.asarray(jnp.roll(odd, 1, 0)))
    assert np.array_equal(np.asarray(rb),
                          np.asarray(jnp.roll(extra, 1, 0)))


def test_ring_odd_head_dim(seq_topo, rng):
    """End-to-end ring attention with an odd head_dim (the shapes the
    packing fix unlocks) matches the full-attention reference."""
    from deepspeed_tpu.sequence.ring import (_block_attend_single,
                                             ring_attention)

    b, s, nh, d = 2, 32, 2, 17
    q = jnp.asarray(rng.standard_normal((b, s, nh, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, nh, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, nh, d)), jnp.bfloat16)
    out = jax.jit(lambda a, b_, c: ring_attention(a, b_, c, seq_topo))(
        q, k, v)
    ref = _block_attend_single(q, k, v, d ** -0.5, True, None)
    assert np.abs(np.asarray(out, np.float32)
                  - np.asarray(ref, np.float32)).max() < 2e-1


# ----------------------------------------------------------------------
# Fused gather-matmul
# ----------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(64, 64, 256), (130, 96, 72),
                                   (8, 300, 128)])
def test_pallas_matmul_parity(m, k, n, rng):
    import deepspeed_tpu.ops.pallas.gather_matmul as gm

    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    old = gm.INTERPRET
    try:
        gm.INTERPRET = True
        got = gm.pallas_matmul(x, w)
        # grads flow through the hand-written VJP
        g = jax.grad(lambda a, b: gm.pallas_matmul(a, b).sum(),
                     argnums=(0, 1))(x, w)
    finally:
        gm.INTERPRET = old
    ref = x @ w
    assert np.abs(np.asarray(got) - np.asarray(ref)).max() < 1e-4
    gx_ref, gw_ref = jax.grad(lambda a, b: (a @ b).sum(),
                              argnums=(0, 1))(x, w)
    assert np.abs(np.asarray(g[0]) - np.asarray(gx_ref)).max() < 1e-4
    assert np.abs(np.asarray(g[1]) - np.asarray(gw_ref)).max() < 1e-4


def _train_losses(model_name, config, steps=2, rows=16, seq=64, seed=0):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.parallel import topology as topo_mod

    model = get_model_config(model_name, max_seq_len=seq)
    engine, _, _, _ = ds.initialize(model=model, config=config)
    try:
        rng = np.random.default_rng(seed)
        ids = rng.integers(0, model.vocab_size, size=(rows, seq + 1),
                           dtype=np.int32)
        batch = {"input_ids": ids[:, :-1],
                 "labels": ids[:, 1:].astype(np.int32)}
        losses = [float(engine.train_batch(batch)) for _ in range(steps)]
        return engine, losses
    finally:
        engine.destroy()
        topo_mod._GLOBAL_TOPOLOGY = None


def _z3_config(**ss):
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
        "gradient_clipping": 1.0,
        "mesh": {"data": 8},
        "steps_per_print": 10_000,
    }
    if ss:
        cfg["step_schedule"] = ss
    return cfg


def test_fused_gather_matmul_engine_parity():
    """stage-3 engine with the fused gather-matmul MLP trains to the
    same losses as the GSPMD-scheduled path (gpt2's biased gelu MLP —
    bi rides the fused region)."""
    _, base = _train_losses("gpt2-tiny", _z3_config())
    eng, fused = _train_losses("gpt2-tiny",
                               _z3_config(fused_gather_matmul=True))
    assert eng.model_config.fused_gather_matmul
    assert eng.model_config.fused_gather_axes == ("data",)
    for a, b in zip(base, fused):
        assert abs(a - b) < 1e-5, (base, fused)


def test_fused_gather_matmul_swiglu_interpreter_parity():
    """swiglu (llama) MLP through the interpreted Pallas matmul kernel —
    the real fused path, forward and backward."""
    import deepspeed_tpu.ops.pallas.gather_matmul as gm

    _, base = _train_losses("llama-tiny", _z3_config())
    old = gm.INTERPRET
    try:
        gm.INTERPRET = True
        eng, fused = _train_losses("llama-tiny",
                                   _z3_config(fused_gather_matmul=True))
    finally:
        gm.INTERPRET = old
    assert eng.model_config.fused_gather_matmul
    for a, b in zip(base, fused):
        assert abs(a - b) < 1e-5, (base, fused)


def test_fused_gather_matmul_fallback_on_indivisible_bias():
    """An MLP bias whose dim cannot shard over the fsdp world (here
    intermediate_size=100 on 8 devices) must warn-fallback — the fused
    region's bias in_spec would otherwise crash at trace time."""
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.parallel import topology as topo_mod

    import deepspeed_tpu as ds

    model = get_model_config("gpt2-tiny", max_seq_len=64,
                             intermediate_size=100)
    engine, _, _, _ = ds.initialize(model=model,
                                    config=_z3_config(
                                        fused_gather_matmul=True))
    try:
        assert not engine.model_config.fused_gather_matmul
        rng = np.random.default_rng(0)
        ids = rng.integers(0, model.vocab_size, size=(16, 65),
                           dtype=np.int32)
        loss = float(engine.train_batch(
            {"input_ids": ids[:, :-1],
             "labels": ids[:, 1:].astype(np.int32)}))
        assert np.isfinite(loss)
    finally:
        engine.destroy()
        topo_mod._GLOBAL_TOPOLOGY = None


def test_fused_gather_matmul_fallback_when_persistent():
    """The default param-persistence threshold keeps tiny MLP weights
    gathered — the gate must warn-fallback, not shard_map over
    unsharded weights."""
    cfg = _z3_config(fused_gather_matmul=True)
    cfg["zero_optimization"] = {"stage": 3}   # default persistence
    eng, losses = _train_losses("gpt2-tiny", cfg)
    assert not eng.model_config.fused_gather_matmul
    assert all(np.isfinite(losses))


# ----------------------------------------------------------------------
# Fused reduce-scatter epilogue
# ----------------------------------------------------------------------
def _z1_config(**ss):
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 1},
        "gradient_clipping": 1.0,
        "mesh": {"data": 8},
        "steps_per_print": 10_000,
        "step_schedule": ss,
    }
    return cfg


def test_fused_reduce_scatter_parity():
    eng0, base = _train_losses(
        "gpt2-tiny", _z1_config(weight_update="decomposed"), steps=3)
    eng1, fused = _train_losses(
        "gpt2-tiny", _z1_config(weight_update="decomposed",
                                fused_reduce_scatter=True), steps=3)
    assert not getattr(eng0, "_fused_rs", False)
    assert eng1._fused_rs
    for a, b in zip(base, fused):
        assert abs(a - b) < 1e-5, (base, fused)


def test_fused_reduce_scatter_fallback_without_decomposed():
    eng, losses = _train_losses(
        "gpt2-tiny", _z1_config(fused_reduce_scatter=True), steps=2)
    assert not eng._fused_rs
    assert all(np.isfinite(losses))


# ----------------------------------------------------------------------
# Scheduler decision arm + config compatibility
# ----------------------------------------------------------------------
def _report(overlap=0.1, dom="all-gather.1"):
    return {"step": 5, "devices": {"d0": {"collective_ms": 4.0}},
            "overlap_fraction": overlap,
            "dominant_collective": {"name": dom}}


def test_scheduler_fused_gather_arm_fires_after_prefetch_exhausted():
    from deepspeed_tpu.autotuning.overlap_scheduler import decide

    ctx = {"zero_stage": 3, "dp": 8, "sp": 1, "seq_impl": "",
           "base": {"gather_prefetch_depth": 2,
                    "param_persistence_threshold": 0,
                    "prefetch_bucket_size": 50_000_000,
                    "ring_interleave": 1, "weight_update": "fused",
                    "fused_gather_matmul": False}}
    updates, decisions = decide(_report(), ctx)
    names = {d.decision for d in decisions}
    assert "fused_gather_matmul" in names
    assert updates["fused_gather_matmul"] is True
    # the scheduled arm keeps deepening in the same pass
    assert "zero3_prefetch" in names


def test_scheduler_fused_gather_arm_waits_for_depth():
    """First low-overlap probe at depth 1 only deepens prefetch — the
    fused arm waits until the scheduled arm is exhausted."""
    from deepspeed_tpu.autotuning.overlap_scheduler import decide

    ctx = {"zero_stage": 3, "dp": 8, "sp": 1, "seq_impl": "",
           "base": {"gather_prefetch_depth": 1,
                    "param_persistence_threshold": 0,
                    "prefetch_bucket_size": 50_000_000,
                    "ring_interleave": 1, "weight_update": "fused",
                    "fused_gather_matmul": False}}
    updates, decisions = decide(_report(), ctx)
    names = {d.decision for d in decisions}
    assert "fused_gather_matmul" not in names
    assert "zero3_prefetch" in names


def test_scheduler_fused_gather_arm_not_on_reduce_dominated():
    from deepspeed_tpu.autotuning.overlap_scheduler import decide

    ctx = {"zero_stage": 3, "dp": 8, "sp": 1, "seq_impl": "",
           "base": {"gather_prefetch_depth": 2,
                    "param_persistence_threshold": 0,
                    "prefetch_bucket_size": 50_000_000,
                    "ring_interleave": 1, "weight_update": "fused",
                    "fused_gather_matmul": False}}
    _, decisions = decide(_report(dom="all-reduce.3"), ctx)
    assert "fused_gather_matmul" not in {d.decision for d in decisions}


def test_pre_existing_pinned_configs_still_load():
    """A step_schedule block pinned BEFORE the fused knobs existed (no
    fused_gather_matmul / fused_reduce_scatter keys, pre-census decision
    records) must keep loading; unknown decisions stay rejected."""
    from deepspeed_tpu.autotuning.overlap_scheduler import ScheduleDecision
    from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                              StepScheduleConfig)

    old_pinned = {
        "mode": "pinned", "probe_steps": 3, "overlap_threshold": 0.5,
        "gather_prefetch_depth": 2,
        "decisions": [{"decision": "zero3_prefetch",
                       "knobs": {"gather_prefetch_depth": 2},
                       "evidence": {"dominant_collective": "all-gather",
                                    "exposed_comm_ms": 3.0,
                                    "overlap_fraction": 0.2,
                                    "overlap_source": "spans",
                                    "probe_step": 4}}],
    }
    ss = StepScheduleConfig(**old_pinned)
    assert ss.fused_gather_matmul is False
    assert ss.fused_reduce_scatter is False
    d = ScheduleDecision.from_dict(old_pinned["decisions"][0])
    assert d.evidence["static_census"] is None
    # new fused records round-trip too
    d2 = ScheduleDecision.from_dict(
        {"decision": "fused_gather_matmul",
         "knobs": {"fused_gather_matmul": True},
         "evidence": dict(d.evidence)})
    assert d2.decision == "fused_gather_matmul"
    with pytest.raises(ValueError):
        ScheduleDecision.from_dict(
            {"decision": "warp_drive", "knobs": {},
             "evidence": dict(d.evidence)})
    with pytest.raises(DeepSpeedConfigError):
        StepScheduleConfig(decisions=[{"decision": "warp_drive",
                                       "knobs": {}, "evidence": {}}])
