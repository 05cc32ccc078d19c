"""The device-side stage names (``telemetry.tracing.STAGE_NAMES``): every
jitted step's lowered text names the stages its model has and no other,
and ``utils/xplane.py:time_by_stage`` adds a capture's device time up by
them."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import get_model_config
from deepspeed_tpu.telemetry import STAGE_NAMES
from deepspeed_tpu.utils import xplane

_BLOCK = {"embed", "layers", "attn.qkv", "attn.append", "attn.read",
          "attn.out", "mlp", "head"}
_MOE = {"moe.router", "moe.dispatch", "moe.experts", "moe.combine",
        "moe.shared"}
_MIXER = {"ssm.in", "ssm.conv", "ssm.scan", "ssm.out"}
_LATENT = {"embed", "layers", "latent.down", "attn.append", "latent.index",
           "latent.select", "latent.gather", "latent.read", "attn.out", "mlp",
           "head"} | _MOE

# preset -> (kind of step, the stages its lowered text has to name)
_STEPS = {
    "mistral-tiny": ("serve", _BLOCK),
    "falcon-h1-tiny": ("serve", _BLOCK | _MIXER),
    "dots3-note-tiny": ("serve", _LATENT | {"latent.window"}),
    "glm-5-tiny": ("draft", _LATENT | {"mtp", "verify"}),
    "mixtral-tiny": ("serve", (_BLOCK - {"mlp"}) | _MOE),
    # models/ and runtime/ carry no scope yet: the four train names
    # (loss, optimizer, grad.norm, grad.accum) come with the PR that
    # edits those files for a reason of its own
    "gpt2-tiny": ("train", set()),
}


def _stages_named(text):
    """The stages on the name stacks of a lowered module's locations."""
    stacks = set(re.findall(r'loc\("([^"]+)"', text))
    return {xplane._stage_of(s) for s in stacks} - {xplane._UNSCOPED}


def _lowered_serving_step(preset, kind):
    from deepspeed_tpu.inference.v2 import latent
    from deepspeed_tpu.inference.v2 import model as v2_model
    from deepspeed_tpu.inference.v2.ragged import PackedIndex
    from deepspeed_tpu.models import transformer as tf_model

    cfg = get_model_config(preset)
    params = jax.eval_shape(lambda k: tf_model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    t, slots, nb, bs = 16, 5, 4, 4
    state = None
    if cfg.mla is not None:
        ck, cv, state = jax.eval_shape(
            lambda: latent.new_cache(cfg, 64 * bs, slots - 1, t))
    else:
        ck = cv = jax.ShapeDtypeStruct(
            (cfg.num_layers, cfg.kv_heads, 64 * bs, cfg.dim_per_head),
            cfg.dtype)
        if cfg.ssm is not None:
            state = jax.eval_shape(
                lambda: v2_model.new_ssm_state(cfg, slots - 1))
    draft = kind == "draft"
    index = PackedIndex(jax.ShapeDtypeStruct(
        (PackedIndex.size(t, slots, nb, draft),), jnp.int32),
        t, slots, nb, draft)
    if draft:
        fn = functools.partial(v2_model.ragged_draft_step, cfg=cfg,
                               block_size=bs)
        return jax.jit(fn).lower(
            params, ck, cv, index,
            jax.ShapeDtypeStruct((4, slots), jnp.int32))   # its ``out``
    fn = functools.partial(v2_model.ragged_step_sampled, cfg=cfg,
                           block_size=bs, greedy=True)
    kw = {} if state is None else {"state": state}
    return jax.jit(fn).lower(
        params, ck, cv, index,
        jax.ShapeDtypeStruct((slots,), jnp.int32),     # the step before's
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((), jnp.float32), **kw)


def _lowered_train_step(preset):
    import deepspeed_tpu as ds

    model = get_model_config(preset, max_seq_len=32)
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True}, "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 1}, "mesh": {"data": 1},
        "steps_per_print": 1_000_000,
        "activation_checkpointing": {"remat_policy": "dots_flash_saveable"},
    }, seed=3)
    step, args = engine.audit_step_args()
    return step.lower(*args)


@pytest.mark.parametrize("preset", list(_STEPS))
def test_lowered_step_names_its_models_stages_and_no_other(preset):
    """Inside the scanned layer body and the self-drafting step's module:
    the step's lowered text carries every stage the model has, and none of
    a model it is not (a mixer's in a plain block, a ring's in a model
    without window layers, the module's in a step that drafts nothing).
    The train step, whose files this vocabulary has not reached, names
    none."""
    kind, want = _STEPS[preset]
    lowered = (_lowered_train_step(preset) if kind == "train"
               else _lowered_serving_step(preset, kind))
    named = _stages_named(lowered.as_text(debug_info=True))
    assert named == want, (sorted(named - want), sorted(want - named))
    assert named <= set(STAGE_NAMES)


# -- utils/xplane.py: the program's reader -----------------------------------
@pytest.mark.parametrize("stack,last,first", [
    ("jit(ragged_step_sampled)/while/body/attn.qkv/dot_general:",
     "attn.qkv", "attn.qkv"),
    # the module's attention: the innermost name, and the module as a whole
    ("jit(ragged_draft_step)/mtp/latent.read/while/body/latent.gather/gather:",
     "latent.gather", "mtp"),
    # backward of a rematerialised layer
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/tanh:", "mlp", "mlp"),
    ("jit(train_step)/transpose(jvp(moe.experts))/mul:", "moe.experts",
     "moe.experts"),
    # a scope whose name is not in the table is no stage
    ("jit(train_step)/optimizer/grad.norm/sqrt:", "unscoped", "unscoped"),
    # a jitted function's name is not a scope; neither is a primitive's
    ("jit(loss)/jit(verify)/add:", "unscoped", "unscoped"),
    ("jit(step)/while/body/transpose:", "unscoped", "unscoped"),
    ("q:", "unscoped", "unscoped"), ("", "unscoped", "unscoped"),
])
def test_stage_of_a_name_stack(stack, last, first):
    assert xplane._stage_of(stack) == last
    assert xplane._stage_of(stack, first=True) == first


def _hand_made_xspace(zero3=False):
    """One device plane: a layer loop (``while``) over an attention
    product, a Pallas kernel under the module, a copy of an argument, an
    operation the compiler made; and a host plane that is not read.
    ``zero3``: besides, what a sharded step adds, as a v5e's capture
    holds it (``tests/benchmark/data/span_trace.xplane.pb`` has such a
    copy): an asynchronous all-gather, on the instruction stream its
    start and its done (the exposed wait) and on the ``Async XLA Ops``
    line ONE event from start to done under the start's name; a
    synchronous all-reduce; and ten products larger than any
    collective's self time."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    xs = xplane_pb2.XSpace()
    plane = xs.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    plane.stat_metadata[2].name = "program_id"
    ops = {
        1: ("%while.3 = (s32[], bf16[8,128]) while((s32[], bf16[8,128]) "
            "%tuple.1), condition=%cond, body=%body",
            "jit(step)/while:"),
        2: ("%fusion.7 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.1), "
            "kind=kOutput, calls=%fused.7",
            "jit(step)/while/body/attn.qkv/dot_general:"),
        3: ("%paged_qblock.1 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} "
            '%q), custom_call_target="tpu_custom_call"',
            "jit(step)/mtp/attn.read/paged_qblock/pallas_call:"),
        4: ("%copy.2 = bf16[8,128]{0,1} copy(bf16[8,128]{1,0} %params)",
            "params['embed']:"),
        5: ("%bitcast_fusion = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} "
            "%p.2), kind=kLoop, calls=%fused.b", ""),
    }
    for mid, (name, stack) in ops.items():
        md = plane.event_metadata[mid]
        md.id, md.name = mid, name
        if stack:
            md.stats.add(metadata_id=1, str_value=stack)
        md.stats.add(metadata_id=2, uint64_value=77)
    line = plane.lines.add(name="XLA Ops", timestamp_ns=0)
    ms = 10 ** 9    # ps
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=8 * ms)
    for k in range(2):      # two trips of the loop
        line.events.add(metadata_id=2, offset_ps=k * 4 * ms,
                        duration_ps=3 * ms)
    line.events.add(metadata_id=3, offset_ps=8 * ms, duration_ps=2 * ms)
    line.events.add(metadata_id=4, offset_ps=10 * ms, duration_ps=1 * ms)
    line.events.add(metadata_id=5, offset_ps=11 * ms, duration_ps=1 * ms)
    if zero3:
        gather = ("bf16[8,128]{1,0} %p.3), replica_groups={{0,1,2,3}}, "
                  "dimensions={0}")
        more = {
            6: (f"%all-gather-start.3 = (bf16[2,128]{{1,0}}, bf16[8,128]"
                f"{{1,0}}) all-gather-start({gather}",
                "jit(step)/while/body/mlp/dot_general:"),
            7: (f"%all-gather-done.3 = bf16[8,128]{{1,0}} all-gather-done("
                f"(bf16[2,128]{{1,0}}, {gather}",
                "jit(step)/while/body/mlp/dot_general:"),
            8: ("%all-reduce.9 = f32[] all-reduce(f32[] %loss.1), "
                "replica_groups={{0,1,2,3}}, to_apply=%add",
                "jit(step)/jvp()/reduce_sum:"),
        }
        more.update({
            10 + k: (f"%fusion.{100 + k} = bf16[8,128]{{1,0}} fusion("
                     f"bf16[8,128]{{1,0}} %p.{k}), kind=kOutput, "
                     f"calls=%fused.{100 + k}",
                     "jit(step)/while/body/mlp/dot_general:")
            for k in range(10)})
        for mid, (name, stack) in more.items():
            md = plane.event_metadata[mid]
            md.id, md.name = mid, name
            md.stats.add(metadata_id=1, str_value=stack)
            md.stats.add(metadata_id=2, uint64_value=77)
        at = 12 * ms
        for mid, dur in [(6, ms // 10), (7, ms // 5), (8, 3 * ms // 10)] + [
                (10 + k, ms // 2) for k in range(10)]:
            line.events.add(metadata_id=mid, offset_ps=at, duration_ps=dur)
            at += dur
        spans = plane.lines.add(name="Async XLA Ops", timestamp_ns=0)
        spans.events.add(metadata_id=6, offset_ps=12 * ms,
                         duration_ps=5 * ms)
    host = xs.planes.add(name="/host:CPU")
    host.event_metadata[1].name = "fusion.7"
    host.lines.add(name="ops").events.add(metadata_id=1, duration_ps=50 * ms)
    return xs


def test_time_by_stage_on_a_hand_made_capture():
    """Last-segment rule, first-segment roll-up, ``unscoped``, and the
    loop left out: its 8 ms are its body's 6."""
    got = xplane.time_by_stage(_hand_made_xspace())
    assert got["total_ms"] == pytest.approx(10.0)
    assert got["stages"] == {"attn.qkv": 6.0, "attn.read": 2.0,
                             "unscoped": 2.0}
    assert got["outer"] == {"attn.qkv": 6.0, "mtp": 2.0, "unscoped": 2.0}
    assert got["unscoped_share"] == pytest.approx(0.2)
    assert list(got["stages"]) == ["attn.qkv", "attn.read", "unscoped"]


def test_top_ops_carry_their_stage_and_leave_the_loop_out():
    rows = xplane.top_device_ops(_hand_made_xspace())
    assert [(r["name"].split(" = ")[0], r["stage"], r["count"])
            for r in rows] == [
        ("%fusion.7", "attn.qkv", 2), ("%paged_qblock.1", "attn.read", 1),
        ("%copy.2", "unscoped", 1), ("%bitcast_fusion", "unscoped", 1)]
    assert xplane._is_control_op("%while.3 = (s32[]) while(%t), body=%b")
    assert xplane._is_control_op("conditional.4")
    assert not xplane._is_control_op("%fusion.7 = bf16[8] fusion(%p), "
                                   "calls=%while_body")


def test_an_async_collective_is_self_time_in_the_table_and_still_dominant(
        tmp_path):
    """The ``Async XLA Ops`` line is no self time: the tables and the
    stages leave it out (its five ms lie over the products'), so the
    all-gather's start and done fall out of the ten largest; the report
    names it all the same, from every collective and by its time from
    start to done, which is what ``overlap_scheduler`` gates on."""
    from deepspeed_tpu.telemetry import build_capture_report

    xs = _hand_made_xspace(zero3=True)
    rows = xplane.top_device_ops(xs)
    assert len(rows) == 10
    assert not any(xplane.classify_op(r["name"]) == "collective"
                   for r in rows)
    assert xplane.dominant_collective(rows) is None
    every = {r["name"].split(" = ")[0]: r for r in
             xplane._op_totals(xs, "TPU")}
    assert len(every) == 17
    assert every["%all-gather-start.3"]["total_ms"] == 0.1
    assert every["%all-gather-done.3"]["total_ms"] == 0.2
    assert every["%all-gather-done.3"]["stage"] == "mlp"
    assert every["%all-reduce.9"]["stage"] == "unscoped"
    spans = {r["name"].split(" = ")[0]: r for r in
             xplane._op_totals(xs, "TPU", async_spans=True)}
    assert spans["%all-gather-start.3"]["total_ms"] == 5.1
    assert spans["%all-gather-start.3"]["count"] == 2
    # busy time is the instruction stream's: 10 + 0.6 + 10 x 0.5
    assert xplane.time_by_stage(xs)["total_ms"] == pytest.approx(15.6)

    (tmp_path / "t.xplane.pb").write_bytes(xs.SerializeToString())
    rep = build_capture_report(str(tmp_path))
    assert len(rep["top_ops"]) == 10
    assert not any("all-" in op["name"] for op in rep["top_ops"])
    dom = rep["dominant_collective"]
    assert dom["name"].startswith("%all-gather-start.3 = ")
    assert dom["total_ms"] == 5.1
    assert rep["stages"]["total_ms"] == 15.6
    # two hosts' files add up by name
    (tmp_path / "u.xplane.pb").write_bytes(xs.SerializeToString())
    assert build_capture_report(str(tmp_path))["dominant_collective"][
        "total_ms"] == 10.2


def test_capture_report_has_a_stages_block(tmp_path):
    from deepspeed_tpu.telemetry import build_capture_report

    (tmp_path / "t.xplane.pb").write_bytes(
        _hand_made_xspace().SerializeToString())
    rep = build_capture_report(str(tmp_path))
    assert rep["stages"]["stages"] == {"attn.qkv": 6.0, "attn.read": 2.0,
                                       "unscoped": 2.0}
    assert rep["stages"]["outer"]["mtp"] == 2.0
    assert rep["stages"]["total_ms"] == 10.0
    assert rep["stages"]["unscoped_share"] == 0.2
    assert [op["stage"] for op in rep["top_ops"]][:2] == ["attn.qkv",
                                                          "attn.read"]
    empty = build_capture_report(str(tmp_path / "none"))
    assert empty["stages"] == {} and empty["top_ops"] == []


def test_every_scope_in_the_package_is_a_stage_and_every_stage_a_scope(
        tmp_path):
    """The lint, both ways, and that it can fail each way."""
    from tools import telemetry_check

    assert telemetry_check.check_stage_names() == []
    used = {n for _, _, names in telemetry_check.named_scopes()
            for n in names}
    assert used == set(STAGE_NAMES)
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        'import jax\n'
        'with jax.named_scope("attn.qkv"):\n    pass\n'
        'with jax.named_scope("my.stage"):\n    pass\n'
        'with jax.named_scope(name):\n    pass\n'
        'with jax.named_scope("mlp" if dense else "moe.router"):\n    pass\n')
    errors = telemetry_check.check_stage_names(str(pkg))
    assert any("'my.stage'" in e and "m.py:4" in e for e in errors), errors
    assert any("without a literal" in e and "m.py:6" in e for e in errors)
    assert any("'verify' is used by no named_scope" in e for e in errors)
    assert not any("'mlp'" in e or "'moe.router'" in e or "'attn.qkv'" in e
                   for e in errors), errors
