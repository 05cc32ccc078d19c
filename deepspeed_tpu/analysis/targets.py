"""Audit targets: every step configuration the engines are run with
gets a statically auditable twin here, scaled to the virtual 8-device
CPU mesh so the tier-1 suite and ``tools/graft_lint.py --rows/--memory``
can lower + audit each one WITHOUT running a step.

The targets, and the step each one audits:

=====================  ==============================================
target                 step it audits
=====================  ==============================================
``train_zero1``        ZeRO-1 data-parallel train step
``train_zero3``        ZeRO-3 train step (gathers + reduce-scatters)
``train_commquant``    ZeRO-1 with the int8 quantized DP reduce
``train_autosched``    ZeRO-3 under a pinned zero3_prefetch schedule
``train_fused_rs``     the schedule's fused A/B (decomposed +
                       fused reduce-scatter epilogue)
``train_fused_gather`` the schedule's fused A/B (stage-3 fused
                       gather-matmul MLP)
``ring_attention``     ring attention fwd+bwd on the 2×4 mesh
``ring_attention_quant``  its quantized-wire A/B (int8
                       ring_rotation)
``v2_decode``          InferenceEngineV2 16-token decode step
``v2_prefill``         InferenceEngineV2 full-budget prefill step
``v2_verify``          speculative target verify-k step
``v2_spec_draft``      draft-model propose/decode step
=====================  ==============================================

Each target PREPARES once — build its engine, read the step fn +
example args + both audit intents off it — and every audit family
(collective census, donation, memory plan) then runs off ONE shared
:class:`~deepspeed_tpu.analysis.auditor.LoweredStep`: with the registry
at 12+ rows and each lowering ~2s, re-lowering per audit would double
the lint's wall time for nothing.  Geometry is tiny (gpt2-tiny class)
because the lint checks graph *structure*; byte volumes scale with the
real config but kind/dtype/alias/shape findings do not.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

from deepspeed_tpu.analysis.report import (GraphAuditReport,
                                           MemoryAuditReport)


def _reset_topology():
    from deepspeed_tpu.parallel import topology

    topology._GLOBAL_TOPOLOGY = None


@dataclass
class PreparedTarget:
    """One target, ready to lower: the jitted step + example args, both
    audit intents (read off the live engine), and the teardown that
    releases the engine/topology.  ``cleanup()`` runs AFTER lowering —
    the AOT artifacts outlive the engine."""
    label: str
    fn: Any
    args: Tuple[Any, ...]
    intent: Any                 # AuditIntent
    memory_intent: Any          # MemoryIntent
    cleanup: Callable[[], None]


def _train_config(n: int, **over):
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 1},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
        "mesh": {"data": n},
    }
    cfg.update(over)
    return cfg


def _prep_engine(engine, label: str,
                 extra_cleanup: Optional[Callable[[], None]] = None
                 ) -> PreparedTarget:
    from deepspeed_tpu.analysis.auditor import intent_for_engine
    from deepspeed_tpu.analysis.memory import memory_intent_for_engine

    fn, args = engine.audit_step_args()

    def cleanup():
        try:
            engine.destroy()
        finally:
            _reset_topology()
            if extra_cleanup is not None:
                extra_cleanup()

    return PreparedTarget(label=label, fn=fn, args=args,
                          intent=intent_for_engine(engine),
                          memory_intent=memory_intent_for_engine(engine),
                          cleanup=cleanup)


def _prep_train(label: str, **over) -> PreparedTarget:
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config

    model = get_model_config("gpt2-tiny", max_seq_len=64)
    engine, _, _, _ = ds.initialize(
        model=model, config=_train_config(jax.device_count(), **over))
    return _prep_engine(engine, label)


def prep_train_zero1() -> PreparedTarget:
    return _prep_train("train_zero1", bf16={"enabled": True})


def prep_train_zero3() -> PreparedTarget:
    return _prep_train("train_zero3", bf16={"enabled": True},
                       zero_optimization={"stage": 3})


def prep_train_commquant() -> PreparedTarget:
    return _prep_train(
        "train_commquant",
        comm_quantization={"enabled": True, "grad_reduce": "int8"})


def prep_train_autosched() -> PreparedTarget:
    # the pinned shape the autosched row converges to on a ZeRO-3 probe
    return _prep_train(
        "train_autosched", bf16={"enabled": True},
        zero_optimization={"stage": 3},
        step_schedule={"mode": "pinned", "gather_prefetch_depth": 2,
                       "param_persistence_threshold": 100_000})


def prep_train_fused_rs() -> PreparedTarget:
    """Fused reduce-scatter twin (step_schedule.fused_reduce_scatter +
    decomposed update at stage 1): the explicit per-leaf psum_scatter in
    the grad-accumulator epilogue must audit clean — reduce-scatter is
    declared intent on the decomposed path."""
    return _prep_train(
        "train_fused_rs",
        step_schedule={"weight_update": "decomposed",
                       "fused_reduce_scatter": True})


def prep_train_fused_gather() -> PreparedTarget:
    """Fused gather-matmul twin (step_schedule.fused_gather_matmul at
    stage 3, persistence off so the tiny MLP weights actually shard):
    the explicit in-region all-gathers must audit clean — all-gather is
    declared stage-3 intent either way; this pins that the fused path
    introduces nothing unexplained."""
    return _prep_train(
        "train_fused_gather", bf16={"enabled": True},
        zero_optimization={"stage": 3, "param_persistence_threshold": 0},
        step_schedule={"fused_gather_matmul": True})


def prep_train_offload_cpu() -> PreparedTarget:
    """Chunked host-optimizer twin (peak_params cpu-chunked rung):
    working_set_bytes forces the ChunkedHostOptimizer, so the audited
    program is the fwd+bwd grads batch — params and moments never enter
    the device program, which is the memory claim the chunked tier
    makes.  The frozen budget pins that the device footprint stays
    params+activations-sized."""
    return _prep_train(
        "train_offload_cpu",
        zero_optimization={"stage": 2,
                           "offload_optimizer": {"device": "cpu",
                                                 "working_set_bytes": 1}})


def prep_train_resumed() -> PreparedTarget:
    """Self-healing resume twin (chaos_recovery row): state saved under
    a pure-data mesh is universally reloaded onto a data×tensor
    factorization through the PartitionOracle, and the RESUMED engine's
    train step is audited.  Zero unbaselined highs means the
    oracle-derived shardings census-match the declared intent — the
    resharding resume introduced no implicit reshard, no dropped
    donation, no unexplained collective — which is the static half of
    the chaos e2e's loss-continuity assertion."""
    import shutil
    import tempfile

    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.checkpoint.universal import (ds_to_universal,
                                                    load_universal)
    from deepspeed_tpu.models import get_model_config

    model = get_model_config("gpt2-tiny", max_seq_len=64)
    n = jax.device_count()
    ckdir = tempfile.mkdtemp(prefix="dstpu_audit_resume_")
    try:
        engine, _, _, _ = ds.initialize(
            model=model,
            config=_train_config(n, zero_optimization={"stage": 2}))
        try:
            engine.save_checkpoint(ckdir, tag="seed")
            udir = ds_to_universal(ckdir, tag="seed")
        finally:
            engine.destroy()
            _reset_topology()
        cfg = _train_config(n, zero_optimization={"stage": 2})
        cfg["mesh"] = ({"data": n // 2, "tensor": 2} if n >= 2
                       else {"data": 1})
        engine2, _, _, _ = ds.initialize(model=model, config=cfg)
        load_universal(engine2, udir)
    except BaseException:
        shutil.rmtree(ckdir, ignore_errors=True)
        raise
    return _prep_engine(
        engine2, "train_resumed",
        extra_cleanup=lambda: shutil.rmtree(ckdir, ignore_errors=True))


def _prep_ring(label: str, wire_dtype: str, intent) -> PreparedTarget:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.analysis.memory import MemoryIntent
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
    from deepspeed_tpu.sequence.ring import ring_attention

    topo = MeshTopology({"seq": 4, "data": 2})
    set_topology(topo)
    b, s, nh, d = 2, 64, 4, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, nh, d)), jnp.float32)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return ring_attention(
                q, k, v, topo, wire_dtype=wire_dtype).astype(
                    jnp.float32).sum()
        l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return l, grads

    def cleanup():
        set_topology(None)
        _reset_topology()

    return PreparedTarget(
        label=label, fn=jax.jit(fwd_bwd), args=(q, q, q), intent=intent,
        memory_intent=MemoryIntent(
            arg_categories=("activations",) * 3,
            seq_len=s // topo.sp_size),
        cleanup=cleanup)


def prep_ring_attention() -> PreparedTarget:
    """longseq_ring twin: jitted ring fwd+bwd on the 2(data)×4(seq)
    mesh — the census must carry the ring's collective-permute hops and
    nothing unexplained."""
    from deepspeed_tpu.analysis.auditor import AuditIntent

    intent = AuditIntent(
        expected=frozenset({"collective-permute", "all-reduce",
                            "all-gather", "reduce-scatter"}),
        required={"collective-permute": ()})
    return _prep_ring("ring_attention", "fp32", intent)


def prep_ring_attention_quant() -> PreparedTarget:
    """Quantized-wire longseq_ring twin (comm_quantization.ring_rotation
    = int8): the rotation's collective-permutes must move s8 payloads —
    the fp32-wire u32 word-packing is BANNED at volume, and an s8
    permute is required (the fused-wire declaration the auditor's
    intent_for_engine derives for quantized ring engines)."""
    from deepspeed_tpu.analysis.auditor import AuditIntent

    intent = AuditIntent(
        expected=frozenset({"collective-permute", "all-reduce",
                            "all-gather", "reduce-scatter"}),
        required={"collective-permute": ("s8",)},
        banned={"collective-permute": ("u32",)})
    return _prep_ring("ring_attention_quant", "int8", intent)


def _prep_v2(phase: str, model_name: str = "gpt2-tiny",
             label: Optional[str] = None, **model_over) -> PreparedTarget:
    from deepspeed_tpu.analysis.auditor import intent_for_v2
    from deepspeed_tpu.analysis.memory import memory_intent_for_v2
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_model_config

    model = get_model_config(model_name, max_seq_len=128, **model_over)
    eng = InferenceEngineV2(model, {
        "state_manager": {"max_tracked_sequences": 4,
                          "max_ragged_batch_size": 64},
        "memory_config": {"num_blocks": 16, "block_size": 16},
        "max_context": 128})
    # the point of the target is the CONFIGURED tiny geometry — a config
    # nesting drift that silently fell back to defaults would audit a
    # 512-block step instead of the configured twin
    assert eng.cfg.num_blocks == 16 and eng.state_manager.max_seqs == 4, \
        (eng.cfg.num_blocks, eng.state_manager.max_seqs)
    fn, args = eng.audit_step_args(phase)
    return PreparedTarget(
        label=label or f"v2_{phase}", fn=fn, args=args,
        intent=intent_for_v2(eng),
        memory_intent=memory_intent_for_v2(eng),
        cleanup=_reset_topology)


def prep_v2_spec_draft() -> PreparedTarget:
    """serve_disagg draft-propose twin: the draft model's decode-phase
    step (speculative proposals are plain greedy decode dispatches of a
    SMALLER model sharing the target's vocabulary — serving/disagg.py
    SpeculativeDecoder)."""
    return _prep_v2("decode", model_name="llama-tiny", num_layers=1,
                    label="v2_spec_draft")


TARGET_PREPARERS: Dict[str, Callable[[], PreparedTarget]] = {
    "train_zero1": prep_train_zero1,
    "train_zero3": prep_train_zero3,
    "train_commquant": prep_train_commquant,
    "train_autosched": prep_train_autosched,
    "train_fused_rs": prep_train_fused_rs,
    "train_fused_gather": prep_train_fused_gather,
    "train_offload_cpu": prep_train_offload_cpu,
    "train_resumed": prep_train_resumed,
    "ring_attention": prep_ring_attention,
    "ring_attention_quant": prep_ring_attention_quant,
    "v2_decode": partial(_prep_v2, "decode"),
    "v2_prefill": partial(_prep_v2, "prefill"),
    "v2_verify": partial(_prep_v2, "verify"),
    "v2_spec_draft": prep_v2_spec_draft,
}


def run_target_audits(name: str, memory: bool = False,
                      budget: Optional[int] = None, graph: bool = True
                      ) -> Tuple[Optional[GraphAuditReport],
                                 Optional[MemoryAuditReport]]:
    """Prepare + lower ``name`` ONCE and run the requested audit
    families off the shared artifacts.  ``budget`` is the frozen
    per-target peak budget (``tools/memory_baseline.json``) the memory
    audit gates against; None audits with a no-budget warning.  A
    memory-only caller (``graft_lint --memory``) passes ``graph=False``
    and pays only lowering + the memory audit."""
    from deepspeed_tpu.analysis.auditor import audit_artifacts, lower_step

    try:
        prep_fn = TARGET_PREPARERS[name]
    except KeyError:
        raise KeyError(f"unknown audit target {name!r} "
                       f"(known: {sorted(TARGET_PREPARERS)})") from None
    prep = prep_fn()
    try:
        art = lower_step(prep.fn, *prep.args, label=prep.label)
    finally:
        prep.cleanup()
    graph_rep = audit_artifacts(art, intent=prep.intent) if graph else None
    mem = None
    if memory:
        from deepspeed_tpu.analysis.memory import audit_memory

        mem = audit_memory(art, intent=prep.memory_intent, budget=budget)
    return graph_rep, mem


def run_audit_target(name: str) -> GraphAuditReport:
    """Back-compat single-family entry: the graph audit only."""
    return run_target_audits(name)[0]


BENCH_AUDIT_TARGETS: Dict[str, Callable[[], GraphAuditReport]] = {
    name: partial(run_audit_target, name) for name in TARGET_PREPARERS}
