"""DeepSpeedEngine — the training engine.

TPU-native re-design of ``runtime/engine.py`` (DeepSpeedEngine :206).  The
reference wraps an eager nn.Module and orchestrates hooks, buckets and NCCL
ops per micro-batch; here the entire train batch — gradient-accumulation
scan over micro-batches, gradient reduction, clipping, loss-scale logic and
the (ZeRO-sharded) optimizer update — is ONE jitted XLA program:

    train_batch → jit[ scan(micro: value_and_grad) → clip → opt.update ]

ZeRO stages are realised purely as shardings (see parallel/sharding.py):
XLA inserts reduce-scatter for sharded grad accumulators (stage 2), per-layer
all-gathers for sharded params (stage 3), and its latency-hiding scheduler
overlaps them with compute — replacing the reference's IPG buckets
(stage_1_and_2.py:1028), prefetch coordinator and overlap_comm machinery.

API parity: ``forward``/``backward``/``step`` trio, ``train_batch``,
``eval_batch``, ``save_checkpoint``/``load_checkpoint``, ``global_steps``,
``get_global_grad_norm``, gradient-accumulation boundary semantics.
"""

from __future__ import annotations

import os
import pickle
import sys
import time
from functools import partial
from typing import Any, Callable, Dict, Iterator, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.models import transformer as tf_model
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.resilience.oracle import (PartitionOracle,
                                             secondary_mode_from_config)
from deepspeed_tpu.parallel.topology import (BATCH_AXES, SEQ_AXIS, MeshTopology, get_topology,
                                             set_topology)
from deepspeed_tpu.runtime.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.runtime.lr_schedules import LRSchedule, build_lr_schedule, constant_lr
from deepspeed_tpu.runtime.optimizers import Optimizer, build_optimizer
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER,
                                       STEP_GLOBAL_TIMER, TRAIN_BATCH_TIMER,
                                       SynchronizedWallClockTimer, ThroughputTimer)

Batch = Dict[str, Any]

# once-per-process throttle for the discarded-prefetch warning (same
# pattern as the accelerator's unbalanced range_pop throttle): every
# checkpoint load cancels prefetches, and a store whose reads reliably
# fail would otherwise warn once per load for the rest of the run
_DISCARDED_PREFETCH_WARNED = False


def _tree_zeros_like(tree, dtype=None):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree)


def _advance_loss_scale(scale, good, skipped, finite, dynamic: bool,
                        window: int, ls_min: float, xp):
    """Dynamic-loss-scale policy (grow after `window` good steps, halve on
    overflow, floor at `ls_min`).  One implementation for both dialects:
    ``xp=jnp`` inside the jitted step, ``xp=np`` on host step paths
    (SuperOffload) — so the two can never drift."""
    skipped = skipped + xp.where(finite, 0, 1)
    if not dynamic:
        return scale, good, skipped
    good = xp.where(finite, good + 1, 0)
    grow = good >= window
    scale = xp.where(finite,
                     xp.where(grow, scale * 2.0, scale),
                     xp.maximum(scale * 0.5, ls_min))
    good = xp.where(grow, 0, good)
    return scale, good, skipped


def _stacked_batch_specs(batch_stack, axes):
    """Per-leaf PartitionSpecs of a stacked micro-batch ``[gas, rows,
    ...]`` for a manual (shard_map) region: row dims shard over the DP
    ``axes``; PRNG keys and sub-2D leaves replicate.  Shared by every
    explicit-collective path (comm-quant reduce, fused reduce-scatter,
    1-bit build) so a new batch leaf's layout is decided once."""
    return {k: (P() if k == "dropout_key" or np.ndim(v) < 2
                else P(*([None, axes] + [None] * (np.ndim(v) - 2))))
            for k, v in batch_stack.items()}


def _global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def _all_finite(tree) -> jnp.ndarray:
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(tree)]
    return jnp.all(jnp.stack(leaves))


def _translate_safe_modules(entries):
    """Map torch_autocast ``lower_precision_safe_modules`` entries (torch
    class names like "torch.nn.Linear" in reference configs) onto this
    model's module classes ("attn"/"mlp"/"embed"/"lm_head").  Unknown
    names are warned about and dropped; if nothing survives, return None
    (= every module low-precision, the pre-policy behavior) rather than
    silently promoting the whole model to fp32."""
    if entries is None:
        return None
    table = {"linear": ("attn", "mlp", "embed", "lm_head"),
             "attention": ("attn",), "attn": ("attn",),
             "mlp": ("mlp",), "ffn": ("mlp",),
             "embedding": ("embed",), "embed": ("embed",),
             "lm_head": ("lm_head",), "conv": ()}
    out = []
    for e in entries:
        key = str(e).rsplit(".", 1)[-1].lower()
        if key in table:
            out.extend(table[key])
        else:
            logger.warning(
                f"torch_autocast.lower_precision_safe_modules: unknown "
                f"module class '{e}' ignored (known: {sorted(table)})")
    if not out:
        logger.warning(
            "torch_autocast.lower_precision_safe_modules matched no model "
            "module classes; keeping every module in the low dtype")
        return None
    return tuple(dict.fromkeys(out))


def _match_state_shardings(state_shape_tree, params_treedef, param_shardings, replicated):
    """Map optimizer-state pytrees to shardings: any subtree whose structure
    equals the params tree reuses the param sharding tree; other leaves are
    replicated (step counts etc.)."""

    def walk(subtree):
        try:
            if jax.tree_util.tree_structure(subtree) == params_treedef:
                return param_shardings
        except Exception:
            pass
        if isinstance(subtree, (list, tuple)):
            rebuilt = [walk(x) for x in subtree]
            if hasattr(subtree, "_fields"):  # namedtuple
                return type(subtree)(*rebuilt)
            return type(subtree)(rebuilt)
        if isinstance(subtree, dict):
            return {k: walk(v) for k, v in subtree.items()}
        if jax.tree_util.treedef_is_leaf(jax.tree_util.tree_structure(subtree)):
            return replicated
        return jax.tree.map(lambda _: replicated, subtree)

    return walk(state_shape_tree)


class DeepSpeedEngine:
    """Training engine over a functional model.

    ``model`` is either a :class:`TransformerConfig` (built-in model zoo) or
    any object exposing ``init(rng) -> params`` and
    ``loss(params, batch) -> scalar`` (duck-typed trainable).
    """

    def __init__(self,
                 model: Union[TransformerConfig, Any],
                 config: Union[DeepSpeedConfig, Dict[str, Any], str, None] = None,
                 topology: Optional[MeshTopology] = None,
                 model_params: Optional[Any] = None,
                 optimizer: Optional[Optimizer] = None,
                 lr_scheduler: Optional[LRSchedule] = None,
                 seed: Optional[int] = None):
        # -- config (batch resolution deferred until topology is known) --
        if isinstance(config, DeepSpeedConfig):
            self.config = config
        else:
            self.config = DeepSpeedConfig(config or {}, world_size=None)

        # -- topology: mesh block merged with tensor_parallel/pipeline/etc.
        zc = self.config.zero_config
        self._secondary_mode = secondary_mode_from_config(zc)
        if topology is None:
            mesh_sizes = self.config.mesh.resolved(len(jax.devices()))
            if self._secondary_mode != "none":
                from deepspeed_tpu.parallel.topology import factor_data_axis

                shard = (zc.zero_hpz_partition_size
                         if self._secondary_mode == "hpz" else zc.mics_shard_size)
                mesh_sizes = factor_data_axis(mesh_sizes, shard)
                log_dist(f"ZeRO++ {self._secondary_mode}: DP world factored "
                         f"into outer={mesh_sizes['data']} × "
                         f"inner={mesh_sizes['subdata']}")
            topology = MeshTopology(mesh_sizes)
        self.topology = topology
        set_topology(topology)

        if not isinstance(config, DeepSpeedConfig):
            self.config.resolve_world(topology.dp_size)
        cfg = self.config
        self.zero_stage = cfg.zero_config.stage
        self.micro_batch_size = cfg.train_micro_batch_size_per_gpu
        self.gradient_accumulation_steps_value = cfg.gradient_accumulation_steps
        self.train_batch_size_value = cfg.train_batch_size
        self.seed = seed if seed is not None else cfg.seed

        # -- ZeRO-Infinity param streaming (decided before the model config
        # freezes: the loss fn must compile the streamed layer scan) -------
        off_param = cfg.zero_config.offload_param
        self._param_stream = bool(
            off_param and off_param.device in ("cpu", "nvme")
            and isinstance(model, TransformerConfig))
        if off_param and off_param.device in ("cpu", "nvme") \
                and not isinstance(model, TransformerConfig):
            logger.warning(
                "layer-streamed offload_param requires the built-in "
                "transformer model; falling back to whole-tree host "
                "placement where supported (no NVMe store%s)"
                % (" — device='nvme' degrades to host RAM"
                   if off_param.device == "nvme" else ""))

        # -- compression (ref deepspeed/compression/compress.py) --------
        # init_compression semantics built into the engine: layer
        # reduction shrinks the model BEFORE params exist; the per-step
        # technique masks are applied inside the jitted loss (see
        # _compile_steps) and re-jit when the active set changes.
        self._compression = None
        cc = cfg.to_dict().get("compression_training")
        if cc:
            from deepspeed_tpu.compression.compress import CompressionManager

            self._compression = CompressionManager(
                {"compression_training": cc})
            self._compression_sig = None
            lr_cfg = self._compression.layer_reduction
            if lr_cfg.enabled and isinstance(model, TransformerConfig):
                keep = lr_cfg.teacher_layer or list(
                    range(lr_cfg.keep_number_layer or model.num_layers))
                model = model.replace(num_layers=len(keep))
                log_dist(f"layer_reduction: student has {len(keep)} layers")

        # -- model ------------------------------------------------------
        self.model_config: Optional[TransformerConfig] = None
        if isinstance(model, TransformerConfig):
            mc = model
            if cfg.bf16.enabled:
                mc = mc.replace(dtype=jnp.bfloat16)
            elif cfg.fp16.enabled:
                mc = mc.replace(dtype=jnp.float16)
            else:
                mc = mc.replace(dtype=jnp.float32)
            if cfg.torch_autocast.enabled:
                ac = cfg.torch_autocast
                if ac.fp32_ops is not None:
                    mc = mc.replace(fp32_ops=tuple(ac.fp32_ops))
                safe = _translate_safe_modules(
                    ac.lower_precision_safe_modules)
                if safe is not None:
                    mc = mc.replace(autocast_safe_modules=safe)
            mc = mc.replace(remat_policy=cfg.activation_checkpointing.remat_policy
                            if cfg.activation_checkpointing.partition_activations
                            or cfg.activation_checkpointing.remat_policy != "nothing_saveable"
                            else mc.remat_policy)
            if (mc.seq_impl == "ring" and topology.sp_size > 1
                    and mc.remat_policy == "nothing_saveable"):
                # Ring attention's forward is a ring of ppermute hops; under
                # nothing_saveable the backward would re-run that whole
                # collective chain per layer just to rebuild (o, lse).  The
                # ring tags exactly those residuals "flash_out"/"flash_lse"
                # (sequence/ring.py), so saving them — and only them — keeps
                # the backward collective-free on the forward side at
                # O(B·S_l·H) extra HBM per layer.
                mc = mc.replace(remat_policy="flash_saveable")
                log_dist("ring sequence parallelism: remat policy upgraded "
                         "nothing_saveable -> flash_saveable (saves the "
                         "ring's (o, lse) so the backward never re-runs "
                         "the forward ppermute chain)", level="info")
            ss = cfg.step_schedule
            if ss.gather_prefetch_depth > 1:
                # gather-prefetch depth (step_schedule): unrolling the
                # layer scan widens the window XLA's latency-hiding
                # scheduler can hoist a ZeRO-3 param all-gather (or a
                # streamed-layer H2D fetch) across — layer i+1's gather
                # overlaps layer i's compute.  The scan only honors a
                # divisor of its length (transformer falls back to 1
                # otherwise), so clamp to the largest divisor <= the
                # pinned depth rather than record a silently-no-op knob.
                depth = ss.gather_prefetch_depth
                while mc.num_layers % depth:
                    depth -= 1
                if depth != ss.gather_prefetch_depth:
                    logger.warning(
                        f"step_schedule.gather_prefetch_depth="
                        f"{ss.gather_prefetch_depth} does not divide "
                        f"num_layers={mc.num_layers}; clamped to {depth}")
                if depth > 1:
                    mc = mc.replace(scan_unroll=max(mc.scan_unroll, depth))
            if ss.ring_interleave > 1 and mc.seq_impl == "ring":
                # ring hop schedule (step_schedule): issue the next hop's
                # ppermute before the current hop's attend
                mc = mc.replace(ring_interleave=ss.ring_interleave)
            cq_ring = cfg.comm_quantization
            if cq_ring.enabled and cq_ring.ring_rotation != "fp32":
                if mc.seq_impl == "ring" and topology.sp_size > 1:
                    # quantized ring wire (comm_quantization.ring_rotation;
                    # sequence/ring.py): the K/V rotation and the traveling
                    # dk/dv move int8/fp8 payloads + fp32 per-row scales
                    # per hop, dequantized in the flash kernel epilogue
                    mc = mc.replace(ring_wire_dtype=cq_ring.ring_rotation)
                    log_dist("comm_quantization: ring rotation wire = "
                             f"{cq_ring.ring_rotation} over "
                             f"sp={topology.sp_size}")
                else:
                    logger.warning(
                        "comm_quantization.ring_rotation: no >1 'seq' "
                        "mesh axis (or seq_impl != 'ring') — nothing "
                        "travels a ring; keeping the fp32 wire")
            if cfg.pipeline.num_microbatches:
                mc = mc.replace(pipeline_microbatches=cfg.pipeline.num_microbatches)
            if self._param_stream:
                mc = mc.replace(param_stream=True)
            self.model_config = mc
            self._init_fn = partial(tf_model.init_params, mc)
            self._loss_fn = partial(tf_model.loss_fn, cfg=mc)
        else:
            self._init_fn = model.init
            self._loss_fn = model.loss

        # -- sharding oracle -------------------------------------------
        # THE partition-spec source for this engine: init, checkpoint
        # save/load (universal resharding included) and any serving
        # engine sharing these weights all read specs from here — the
        # construction recipe (zero stage, hpZ/MiCS mode, persistence
        # threshold incl. the pinned step_schedule override) lives in
        # PartitionOracle.from_config, not at this call site.
        self.oracle = PartitionOracle.from_config(topology, cfg)
        self.rules = self.oracle
        rng = jax.random.PRNGKey(self.seed)

        params_shape = jax.eval_shape(self._init_fn, rng)
        self.param_shardings = self.rules.tree_shardings(
            jax.tree.map(lambda x: x, params_shape), param_style=True)
        self._replicated = NamedSharding(topology.mesh, P())

        offenders = self.rules.audit_replicated(params_shape)
        if offenders:
            desc = ", ".join(f"{p} {s} ({b / 1e6:.1f}MB)"
                             for p, s, b in offenders[:8])
            msg = (f"{len(offenders)} large param(s) could not be sharded "
                   f"(no dim divisible by the shard world) and will be "
                   f"REPLICATED on every device: {desc}")
            if self.config.zero_config.strict_sharding:
                from deepspeed_tpu.runtime.config import DeepSpeedConfigError

                raise DeepSpeedConfigError(
                    msg + " — zero_optimization.strict_sharding is set")
            log_dist(msg, level="warning")

        # -- ZeRO-3 fused gather-matmul (step_schedule.fused_gather_matmul;
        # ops/pallas/gather_matmul.py) ----------------------------------
        # The layer MLP runs as an explicit shard_map over the fsdp axes
        # whose matmul region issues the FOLLOWING matmul's param
        # all-gather ahead of the current one (T3, arXiv:2401.16677) —
        # decided here, after the sharding rules exist, because the path
        # is only correct when the MLP weights actually carry the
        # expected fsdp pattern (wi/wg sharded on the embed dim 0, wo on
        # the embed dim 1, same axes).
        if cfg.step_schedule.fused_gather_matmul:
            mc2 = self.model_config
            cqg = cfg.comm_quantization
            qwz_on = ((cqg.enabled and cqg.zero3_gather != "fp32")
                      or cfg.zero_config.zero_quantized_weights)
            blocked = (
                "requires the built-in transformer model" if mc2 is None
                else "requires ZeRO stage 3" if self.zero_stage < 3 else
                "TP/PP/SP/EP mesh axes unsupported" if (
                    topology.tp_size > 1 or topology.pp_size > 1
                    or topology.sp_size > 1 or topology.ep_size > 1) else
                "hierarchical (hpz/mics) partitioning unsupported"
                if self._secondary_mode != "none" else
                "param streaming unsupported" if self._param_stream else
                "quantized zero3_gather (qwZ) already owns the gather"
                if qwz_on else
                "compression masking unsupported"
                if self._compression is not None else
                "MoE layers unsupported" if mc2.is_moe else "")
            axes = None
            if not blocked:
                def _axes_of(entry):
                    if entry is None:
                        return ()
                    return tuple(entry) if isinstance(entry, (tuple, list)) \
                        else (entry,)

                try:
                    mlp_sh = self.param_shardings["layers"]["mlp"]
                    wi_s = tuple(mlp_sh["wi"].spec)
                    wo_s = tuple(mlp_sh["wo"].spec)
                except (KeyError, TypeError):
                    wi_s = wo_s = ()
                ok = (len(wi_s) == 3 and len(wo_s) == 3
                      and wi_s[0] is None and wi_s[2] is None
                      and wo_s[0] is None and wo_s[1] is None
                      and _axes_of(wi_s[1])
                      and _axes_of(wi_s[1]) == _axes_of(wo_s[2]))
                if ok and mc2.activation == "swiglu":
                    wg_s = tuple(mlp_sh["wg"].spec)
                    ok = wg_s == wi_s
                elif ok and "bi" in mlp_sh:
                    # the pre-activation bias rides the fused region with
                    # an in_spec over the same axes — an indivisible bias
                    # dim (replicated spec) must fall back, not crash at
                    # trace time
                    bi_s = tuple(mlp_sh["bi"].spec)
                    ok = (len(bi_s) == 2 and bi_s[0] is None
                          and _axes_of(bi_s[1]) == _axes_of(wi_s[1]))
                if ok:
                    axes = _axes_of(wi_s[1])
                else:
                    blocked = ("MLP weights do not carry the expected "
                               "fsdp sharding pattern (persistence "
                               "threshold or indivisible dims)")
            if axes:
                mc2 = mc2.replace(fused_gather_matmul=True,
                                  fused_gather_axes=axes)
                self.model_config = mc2
                self._init_fn = partial(tf_model.init_params, mc2)
                self._loss_fn = partial(tf_model.loss_fn, cfg=mc2)
                log_dist("step_schedule: fused gather-matmul — MLP "
                         f"all-gathers issued in-region over {axes}")
            else:
                logger.warning(
                    "step_schedule.fused_gather_matmul: unsupported with "
                    f"this configuration ({blocked}) — keeping the "
                    "scheduled (GSPMD) gather path")

        def _init_sharding_unsafe() -> bool:
            """True when jitting rng init straight into the param
            shardings is known-miscompiled on jax 0.4.37: some leaf is
            sharded over a proper subset of the >1-sized mesh axes
            (fully-replicated leaves and leaves covering every big axis
            are observed-correct — see the init branch below)."""
            big = {ax for ax, sz in self.topology.sizes.items() if sz > 1}
            if not big:
                return False
            for shd in jax.tree.leaves(self.param_shardings):
                used = set()
                for part in getattr(shd, "spec", ()) or ():
                    if part is None:
                        continue
                    if isinstance(part, (tuple, list)):
                        used.update(part)
                    else:
                        used.add(part)
                if used and (big - used):
                    return True
            return False

        self._init_sharding_unsafe = _init_sharding_unsafe

        if model_params is not None:
            if self._compression is not None:
                # teacher checkpoint → layer-reduced student rows
                model_params = self._compression.reduce_layers(model_params)
            self.params = jax.device_put(model_params, self.param_shardings)
        elif self._init_sharding_unsafe():
            # jax 0.4.37 / XLA SPMD miscompiles rng-based init when jitted
            # straight into out_shardings where some leaf is sharded over
            # a PROPER SUBSET of the >1-sized mesh axes: P(pipe) stacked
            # layers on a pipe×data mesh come back scaled by the data-axis
            # size (exactly 4x at data=4 — summed over the replica group
            # instead of selected from it), and P(tensor) leaves on a
            # data×tensor×seq mesh come back as different draws entirely.
            # A hot/wrong init trains visibly slower while every
            # grad-parity test still passes (the schedules are correct;
            # the weights aren't).  Materialize unsharded, then place —
            # device_put is pure data movement and cannot rescale.  The
            # fast sharded-init path is kept when every sharded leaf
            # covers all big axes (pure-data ZeRO-3: the peak-params
            # ladder must not materialize its models replicated).
            # Known tradeoff: this branch peaks at full-model size on ONE
            # device — a pipe/TP model sharded precisely because it
            # exceeds one chip should load params from a checkpoint
            # (model_params path above) rather than rng-init here; wrong
            # silent init was strictly worse than a loud OOM.
            self.params = jax.device_put(jax.jit(self._init_fn)(rng),
                                         self.param_shardings)
        else:
            init_jit = jax.jit(self._init_fn, out_shardings=self.param_shardings)
            self.params = init_jit(rng)
        n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(self.params))
        log_dist(f"engine: {n_params/1e6:.1f}M params | zero_stage={self.zero_stage} "
                 f"| mesh={topology.sizes} | micro_bs={self.micro_batch_size} "
                 f"| gas={self.gradient_accumulation_steps_value}")

        # -- decomposed weight-update schedule (step_schedule block;
        # autotuning/overlap_scheduler.py; arXiv:2004.13336) ------------
        # "decomposed" shards the optimizer state AND the gradient
        # accumulator over the ZeRO axes even at stage 0/1: XLA then
        # compiles the DP gradient reduction as reduce-scatter, each
        # replica steps its 1/world shard of the optimizer, and the
        # updated params are re-gathered — the all-gathers of early
        # tensors overlap the update compute of later ones under the
        # latency-hiding scheduler.  Stage ≥ 2 already has this layout
        # (the knob is a no-op there); stage 3 additionally defers the
        # re-gather to the next step's per-layer forward gathers.
        self._decomposed_update = False
        if cfg.step_schedule.weight_update == "decomposed":
            off_opt_pre = cfg.zero_config.offload_optimizer
            onebit_opt = (cfg.optimizer is not None and cfg.optimizer.type
                          in ("onebitadam", "onebitlamb", "zerooneadam",
                              "0/1adam"))
            blocked = ("no >1 ZeRO axis" if topology.zero_size <= 1 else
                       "offload_param streaming" if self._param_stream else
                       "SuperOffload" if (off_opt_pre is not None
                                          and off_opt_pre.super_offload)
                       else
                       "chunked host optimizer"
                       if (off_opt_pre is not None
                           and off_opt_pre.device in ("cpu", "nvme")
                           and off_opt_pre.working_set_bytes > 0) else
                       "NVMe optimizer store" if (off_opt_pre is not None
                                                  and off_opt_pre.device
                                                  == "nvme") else
                       "1-bit optimizer" if onebit_opt else
                       "qgZ compressed gradients"
                       if zc.zero_quantized_gradients
                       and self.zero_stage <= 1 else "")
            if blocked:
                logger.warning(
                    "step_schedule.weight_update='decomposed': unsupported "
                    f"with this configuration ({blocked}) — keeping the "
                    "stage's native update layout")
            else:
                self._decomposed_update = True
                log_dist("step_schedule: decomposed weight update — "
                         "optimizer state + grad accumulator sharded over "
                         f"the ZeRO axes (world={topology.zero_size}, "
                         f"stage={self.zero_stage})")

        # -- optimizer --------------------------------------------------
        if optimizer is not None:
            self.optimizer = optimizer
        else:
            # sharded when ZeRO partitions opt state (stage≥1), any param
            # sharding is non-replicated (tensor parallel), or the update
            # runs host-streamed — in all of these the pallas_fused kernel
            # path must be downgraded (see build_optimizer).
            any_sharded = any(
                any(ax is not None for ax in getattr(sh, "spec", P()))
                for sh in jax.tree.leaves(self.param_shardings))
            sharded = (self.zero_stage >= 1 or any_sharded
                       or bool(self._param_stream)
                       or self._decomposed_update)
            if cfg.optimizer is not None:
                self.optimizer = build_optimizer(cfg.optimizer.type, cfg.optimizer.params,
                                                 sharded_params=sharded)
            else:
                self.optimizer = build_optimizer("adamw", {}, sharded_params=sharded)
        self.base_lr = (cfg.optimizer.lr if cfg.optimizer else 1e-3)

        params_treedef = jax.tree_util.tree_structure(params_shape)
        if self._decomposed_update:
            # always-fsdp specs (what stage >= 1 / >= 2 would use)
            opt_param_shardings = self.rules.tree_shardings(
                params_shape, param_style=False)
        else:
            opt_param_shardings = self.rules.optimizer_shardings(params_shape)
        if self._param_stream:
            # split the optimizer: the streamed layer partition's state
            # lives host-resident and is stepped one layer-slice at a time
            # (runtime/infinity.streamed_update); the small resident part
            # (embed/norm/head) keeps the normal device update.  On
            # backends without memory kinds (the CPU test mesh) the
            # streaming code path still runs; placement is a no-op.
            from deepspeed_tpu.runtime.offload import (host_offload_supported,
                                                       with_memory_kind)

            self._host_kinds = host_offload_supported(topology)

            def hostify(sh):
                return with_memory_kind(sh, "pinned_host") \
                    if self._host_kinds else sh

            res_shape = {k: v for k, v in params_shape.items()
                         if k != "layers"}
            res_treedef = jax.tree_util.tree_structure(res_shape)
            res_param_sh = {k: v for k, v in opt_param_shardings.items()
                            if k != "layers"}
            res_state_shape = jax.eval_shape(self.optimizer.init, res_shape)
            layers_treedef = jax.tree_util.tree_structure(
                params_shape["layers"])
            layers_state_shape = jax.eval_shape(self.optimizer.init,
                                                params_shape["layers"])
            self.opt_shardings = {
                "resident": _match_state_shardings(
                    res_state_shape, res_treedef, res_param_sh,
                    self._replicated),
                "stream": hostify(_match_state_shardings(
                    layers_state_shape, layers_treedef,
                    opt_param_shardings["layers"], self._replicated)),
            }
            opt_state_shape = {"resident": res_state_shape,
                               "stream": layers_state_shape}
        else:
            opt_state_shape = jax.eval_shape(self.optimizer.init, params_shape)
            self.opt_shardings = _match_state_shardings(
                opt_state_shape, params_treedef, opt_param_shardings,
                self._replicated)

        # -- ZeRO-Offload / -Infinity tiering --------------------------
        # Two realisations (runtime/offload.py): streaming mode keeps opt
        # state in host memory via XLA memory kinds with device↔host
        # transfers compiled into the step (TPU); store mode keeps numpy
        # arrays on the host / NVMe and swaps around each step.
        self._opt_store = None
        self._opt_stream_offload = False
        self._opt_device_shardings = self.opt_shardings
        self._super_opt = None
        off_opt = cfg.zero_config.offload_optimizer
        if off_opt and getattr(off_opt, "super_offload", False) \
                and self._param_stream:
            raise DeepSpeedConfigError(
                "offload_optimizer.super_offload cannot combine with "
                "offload_param streaming (ZeRO-Infinity already steps the "
                "streamed partition host-side); drop one of the two")
        # Chunked host optimizer pipeline (runtime/offload.
        # ChunkedHostOptimizer): opted in via working_set_bytes > 0, taken
        # only when the fp32 state (12 B/param) actually exceeds the
        # budget — smaller models keep the legacy streaming/store paths.
        self._chunked_opt = bool(
            off_opt and off_opt.device in ("cpu", "nvme")
            and not off_opt.super_offload
            and off_opt.working_set_bytes > 0
            and 12 * n_params > off_opt.working_set_bytes)
        if self._chunked_opt:
            log_dist(f"ZeRO-Offload chunked: host Adam over "
                     f"{off_opt.chunk_bytes >> 20}MB chunks "
                     f"(tier={off_opt.device}, state="
                     f"{12 * n_params >> 20}MB > working set="
                     f"{off_opt.working_set_bytes >> 20}MB)")
        elif off_opt and off_opt.device == "cpu" and off_opt.super_offload \
                and not self._param_stream:
            # SuperOffload (ref engine.py:935 + superoffload_stage3.py):
            # the full fp32 master + moments live on the host; the step is
            # a pipelined bucketed host Adam (device keeps working params
            # only). Created after params exist, below.
            log_dist("SuperOffload: host-resident pipelined Adam with "
                     "rollback")
        elif off_opt and off_opt.device == "cpu" and self._param_stream:
            # the streamed layer partition's opt state is already
            # host-resident and slice-stepped; nothing extra to offload
            log_dist("ZeRO-Offload: opt state host placement subsumed by "
                     "param streaming")
        elif off_opt and off_opt.device == "cpu":
            from deepspeed_tpu.runtime.offload import (HostOptimizerStore,
                                                       host_offload_supported,
                                                       partial_offload_shardings)

            if host_offload_supported(topology):
                self.opt_shardings = partial_offload_shardings(
                    opt_state_shape, self.opt_shardings, off_opt.ratio)
                self._opt_stream_offload = True
                log_dist(f"ZeRO-Offload: opt state → host RAM via memory kinds "
                         f"(ratio={off_opt.ratio})")
            else:
                self._opt_store = HostOptimizerStore()
                log_dist("ZeRO-Offload: opt state → host-store (numpy) mode")
        self._param_store = None
        if off_param and off_param.device in ("cpu", "nvme") \
                and not self._param_stream:
            # custom (non-TransformerConfig) models can't stream the layer
            # scan; keep the coarse whole-tree host placement (XLA bulk-
            # transfers params into the step)
            from deepspeed_tpu.runtime.offload import (host_offload_supported,
                                                       with_memory_kind)

            if host_offload_supported(topology):
                self.param_shardings = with_memory_kind(self.param_shardings,
                                                        "pinned_host")
                self.params = jax.device_put(self.params, self.param_shardings)
                log_dist("ZeRO-Infinity: params → host RAM (whole-tree)")
        if self._param_stream:
            # ZeRO-Infinity: the stacked layer weights live in pinned host
            # memory and are streamed one layer at a time through the
            # compiled step (models/transformer.py streamed scan_segment +
            # runtime/infinity.py; ref partitioned_param_swapper.py:37)
            layer_sh = hostify(self.param_shardings["layers"])
            self.param_shardings = {**self.param_shardings,
                                    "layers": layer_sh}
            self.params = {**self.params,
                           "layers": jax.device_put(self.params["layers"],
                                                    layer_sh)}
            log_dist("ZeRO-Infinity: layer params → host RAM, streamed "
                     "layer-by-layer through the step")
            if off_param.device == "nvme":
                from deepspeed_tpu.runtime.offload import NVMeOptimizerSwapper

                swap_dir = off_param.nvme_path or os.path.join(
                    os.environ.get("TMPDIR", "/tmp"), "dstpu_param_swap")
                # the swapper is a generic AIO-backed tree store; between
                # steps the layer weights live on NVMe, around each step
                # they are staged through host RAM only
                self._param_store = NVMeOptimizerSwapper(swap_dir,
                                                         cfg.aio_config,
                                                         prefix="param")
                log_dist(f"ZeRO-Infinity: layer params → NVMe at {swap_dir}")

        if self._chunked_opt:
            from deepspeed_tpu.runtime.offload import ChunkedHostOptimizer

            opt_type = (cfg.optimizer.type if cfg.optimizer else "adamw").lower()
            if opt_type not in ("adam", "adamw", "fusedadam"):
                raise DeepSpeedConfigError(
                    f"offload_optimizer.working_set_bytes (chunked host "
                    f"step) supports Adam/AdamW only, got "
                    f"optimizer.type={opt_type!r}")
            op = (cfg.optimizer.params if cfg.optimizer else {})
            store = None
            if off_opt.device == "nvme":
                from deepspeed_tpu.nvme.chunk_store import NVMeChunkStore

                swap_dir = off_opt.nvme_path or os.path.join(
                    os.environ.get("TMPDIR", "/tmp"), "dstpu_nvme_swap")
                store = NVMeChunkStore(swap_dir, cfg.aio_config,
                                       buffer_count=off_opt.buffer_count)
                log_dist(f"ZeRO-Infinity: optimizer chunks → NVMe at "
                         f"{swap_dir}")
            # rides the _super_opt slot: the grads-only device program,
            # the host-stepped train_batch, and the superoffload
            # checkpoint format are all shared with SuperOffload.
            # adamw/wd defaults MIRROR build_optimizer's fused chain
            # (adam_w_mode defaults True, AdamW wd defaults 0.01) — the
            # chunked host step must be numerically the same update the
            # fused path would have applied
            adamw = (opt_type == "adamw"
                     or bool(op.get("adam_w_mode", True)))
            self._super_opt = ChunkedHostOptimizer(
                self.params, lr=self.base_lr,
                betas=tuple(op.get("betas", (0.9, 0.999))),
                eps=float(op.get("eps", 1e-8)),
                weight_decay=float(op.get("weight_decay",
                                          0.01 if adamw else 0.0)),
                chunk_bytes=off_opt.chunk_bytes,
                adamw=adamw,
                store=store)
            self.opt_state = None  # host/NVMe chunks are authoritative
        elif off_opt and off_opt.device == "cpu" and off_opt.super_offload \
                and not self._param_stream:
            from deepspeed_tpu.runtime.superoffload import SuperOffloadOptimizer

            opt_type = (cfg.optimizer.type if cfg.optimizer else "adamw").lower()
            if opt_type not in ("adam", "adamw", "fusedadam"):
                raise DeepSpeedConfigError(
                    f"super_offload supports Adam/AdamW only, got "
                    f"optimizer.type={opt_type!r}")
            op = (cfg.optimizer.params if cfg.optimizer else {})
            workers = max(1, int((os.cpu_count() or 4)
                                 * off_opt.cpuadam_cores_perc))
            self._super_opt = SuperOffloadOptimizer(
                self.params, lr=self.base_lr,
                betas=tuple(op.get("betas", (0.9, 0.999))),
                eps=float(op.get("eps", 1e-8)),
                weight_decay=float(op.get("weight_decay", 0.0)),
                max_workers=workers,
                adamw=opt_type in ("adamw", "fusedadam"))
            self.opt_state = None  # host masters/moments are authoritative
        elif self._param_stream:
            res_params = {k: v for k, v in self.params.items()
                          if k != "layers"}
            opt_init_jit = jax.jit(
                lambda lp, rp: {"stream": self.optimizer.init(lp),
                                "resident": self.optimizer.init(rp)},
                out_shardings={"stream": self.opt_shardings["stream"],
                               "resident": self.opt_shardings["resident"]})
            self.opt_state = opt_init_jit(self.params["layers"], res_params)
        else:
            opt_init_jit = jax.jit(self.optimizer.init,
                                   out_shardings=self.opt_shardings)
            self.opt_state = opt_init_jit(self.params)

        if off_opt and off_opt.device == "nvme" and not self._chunked_opt:
            from deepspeed_tpu.runtime.offload import NVMeOptimizerSwapper

            swap_dir = off_opt.nvme_path or os.path.join(
                os.environ.get("TMPDIR", "/tmp"), "dstpu_nvme_swap")
            self._opt_store = NVMeOptimizerSwapper(swap_dir, cfg.aio_config)
            log_dist(f"ZeRO-Infinity: optimizer state → NVMe at {swap_dir}")
        if self._opt_store is not None:
            self._opt_store.swap_out(self.opt_state)
            self.opt_state = None  # store is authoritative between steps
        if self._param_store is not None:
            self._param_store.swap_out(self.params["layers"])
            self.params = {**self.params, "layers": None}
        # Pipelined (overlapped) store swapping, ref
        # swap_tensor/pipelined_optimizer_swapper.py:26: with
        # offload_optimizer.pipeline_read set, the next step's store reads
        # drain on a worker thread behind the writes while the host
        # dispatches this step's compute.  (pipeline_write is accepted for
        # config parity but controls nothing extra: store writes are
        # always issued async via the AIO handle.)
        self._opt_fut = None
        self._param_fut = None
        self._swap_pool = None
        if (off_opt is not None and off_opt.pipeline_read
                and (self._opt_store is not None
                     or self._param_store is not None)):
            import concurrent.futures

            self._swap_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="dstpu-swap")

        if self._decomposed_update:
            self.grad_shardings = self.rules.tree_shardings(
                params_shape, param_style=False)
        else:
            self.grad_shardings = self.rules.grad_accum_shardings(params_shape)
        if self._param_stream:
            self.grad_shardings = {
                **self.grad_shardings,
                "layers": hostify(self.grad_shardings["layers"])}

        # -- precision / loss scaling ----------------------------------
        self.fp16_enabled = cfg.fp16.enabled
        self.bfloat16_enabled = cfg.bf16.enabled
        if self.fp16_enabled and cfg.fp16.dynamic:
            init_scale = 2.0 ** cfg.fp16.initial_scale_power
        elif self.fp16_enabled:
            init_scale = float(cfg.fp16.loss_scale)
        else:
            init_scale = 1.0
        self.loss_scale_state = jax.device_put(
            {"scale": jnp.float32(init_scale), "good_steps": jnp.int32(0),
             "skipped": jnp.int32(0)},
            self._replicated)
        self._ls_window = cfg.fp16.loss_scale_window
        self._ls_min = cfg.fp16.min_loss_scale
        self._ls_dynamic = self.fp16_enabled and cfg.fp16.dynamic

        # -- lr schedule ------------------------------------------------
        if lr_scheduler is not None:
            self.lr_scheduler = lr_scheduler
        elif cfg.scheduler is not None:
            self.lr_scheduler = build_lr_schedule(cfg.scheduler.type, cfg.scheduler.params,
                                                  base_lr=self.base_lr)
        else:
            self.lr_scheduler = constant_lr(self.base_lr)

        # -- bookkeeping ------------------------------------------------
        self.global_steps = 0
        self.micro_steps = 0
        self._last_metrics: Dict[str, float] = {}
        self.timers = SynchronizedWallClockTimer(synchronize=cfg.wall_clock_breakdown)
        self.tput_timer = ThroughputTimer(batch_size=cfg.train_batch_size,
                                          steps_per_output=cfg.steps_per_print)
        self.monitor = self._build_monitor(cfg)

        # -- unified telemetry (telemetry/; docs/OBSERVABILITY.md) -------
        self.telemetry = None
        self._last_batch_tokens = 0
        if cfg.telemetry.enabled:
            from deepspeed_tpu.telemetry import Telemetry
            from deepspeed_tpu.utils.comms_logging import get_comms_logger

            self.telemetry = Telemetry(cfg.telemetry, monitor=self.monitor)
            # the comm-volume field of every StepRecord reads the global
            # CommsLogger; telemetry implies recording even when the
            # verbose comms_logger block is off.  The logger is process-
            # global, so records carry the DELTA vs this baseline (a
            # second engine in the same process must not inherit the
            # first one's traffic) and destroy() restores the flag.
            cl = get_comms_logger()
            self._comms_prev_enabled = cl.enabled
            cl.enabled = True
            self._comms_baseline = cl.totals()
        # -- software spans + hang watchdog (telemetry/tracing, flight) --
        # one unconditional code path: without telemetry the NULL tracer
        # answers every span call with the shared no-op singleton.
        # ``telemetry.tracing.enabled`` without ``telemetry.enabled``
        # builds a tracer and no hub (the rule InferenceServer follows):
        # spans alone, with no host sync and no StepRecord, so a traced
        # step is the step that runs untraced
        from deepspeed_tpu.telemetry.tracing import NULL_TRACER

        self._trace_path = ""
        if self.telemetry is not None:
            self._tracer = self.telemetry.tracer
        elif cfg.telemetry.tracing.enabled:
            from deepspeed_tpu.telemetry.flight import make_span_recorder

            self._tracer, _ = make_span_recorder(
                True, False, max_events=cfg.telemetry.tracing.max_events)
            self._trace_path = cfg.telemetry.tracing.trace_path
        else:
            self._tracer = NULL_TRACER
        self._train_trace_id = (self._tracer.new_trace_id()
                                if self._tracer.enabled else "")
        if self._super_opt is not None and hasattr(self._super_opt,
                                                   "_tracer"):
            # chunked host optimizer (built before telemetry exists): its
            # pipeline stages emit the offload.* spans through this tracer
            self._super_opt._tracer = self._tracer
            self._super_opt._trace_id = self._train_trace_id
        self._step_span = None
        # created here, armed per-step from train_batch: monitoring only
        # covers time spent *inside* a step (eval/checkpoint gaps are
        # legitimate silence), and this process's first train_batch is
        # skipped so a >60s XLA compile doesn't write a spurious hang
        # bundle — per-process, not global_steps, because a checkpoint
        # resume restores global_steps yet still pays the full compile
        self._compiled_step_done = False
        self._watchdog = (self.telemetry.make_watchdog("train")
                          if self.telemetry is not None else None)

        # -- data efficiency: curriculum learning (seqlen truncation) ----
        # Ref: engine curriculum integration — batches are truncated to the
        # schedule's current difficulty; difficulty_step rounding bounds the
        # number of distinct shapes (= XLA recompiles).
        self.curriculum_scheduler = None
        cl_cfg = cfg.data_efficiency.curriculum_config \
            if cfg.data_efficiency.enabled else None
        if cl_cfg:
            from deepspeed_tpu.runtime.data_pipeline import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(cl_cfg)
            self._curriculum_type = cl_cfg.get("curriculum_type", "seqlen")

        # -- random-LTD: kept-seqlen schedule → model re-jit per value ----
        self.random_ltd_scheduler = None
        rl_cfg = cfg.data_efficiency.random_ltd_config \
            if cfg.data_efficiency.enabled else None
        if rl_cfg and self.model_config is not None:
            from deepspeed_tpu.runtime.data_pipeline import RandomLTDScheduler

            sched = rl_cfg.get("random_ltd_schedule", rl_cfg)
            sc = sched.get("schedule_config", {})
            self.random_ltd_scheduler = RandomLTDScheduler(
                min_value=int(sched.get("min_value", 128)),
                max_value=int(sched.get("max_value",
                                        self.model_config.max_seq_len)),
                total_steps=int(sc.get("require_steps",
                                       sched.get("total_steps", 1000))),
                step_size=int(sc.get("seq_per_step",
                                     sched.get("step_size", 16))))
            self._ltd_band = (int(rl_cfg.get("ltd_start", 1)),
                              rl_cfg.get("ltd_end"))

        # -- progressive layer drop (theta rides the batch; no recompile) --
        self.progressive_layer_drop = None
        pld_dict = (cfg.to_dict().get("progressive_layer_drop", {})
                    if hasattr(cfg, "to_dict") else {})
        if pld_dict.get("enabled"):
            from deepspeed_tpu.runtime.model_features import ProgressiveLayerDrop

            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=float(pld_dict.get("theta", 0.5)),
                gamma=float(pld_dict.get("gamma", 0.001)))

        # -- flops profiler (XLA cost analysis at profile_step) ----------
        self._flops_profiler = None
        self._last_flops_profile = None
        if cfg.flops_profiler.enabled:
            from deepspeed_tpu.profiling import FlopsProfiler

            self._flops_profiler = FlopsProfiler(cfg.flops_profiler)

        # -- XPlane trace capture (ref pytorch-profiler integration) -----
        self._trace_profiler = None
        if cfg.profiler.enabled:
            from deepspeed_tpu.utils.trace import TraceProfiler

            self._trace_profiler = TraceProfiler(
                cfg.profiler.output_dir, cfg.profiler.start_step,
                cfg.profiler.num_steps)

        # grad accumulation buffer for the forward/backward/step trio
        self._grad_buffer = None
        self._micro_in_step = 0
        self._checkpoint_engine = None

        # -- 1-bit compressed-DP mode (OnebitAdam/OnebitLamb/ZeroOneAdam) --
        self._onebit = None
        self._onebit_state = None
        _dp_only = (self.topology.dp_size > 1 and self.topology.tp_size == 1
                    and self.topology.pp_size == 1 and self.topology.sp_size == 1
                    and not self._param_stream)
        if (cfg.optimizer is not None and _dp_only
                and cfg.optimizer.type in ("onebitadam", "onebitlamb",
                                           "zerooneadam", "0/1adam")):
            from deepspeed_tpu.runtime.onebit import OnebitConfig, OnebitTrainStep

            variant = ("zerooneadam" if cfg.optimizer.type in ("zerooneadam",
                                                               "0/1adam")
                       else cfg.optimizer.type)
            ob_cfg = OnebitConfig(cfg.optimizer.params, variant)
            self._onebit = OnebitTrainStep(self.topology, self._loss_fn,
                                           self.params, ob_cfg,
                                           gas=self.gradient_accumulation_steps_value,
                                           grad_clip=cfg.gradient_clipping)
            self._onebit_state = self._onebit.init_state(self.params)
        elif (zc.zero_quantized_gradients and _dp_only and self.zero_stage <= 1
              and cfg.optimizer is not None
              and cfg.optimizer.type in ("adam", "adamw", "fusedadam")):
            # qgZ without ZeRO-3: int8-compressed DP gradient reduction
            from deepspeed_tpu.runtime.onebit import OnebitConfig, OnebitTrainStep

            ob_cfg = OnebitConfig(cfg.optimizer.params, "qgz")
            self._onebit = OnebitTrainStep(self.topology, self._loss_fn,
                                           self.params, ob_cfg,
                                           gas=self.gradient_accumulation_steps_value,
                                           grad_clip=cfg.gradient_clipping)
            self._onebit_state = self._onebit.init_state(self.params)

        if self._onebit is not None and self._compression is not None:
            raise DeepSpeedConfigError(
                "compression_training is not supported with 1-bit/qgZ "
                "compressed-DP optimizers (their step wraps the raw loss, "
                "so compression masks would silently not apply)")

        # -- quantized ZeRO collectives (comm_quantization block;
        # comm/quantized.py, docs/QUANTIZED_COMM.md) -------------------
        # grad_reduce: the engine grows an EXPLICIT reduce path — the DP
        # gradient reduction leaves GSPMD's implicit insertion and runs
        # as a shard_map quantized all-reduce whose wire volume (int8/
        # fp8/fp32 payload + scales) is recorded per-collective in
        # telemetry.  zero3_gather is wired in _compile_steps (the qwZ
        # straight-through gather with a selectable wire dtype).
        self._comm_quant = None         # active grad-reduce config
        self._comm_quant_state = None   # error-feedback residual state
        cqc = cfg.comm_quantization
        if cqc.enabled:
            from deepspeed_tpu.comm.quantized import fp8_supported

            for coll in cqc.COLLECTIVES:
                if getattr(cqc, coll) == "fp8" and not fp8_supported():
                    raise DeepSpeedConfigError(
                        f"comm_quantization.{coll}='fp8' requires "
                        "jnp.float8_e4m3fn, which this jax build lacks — "
                        "use 'int8'")
            _quant_dp = (_dp_only and self.zero_stage <= 2
                         and self._onebit is None
                         and self._super_opt is None
                         and self._opt_store is None)
            if _quant_dp:
                self._comm_quant = cqc
                n_total = sum(int(np.prod(x.shape))
                              for x in jax.tree.leaves(self.params))
                world = self.topology.dp_size
                base = world * cqc.group_size
                self._comm_quant_padded = -(-n_total // base) * base
                from deepspeed_tpu.parallel.topology import BATCH_AXES as _BA

                self._comm_quant_res_sharding = NamedSharding(
                    self.topology.mesh, P(_BA))
                if cqc.error_feedback and cqc.grad_reduce != "fp32":
                    # per-rank first-send quantization residual, carried
                    # step to step (LoCo-style).  Stored [world, padded]
                    # with the leading axis sharded over the DP axes —
                    # the same layout as the onebit error state.  Not
                    # checkpointed: a resume re-accumulates it within a
                    # step at no quality cost.
                    self._comm_quant_state = {
                        "residual": jax.device_put(
                            jnp.zeros((world, self._comm_quant_padded),
                                      jnp.float32),
                            self._comm_quant_res_sharding)}
                log_dist(
                    f"comm_quantization: explicit grad reduce over "
                    f"dp={world} wire={cqc.grad_reduce} "
                    f"group_size={cqc.group_size} "
                    f"error_feedback={self._comm_quant_state is not None}")
            elif cqc.grad_reduce != "fp32":
                logger.warning(
                    "comm_quantization.grad_reduce: unsupported with this "
                    "configuration (needs a >1 data-parallel mesh without "
                    "TP/PP/SP, ZeRO stage <= 2, no param streaming / "
                    "SuperOffload / optimizer store / 1-bit optimizer) — "
                    "falling back to the implicit fp32 reduction")

        # -- fused reduce-scatter epilogue (step_schedule block) --------
        # With the decomposed update, GSPMD compiles the DP grad reduce
        # as reduce-scatter wherever its layout pass places it; the
        # fused variant instead accumulates gradients LOCALLY inside a
        # shard_map over the DP axes and issues an explicit per-leaf
        # psum_scatter in the accumulation epilogue — the scatter
        # consumes the just-written accumulator in place (the last
        # micro-batch's adds and the wire movement are one fused region)
        # and early leaves' scatters overlap later leaves' update math.
        self._fused_rs = False
        if cfg.step_schedule.fused_reduce_scatter:
            blocked = (
                "requires weight_update='decomposed'"
                if not self._decomposed_update else
                "requires ZeRO stage <= 1 (stage >= 2 grads are already "
                "scatter-laid-out by GSPMD)" if self.zero_stage > 1 else
                "needs a >1 data-parallel mesh without TP/PP/SP"
                if not _dp_only else
                # the full-manual region over BATCH_AXES cannot host the
                # MoE expert-parallel nested shard_map, and expert-
                # sharded grad leaves would scatter over the wrong axes
                "MoE / expert-parallel unsupported"
                if (self.topology.ep_size > 1
                    or (self.model_config is not None
                        and self.model_config.is_moe)) else
                "hierarchical (hpz/mics) partitioning unsupported"
                if self._secondary_mode != "none" else
                "comm_quantization grad reduce already owns the wire"
                if self._comm_quant is not None else
                "1-bit/qgZ optimizer owns the reduction"
                if self._onebit is not None else
                "sparse gradients unsupported"
                if cfg.sparse_gradients_enabled else "")
            if blocked:
                logger.warning(
                    "step_schedule.fused_reduce_scatter: unsupported with "
                    f"this configuration ({blocked}) — keeping the GSPMD "
                    "scatter placement")
            else:
                self._fused_rs = True
                log_dist("step_schedule: fused reduce-scatter — explicit "
                         "per-leaf psum_scatter in the grad-accumulator "
                         f"epilogue over dp={self.topology.dp_size}")

        self._compile_steps()

    # ------------------------------------------------------------------
    def _build_monitor(self, cfg):
        try:
            from deepspeed_tpu.monitor.monitor import MonitorMaster

            return MonitorMaster(cfg)
        except Exception:
            return None

    # ------------------------------------------------------------------
    # Compiled step functions
    # ------------------------------------------------------------------
    def _watchdog_expect_compile(self) -> None:
        """Disarm the hang watchdog for the remainder of the current step:
        the caller just changed the compiled functions or traced shapes,
        so this step legitimately pays a fresh XLA compile that can
        exceed any sane stall deadline (same reasoning as the per-process
        first-step skip in train_batch).  Re-armed at the next step."""
        wd = getattr(self, "_watchdog", None)
        if wd is not None:
            wd.pause()

    def _compile_steps(self) -> None:
        self._watchdog_expect_compile()
        cfg = self.config
        clip = cfg.gradient_clipping
        gas = self.gradient_accumulation_steps_value
        opt = self.optimizer
        loss_fn = self._loss_fn
        if self._compression is not None:
            # per-step compression view of the params inside the jitted
            # loss (masks fuse with the matmuls); the step gate is python-
            # static — train_batch re-compiles when the active set changes
            mgr = self._compression
            comp_step = self.global_steps
            nh = self.model_config.num_heads if self.model_config else 0
            inner_loss = loss_fn

            def loss_fn(params, batch, **kw):  # noqa: F811
                return inner_loss(mgr.apply(params, comp_step,
                                            num_heads=nh), batch, **kw)

            self._compression_sig = mgr.active_signature(comp_step)
        grad_shardings = self.grad_shardings
        ls_dynamic = self._ls_dynamic
        ls_window, ls_min = self._ls_window, self._ls_min
        fp16 = self.fp16_enabled

        # stage-3 gather quantization: the comm_quantization block's
        # zero3_gather selects the wire dtype; the legacy ZeRO++
        # zero_quantized_weights flag keeps meaning int8
        cqc = cfg.comm_quantization
        qwz_dtype = None
        if self.zero_stage >= 3:
            if cqc.enabled and cqc.zero3_gather != "fp32":
                qwz_dtype = cqc.zero3_gather
            elif cfg.zero_config.zero_quantized_weights:
                qwz_dtype = "int8"
        qwz = qwz_dtype is not None
        qwz_group = cqc.group_size if cqc.enabled else 256
        rules = self.rules

        # -- sparse gradients (ref runtime/sparse_tensor.py + the sparse
        # allreduce bucket of engine.py:145): hoist the token-embedding
        # lookup out of AD so the table cotangent is (ids, values)-COO and
        # the dp reduction is an all_gather of O(tokens·H) bytes, not a
        # dense [V,H] scatter+psum. See runtime/sparse.py.
        mc = self.model_config
        # compression masks the embed table inside loss_fn, which the
        # sparse path's hoisted lookup would bypass — keep dense grads
        sparse_grads = (cfg.sparse_gradients_enabled and mc is not None
                        and not mc.tie_embeddings
                        and self.topology.pp_size == 1
                        and not self._param_stream and not qwz
                        and self._compression is None
                        and self._comm_quant is None)
        if cfg.sparse_gradients_enabled and not sparse_grads:
            logger.warning(
                "sparse_gradients: unsupported with this configuration "
                "(tied embeddings, pipeline, param streaming, qwZ, or "
                "comm_quantization) — falling back to dense gradients")
        topo = self.topology

        def micro_grads_dense(params, batch, scale):
            def scaled_loss(p):
                if qwz:
                    from deepspeed_tpu.parallel.zeropp import qwz_weight_gather

                    p = qwz_weight_gather(p, rules, group_size=qwz_group,
                                          wire_dtype=qwz_dtype)
                loss = loss_fn(p, batch)
                return loss * scale.astype(loss.dtype)

            sloss, grads = jax.value_and_grad(scaled_loss)(params)
            return sloss / scale, grads

        def micro_grads_sparse(params, batch, scale):
            from deepspeed_tpu.runtime.sparse import sparse_embedding_grad

            ids = batch["input_ids"]
            table = params["embed"]["tokens"]
            emb = jnp.take(table, ids, axis=0)

            def scaled_loss(p, emb_):
                loss = loss_fn(p, batch, token_embeds=emb_)
                return loss * scale.astype(loss.dtype)

            sloss, (g_params, g_emb) = jax.value_and_grad(
                scaled_loss, argnums=(0, 1))(params, emb)
            st = sparse_embedding_grad(g_emb, ids, table.shape, topo)
            g_table = st.add_into(g_params["embed"]["tokens"])
            g_params = {**g_params,
                        "embed": {**g_params["embed"], "tokens": g_table}}
            return sloss / scale, g_params

        micro_grads = micro_grads_sparse if sparse_grads else micro_grads_dense

        stream_offload = self._opt_stream_offload
        opt_device_shardings = self._opt_device_shardings

        def ls_advance(finite, ls_state):
            scale, good, skipped = _advance_loss_scale(
                ls_state["scale"], ls_state["good_steps"],
                ls_state["skipped"], finite, ls_dynamic, ls_window, ls_min,
                jnp)
            return {"scale": scale, "good_steps": good.astype(jnp.int32),
                    "skipped": skipped.astype(jnp.int32)}

        def apply_update(params, opt_state, grads, lr, ls_state):
            if stream_offload:
                # ZeRO-Offload streaming: state arrives in host memory; move
                # to device for the update (XLA schedules the transfers).
                opt_state = jax.device_put(opt_state, opt_device_shardings)
            scale = ls_state["scale"]
            inv = 1.0 / (scale * gas)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, grads)
            grad_norm = _global_norm(grads)
            if clip and clip > 0:
                coef = jnp.minimum(1.0, clip / (grad_norm + 1e-6))
                grads = jax.tree.map(lambda g: g * coef, grads)

            if fp16:
                finite = _all_finite(grads) & jnp.isfinite(grad_norm)
            else:
                finite = jnp.bool_(True)

            new_params, new_opt = opt.update(grads, opt_state, params, lr)
            # overflow → keep old state (select, branch-free)
            new_params = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_params, params)
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(finite, n.astype(o.dtype), o), new_opt, opt_state)

            return new_params, new_opt, ls_advance(finite, ls_state), grad_norm, finite

        from deepspeed_tpu.runtime.infinity import split_layers

        def stream_apply_update(params, opt_state, g_layers, g_res, lr,
                                ls_state):
            """ZeRO-Infinity update: layer partition stepped slice-wise
            against host-resident grads/params/opt-state; the small
            resident partition (embed/norms/head) updated normally."""
            from deepspeed_tpu.runtime.infinity import (streamed_sq_norm,
                                                        streamed_update)

            p_layers, p_res = split_layers(params)
            scale = ls_state["scale"]
            inv = 1.0 / (scale * gas)
            g_res = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, g_res)
            sq = streamed_sq_norm(g_layers) * inv * inv
            sq = sq + sum(jnp.sum(g ** 2) for g in jax.tree.leaves(g_res))
            grad_norm = jnp.sqrt(sq)
            coef = jnp.float32(1.0)
            if clip and clip > 0:
                coef = jnp.minimum(1.0, clip / (grad_norm + 1e-6))
                g_res = jax.tree.map(lambda g: g * coef, g_res)
            finite = jnp.isfinite(grad_norm) if fp16 else jnp.bool_(True)

            new_res, new_opt_res = opt.update(g_res, opt_state["resident"],
                                              p_res, lr)
            new_res = jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                                   new_res, p_res)
            new_opt_res = jax.tree.map(
                lambda n, o: jnp.where(finite, n.astype(o.dtype), o),
                new_opt_res, opt_state["resident"])

            new_layers, new_opt_stream = streamed_update(
                opt.update, g_layers, opt_state["stream"], p_layers, lr,
                scale=inv * coef, gate=finite)

            new_params = {**new_res, "layers": new_layers}
            new_opt = {"resident": new_opt_res, "stream": new_opt_stream}
            return (new_params, new_opt, ls_advance(finite, ls_state),
                    grad_norm, finite)

        def accum_grads(params, batch_stack, scale):
            """Scan gas micro-batches, accumulating fp32 grads under the
            grad shardings (shared by train_step and the SuperOffload
            grads_batch so the accumulation semantics cannot drift)."""
            zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
            zeros = lax.with_sharding_constraint(zeros, grad_shardings)

            def body(carry, mb):
                grad_acc, loss_acc = carry
                loss, grads = micro_grads(params, mb, scale)
                grad_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                        grad_acc, grads)
                grad_acc = lax.with_sharding_constraint(grad_acc, grad_shardings)
                return (grad_acc, loss_acc + loss), None

            (grads, loss_sum), _ = lax.scan(
                body, (zeros, jnp.float32(0.0)), batch_stack)
            return grads, loss_sum

        # -- explicit quantized DP gradient reduction (comm_quantization;
        # comm/quantized.py) -------------------------------------------
        cq = self._comm_quant
        cq_ef = self._comm_quant_state is not None
        if cq is not None:
            from deepspeed_tpu.comm.quantized import quantized_all_reduce
            from deepspeed_tpu.parallel.topology import BATCH_AXES as _Q_AXES
            from deepspeed_tpu.utils.jax_compat import shard_map as _shard_map

            q_world = topo.dp_size
            q_pad = self._comm_quant_padded
            q_wire, q_gs = cq.grad_reduce, cq.group_size
            q_param_specs = jax.tree.map(lambda s: s.spec,
                                         self.param_shardings)
            q_grad_out_specs = jax.tree.map(lambda _: P(), q_param_specs,
                                            is_leaf=lambda x: isinstance(x, P))

            def accum_grads_quant(params, batch_stack, scale, residual):
                """Explicit-reduce variant of accum_grads: gradients
                accumulate LOCALLY inside a shard_map over the DP axes (no
                implicit GSPMD reduction), then ONE quantized all-reduce
                moves the flat buffer — int8/fp8 payload + fp32 block
                scales on the wire, fp32 accumulation, optional LoCo-style
                error-feedback residual carried across steps."""
                batch_specs = _stacked_batch_specs(batch_stack, _Q_AXES)
                err_spec = P(_Q_AXES) if cq_ef else P()

                def local(params, batch_stack, scale, res):
                    def body(carry, mb):
                        grad_acc, loss_acc = carry
                        loss, grads = micro_grads(params, mb, scale)
                        grad_acc = jax.tree.map(
                            lambda a, g: a + g.astype(jnp.float32),
                            grad_acc, grads)
                        return (grad_acc, loss_acc + loss), None

                    zeros = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)
                    (grads, loss_sum), _ = lax.scan(
                        body, (zeros, jnp.float32(0.0)), batch_stack)
                    # local loss is a mean over this shard's rows; the
                    # pmean restores the global-batch mean
                    loss_sum = lax.pmean(loss_sum, _Q_AXES)
                    leaves, treedef = jax.tree.flatten(grads)
                    shapes = [x.shape for x in leaves]
                    sizes = [int(np.prod(s)) for s in shapes]
                    flat = jnp.concatenate([jnp.ravel(x) for x in leaves])
                    flat = jnp.pad(flat, (0, q_pad - flat.size))
                    # the residual is stored in UNSCALED grad units — the
                    # flat buffer carries the fp16 loss-scale factor, and
                    # a dynamic-scale change between steps would otherwise
                    # mis-weight the carried compensation by old/new
                    avg, new_r = quantized_all_reduce(
                        flat, _Q_AXES, q_world, wire_dtype=q_wire,
                        group_size=q_gs,
                        residual=res[0] * scale if cq_ef else None)
                    out, off = [], 0
                    for shape, size in zip(shapes, sizes):
                        out.append(avg[off:off + size].reshape(shape))
                        off += size
                    new_res = (new_r / scale)[None] if cq_ef else res
                    return jax.tree.unflatten(treedef, out), loss_sum, new_res

                res_in = residual if cq_ef else jnp.zeros((1, 1), jnp.float32)
                mapped = _shard_map(
                    local, mesh=topo.mesh,
                    in_specs=(q_param_specs, batch_specs, P(), err_spec),
                    out_specs=(q_grad_out_specs, P(), err_spec),
                    check_vma=False)
                grads, loss_sum, new_res = mapped(params, batch_stack, scale,
                                                  res_in)
                # stage-2 configs keep their sharded grad layout downstream
                # (slicing a replicated mean is local — no extra comm)
                grads = lax.with_sharding_constraint(grads, grad_shardings)
                return grads, loss_sum, new_res

        def train_step(params, opt_state, ls_state, batch_stack, lr):
            """One full train batch: scan over gas micro-batches + update.
            micro_grads returns grads of scale·loss; apply_update divides the
            accumulated sum by scale·gas."""
            grads, loss_sum = accum_grads(params, batch_stack, ls_state["scale"])
            new_params, new_opt, new_ls, grad_norm, finite = apply_update(
                params, opt_state, grads, lr, ls_state)
            metrics = {"loss": loss_sum / gas, "grad_norm": grad_norm,
                       "loss_scale": ls_state["scale"],
                       "skipped": jnp.logical_not(finite)}
            return new_params, new_opt, new_ls, metrics

        def stream_train_step(params, opt_state, ls_state, batch_stack, lr):
            """ZeRO-Infinity train batch: layer gradients accumulate
            host-resident via slice-wise adds — no full-size device
            gradient buffer ever exists.  The gas loop is a lax.scan so the
            compiled program stays O(1) in gradient_accumulation_steps."""
            from deepspeed_tpu.runtime.infinity import streamed_tree_add, to_host

            p_layers, p_res = split_layers(params)
            zeros_l = to_host(jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), p_layers))
            zeros_r = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), p_res)

            def body(carry, mb):
                g_layers, g_res, loss_acc = carry
                loss, grads = micro_grads(params, mb, ls_state["scale"])
                gl, gr = split_layers(grads)
                g_res = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                     g_res, gr)
                g_layers = streamed_tree_add(g_layers, gl)
                return (g_layers, g_res, loss_acc + loss), None

            (g_layers, g_res, loss_sum), _ = lax.scan(
                body, (zeros_l, zeros_r, jnp.float32(0.0)), batch_stack)
            new_params, new_opt, new_ls, grad_norm, finite = \
                stream_apply_update(params, opt_state, g_layers, g_res, lr,
                                    ls_state)
            metrics = {"loss": loss_sum / gas, "grad_norm": grad_norm,
                       "loss_scale": ls_state["scale"],
                       "skipped": jnp.logical_not(finite)}
            return new_params, new_opt, new_ls, metrics

        if self._param_stream:
            train_step = stream_train_step

        if cq is not None:
            def _quant_step_core(params, opt_state, ls_state, batch_stack,
                                 lr, cq_res):
                """One comm-quant train batch: grads → explicit quantized
                reduce → the shared update; the residual rides the step
                signature so one jitted program owns the whole thing."""
                grads, loss_sum, new_res = accum_grads_quant(
                    params, batch_stack, ls_state["scale"], cq_res)
                new_params, new_opt, new_ls, grad_norm, finite = \
                    apply_update(params, opt_state, grads, lr, ls_state)
                metrics = {"loss": loss_sum / gas, "grad_norm": grad_norm,
                           "loss_scale": ls_state["scale"],
                           "skipped": jnp.logical_not(finite)}
                return new_params, new_opt, new_ls, new_res, metrics, finite

            if cq_ef:
                def train_step(params, opt_state, ls_state, cq_res,  # noqa: F811
                               batch_stack, lr):
                    new_params, new_opt, new_ls, new_res, metrics, finite = \
                        _quant_step_core(params, opt_state, ls_state,
                                         batch_stack, lr, cq_res)
                    # an overflow-skipped step must not poison the carried
                    # residual (its compensation buffer contains the very
                    # inf/NaN grads that made the step skip) — keep the
                    # previous residual, matching the params/opt rollback
                    new_res = jnp.where(finite, new_res, cq_res)
                    return new_params, new_opt, new_ls, new_res, metrics
            else:
                def train_step(params, opt_state, ls_state,  # noqa: F811
                               batch_stack, lr):
                    new_params, new_opt, new_ls, _, metrics, _ = \
                        _quant_step_core(params, opt_state, ls_state,
                                         batch_stack, lr, None)
                    return new_params, new_opt, new_ls, metrics

        if self._fused_rs:
            # -- fused reduce-scatter epilogue (step_schedule block;
            # eligibility decided in __init__) ------------------------
            from deepspeed_tpu.parallel.topology import BATCH_AXES as _RS_AXES
            from deepspeed_tpu.utils.jax_compat import \
                shard_map as _rs_shard_map

            rs_world = topo.dp_size
            rs_param_specs = jax.tree.map(lambda s: s.spec,
                                          self.param_shardings)
            rs_grad_specs = jax.tree.map(lambda s: s.spec,
                                         self.grad_shardings)

            def accum_grads_fused_rs(params, batch_stack, scale):
                """Decomposed-update variant of accum_grads: gradients
                accumulate LOCALLY inside a shard_map over the DP axes
                (no implicit GSPMD reduction), and the accumulation
                epilogue issues ONE explicit psum_scatter per leaf into
                the always-fsdp grad layout — the scatter consumes the
                local accumulator in place and the 1/world update
                (apply_update) runs on the shard it returns."""
                batch_specs = _stacked_batch_specs(batch_stack, _RS_AXES)

                def local(params, batch_stack, scale):
                    def body(carry, mb):
                        grad_acc, loss_acc = carry
                        loss, grads = micro_grads(params, mb, scale)
                        grad_acc = jax.tree.map(
                            lambda a, g: a + g.astype(jnp.float32),
                            grad_acc, grads)
                        return (grad_acc, loss_acc + loss), None

                    zeros = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)
                    (grads, loss_sum), _ = lax.scan(
                        body, (zeros, jnp.float32(0.0)), batch_stack)
                    # local loss is a mean over this shard's rows; the
                    # pmean restores the global-batch mean
                    loss_sum = lax.pmean(loss_sum, _RS_AXES)

                    def scatter(g, spec):
                        dims = [i for i, s in enumerate(spec)
                                if s is not None]
                        if not dims:
                            # indivisible leaf: the fsdp layout kept it
                            # replicated, so the reduce stays a mean
                            return lax.pmean(g, _RS_AXES)
                        return lax.psum_scatter(
                            g, _RS_AXES, scatter_dimension=dims[0],
                            tiled=True) / rs_world

                    grads = jax.tree.map(scatter, grads, rs_grad_specs)
                    return grads, loss_sum

                mapped = _rs_shard_map(
                    local, mesh=topo.mesh,
                    in_specs=(rs_param_specs, batch_specs, P()),
                    out_specs=(rs_grad_specs, P()),
                    check_vma=False)
                return mapped(params, batch_stack, scale)

            def train_step(params, opt_state, ls_state,  # noqa: F811
                           batch_stack, lr):
                grads, loss_sum = accum_grads_fused_rs(
                    params, batch_stack, ls_state["scale"])
                new_params, new_opt, new_ls, grad_norm, finite = \
                    apply_update(params, opt_state, grads, lr, ls_state)
                metrics = {"loss": loss_sum / gas, "grad_norm": grad_norm,
                           "loss_scale": ls_state["scale"],
                           "skipped": jnp.logical_not(finite)}
                return new_params, new_opt, new_ls, metrics

        if self._super_opt is not None:
            # SuperOffload path: device computes grads + norm + finite in
            # one jit; the optimizer step runs on the host (pipelined
            # bucketed Adam), so no fused device update is compiled.
            def grads_batch(params, batch_stack, scale):
                grads, loss_sum = accum_grads(params, batch_stack, scale)
                gn = _global_norm(grads)
                # match apply_update's semantics: only fp16 runs skip on
                # overflow — fp32/bf16 NaNs must land in params and be
                # visible, not silently stall training by skipping forever
                finite = (_all_finite(grads) & jnp.isfinite(gn)) if fp16 \
                    else jnp.bool_(True)
                return loss_sum / gas, grads, gn, finite

            self._grads_batch_jit = jax.jit(
                grads_batch,
                out_shardings=(self._replicated, self.grad_shardings,
                               self._replicated, self._replicated))

        if self._opt_store is not None and not self._param_stream:
            # Pipelined-swap split: grads need no optimizer state, so the
            # store read can drain while this compiles/runs; apply_step
            # then consumes the prefetched state (train_batch split path).
            def grads_batch_store(params, batch_stack, scale):
                grads, loss_sum = accum_grads(params, batch_stack, scale)
                return loss_sum / gas, grads

            self._grads_batch_store_jit = jax.jit(
                grads_batch_store,
                out_shardings=(self._replicated, self.grad_shardings))

        metrics_sh = jax.tree.map(
            lambda _: self._replicated,
            {"loss": 0, "grad_norm": 0, "loss_scale": 0, "skipped": 0})
        if cq is not None and cq_ef:
            state_out = (self.param_shardings, self.opt_shardings,
                         self._replicated, self._comm_quant_res_sharding,
                         metrics_sh)
            donate = (0, 1, 2, 3)
        else:
            state_out = (self.param_shardings, self.opt_shardings,
                         self._replicated, metrics_sh)
            donate = (0, 1, 2)
        self._train_step_jit = jax.jit(
            train_step,
            donate_argnums=donate,
            out_shardings=state_out)

        def micro_step(params, grad_acc, batch, scale):
            loss, grads = micro_grads(params, batch, scale)
            if self._param_stream:
                from deepspeed_tpu.runtime.infinity import streamed_tree_add

                gl, gr = split_layers(grads)
                al, ar = split_layers(grad_acc)
                ar = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                  ar, gr)
                return loss, {**ar, "layers": streamed_tree_add(al, gl)}
            grad_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), grad_acc, grads)
            grad_acc = lax.with_sharding_constraint(grad_acc, grad_shardings)
            return loss, grad_acc

        self._micro_step_jit = jax.jit(
            micro_step, donate_argnums=(1,),
            out_shardings=(self._replicated, self.grad_shardings))

        def apply_step(params, opt_state, ls_state, grads, lr):
            if self._param_stream:
                gl, gr = split_layers(grads)
                new_params, new_opt, new_ls, grad_norm, finite = \
                    stream_apply_update(params, opt_state, gl, gr, lr,
                                        ls_state)
            else:
                new_params, new_opt, new_ls, grad_norm, finite = apply_update(
                    params, opt_state, grads, lr, ls_state)
            metrics = {"grad_norm": grad_norm, "loss_scale": ls_state["scale"],
                       "skipped": jnp.logical_not(finite)}
            if self._param_stream:
                return new_params, new_opt, new_ls, metrics
            # Return the DONATED grad buffer zeroed in place: without a
            # same-shaped output the donation could never be honored
            # (params/opt/ls already claim the other aliases — graph
            # auditor finding `donation_miss`), so the full fp32 gradient
            # tree stayed live across the update AND the next
            # accumulation round re-materialized a fresh zeros tree,
            # unsharded on one device, before resharding it.  Now the
            # alias is real (a memset, no allocation) and step()/forward()
            # recycle the buffer instead.
            zero_grads = jax.tree.map(jnp.zeros_like, grads)
            return new_params, new_opt, new_ls, zero_grads, metrics

        metrics3_sh = jax.tree.map(
            lambda _: self._replicated,
            {"grad_norm": 0, "loss_scale": 0, "skipped": 0})
        if self._param_stream:
            apply_out = (self.param_shardings, self.opt_shardings,
                         self._replicated, metrics3_sh)
            # no grad-shaped output exists to alias (the streamed grads
            # are consumed layer-wise), so donating grads could never be
            # honored — same pigeonhole as apply_step_store
            apply_donate = (0, 1, 2)
            self._zero_grads_jit = None
        else:
            apply_out = (self.param_shardings, self.opt_shardings,
                         self._replicated, self.grad_shardings, metrics3_sh)
            apply_donate = (0, 1, 2, 3)
            gshapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
                self.params)
            # cold-start grad buffer born IN the accumulator sharding —
            # the eager zeros + device_put it replaces held the whole
            # unsharded fp32 tree on one device first
            self._zero_grads_jit = jax.jit(
                lambda: jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), gshapes),
                out_shardings=self.grad_shardings)
        self._apply_step_jit = jax.jit(
            apply_step, donate_argnums=apply_donate,
            out_shardings=apply_out)

        def apply_step_store(params, opt_state, ls_state, grads, lr):
            """Overlapped opt-store variant: grads arrive fresh from
            `_grads_batch_store_jit` each step and are never recycled,
            so skip apply_step's zero-grads output (a full-tree memset)
            — and don't donate a buffer no output can alias (4 input
            trees, 3 outputs: the pigeonhole leaves grads over)."""
            new_params, new_opt, new_ls, grad_norm, finite = apply_update(
                params, opt_state, grads, lr, ls_state)
            metrics = {"grad_norm": grad_norm,
                       "loss_scale": ls_state["scale"],
                       "skipped": jnp.logical_not(finite)}
            return new_params, new_opt, new_ls, metrics

        self._apply_step_store_jit = jax.jit(
            apply_step_store, donate_argnums=(0, 1, 2),
            out_shardings=(self.param_shardings, self.opt_shardings,
                           self._replicated, metrics3_sh))

        def eval_step(params, batch):
            return loss_fn(params, batch)

        self._eval_step_jit = jax.jit(eval_step, out_shardings=self._replicated)

    # ------------------------------------------------------------------
    # NVMe optimizer-state swapping (ZeRO-Infinity)
    # ------------------------------------------------------------------
    def _opt_store_read(self):
        """All opt-store reads funnel here: join an in-flight prefetch if
        one exists (the AIO handle is single-owner; concurrent use from
        two threads is not allowed), else read synchronously."""
        fut, self._opt_fut = self._opt_fut, None
        return fut.result() if fut is not None else self._opt_store.swap_in()

    def _param_store_read(self):
        fut, self._param_fut = self._param_fut, None
        return fut.result() if fut is not None \
            else self._param_store.swap_in()

    def _prefetch_stores(self) -> None:
        """Queue the next step's store reads behind the writes just issued
        (ref pipelined_optimizer_swapper.py:26 + async_swapper.py:19): the
        swapper's swap_in drains pending writes then reads, all on a worker
        thread, overlapping the host's dispatch of the next step."""
        if self._swap_pool is None:
            return
        if self._opt_store is not None and self._opt_fut is None:
            self._opt_fut = self._swap_pool.submit(self._opt_store.swap_in)
        if self._param_store is not None and self._param_fut is None:
            self._param_fut = self._swap_pool.submit(
                self._param_store.swap_in)

    def _cancel_prefetch(self) -> None:
        """Join and discard in-flight prefetches — required before any
        out-of-band store write (checkpoint load) so the stale read result
        is never consumed.  Errors are swallowed: the result is discarded
        by construction, and the caller is usually about to overwrite the
        very state the failed read targeted."""
        global _DISCARDED_PREFETCH_WARNED
        for name in ("_opt_fut", "_param_fut"):
            fut = getattr(self, name, None)
            if fut is not None:
                try:
                    fut.result()
                except Exception as e:
                    if not _DISCARDED_PREFETCH_WARNED:
                        _DISCARDED_PREFETCH_WARNED = True
                        logger.warning(
                            f"discarded prefetch failed: {e} (further "
                            "discarded-prefetch failures are not logged)")
                setattr(self, name, None)

    def destroy(self) -> None:
        """Release background resources (swap worker pool, in-flight
        prefetches).  Call when done training — the last step always
        leaves one speculative store read in flight (whose NVMe buffer
        stays pinned until consumed).  Ref DeepSpeedEngine.destroy."""
        # the recycled (trio-path) grad accumulator persists between
        # steps by design — that is what lets apply_step alias it in
        # place — but must not outlive training
        self._grad_buffer = None
        self._cancel_prefetch()
        ce = self._checkpoint_engine
        if ce is not None and hasattr(ce, "wait"):
            # an async writer (orbax/decoupled) publishes meta.json + the
            # `latest` pointer only at wait() — without this, the run's
            # FINAL save would stream all its shards and still be
            # unloadable because its commit point never ran
            try:
                ce.wait()
            except Exception as e:
                logger.warning(f"checkpoint writer wait() failed during "
                               f"destroy: {e}")
        if self._watchdog is not None:
            self._watchdog.stop()
        if self.telemetry is not None and sys.exc_info()[0] is not None:
            # destroy() running while an exception propagates (the usual
            # `finally: engine.destroy()` after a crashed step): leave
            # forensics behind — same bundle the hang watchdog writes.
            # Deliberately conservative: exc_info is also set inside an
            # `except:` handler that already recovered, so a handled-
            # error teardown writes a (harmless) bundle too — a spare
            # bundle is noise, a missing one on a real crash is not.
            self.telemetry.dump_flight("engine_crash",
                                       error=sys.exc_info()[1])
        if self._trace_profiler is not None:
            self._trace_profiler.close()  # flush a capture cut short
        if self.telemetry is not None:
            self.telemetry.close()  # flush jsonl + trace + capture
            from deepspeed_tpu.utils.comms_logging import get_comms_logger

            get_comms_logger().enabled = self._comms_prev_enabled
        elif self._trace_path:
            # standalone tracer: nobody else will write the trace file
            try:
                self._tracer.export_chrome_trace(self._trace_path)
            except OSError as e:
                logger.warning(f"trace export failed: {e}")
            self._trace_path = ""
        if self._swap_pool is not None:
            self._swap_pool.shutdown(wait=True)
            self._swap_pool = None
        so = self._super_opt
        if so is not None and hasattr(so, "close"):
            so.close()  # chunked pipeline: drain d2h/h2d pools + NVMe IO

    def __del__(self):  # best-effort: destroy() is the real API
        try:
            if getattr(self, "_swap_pool", None) is not None:
                self._swap_pool.shutdown(wait=False)
        except Exception:
            pass

    def _swap_in_opt_state(self):
        if self._opt_store is None:
            return self.opt_state
        return jax.device_put(self._opt_store_read(),
                              self._opt_device_shardings)

    def _swap_out_opt_state(self, opt_state) -> None:
        if self._opt_store is None:
            self.opt_state = opt_state
            return
        self._opt_store.swap_out(opt_state)
        self.opt_state = None

    def _swap_in_params(self) -> None:
        """NVMe param tier (ZeRO-Infinity): stage the layer weights
        NVMe → host pinned RAM for this step (ref
        partitioned_param_swapper.py:37)."""
        if self._param_store is None or self.params.get("layers") is not None:
            return
        layers = jax.device_put(self._param_store_read(),
                                self.param_shardings["layers"])
        self.params = {**self.params, "layers": layers}

    def _swap_out_params(self) -> None:
        if self._param_store is None:
            return
        self._param_store.swap_out(self.params["layers"])
        self.params = {**self.params, "layers": None}

    def offload_states(self, include=None) -> None:
        """Move params/optimizer state to host RAM (ref offload_states.py:90)."""
        from deepspeed_tpu.runtime.offload import offload_states as _off

        _off(self, include)

    def reload_states(self, include=None) -> None:
        from deepspeed_tpu.runtime.offload import reload_states as _rl

        _rl(self, include)

    # ------------------------------------------------------------------
    # Batch handling
    # ------------------------------------------------------------------
    def _batch_sharding_for(self, arr, stacked: bool) -> NamedSharding:
        ndim = np.ndim(arr)
        spec: list = [None] * ndim
        batch_dim = 1 if stacked else 0
        seq_dim = batch_dim + 1
        if ndim > batch_dim:
            spec[batch_dim] = BATCH_AXES
        if ndim > seq_dim and self.topology.sp_size > 1:
            spec[seq_dim] = SEQ_AXIS
        return NamedSharding(self.topology.mesh, P(*spec))

    def _put_batch(self, batch: Batch, stacked: bool) -> Batch:
        if stacked and "input_ids" in batch:
            # token count for this train batch (telemetry tokens/s) —
            # shape-only, so curriculum truncation is accounted exactly
            self._last_batch_tokens = int(
                np.prod(np.shape(batch["input_ids"])))
        out = {}
        for k, v in batch.items():
            if k == "dropout_key":
                # [gas, 2] PRNG keys: replicated (the [gas] axis is the
                # accumulation scan, dim 1 is key data — not batch rows)
                sh = NamedSharding(self.topology.mesh, P())
            else:
                sh = self._batch_sharding_for(v, stacked)
            out[k] = jax.device_put(np.asarray(v), sh)
        return out

    def _stack_micro_batches(self, data) -> Batch:
        """Accept a stacked batch dict [gas*dp*micro, ...], a dict already
        shaped [gas, dp*micro, ...], or an iterator of micro-batches."""
        gas = self.gradient_accumulation_steps_value
        if isinstance(data, dict):
            first = next(iter(data.values()))
            n = np.shape(first)[0]
            per_step = self.micro_batch_size * self.topology.dp_size
            if n == gas and np.ndim(first) >= 2 and np.shape(first)[1] == per_step:
                return self._maybe_stripe_ring(data, seq_axis=2)
            if n != gas * per_step:
                raise ValueError(
                    f"batch dim {n} != gas({gas}) * micro*dp({per_step})")
            return self._maybe_stripe_ring(
                {k: np.asarray(v).reshape((gas, per_step) + np.shape(v)[1:])
                 for k, v in data.items()}, seq_axis=2)
        # iterator of micro-batches
        micros = [next(data) for _ in range(gas)]
        return self._maybe_stripe_ring(
            {k: np.stack([np.asarray(m[k]) for m in micros], axis=0)
             for k in micros[0]}, seq_axis=2)

    def _maybe_stripe_ring(self, batch, seq_axis: int):
        """Striped ring placement (model cfg ring_placement="striped"):
        permute sequence-axis batch arrays into the stripe order the
        model's positions assume — shard r of the seq mesh then owns
        tokens r, r+sp, … and every causal ring hop is load-balanced
        (sequence/ring.py).  Host-side numpy: the permutation costs no
        device collectives, and labels ride the same order so the loss
        pairing is untouched."""
        mc = self.model_config
        if (mc is None or getattr(mc, "seq_impl", None) != "ring"
                or getattr(mc, "ring_placement", None) != "striped"
                or self.topology.sp_size <= 1):
            return batch
        from deepspeed_tpu.sequence.ring import stripe_sequence

        sp = self.topology.sp_size
        out = dict(batch)
        for k in ("input_ids", "labels", "attention_mask",
                  "token_type_ids", "position_ids"):
            v = out.get(k)
            if v is not None and np.ndim(v) > seq_axis \
                    and np.shape(v)[seq_axis] % sp == 0:
                out[k] = stripe_sequence(np.asarray(v), sp, axis=seq_axis)
        return out

    def _apply_curriculum(self, data):
        """Truncate seq-dim batch keys to the curriculum's current
        difficulty (seqlen curricula only)."""
        if self.curriculum_scheduler is None or self._curriculum_type != "seqlen":
            return data
        seqlen = self.curriculum_scheduler.update_difficulty(self.global_steps)
        # a difficulty change means new traced shapes → an implicit XLA
        # recompile at dispatch; don't let the watchdog count it as a stall
        if getattr(self, "_last_curriculum_seqlen", None) not in (None, seqlen):
            self._watchdog_expect_compile()
        self._last_curriculum_seqlen = seqlen

        def trunc(batch):
            out = {}
            for k, v in batch.items():
                if k in ("input_ids", "labels", "attention_mask",
                         "position_ids") and np.ndim(v) >= 2 \
                        and np.shape(v)[1] > seqlen:
                    out[k] = v[:, :seqlen]
                else:
                    out[k] = v
            return out

        if isinstance(data, dict):
            return trunc(data)
        if isinstance(data, (list, tuple)):
            return type(data)(trunc(b) if isinstance(b, dict) else b for b in data)
        return data

    def _maybe_recompile_compression(self) -> None:
        """Re-jit when the compression schedule flips a technique on/off
        (the step gate inside apply() is python-static; ref
        compression/scheduler.py schedule_offset)."""
        if self._compression is None:
            return
        if self._compression.active_signature(self.global_steps) \
                != self._compression_sig:
            self._compile_steps()

    def _maybe_update_random_ltd(self) -> None:
        """Raise the model's kept-token count per the LTD schedule; a value
        change swaps the model config and re-jits the step (the bounded
        recompile the reference pays as a reshape)."""
        if self.random_ltd_scheduler is None:
            return
        kept = self.random_ltd_scheduler.update(self.global_steps)
        # reaching the schedule's max means full-sequence training resumes
        effective = 0 if kept >= self.random_ltd_scheduler.max_value else kept
        if effective == self.model_config.ltd_kept:
            return
        from functools import partial as _partial

        from deepspeed_tpu.models import transformer as tf_model

        start, end = self._ltd_band
        self.model_config = self.model_config.replace(
            ltd_kept=effective, ltd_start=start, ltd_end=end)
        self._loss_fn = _partial(tf_model.loss_fn, cfg=self.model_config)
        self._compile_steps()
        log_dist(f"random-ltd: kept seqlen → "
                 f"{effective if effective else 'full'}")

    def _maybe_add_pld(self, batch_stack):
        """Attach the PLD keep-prob to the stacked batch (traced scalar —
        the theta schedule never forces a recompile)."""
        if self.progressive_layer_drop is None:
            return batch_stack
        theta = self.progressive_layer_drop.update_state(self.global_steps)
        gas = next(iter(batch_stack.values())).shape[0]
        # copy: _stack_micro_batches can return the caller's own dict
        return {**batch_stack,
                "pld_theta": np.full((gas,), theta, np.float32)}

    def _maybe_add_dropout_key(self, batch_stack):
        """Attach per-micro-batch PRNG keys when the model needs training
        randomness (cfg.dropout > 0 or a noisy MoE gate policy).  Keys
        are data, not trace constants —
        every step reuses the one compiled program.  Inference/eval paths
        never thread a key, so dropout is identically off there.
        Returns a COPY: _stack_micro_batches can hand back the caller's
        own dict, which must not grow a dropout_key entry."""
        mc = self.model_config
        needs_key = mc is not None and (
            getattr(mc, "dropout", 0.0) > 0.0
            or getattr(mc, "moe_noisy_gate_policy", None))
        if not needs_key:
            return batch_stack
        if not hasattr(self, "_dropout_base_key"):
            self._dropout_base_key = jax.random.PRNGKey(self.seed + 7919)
        step_key = jax.random.fold_in(self._dropout_base_key,
                                      self.global_steps)
        gas = next(iter(batch_stack.values())).shape[0]
        keys = np.asarray(jax.vmap(jax.random.fold_in, (None, 0))(
            step_key, np.arange(gas)))  # one dispatch, one fetch
        return {**batch_stack, "dropout_key": keys}

    # ------------------------------------------------------------------
    # Public API (DeepSpeed parity)
    # ------------------------------------------------------------------
    def train_batch(self, data) -> jnp.ndarray:
        """Run one full train batch (gas micro-batches + optimizer step).
        Ref: PipelineEngine.train_batch / engine forward+backward+step."""
        tel = self.telemetry
        cap = tel.capture if tel is not None else None
        if cap is not None:
            cap.on_step_start(self.global_steps + 1)
        tr = self._tracer
        self._step_span = sp = tr.span("train.step", self._train_trace_id)
        if tr.enabled:
            sp.set(step=self.global_steps + 1)
            if not self._compiled_step_done:
                sp.set(**self._flash_executed_shares(data))
        wd = self._watchdog
        if wd is not None and self._compiled_step_done:
            wd.resume()     # arm for this step (no-op deadline otherwise)
        t0 = time.perf_counter()
        try:
            if self._trace_profiler is not None:
                step = self.global_steps + 1
                self._trace_profiler.maybe_start(step)
                with self._trace_profiler.step(step):
                    loss = self._train_batch_traced_body(data)
                self._trace_profiler.maybe_stop(self.global_steps + 1)
            else:
                loss = self._train_batch_traced_body(data)
            if tel is not None:
                self._emit_telemetry(tel, t0)
                if cap is not None:
                    # next_step: global_steps already advanced in the body
                    cap.on_step_end(self.global_steps + 1)
        finally:
            sp.end()
            self._step_span = None
            if wd is not None:
                wd.beat()
                wd.pause()  # inter-step time is not a stall
        self._compiled_step_done = True
        return loss

    def _flash_executed_shares(self, data) -> Dict[str, float]:
        """Arguments of a run's first ``train.step`` span where the
        model's attention runs the repo flash kernels: the share of S²
        they execute, forward and backward, for the batch's sequence
        length (``ops/pallas/flash_mha.plan``; 0.5 is all a causal mask
        keeps).  Empty where attention takes another path."""
        mc = self.model_config
        ids = data.get("input_ids") if isinstance(data, dict) else None
        if (mc is None or ids is None or mc.use_alibi or mc.alt_window
                or mc.attn_impl not in ("pallas_flash", "auto")
                or (mc.seq_impl == "ring" and self.topology.sp_size > 1)):
            return {}
        from deepspeed_tpu.ops.flash_attention import flash_executed_shares

        shares = flash_executed_shares(
            np.shape(ids)[-1], mc.dim_per_head, mc.num_heads // mc.kv_heads,
            mc.causal, mc.sliding_window)
        if shares is None:
            return {}
        return {"flash_executed_share_fwd": round(shares[0], 4),
                "flash_executed_share_bwd": round(shares[1], 4)}

    # ------------------------------------------------------------------
    # Telemetry (unified per-step StepRecord; telemetry/)
    # ------------------------------------------------------------------
    def _step_flops(self, step_args=None):
        """FLOPs for one whole train batch on this device: XLA cost
        analysis of the compiled step when args are at hand (exact for
        the fused program), analytic model profile fallback.

        profile_compiled pays one extra AOT compile (lower().compile()
        does not share the jit dispatch cache) — once per process, at
        the first recorded step; a flops_profiler run that already
        measured is reused instead."""
        prof = self._last_flops_profile
        if prof and prof.get("flops"):
            return float(prof["flops"]), "measured"
        if step_args is not None and self.config.telemetry.measure_flops:
            try:
                from deepspeed_tpu.profiling.flops_profiler import \
                    profile_compiled

                prof = profile_compiled(self._train_step_jit, *step_args)
                if self.telemetry is not None and prof.get("memory"):
                    # static-memory handshake: the same one-time AOT
                    # compile that prices flops also reads XLA's memory
                    # plan — capture reports diff the runtime HBM
                    # watermarks against it (report.json `hbm` block)
                    self.telemetry.set_static_memory(
                        {"backend": jax.default_backend(),
                         **prof["memory"]})
                if prof.get("flops"):
                    return float(prof["flops"]), "measured"
            except Exception as e:
                logger.warning(f"telemetry: profile_compiled failed "
                               f"({e}); using the analytic profile")
        if self.model_config is not None:
            from deepspeed_tpu.profiling.flops_profiler import \
                get_model_profile

            prof = get_model_profile(
                self.model_config, self.micro_batch_size,
                getattr(self.model_config, "max_seq_len", 0),
                recompute_fwd_factor=self.config.flops_profiler
                .recompute_fwd_factor)
            return (prof["total_flops_per_step"]
                    * self.gradient_accumulation_steps_value, "analytic")
        return 0.0, "none"

    def _emit_telemetry(self, tel, t0: float) -> None:
        """Assemble this step's StepRecord.  Fetching the loss value is a
        hard host sync — the price of a record; off-interval steps skip
        the whole assembly (sync included), except when a regression-
        triggered capture needs every step time (tel.should_record)."""
        if not tel.should_record(self.global_steps):
            return
        metrics = self._last_metrics
        if not tel.is_full_record_step(self.global_steps):
            # regression-trigger bookkeeping only (capture still has
            # budget): sync so the wall time is real, feed the trailing
            # window, skip record assembly and export
            with self._tracer.span("train.sync", self._train_trace_id,
                                   self._step_span):
                np.asarray(metrics["loss"])
            tel.observe_step_time(time.perf_counter() - t0)
            return
        if tel.needs_flops():     # paths without step args: analytic
            tel.set_flops(*self._step_flops(None))

        def _f(key):
            v = metrics.get(key)
            return None if v is None else float(np.asarray(v))

        with self._tracer.span("train.sync", self._train_trace_id,
                               self._step_span):
            # fetching the loss VALUE is the hard host sync — its span is
            # the "how much overlap did the record cost" number
            loss = _f("loss")
        wall = time.perf_counter() - t0
        skipped = metrics.get("skipped")
        with self._tracer.span("train.telemetry", self._train_trace_id,
                               self._step_span):
            tel.record_train_step(
                step=self.global_steps, wall_time_s=wall,
                tokens=self._last_batch_tokens, loss=loss,
                grad_norm=_f("grad_norm"),
                lr=float(self.lr_scheduler(self.global_steps - 1)),
                loss_scale=_f("loss_scale"),
                skipped=bool(np.asarray(skipped)) if skipped is not None
                else False,
                comm=self._comm_delta(),
                offload_overlap_fraction=getattr(
                    self, "_last_offload_overlap", None))

    def _comm_delta(self):
        """Comm volume since THIS engine's construction (the CommsLogger
        is process-global; the raw cumulative totals would include a
        previous engine's traffic)."""
        from deepspeed_tpu.utils.comms_logging import get_comms_logger

        out = {}
        for op, cur in get_comms_logger().totals().items():
            base = self._comms_baseline.get(op, {"count": 0, "bytes": 0})
            count = cur["count"] - base["count"]
            nbytes = cur["bytes"] - base["bytes"]
            if count or nbytes:
                out[op] = {"count": count, "bytes": nbytes}
        return out

    def _train_step_args(self, opt_state, batch_stack, lr):
        """Argument tuple matching the active ``_train_step_jit``
        signature (the comm-quant error-feedback path threads its
        residual state between loss-scale state and the batch)."""
        if self._comm_quant_state is not None:
            return (self.params, opt_state, self.loss_scale_state,
                    self._comm_quant_state["residual"], batch_stack, lr)
        return (self.params, opt_state, self.loss_scale_state, batch_stack,
                lr)

    def audit_step_args(self, data=None):
        """``(jitted step, example args)`` for the static graph auditor
        (``analysis/auditor.py``) — everything needed to lower and
        compile the train step WITHOUT running it.  ``data`` defaults to
        a zero-filled batch of the configured geometry (the auditor only
        reads shapes).  Donated example buffers are never consumed: AOT
        ``lower()``/``compile()`` does not execute.

        Host-stepped paths are auditable too: with a SuperOffload/chunked
        optimizer mounted the device-side program IS the grads batch
        (params, batch stack, loss-scale scalar) — the Adam update runs
        on the host and owns no HBM; with an offload store the fused step
        is lowered against the store's state staged at the device
        shardings, exactly what the non-pipelined step path executes."""
        if data is None:
            mc = self.model_config
            if mc is None:
                raise ValueError("audit_step_args: no model_config to "
                                 "synthesize a batch from — pass data")
            rows = (self.micro_batch_size
                    * self.gradient_accumulation_steps_value
                    * self.topology.dp_size)
            seq = int(getattr(mc, "max_seq_len", 128)) or 128
            ids = np.zeros((rows, seq), np.int32)
            data = {"input_ids": ids, "labels": ids}
        batch_stack = self._stack_micro_batches(data)
        batch_stack = self._maybe_add_pld(batch_stack)
        batch_stack = self._maybe_add_dropout_key(batch_stack)
        batch_stack = self._put_batch(batch_stack, stacked=True)
        lr = jnp.float32(self.lr_scheduler(self.global_steps))
        if self._super_opt is not None:
            return (self._grads_batch_jit,
                    (self.params, batch_stack,
                     self.loss_scale_state["scale"]))
        opt_state = self.opt_state
        if self._opt_store is not None:
            opt_state = self._swap_in_opt_state()
        return (self._train_step_jit,
                self._train_step_args(opt_state, batch_stack, lr))

    def audit_arg_categories(self):
        """Memory-class manifest for the ``audit_step_args`` tuple — one
        ``analysis.MEMORY_CLASSES`` entry per top-level argument, in the
        exact ``_train_step_args`` order (the comm-quant error-feedback
        residual rides between loss-scale state and the batch), so the
        memory auditor can classify every flat parameter buffer by its
        tree-path subtree (the same name manifests the PartitionOracle
        exposes)."""
        if self._super_opt is not None:
            # grads-program signature: params, batch stack, scale scalar
            return ("params", "activations", "other")
        cats = ["params", "opt_state", "opt_state"]
        if self._comm_quant_state is not None:
            cats.append("grads")    # error-feedback residual, grad units
        cats += ["activations", "other"]   # batch stack, lr scalar
        return tuple(cats)

    def _train_batch_traced_body(self, data) -> jnp.ndarray:
        if self._onebit is not None:
            return self._train_batch_onebit(data)
        if self._super_opt is not None:
            return self._train_batch_super(data)
        data = self._apply_curriculum(data)
        self._maybe_update_random_ltd()
        self._maybe_recompile_compression()
        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        with self._tracer.span("train.data_ingest", self._train_trace_id,
                               self._step_span):
            batch_stack = self._stack_micro_batches(data)
            batch_stack = self._maybe_add_pld(batch_stack)
            batch_stack = self._maybe_add_dropout_key(batch_stack)
            batch_stack = self._put_batch(batch_stack, stacked=True)
        lr = jnp.float32(self.lr_scheduler(self.global_steps))
        profiling = (self._flops_profiler is not None
                     and not self._flops_profiler.profile_done
                     and self.global_steps + 1
                     >= self.config.flops_profiler.profile_step)
        if (self._swap_pool is not None and self._opt_store is not None
                and not self._param_stream and not profiling):
            # Overlapped store path: dispatch the grads compute (needs no
            # optimizer state), then join the prefetched store read — the
            # NVMe/host transfer drains while the device computes, so step
            # time approaches max(compute, transfer) instead of the sum.
            self._swap_in_params()
            with self._tracer.span("train.dispatch", self._train_trace_id,
                                   self._step_span):
                loss, grads = self._grads_batch_store_jit(
                    self.params, batch_stack, self.loss_scale_state["scale"])
                opt_state = self._swap_in_opt_state()
                (self.params, opt_state, self.loss_scale_state,
                 metrics) = self._apply_step_store_jit(
                    self.params, opt_state, self.loss_scale_state, grads,
                    lr)
            metrics = {**metrics, "loss": loss}
        else:
            opt_state = self._swap_in_opt_state()
            self._swap_in_params()
            step_args = self._train_step_args(opt_state, batch_stack, lr)
            if self.telemetry is not None and self.telemetry.needs_flops():
                # before the step runs, while donated buffers are still
                # live (lowering reads their shapes); the compile() behind
                # profile_compiled is a one-time AOT cost — see _step_flops
                self.telemetry.set_flops(*self._step_flops(step_args))
            if profiling:
                self._last_flops_profile = \
                    self._flops_profiler.profile_engine_step(
                        self, *step_args)
                self._flops_profiler.print_profile(self._last_flops_profile)
            with self._tracer.span("train.dispatch", self._train_trace_id,
                                   self._step_span):
                if self._comm_quant_state is not None:
                    (self.params, opt_state, self.loss_scale_state,
                     self._comm_quant_state["residual"], metrics) = \
                        self._train_step_jit(*step_args)
                else:
                    self.params, opt_state, self.loss_scale_state, metrics = \
                        self._train_step_jit(*step_args)
        self._swap_out_opt_state(opt_state)
        self._swap_out_params()
        self._prefetch_stores()
        self.global_steps += 1
        self.micro_steps += self.gradient_accumulation_steps_value
        self.lr_scheduler.step()
        self._after_step(metrics)
        self.timers(TRAIN_BATCH_TIMER).stop(ready=metrics["loss"])
        self.tput_timer.stop()
        return metrics["loss"]

    def _train_batch_super(self, data) -> jnp.ndarray:
        """SuperOffload train batch (ref superoffload_stage3.py): grads are
        computed in one compiled step; the optimizer runs on the host as a
        pipelined bucketed Adam (overflow skips the step; the rollback
        window additionally allows post-hoc recovery via engine.rollback)."""
        data = self._apply_curriculum(data)
        self._maybe_update_random_ltd()
        self._maybe_recompile_compression()
        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        batch_stack = self._stack_micro_batches(data)
        batch_stack = self._maybe_add_pld(batch_stack)
        batch_stack = self._maybe_add_dropout_key(batch_stack)
        batch_stack = self._put_batch(batch_stack, stacked=True)
        self._swap_in_params()  # chunked mode can ride the NVMe param tier
        lr = float(self.lr_scheduler(self.global_steps))
        gas = self.gradient_accumulation_steps_value
        scale = self.loss_scale_state["scale"]
        # bookkeeping snapshot so rollback() can restore EVERYTHING the
        # step mutates (scheduler counter, loss scale, step counts), not
        # just the optimizer masters
        self._super_prev_bookkeeping = {
            "sched": self.lr_scheduler.state_dict(),
            "ls": self.loss_scale_state,
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
        }
        loss, grads, gn, finite = self._grads_batch_jit(
            self.params, batch_stack, scale)
        scale_v = float(np.asarray(scale))
        finite_v = bool(np.asarray(finite))
        inv = 1.0 / (scale_v * gas)
        gnorm = float(np.asarray(gn)) * inv
        clip = self.config.gradient_clipping
        coef = inv * (min(1.0, clip / (gnorm + 1e-6))
                      if clip and clip > 0 else 1.0)
        if finite_v:
            self._super_opt.lr = lr
            self.params = self._super_opt.step(self.params, grads,
                                               grad_scale=coef)
        self._super_last_skipped = not finite_v
        # chunked pipeline: how much of the d2h/h2d transfer time the host
        # Adam hid this step (None on plain SuperOffload → field omitted)
        self._last_offload_overlap = getattr(
            self._super_opt, "last_overlap_fraction", None)
        self._swap_out_params()
        self._prefetch_stores()
        self._advance_loss_scale_host(finite_v)
        self.global_steps += 1
        self.micro_steps += gas
        self.lr_scheduler.step()
        metrics = {"loss": loss, "grad_norm": gnorm, "loss_scale": scale_v,
                   "skipped": not finite_v}
        self._after_step(metrics)
        self.timers(TRAIN_BATCH_TIMER).stop(ready=loss)
        self.tput_timer.stop()
        return loss

    def rollback(self) -> None:
        """Undo the last SuperOffload optimizer step (host masters, moments,
        step counter) and restore the device params from the rolled-back
        masters — post-hoc overflow/divergence recovery (ref
        superoffload_stage3 rollback optimizer)."""
        if self._super_opt is None:
            raise RuntimeError("rollback requires SuperOffload mode "
                               "(offload_optimizer.super_offload)")
        if getattr(self, "_super_last_skipped", False):
            raise RuntimeError(
                "last train_batch was overflow-skipped (no optimizer step "
                "ran); the rollback snapshot belongs to an earlier step")
        bk = getattr(self, "_super_prev_bookkeeping", None)
        if bk is None:
            # No snapshot means there is no consistent state to revert the
            # scheduler/loss-scale/counters to; a partial revert (params
            # rolled back, bookkeeping not) would silently diverge.
            raise RuntimeError(
                "rollback requires a bookkeeping snapshot from a completed "
                "train_batch; none exists (no step has run since the last "
                "rollback or load)")
        self._super_opt.rollback()
        self.params = self._super_opt.push_params(self.params)
        self.lr_scheduler.load_state_dict(bk["sched"])
        self.loss_scale_state = bk["ls"]
        self.global_steps = bk["global_steps"]
        self.micro_steps = bk["micro_steps"]
        self._super_prev_bookkeeping = None

    def _advance_loss_scale_host(self, finite: bool) -> None:
        """Host-side entry to the SAME loss-scale policy the jitted step
        uses (_advance_loss_scale with xp=np) for step paths that decide on
        the host (SuperOffload)."""
        ls = {k: np.asarray(v) for k, v in self.loss_scale_state.items()}
        scale, good, skipped = _advance_loss_scale(
            ls["scale"], ls["good_steps"], ls["skipped"], np.bool_(finite),
            self._ls_dynamic, self._ls_window, self._ls_min, np)
        self.loss_scale_state = jax.device_put(
            {"scale": jnp.float32(float(scale)),
             "good_steps": jnp.int32(int(good)),
             "skipped": jnp.int32(int(skipped))},
            self._replicated)

    def _train_batch_onebit(self, data) -> jnp.ndarray:
        """Compressed-DP train batch: explicit shard_map step with 1-bit
        error-feedback momentum allreduce (ref onebit/adam.py step)."""
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.parallel.topology import BATCH_AXES

        data = self._apply_curriculum(data)
        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        batch_stack = self._stack_micro_batches(data)
        batch_stack = self._maybe_add_dropout_key(batch_stack)
        batch_stack = self._put_batch(batch_stack, stacked=True)
        if not self._onebit._built:
            self._onebit.build(self.param_shardings,
                               _stacked_batch_specs(batch_stack,
                                                    BATCH_AXES))
        lr = jnp.float32(self.lr_scheduler(self.global_steps))
        self.params, self._onebit_state, loss = self._onebit(
            self.params, self._onebit_state, batch_stack, lr)
        self.global_steps += 1
        self.micro_steps += self.gradient_accumulation_steps_value
        self.lr_scheduler.step()
        metrics = {"loss": loss}
        self._after_step(metrics)
        self.timers(TRAIN_BATCH_TIMER).stop(ready=loss)
        self.tput_timer.stop()
        return loss

    def forward(self, batch: Batch) -> jnp.ndarray:
        """Compute loss AND gradients for one micro-batch (accumulated).
        With XLA there is no separate autograd tape, so forward+backward fuse;
        ``backward`` is then bookkeeping only — same user-visible contract."""
        self.timers(FORWARD_GLOBAL_TIMER).start()
        self._swap_in_params()
        if self._grad_buffer is None:
            if self._zero_grads_jit is not None:
                # sharded from birth; also aliased-recycled from the
                # previous step() so this only runs on the cold start
                self._grad_buffer = self._zero_grads_jit()
            else:
                zeros = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, jnp.float32), self.params)
                self._grad_buffer = jax.device_put(zeros, self.grad_shardings)
        mc = self.model_config
        if mc is not None and (getattr(mc, "dropout", 0.0) > 0.0
                               or getattr(mc, "moe_noisy_gate_policy", None)):
            # trio path gets its own per-micro key (train_batch's stacked
            # path attaches [gas, 2] keys via _maybe_add_dropout_key)
            if not hasattr(self, "_dropout_base_key"):
                self._dropout_base_key = jax.random.PRNGKey(self.seed + 7919)
            k = jax.random.fold_in(
                jax.random.fold_in(self._dropout_base_key, self.global_steps),
                100_000 + self._micro_in_step)
            batch = {**batch, "dropout_key": np.asarray(k)}
        batch = self._put_batch(batch, stacked=False)
        loss, self._grad_buffer = self._micro_step_jit(
            self.params, self._grad_buffer, batch, self.loss_scale_state["scale"])
        self._last_loss = loss
        self.timers(FORWARD_GLOBAL_TIMER).stop(ready=loss)
        return loss

    def backward(self, loss=None) -> None:
        """Gradients were produced in ``forward`` (fused). Advances the
        micro-step counter that defines the accumulation boundary."""
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        self._micro_in_step += 1
        self.micro_steps += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._micro_in_step >= self.gradient_accumulation_steps_value

    def step(self) -> None:
        """Apply the optimizer step at the accumulation boundary."""
        self.timers(STEP_GLOBAL_TIMER).start()
        if not self.is_gradient_accumulation_boundary():
            self.timers(STEP_GLOBAL_TIMER).stop()
            return
        lr = jnp.float32(self.lr_scheduler(self.global_steps))
        opt_state = self._swap_in_opt_state()
        self._swap_in_params()
        if self._param_stream:
            (self.params, opt_state, self.loss_scale_state,
             metrics) = self._apply_step_jit(
                self.params, opt_state, self.loss_scale_state,
                self._grad_buffer, lr)
            self._grad_buffer = None
        else:
            # the donated grad buffer comes back zeroed (aliased in
            # place) and seeds the next accumulation round
            (self.params, opt_state, self.loss_scale_state,
             self._grad_buffer, metrics) = self._apply_step_jit(
                self.params, opt_state, self.loss_scale_state,
                self._grad_buffer, lr)
        self._swap_out_opt_state(opt_state)
        self._swap_out_params()
        self._prefetch_stores()
        self._micro_in_step = 0
        self.global_steps += 1
        self.lr_scheduler.step()
        self._after_step(metrics)
        self.timers(STEP_GLOBAL_TIMER).stop()

    def eval_batch(self, batch: Batch) -> jnp.ndarray:
        self._swap_in_params()
        batch = self._maybe_stripe_ring(batch, seq_axis=1)
        batch = self._put_batch(batch, stacked=False)
        return self._eval_step_jit(self.params, batch)

    # ------------------------------------------------------------------
    def _after_step(self, metrics) -> None:
        self._last_metrics = metrics
        if self.global_steps % self.config.steps_per_print == 0:
            m = {k: float(np.asarray(v)) for k, v in metrics.items()}
            log_dist(f"step={self.global_steps} "
                     + " ".join(f"{k}={v:.6g}" for k, v in m.items())
                     + f" lr={self.lr_scheduler(self.global_steps - 1):.3e}")
            if self.monitor:
                self.monitor.write_events([
                    ("Train/Samples/train_loss", m.get("loss", 0.0), self.global_steps),
                    ("Train/Samples/lr", self.lr_scheduler(self.global_steps - 1), self.global_steps),
                ])
        if self.config.memory_breakdown:
            # independent of steps_per_print (ref memory_breakdown logs
            # around every step); deferred import so tests can patch it
            from deepspeed_tpu.runtime import utils as _rt_utils

            _rt_utils.see_memory_usage(f"after step {self.global_steps}",
                                       force=True)

    def get_global_grad_norm(self) -> float:
        gn = self._last_metrics.get("grad_norm")
        return float(np.asarray(gn)) if gn is not None else 0.0

    @property
    def loss_scale(self) -> float:
        return float(np.asarray(self.loss_scale_state["scale"]))

    @property
    def skipped_steps(self) -> int:
        """Total optimizer steps skipped on fp16 overflow. Counted on device
        (no per-step host sync); reading this syncs."""
        return int(np.asarray(self.loss_scale_state["skipped"]))

    def get_lr(self):
        return self.lr_scheduler.get_last_lr()

    @property
    def train_micro_batch_size_per_gpu(self) -> int:
        return self.micro_batch_size

    def train_batch_size(self) -> int:
        return self.train_batch_size_value

    @property
    def tracer(self):
        """The ``Tracer`` this engine's ``train.*`` spans go to (the
        hub's, a standalone one, or the shared disabled one), as
        ``InferenceServer.tracer`` exposes the serve loop's."""
        return self._tracer

    def gradient_accumulation_steps(self) -> int:
        return self.gradient_accumulation_steps_value

    # ------------------------------------------------------------------
    # Checkpointing (basic pickle-of-host-arrays; checkpoint/ has the full
    # sharded + universal formats)
    # ------------------------------------------------------------------
    @property
    def checkpoint_engine(self):
        """Pluggable writer (ref runtime/checkpoint_engine/): 'orbax' (sharded
        tensorstore, optional async) or the default pickle engine."""
        if self._checkpoint_engine is None:
            cc = self.config.checkpoint_config
            writer_type = (cc.writer or {}).get("type", "")
            if writer_type == "fast":
                from deepspeed_tpu.checkpoint.fast_engine import FastCheckpointEngine

                self._checkpoint_engine = FastCheckpointEngine()
            elif writer_type == "decoupled":
                from deepspeed_tpu.checkpoint.fast_engine import DecoupledCheckpointEngine

                self._checkpoint_engine = DecoupledCheckpointEngine()
            elif writer_type == "orbax" or cc.async_save:
                from deepspeed_tpu.checkpoint.orbax_engine import OrbaxCheckpointEngine

                self._checkpoint_engine = OrbaxCheckpointEngine(async_save=cc.async_save)
            else:
                self._checkpoint_engine = "pickle"
        return self._checkpoint_engine

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None) -> None:
        self._swap_in_params()  # NVMe param tier: stage layers for the save
        ce = self.checkpoint_engine
        if ce != "pickle":
            ce.save(self, save_dir, tag or f"global_step{self.global_steps}",
                    client_state=client_state or {})
            return
        from deepspeed_tpu.checkpoint.engine import save_checkpoint as _save

        _save(self, save_dir, tag=tag, client_state=client_state or {})

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True):
        if self.config.load_universal_checkpoint:
            from deepspeed_tpu.checkpoint.universal import (load_universal,
                                                            resolve_universal_dir)

            load_universal(self, resolve_universal_dir(load_dir, tag))
            self._sync_store_after_load()
            return load_dir, {}
        ce = self.checkpoint_engine
        if ce != "pickle":
            result = ce.load(self, load_dir, tag=tag,
                             load_optimizer_states=load_optimizer_states,
                             load_lr_scheduler_states=load_lr_scheduler_states)
        else:
            from deepspeed_tpu.checkpoint.engine import load_checkpoint as _load

            result = _load(self, load_dir, tag=tag,
                           load_optimizer_states=load_optimizer_states,
                           load_lr_scheduler_states=load_lr_scheduler_states)
        self._sync_store_after_load()
        return result

    def _opt_state_template(self):
        """Optimizer-state pytree usable as a structure/shape template even
        when an offload store (host/NVMe) is authoritative."""
        if self.opt_state is not None:
            return self.opt_state
        if self._opt_store is not None:
            return self._opt_store_read()
        return None

    def _sync_store_after_load(self) -> None:
        """After any checkpoint load: if an offload store is authoritative,
        push the freshly-loaded optimizer state into it."""
        self._cancel_prefetch()  # a pre-load prefetch would be stale
        if self._opt_store is not None and self.opt_state is not None:
            self._opt_store.swap_out(self.opt_state)
            self.opt_state = None
