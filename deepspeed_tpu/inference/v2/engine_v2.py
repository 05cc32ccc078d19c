"""InferenceEngineV2 — continuous-batching serve engine (FastGen analog).

Ref: ``InferenceEngineV2`` (inference/v2/engine_v2.py:30) +
``build_hf_engine`` (engine_factory.py:69). The engine owns the paged KV
cache, the sequence state manager and the SplitFuse scheduler; ``put()``
schedules one ragged step; ``generate()`` runs full continuous-batching
text generation with per-call sampling params (greedy / temperature /
top-k / top-p, sampled on device).

TPU specifics: the ragged step is ONE jitted function with donated KV-cache
buffers (no copies between steps) and fixed shapes — every prefill/decode
mix replays the same executable, and a step reaches the chip as one
host→device transfer (its packed index buffer) and one program
(``_ship``); tensor-parallel serving reuses the training
ShardingRules so weights shard over the "tensor" mesh axis and XLA inserts
the same collectives AutoTP injection produces in the reference.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.inference.v2.model import (attention_impl_name,
                                              check_sampling_params,
                                              new_ssm_state,
                                              new_window_pools,
                                              ragged_decode_loop,
                                              ragged_draft_step,
                                              ragged_step,
                                              ragged_step_sampled,
                                              ragged_verify, ssm_impl_name)
from deepspeed_tpu.inference.v2.ragged import (IN_FLIGHT, DSStateManager,
                                               KVCacheExhausted, PackedIndex,
                                               RaggedBatch,
                                               build_ragged_batch)
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models import transformer as tf_model
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops.pallas.kv_append import append_pages
from deepspeed_tpu.ops.pallas.kv_append import fit as kv_append_fit
from deepspeed_tpu.ops.pallas.paged_attention import (QUERY_BLOCK,
                                                      shared_walk_rows)
from deepspeed_tpu.resilience.oracle import PartitionOracle
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
from deepspeed_tpu.utils.logging import log_dist


class RaggedInferenceEngineConfig:
    """Engine knobs (ref inference/v2/config_v2.py RaggedInferenceEngineConfig)."""

    def __init__(self, d: Optional[Dict[str, Any]] = None, **kw):
        d = {**(d or {}), **kw}
        self.tp_size = int(d.get("tensor_parallel", {}).get("tp_size", 1)
                           if isinstance(d.get("tensor_parallel"), dict)
                           else d.get("tp_size", 1))
        state = d.get("state_manager", {})
        self.max_tracked_sequences = int(state.get("max_tracked_sequences", 64))
        self.max_ragged_batch_size = int(state.get("max_ragged_batch_size", 256))
        # the narrowest block table a step program is compiled for: a
        # deployment whose contexts all run past it compiles no program
        # for the narrower tables (one a power of two otherwise); a
        # shorter context then runs in this bucket's program
        self.min_context_blocks = int(state.get("min_context_blocks", 1))
        self.memory_config = d.get("memory_config", {})
        self.num_blocks = int(self.memory_config.get("num_blocks", 512))
        self.block_size = int(self.memory_config.get("block_size", 16))
        # pages of the window layers' own pool, for a model that mixes
        # window and full attention by layer (any other keeps one pool)
        self.window_blocks = int(self.memory_config.get("window_blocks",
                                                        self.num_blocks))
        # "int8": blockwise-quantized KV pages (one fp32 scale per
        # (head, row)) — halves decode's KV bandwidth, the bound resource
        # (ref KV-block layout inference/v2/ragged/kv_cache.py:40)
        self.kv_dtype = str(self.memory_config.get("kv_dtype", "auto"))
        if self.kv_dtype not in ("auto", "int8", "bf16", "bfloat16"):
            raise ValueError(f"memory_config.kv_dtype={self.kv_dtype!r}: "
                             "expected 'auto', 'int8', or 'bf16'")
        self.max_context = int(d.get("max_context", 2048))
        # Compile-time guard: the paged decode kernel's per-token page loop
        # is ceil(max_context / block_size) long, and Mosaic compile time
        # grows sharply with it — observed >880 s at 512 blocks/seq on v5e
        # (r04, block_size=64 at 32k context) where a user would assume a
        # hang.  A config error beats a silent 15-minute compile; opt in
        # with {"allow_slow_compile": true} if the one-off compile is
        # acceptable (executions are cached afterwards).
        blocks_per_seq = -(-self.max_context // self.block_size)
        if blocks_per_seq > 256 and not bool(d.get("allow_slow_compile")):
            raise ValueError(
                f"max_context={self.max_context} / block_size="
                f"{self.block_size} = {blocks_per_seq} blocks per sequence: "
                "TPU compile time grows sharply past ~256 (observed >880 s "
                "at 512 on v5e). Raise memory_config.block_size, lower "
                "max_context, or set allow_slow_compile=true to proceed.")
        if blocks_per_seq > 128:
            log_dist(
                f"inference v2: {blocks_per_seq} KV blocks per sequence — "
                "first-compile time on TPU may reach minutes; larger "
                "memory_config.block_size compiles faster", level="warning")
        # longest fused multi-step decode dispatch (one host round-trip
        # runs up to this many steps on device); latency-sensitive hosts
        # raise it to amortize dispatch overhead.  Rounded down to a power
        # of two so the chunk round-up in _fused_decode can never exceed
        # the configured bound (chunk sizes are pow2 compile buckets).
        mdc = max(1, int(d.get("max_decode_chunk", 32)))
        self.max_decode_chunk = 1 << (mdc.bit_length() - 1)
        self.dtype = d.get("dtype", "bfloat16")
        # self-drafting through the model's multi-token-prediction module
        # (docs/SERVING.md): a greedy step() verifies each decoding
        # sequence's draft and makes the next, in the one ragged program
        self.self_draft = bool(d.get("self_draft", False))
        ep = d.get("expert_parallel", {})
        self.ep_size = int(ep.get("ep_size", 1) if isinstance(ep, dict)
                           else ep)
        # module-implementation overrides, e.g. {"attention": "paged_xla"}
        # (ref inference/v2/modules: ConfigBundle names); resolved through
        # inference/v2/modules.py at each attention call.  Validate names
        # NOW — a typo surfacing as a KeyError inside jit tracing at the
        # first generate() would point nowhere near the config
        from deepspeed_tpu.inference.v2 import model as _model  # registers
        from deepspeed_tpu.inference.v2.modules import (available,
                                                        module_overrides)

        self.modules = module_overrides(d)
        for kind, name in self.modules.items():
            if name != "auto" and name not in available(kind):
                raise ValueError(
                    f"unknown {kind} implementation '{name}' "
                    f"(available: {', '.join(available(kind)) or 'none'})")


def _named(name: str, fn, **static):
    """``partial(fn, **static)`` under a ``__name__``: what ``jax.jit``
    names the compiled program by."""
    p = partial(fn, **static)
    p.__name__ = name
    return p


def _next_key(key, temperature: float) -> tuple:
    """``(key', subkey, programs)`` for one sampled dispatch.  A greedy
    sampler never reads its key, so a greedy step splits none and hands
    the key over as it is; any other draws from a fresh subkey (a fixed
    key would correlate every step's draws; deterministic per seed).
    ``programs``: what the split dispatched ahead of the step, the split
    and the pair's unpacking."""
    if temperature <= 0:
        return key, key, 0
    key, sub = jax.random.split(key)
    return key, sub, 2


def step_counts(items: Sequence[tuple], window: Optional[int] = None,
                query_block: int = 0) -> Dict[str, int]:
    """What one ragged step is asked to do, from its ``(cached, n_new)``
    work items (tokens already in the cache, tokens this step adds); the
    arguments of the ``v2.schedule`` span.  A decode item adds one token
    to a context that is already there; anything else is a prefill
    chunk.  ``kv_rows``: each sequence's context after the step, counted
    once, cut to ``window``: the keys (and as many values) the step's
    attention has to read.  ``qk_pairs``: live (query, key) pairs of the
    new tokens under the causal mask, the token at 0-based position p
    seeing ``min(p + 1, window)`` keys.  ``blocked_rows``: the new tokens
    that share a page walk in the query-blocked paged kernel, which
    serves ``query_block`` rows of the step a program (0: the step does
    not run that kernel); ``one_row_walks``: the rest, each a piece of one
    row under the same cut (a decode row, or what a block boundary left
    of a longer run), which that kernel multiplies on a narrow window of
    its tile."""
    prefill = decode = kv_rows = pairs = 0
    for cached, n in items:
        if n == 1 and cached > 0:
            decode += 1
        else:
            prefill += n
        end = cached + n
        if window and end > window:
            kv_rows += window
            # positions below the window see p + 1 keys, the rest window
            full = max(cached, window)
            pairs += (full * (full + 1) - cached * (cached + 1)) // 2 \
                + (end - full) * window
        else:
            kv_rows += end
            pairs += (end * (end + 1) - cached * (cached + 1)) // 2
    blocked = (shared_walk_rows([n for _, n in items], query_block)
               if query_block else 0)
    return {"seqs": len(items), "tokens": prefill + decode,
            "prefill_tokens": prefill, "decode_tokens": decode,
            "blocked_rows": blocked,
            "one_row_walks": prefill + decode - blocked if query_block else 0,
            "kv_rows": kv_rows, "qk_pairs": pairs}


def window_step_counts(items: Sequence[tuple], cfg: TransformerConfig,
                       held: tuple, freed: int) -> Dict[str, Any]:
    """What one ragged step asks of a model that mixes window and full
    attention by layer, from the same ``(cached, n_new)`` items: further
    arguments of ``v2.schedule``, each for ONE layer of its kind.
    ``full_kv_rows`` / ``window_kv_rows``: keys the step's rows read,
    each sequence's context after the step counted once, cut to the
    window for a window layer; ``full_pages`` / ``window_pages``: pages
    live sequences hold after the step (``held``); ``pages_freed``: window
    pages this step returned; ``full_qk_pairs`` / ``window_qk_pairs``:
    live (query, key) pairs of the step's new rows, causal, cut to the
    window for a window layer (:func:`step_counts`' ``qk_pairs``);
    ``expert_rows``: (row, held expert) products
    an expert layer expects under even routing, rows x experts per token x
    held / routed."""
    mx = cfg.mixed
    ends = [cached + n for cached, n in items]
    return {"full_kv_rows": sum(ends),
            "window_kv_rows": sum(min(e, mx.sliding_window) for e in ends),
            "full_pages": held[0], "window_pages": held[1],
            "pages_freed": freed,
            "full_qk_pairs": step_counts(items)["qk_pairs"],
            "window_qk_pairs": step_counts(
                items, mx.sliding_window)["qk_pairs"],
            "expert_rows": sum(n for _, n in items) * mx.num_experts_per_tok
            * mx.experts_held[1] / mx.n_routed_experts}


def mixed_alloc_counts(cfg: TransformerConfig, engine_cfg, cache_k, cache_v,
                       pools) -> Dict[str, int]:
    """Further arguments of a mixed-attention model's ``v2.state_alloc``:
    what the two pools keep a kind of layer.  ``full_page_bytes`` /
    ``window_page_bytes``: ONE layer's page of the kind, K and V, as LAID
    OUT (a key row in ``row_width`` lanes); both kinds' KV heads, the
    key's and the value's published widths, the layers whose softmax has
    a sink, and ``kernel_calls_per_step``: the Pallas calls ONE step
    program makes, from the layer kinds and the kernels resolved (the
    append and the read a layer, each kind's append by its own shapes)."""
    bs = engine_cfg.block_size

    def page_bytes(k, v):       # 0: no layer of the kind
        return (int(k.nbytes) + int(v.nbytes)) // max(
            1, k.shape[0] * k.shape[2] // bs)

    calls = 0
    if attention_impl_name(cfg, bs) == "paged_pallas":
        for layers, k, v in ((cfg.attn_layers, cache_k, cache_v),
                             (cfg.window_layers, pools["k"], pools["v"])):
            appends = kv_append_fit(16, k.shape[1], k.shape[3], bs, k.dtype,
                                    v.shape[3]) is not None
            calls += layers * (1 + int(appends))
    return {"full_page_bytes": page_bytes(cache_k, cache_v),
            "window_page_bytes": page_bytes(pools["k"], pools["v"]),
            "full_kv_heads": cfg.kv_heads,
            "window_kv_heads": cfg.window_kv_heads,
            "key_width": cfg.dim_per_head, "value_width": cfg.value_width,
            "sink_layers": cfg.sink_layers, "kernel_calls_per_step": calls}


def ssm_step_counts(items: Sequence[tuple], slot_bytes: int,
                    live: int) -> Dict[str, int]:
    """What one ragged step asks of a model's SSM mixer, from the same
    ``(cached, n_new)`` items: further arguments of ``v2.schedule``.  Each
    item is one run of rows through the scan.  ``state_bytes``: float32
    recurrent state the runs move, every layer's — a run writes its
    slot once and reads it once, unless it starts at position 0 and so
    from zeros (``slot_bytes``: one slot's, all layers; the convolution's
    tails, under a hundredth of it, are not counted).
    ``state_slots_live``: sequences that hold a slot."""
    fresh = sum(1 for cached, _ in items if cached == 0)
    return {"ssm_runs": len(items), "ssm_rows": sum(n for _, n in items),
            "state_slots_live": live,
            "state_bytes": slot_bytes * (2 * len(items) - fresh)}


def hybrid_step_counts(items: Sequence[tuple], cfg: TransformerConfig,
                       mgr) -> Dict[str, Any]:
    """What one ragged step asks of a one-mixer-a-layer model beside
    :func:`ssm_step_counts` (its ``state_bytes`` are the ``M`` layers')
    and :func:`step_counts` (``kv_rows``, ``qk_pairs`` and
    ``append_pages`` are ONE ``*`` layer's): ``kv_pages_held``, pages the
    live sequences hold after the step, one ``*`` layer's; ``expert_rows``,
    (row, held expert) products ONE ``E`` layer expects under even
    routing, rows x experts per token x held / routed."""
    hy = cfg.hybrid
    return {"kv_pages_held": mgr.allocator.num_blocks - 1
            - mgr.allocator.free_blocks,
            "expert_rows": sum(n for _, n in items) * hy.num_experts_per_tok
            * hy.experts_held[1] / hy.n_routed_experts}


def hybrid_alloc_counts(cfg: TransformerConfig, engine_cfg, state, cache_k,
                        ssm_impl: str) -> Dict[str, int]:
    """Further arguments of a one-mixer-a-layer model's
    ``v2.state_alloc``: how many layers of each kind, what ONE sequence
    holds of recurrent state and convolution tails over every ``M`` layer
    (``slot_bytes``: admission is bound by these slots, not by pages),
    both pools' bytes, one page of one ``*`` layer (K and V), and
    ``kernel_calls_per_step``: the Pallas calls ONE step program makes,
    from the layer kinds and the kernels resolved (the scan a ``M``
    layer; the append and the read a ``*`` layer)."""
    slots = engine_cfg.max_tracked_sequences + 1
    per_attn = 0
    if attention_impl_name(cfg, engine_cfg.block_size) == "paged_pallas":
        appends = engine_cfg.kv_dtype != "int8" and kv_append_fit(
            16, cfg.kv_heads, cfg.dim_per_head, engine_cfg.block_size,
            cache_k.dtype) is not None
        per_attn = 1 + int(appends)
    pool = sum(int(a.nbytes) for a in jax.tree.leaves(cache_k))
    return {"ssm_layers": cfg.ssm_layers, "attn_layers": cfg.attn_layers,
            "expert_layers": cfg.expert_layers,
            "slot_bytes": int(state["ssm"].nbytes) // slots
            + int(state["conv"].nbytes) * cfg.ssm_layers
            // state["conv"].shape[0],
            "state_pool_bytes": sum(int(a.nbytes)
                                    for a in jax.tree.leaves(state)),
            "kv_pool_bytes": 2 * pool,
            "page_bytes": 2 * pool // (cfg.attn_layers
                                       * engine_cfg.num_blocks),
            "kernel_calls_per_step": cfg.ssm_layers
            * int(ssm_impl == "ssd_pallas") + cfg.attn_layers * per_attn}


# _ragged_step's ``sample`` for a self-drafting greedy step
_DRAFT = {"draft": True}


class StepInFlight(NamedTuple):
    """A sampled step that :meth:`InferenceEngineV2.launch` put on the
    chip and :meth:`InferenceEngineV2.fetch` has not read back."""
    out: Any                    # the sampled tokens by slot, on the device
    key: tuple                  # the step program's key (``_ship``)
    compiled: bool              # the key's first dispatch: it compiled
    # of each sequence the step samples: its slot (the token's row of
    # ``out``) and where the token goes in the sequence's ``tokens`` (an
    # ``extend(uid, IN_FLIGHT)`` keeps the place)
    places: Dict[int, tuple]
    # a self-drafting step's: the uids whose run is a verify run
    verified: frozenset = frozenset()

    @property
    def uids(self):
        return self.places.keys()


class RecurrentStateUnsupported(NotImplementedError):
    """A path that would need a snapshot of a sequence's slot state
    (prefix reuse, speculative verify and rewind, KV hand-off) was asked
    of a model that has such state, a mixer's recurrent state or a window
    latent layer's ring of rows: there are no snapshots."""


# what the refusals call the state a slot holds, and why no earlier
# position of it can be adopted, rewound to or shipped
_MIXER_STATE = (
    "this model's Mamba-2 SSM mixer keeps recurrent state per sequence, "
    "which is only ever the state after the last row run — there is no "
    "copy of it at an earlier position to adopt, rewind to or ship")
_WINDOW_PAGES = (
    "this model's sliding-window layers keep their rows in a page pool of "
    "their own and return a page to it once every row of it lies more "
    "than the window below the sequence's last row — the pages at an "
    "earlier position are gone, and there is no copy of them to adopt, "
    "rewind to or ship")
_WINDOW_ROWS = (
    "this model's sliding-window latent layers keep only the last window "
    "of a sequence's rows, in a ring per sequence that later rows "
    "overwrite — pages alone do not make a sequence, and there is no copy "
    "of the ring at an earlier position to adopt, rewind to or ship")


def _kv_scatter(cache_k, cache_v, rows, k, v):
    """Write handed-off KV page rows into the paged caches (both cache
    layouts: plain array [L, nkv, P, d], or the int8 quantized dict
    {"q": [L, nkv, P, d] int8, "s": [L, nkv, P] fp32})."""
    if isinstance(cache_k, dict):
        cache_k = {"q": cache_k["q"].at[:, :, rows, :].set(k["q"]),
                   "s": cache_k["s"].at[:, :, rows].set(k["s"])}
        cache_v = {"q": cache_v["q"].at[:, :, rows, :].set(v["q"]),
                   "s": cache_v["s"].at[:, :, rows].set(v["s"])}
    else:
        cache_k = cache_k.at[:, :, rows, :].set(k.astype(cache_k.dtype))
        cache_v = cache_v.at[:, :, rows, :].set(v.astype(cache_v.dtype))
    return cache_k, cache_v


def _kv_gather(cache, rows):
    """Read page rows out of either cache layout (host numpy)."""
    if isinstance(cache, dict):
        return {"q": np.asarray(jnp.take(cache["q"], rows, axis=2)),
                "s": np.asarray(jnp.take(cache["s"], rows, axis=2))}
    return np.asarray(jnp.take(cache, rows, axis=2))


def _payload_nbytes(part) -> int:
    if isinstance(part, dict):
        return sum(int(a.nbytes) for a in part.values())
    return int(part.nbytes)


class InferenceEngineV2:
    def __init__(self, model: TransformerConfig,
                 config: Optional[Dict[str, Any]] = None,
                 model_params: Optional[Any] = None, seed: int = 0,
                 devices: Optional[Sequence[Any]] = None, **kw):
        self.cfg = RaggedInferenceEngineConfig(config, **kw)
        dt = jnp.bfloat16 if "bf" in str(self.cfg.dtype) else jnp.float32
        self.model_config = model.replace(dtype=dt)
        if self.cfg.modules:
            self.model_config = self.model_config.replace(
                v2_modules=tuple(sorted(self.cfg.modules.items())))
        mesh_sizes = {}
        if self.cfg.tp_size > 1:
            mesh_sizes["tensor"] = self.cfg.tp_size
        if self.cfg.ep_size > 1:
            mesh_sizes["expert"] = self.cfg.ep_size
        # `devices` pins this engine to a mesh SLICE — the replica tier
        # (serving/replica.py) builds N engines on disjoint slices of one
        # host's devices.  None keeps the whole-world default.
        self.topology = MeshTopology(mesh_sizes or None, devices=devices)
        set_topology(self.topology)
        self.oracle = PartitionOracle(self.topology, zero_stage=0)
        self.rules = self.oracle

        if model_params is None:
            shapes = jax.eval_shape(partial(tf_model.init_params, self.model_config),
                                    jax.random.PRNGKey(seed))
            shardings = self.rules.tree_shardings(shapes)
            self.params = jax.jit(partial(tf_model.init_params, self.model_config),
                                  out_shardings=shardings)(jax.random.PRNGKey(seed))
        else:
            self.params = jax.device_put(model_params,
                                         self.rules.tree_shardings(model_params))

        mc = self.model_config
        max_blocks_per_seq = -(-self.cfg.max_context // self.cfg.block_size)
        self.state_manager = DSStateManager(
            max_seqs=self.cfg.max_tracked_sequences,
            num_blocks=self.cfg.num_blocks,
            block_size=self.cfg.block_size,
            max_blocks_per_seq=max_blocks_per_seq,
            min_blocks_bucket=self.cfg.min_context_blocks,
            window=mc.layer_window, window_blocks=self.cfg.window_blocks)
        self.scheduler = SplitFuseScheduler(self.state_manager,
                                            token_budget=self.cfg.max_ragged_batch_size)
        # software-span tracer (telemetry/tracing.py) — the serving layer
        # injects both so ragged dispatches appear in the request trace
        # under the serve loop's trace id instead of one-off orphan ids
        self._state_alloc = None    # what v2.state_alloc will say, once
        self.tracer = None
        self.trace_id = ""
        self._step_span = None      # the open v2.ragged_step, if any
        # (program, buckets, static arguments) this engine has dispatched:
        # the first dispatch of a key is the one that compiles
        self._dispatched: set = set()
        # fault injection (resilience/chaos.py ChaosInjector): attached by
        # attach_chaos; None keeps step() at one attribute check per call
        self.chaos = None

        # Everything this engine creates goes to ITS mesh slice, never to
        # the process default device: N replicas share one host, and an
        # unplaced array would land on devices[0] for all of them.
        replicated = NamedSharding(self.topology.mesh, PartitionSpec())
        self._put = partial(jax.device_put, device=replicated)
        zeros = partial(jnp.zeros, device=replicated)
        # step()'s default key, on the engine's devices once (_next_key)
        self._step_key = self._put(jax.random.PRNGKey(seed ^ 0x57E9))
        # what the last sampled step sampled, by slot, where it left it:
        # the next one's rows of IN_FLIGHT tokens read it there (zeros
        # before any: the SAME program whether or not a row reads it)
        self._prev = zeros((self.cfg.max_tracked_sequences + 1,), jnp.int32)
        self._flight: Optional[StepInFlight] = None    # launched, unfetched

        pages = self.cfg.num_blocks * self.cfg.block_size
        # [L, nkv, P, d]: kv-head-major so the paged-attention kernel's page
        # blocks have (rows, head_dim) as their minor dims (lane-aligned).
        # (every layer's, but for a one-mixer-a-layer model: its pools
        # hold its attention layers)
        kv_shape = (mc.attn_layers, mc.kv_heads, pages, mc.dim_per_head)
        latent = pools = None
        if mc.mixed is not None:
            # the full layers' rows in the pool, the window layers' in a
            # second one (kept where a mixer's state is: donated, carried)
            if self.cfg.kv_dtype == "int8":
                raise ValueError("memory_config.kv_dtype='int8': a mixed-"
                                 "attention model's rows are kept in the "
                                 "compute dtype")
            t0 = time.monotonic()
            self.cache_k, self.cache_v, pools = new_window_pools(
                mc, pages, self.cfg.window_blocks * self.cfg.block_size,
                zeros, dt)
        elif mc.mla is not None:
            # latent rows and index keys in the pages, the window layers'
            # rings in the slots (made below, where a mixer's state is)
            from deepspeed_tpu.inference.v2 import latent

            if self.cfg.kv_dtype == "int8":
                raise ValueError("memory_config.kv_dtype='int8': a latent "
                                 "model's rows are kept in the compute dtype")
            t0 = time.monotonic()
            self.cache_k, self.cache_v, rings = latent.new_cache(
                mc, pages, self.cfg.max_tracked_sequences,
                self.cfg.max_ragged_batch_size, zeros, dt)
        elif self.cfg.kv_dtype == "int8":
            # quantized cache: int8 payload + one fp32 scale per (head,
            # row) — decode reads half the KV bytes (bandwidth-bound)
            sc_shape = kv_shape[:-1]
            self.cache_k = {"q": zeros(kv_shape, jnp.int8),
                            "s": zeros(sc_shape, jnp.float32)}
            self.cache_v = {"q": zeros(kv_shape, jnp.int8),
                            "s": zeros(sc_shape, jnp.float32)}
        else:
            kv_dt = (jnp.bfloat16 if self.cfg.kv_dtype in ("bf16", "bfloat16")
                     else dt)
            self.cache_k = zeros(kv_shape, dtype=kv_dt)
            self.cache_v = zeros(kv_shape, dtype=kv_dt)

        # a model with an SSM mixer keeps a second kind of per-sequence
        # state beside the pages: one slot a tracked sequence (and the
        # padding rows' garbage slot), indexed by SequenceDescriptor.slot.
        # Never cleared: a run that starts at position 0 starts from zeros
        # inside the step
        self.state = None
        self.state_kind = None      # what the refusals call it
        self.ssm_impl = None
        self._slot_bytes = 0        # float32 recurrent state of one slot
        donate: Dict[str, Any] = {"donate_argnums": (1, 2)}
        if pools is not None:
            self.state = jax.block_until_ready(pools)
            self.state_kind = _WINDOW_PAGES
            self._state_alloc = {
                "ts": t0 * 1e6, "dur": (time.monotonic() - t0) * 1e6,
                "full_pool_bytes": int(self.cache_k.nbytes)
                + int(self.cache_v.nbytes),
                "window_pool_bytes": self.state_bytes,
                "window_layers": mc.window_layers,
                **mixed_alloc_counts(mc, self.cfg, self.cache_k,
                                     self.cache_v, pools)}
            donate["donate_argnames"] = ("state",)
        if latent is not None and rings is not None:
            self.state = jax.block_until_ready(rings)
            self.state_kind = _WINDOW_ROWS
            self._state_alloc = {
                "ts": t0 * 1e6, "dur": (time.monotonic() - t0) * 1e6,
                "window_bytes": int(rings["win"].nbytes),
                "ring_rows": int(rings["win"].shape[2]),
                "slots": self.cfg.max_tracked_sequences + 1}
            donate["donate_argnames"] = ("state",)
        if mc.ssm is not None:
            t0 = time.monotonic()
            self.state = jax.block_until_ready(new_ssm_state(
                mc, self.cfg.max_tracked_sequences, zeros))
            self.ssm_impl = ssm_impl_name(mc)
            self.state_kind = _MIXER_STATE
            self._slot_bytes = (int(self.state["ssm"].nbytes)
                                // self.state["ssm"].shape[1])
            self._state_alloc = {
                "ts": t0 * 1e6, "dur": (time.monotonic() - t0) * 1e6,
                "ssm_bytes": int(self.state["ssm"].nbytes),
                "conv_bytes": int(self.state["conv"].nbytes),
                "slots": self.cfg.max_tracked_sequences + 1,
                "ssm_impl": self.ssm_impl}
            if mc.hybrid is not None:
                self._state_alloc.update(hybrid_alloc_counts(
                    mc, self.cfg, self.state, self.cache_k, self.ssm_impl))
            # by name wherever the engine calls; by place too for the
            # ragged step, whose audit arguments are positional
            donate["donate_argnames"] = ("state",)

        # every jitted step carries a function name of its own, so the
        # profiler's ``XLA Modules`` line reads ``jit_ragged_step(...)``
        # and not ``jit__unknown(...)`` (a partial has no ``__name__``)
        self._step = jax.jit(
            _named("ragged_step", ragged_step, cfg=mc,
                   block_size=self.cfg.block_size),
            **dict(donate, donate_argnums=(1, 2) if self.state is None
                   else (1, 2, 4)))
        # sampled variant: mixed prefill/decode steps fetch [max_seqs] int32
        # tokens instead of full [max_seqs, V] logits (ref Weak: v2 prefill
        # loop host-bound — sampling now happens on device for BOTH phases)
        self._step_sampled = jax.jit(
            _named("ragged_step_sampled", ragged_step_sampled, cfg=mc,
                   block_size=self.cfg.block_size),
            static_argnames=("greedy", "top_k"), **donate)
        self._decode_loop = jax.jit(
            _named("ragged_decode_loop", ragged_decode_loop, cfg=mc,
                   block_size=self.cfg.block_size),
            static_argnames=("n_steps", "greedy", "top_k"), **donate)
        # speculative-decoding verify-k: same argument tuple as _step, but
        # the greedy argmax comes back for EVERY token row ([T] int32), so
        # one ragged dispatch scores a whole batch of draft proposals
        self._verify = jax.jit(
            _named("ragged_verify", ragged_verify, cfg=mc,
                   block_size=self.cfg.block_size),
            donate_argnums=(1, 2))
        # disaggregated-serving KV import: scatter handed-off page rows
        # into the donated caches in place (rows padded to a pow2 bucket
        # of block rows; padding points at the reserved garbage block 0)
        self._kv_write = jax.jit(_named("kv_write", _kv_scatter),
                                 donate_argnums=(0, 1))
        # self-drafting: the greedy step's program and what it has
        # verified and accepted so far (the serving metrics' counters)
        self.self_draft = self.cfg.self_draft
        self.drafts_verified = self.drafts_accepted = 0
        if self.self_draft:
            if not mc.mtp_layers or self.state is not None:
                raise ValueError(
                    "self_draft: the model needs a multi-token-prediction "
                    "module (mla.mtp_layers) and no per-sequence state a "
                    "refused draft row would have advanced; "
                    + (self.state_kind or "this model has no module"))
            self.state_manager.drafting = True
            # what the last drafting step left by slot (its ``out``), as
            # ``_prev`` is the sampled step's; the program returns it
            # under the sharding these zeros have, so that the first
            # step's operand and every later one's are one signature
            self._prev_draft = zeros(
                (4, self.cfg.max_tracked_sequences + 1), jnp.int32)
            self._draft = jax.jit(
                _named("ragged_draft_step", ragged_draft_step, cfg=mc,
                       block_size=self.cfg.block_size),
                donate_argnums=(1, 2),
                out_shardings=(replicated, None, None))
            # a greedy step's two halves and the place kept between them
            # are the drafting step's: bound here, once, so that an
            # engine that does not draft runs its own with no test for it
            self.launch = self._launch_drafting
            self.fetch = self._fetch_drafting
            self.extend = self._extend_drafting
        self.attention_impl = (
            attention_impl_name(mc, self.cfg.block_size) if latent is None
            else "latent_" + latent.indexer_impl_name(mc))
        # rows of a step one program of the query-blocked paged kernel
        # serves; 0 where the step runs another kernel (the XLA gather
        # path, the int8-KV row kernel)
        self._query_block = (
            QUERY_BLOCK if self.attention_impl == "paged_pallas"
            and self.cfg.kv_dtype != "int8" else 0)
        log_dist(f"InferenceEngineV2: budget={self.cfg.max_ragged_batch_size} "
                 f"blocks={self.cfg.num_blocks}×{self.cfg.block_size} "
                 f"max_seqs={self.cfg.max_tracked_sequences} tp={self.cfg.tp_size} "
                 f"attention={self.attention_impl}"
                 + (f" ssm={self.ssm_impl}" if self.ssm_impl else "")
                 + (f" state={self.state_bytes / 2**20:.0f}MiB"
                    if self.state is not None else ""))

    # -- recurrent state (a model with an SSM mixer) -------------------
    @property
    def tracer(self):
        return self._tracer

    @tracer.setter
    def tracer(self, tr) -> None:
        """The serving layer hands its tracer over after construction:
        the first enabled one is told of the state's allocation (span
        ``v2.state_alloc``, at the time it happened)."""
        self._tracer = tr
        if tr is not None and tr.enabled and self._state_alloc is not None:
            alloc, self._state_alloc = self._state_alloc, None
            tr.complete("v2.state_alloc", alloc.pop("ts"), alloc.pop("dur"),
                        **alloc)

    @property
    def state_bytes(self) -> int:
        """Device bytes of the recurrent state slots (0 without a mixer):
        what the engine holds beside the weights and the KV pool."""
        if self.state is None:
            return 0
        return sum(int(a.nbytes) for a in jax.tree.leaves(self.state))

    def _refuse_recurrent(self, what: str) -> None:
        if self.state is not None:
            raise RecurrentStateUnsupported(
                f"{what} needs state snapshots: {self.state_kind}")

    def _refuse_latent_handoff(self) -> None:
        if self.model_config.mla is not None:
            raise NotImplementedError(
                "KV hand-off of a latent-attention model: its pages hold "
                "one latent row and one index key a token and layer, "
                "[layers, rows, width], and the hand-off's gather, "
                "scatter and geometry are written for [layers, heads, "
                "rows, head_dim] pages")

    def _refuse_external_draft(self, what: str) -> None:
        if self.self_draft:
            raise ValueError(
                f"{what}: this engine drafts for itself (self_draft), and "
                "rows a caller verifies or takes back would leave its "
                "module's cache rows behind the trunk's")

    def _carried(self, out):
        """Rebind what a step carries (the KV pools, and the recurrent
        state after them where there is one); the rest of ``out``."""
        if self.state is not None:
            *out, self.state = out
        *out, self.cache_k, self.cache_v = out
        return out[0] if len(out) == 1 else out

    def _state_kw(self) -> Dict[str, Any]:
        return {} if self.state is None else {"state": self.state}

    # ------------------------------------------------------------------
    def _ragged_step(self, batch_uids: Sequence[int],
                     batch_tokens: Sequence[Sequence[int]],
                     sample: Optional[Dict[str, Any]] = None,
                     programs: int = 1):
        """Admit prompts and run ONE ragged step; returns (rb, result) where
        result is the full logits array (sample=None) or on-device-sampled
        tokens [max_seqs] (sample={'key','temperature'} with optional
        'top_k'/'top_p' — see check_sampling_params for their contract).
        ``programs``: this step's program and what the caller dispatched
        for it beforehand (a key split), for the ``v2.dispatch`` span."""
        # Validate the whole batch before touching any state, so a bad entry
        # cannot leave earlier prompts half-admitted.
        if len(batch_uids) != len(batch_tokens):
            raise ValueError(f"{len(batch_uids)} uids vs {len(batch_tokens)} "
                             "token lists")
        seen = set()
        for uid, toks in zip(batch_uids, batch_tokens):
            if uid in self.state_manager or uid in seen:
                raise ValueError(f"uid {uid} already active")
            if not len(toks):
                raise ValueError(f"uid {uid}: empty prompt")
            seen.add(uid)
        for uid, toks in zip(batch_uids, batch_tokens):
            self.admit(uid, toks)
        # host spans of the step (children of v2.ragged_step under the
        # serve loop); ``tr`` is None unless spans are being recorded
        tr = self.tracer
        if tr is not None and not tr.enabled:
            tr = None
        parent = self._step_span
        sp = (tr.span("v2.schedule", self.trace_id, parent)
              if tr is not None else None)
        schedule = self.scheduler.next_schedule(sample is _DRAFT)
        if not schedule:
            if sp is not None:
                sp.end(seqs=0, tokens=0)
            return None, None
        try:
            rb = build_ragged_batch(schedule, self.state_manager,
                                    self.scheduler.token_budget)
        except KVCacheExhausted:
            if sp is not None:
                sp.end(kv_exhausted=True)
            # Nothing ran: no num_cached advanced, no KV written.  But
            # next_schedule already promoted prompts whose FINAL chunk was
            # scheduled into the decode set — roll mid-prefill ones back to
            # the head of the prefill queue so they keep chunked prefill
            # (a wrongly-"decoding" prompt would creep 1 token/step).
            # Pages allocated for earlier schedule entries stay attached
            # to their sequences (used next step or freed at flush).
            # Reversed: each demote lands at the queue head, so walking
            # the schedule backwards keeps the original relative order.
            for seq, _n in reversed(schedule):
                if seq.uncached > 1:
                    self.scheduler.demote(seq.uid)
            raise
        if sp is not None:
            # build_ragged_batch advanced num_cached past the new tokens
            items = [(seq.num_cached - n, n) for seq, n in schedule]
            counts = step_counts(items, self.model_config.sliding_window,
                                 self._query_block)
            # the pages the new rows land in, a sequence's counted apart:
            # with ``tokens``, what the append moves (whole pages)
            counts["append_pages"] = append_pages(items, self.cfg.block_size)
            if self.model_config.mixed is not None:
                mgr = self.state_manager
                counts.update(window_step_counts(
                    items, self.model_config, mgr.pages_held(),
                    mgr.pages_freed))
            elif self.model_config.ssm is not None:
                counts.update(ssm_step_counts(
                    items, self._slot_bytes, self.state_manager.n_active))
                if self.model_config.hybrid is not None:
                    counts.update(hybrid_step_counts(
                        items, self.model_config, self.state_manager))
            elif self.model_config.mla is not None:
                from deepspeed_tpu.inference.v2.latent import \
                    latent_step_counts

                counts.update(latent_step_counts(
                    items, self.model_config, rb.index.rows,
                    rb.index.blocks * self.cfg.block_size))
                if sample is _DRAFT:
                    counts.update(
                        verify_runs=len(rb.verified),
                        draft_rows=len(rb.verified), mtp_rows=rb.n_tokens,
                        ahead_runs=int(np.count_nonzero(
                            rb.index.draft_arrays()[1] >= 2)))
            sp.end(**counts)
        if sample is None:
            program, variant, kw = self._step, (), {}
        elif sample is _DRAFT:
            program, variant, kw = self._draft, (), {
                "prev": self._prev_draft}
        else:
            greedy = sample["temperature"] <= 0
            top_k, top_p = sample.get("top_k", 0), sample.get("top_p")
            program, variant = self._step_sampled, (greedy, top_k,
                                                    top_p is None)
            # host scalars: the jitted call ships them itself, and not at
            # all where the program does not read them (a greedy step)
            kw = {"prev": self._prev, "key": sample["key"], "greedy": greedy,
                  "top_k": top_k, "top_p": top_p, "temperature": np.float32(
                      max(sample["temperature"], 1e-6))}
        if self.self_draft and sample is not _DRAFT:
            # a step without the module leaves a hole in its cache rows
            for seq, _ in schedule:
                seq.draft, seq.draftable = None, False
        index, sp, shape = self._ship(rb, program, variant, programs)
        try:
            out = self._carried(program(
                self.params, self.cache_k, self.cache_v, index, **kw,
                **self._state_kw()))
        finally:
            if sp is not None:
                sp.end(**shape)
        out.copy_to_host_async()    # the fetch waits for a copy under way
        if program is self._step_sampled:
            self._prev = out
        return rb, out

    def _ship(self, rb: RaggedBatch, program, variant: tuple = (),
              programs: int = 1):
        """The launch path every ragged step shares (plain, sampled,
        verify), up to the jitted call: ONE host→device transfer, the
        step's packed index buffer (span ``v2.h2d``).  Returns the device
        index, the open ``v2.dispatch`` span (None unless spans are
        recorded) and that span's arguments (``programs``: what the caller
        dispatched for this step, a key split's two programs included;
        ``ahead``: 1 where the step before it has not been fetched).
        The caller makes the ONE program call itself, in its own frame,
        ends the span and asks for the result's copy back at once: a
        frame more between the serve loop and the jitted call costs every
        program's first call 0.1 s (PERF.md §6, PR 32)."""
        tr = self.tracer
        if tr is not None and not tr.enabled:
            tr = None
        parent = self._step_span
        host = rb.index
        sp = (tr.span("v2.h2d", self.trace_id, parent)
              if tr is not None else None)
        index = self._put(host)         # a pytree of one leaf
        if sp is not None:
            sp.end(arrays=1, bytes=host.buf.nbytes)
        key = rb.key = (program.__name__, host.rows, host.blocks) + variant
        new_shape = rb.compiled = key not in self._dispatched
        if new_shape:
            self._dispatched.add(key)
        sp = (tr.span("v2.dispatch", self.trace_id, parent)
              if tr is not None else None)
        return index, sp, {"t_bucket": host.rows, "nb_bucket": host.blocks,
                           "new_shape": new_shape, "programs": programs,
                           "ahead": int(self._flight is not None)}

    def audit_step_args(self, phase: str = "decode"):
        """``(jitted ragged step, example args)`` for the static graph
        auditor (``analysis/auditor.py``): the decode-shaped (16-token
        bucket), prefill-shaped (full token budget), or speculative
        verify-k (full budget, per-row argmax) step, buildable
        without admitting any sequence.  Zero-filled index arrays are
        fine — the auditor lowers and compiles, never executes, so the
        donated KV caches are not consumed."""
        if phase not in ("decode", "prefill", "verify"):
            raise ValueError(f"audit_step_args: unknown phase {phase!r} "
                             "(decode|prefill|verify)")
        if phase == "verify":
            self._refuse_recurrent("the speculative verify step")
        sm = self.state_manager
        t = (min(16, self.scheduler.token_budget) if phase == "decode"
             else self.scheduler.token_budget)
        sizes = (t, sm.max_seqs + 1, sm.max_blocks_per_seq, False,
                 bool(sm.window))
        index = PackedIndex(jnp.zeros((PackedIndex.size(*sizes),), jnp.int32),
                            *sizes)
        args = (self.params, self.cache_k, self.cache_v, index)
        if self.state is not None:
            args += (self.state,)
        return (self._verify if phase == "verify" else self._step), args

    def audit_arg_categories(self):
        """Memory-class manifest for the ``audit_step_args`` tuple (one
        ``analysis.MEMORY_CLASSES`` entry per top-level argument): the
        weights, the two paged KV pools (state, not step-local —
        classed ``other``), and the packed ragged index buffer."""
        return ("params", "other", "other", "other") + (
            ("other",) if self.state is not None else ())

    def put(self, batch_uids: Sequence[int],
            batch_tokens: Sequence[Sequence[int]]) -> Dict[int, np.ndarray]:
        """Admit prompts and run ONE ragged step (ref engine_v2.py:30 put).

        Returns {uid: next-token logits} for sequences whose full prompt (or
        pending decode token) was processed this step; uids mid-prefill
        return nothing yet — call put([], []) again to continue.
        """
        self._refuse_in_flight("put")
        rb, logits = self._ragged_step(batch_uids, batch_tokens)
        if rb is None:
            return {}
        logits_np = np.asarray(logits)
        return {uid: logits_np[slot] for slot, uid in rb.uids_by_slot.items()}

    def admit(self, uid: int, tokens: Sequence[int], priority: int = 0,
              front: bool = False, cached_blocks: Sequence[int] = (),
              num_cached: int = 0) -> None:
        """Open a sequence and schedule it WITHOUT running a step.

        The serving layer's admission controller decides *when* to call
        this; ``step()`` decides when work runs.  ``priority`` orders the
        SplitFuse queues (higher first); ``front=True`` requeues ahead of
        every waiting prompt (preempted-request requeue).

        ``cached_blocks``/``num_cached`` seed the sequence with adopted
        prefix-cache pages whose KV already holds the first ``num_cached``
        tokens (pre-acquired by the caller; ownership transfers to the
        sequence — see ``DSStateManager.open``).  Prefill then starts at
        ``num_cached`` instead of 0: the adopted tokens never re-run.
        """
        if uid in self.state_manager:
            raise ValueError(f"uid {uid} already active")
        if not len(tokens):
            raise ValueError(f"uid {uid}: empty prompt")
        if num_cached or cached_blocks:
            self._refuse_recurrent("adopting cached prefix pages "
                                   f"(num_cached={num_cached})")
        seq = self.state_manager.open(uid, [int(x) for x in tokens],
                                      cached_blocks=cached_blocks,
                                      num_cached=num_cached)
        # adopted pages may lack the module's rows (their donor's steps
        # may have run without it)
        seq.draftable = not num_cached
        self.scheduler.add(uid, priority=priority, front=front)

    def step(self, temperature: float = 0.0, key: Optional[Any] = None,
             top_k: int = 0, top_p: float = 1.0,
             return_logits: bool = False) -> Dict[int, Any]:
        """Run ONE ragged step over currently-scheduled work.

        The reusable core of ``generate()`` (factored out for the serving
        loop): returns ``{uid: sampled_token}`` for every sequence whose
        pending work completed this step (``{uid: logits_row}`` with
        ``return_logits=True`` — the serving layer's heterogeneous-
        sampling path), or ``{}`` when nothing is scheduled.  The caller
        owns the extend-or-flush decision per sampled uid.  Raises
        ``KVCacheExhausted`` (with scheduler state rolled back, nothing
        run) when the step needs more KV pages than remain — preempt a
        victim and retry.

        **A self-drafting engine** (``self_draft`` in its configuration):
        a greedy step is :meth:`step_bursts`, which delivers one token a
        sequence or two.  The value by uid here is the LAST of them, the
        one the caller's ``extend`` appends as after any step, so the
        plain calling sequence (``step``, ``extend``, ``flush``) serves
        and runs the programs a server will; a burst's first token is
        then in the sequence's ``tokens`` and NOT in what this call
        returns: a caller that delivers tokens asks ``step_bursts``.  A
        step that runs WITHOUT the module (``put``, ``return_logits``, a
        temperature) leaves its sequences' module rows with a hole: they
        are served on, one row a step, and draft no more
        (``SequenceDescriptor.draftable``).
        """
        if self.self_draft and temperature <= 0 and not return_logits:
            return {uid: burst[-1]
                    for uid, burst in self.step_bursts().items()}
        with self.stepping():
            if return_logits:
                return self._step_logits()
            flight = self.launch(temperature, key, top_k, top_p)
            return {} if flight is None else self.fetch(flight)

    def launch(self, temperature: float = 0.0, key: Optional[Any] = None,
               top_k: int = 0, top_p: float = 1.0
               ) -> Optional[StepInFlight]:
        """The first half of a sampled :meth:`step`: schedule, build, ONE
        transfer, ONE program, and no wait for the chip.  ``None`` when
        nothing is scheduled; ``KVCacheExhausted`` as ``step`` raises it.

        :meth:`fetch` is the other half.  A caller may launch the NEXT
        step first, once, so that it waits in the device's queue when
        this one ends: of the sequences this step samples (``.uids``),
        those that go on are extended with ``ragged.IN_FLIGHT`` in place
        of the token nobody has yet; the next step's row then reads the
        token where this step's program left it, and ``fetch`` writes it
        over the placeholder.  A sequence that turns out to have ended
        (an ``eos_token_id`` the host could not see) has ridden one dead
        row: flush it as any other, the device runs programs in order.
        Everything else the engine does (``put``, ``step_bursts``,
        ``verify_step``, ``preempt``, hand-off, ``rewind``) wants the
        launched step fetched first, and says so."""
        top_k, top_p = check_sampling_params(top_k, top_p,
                                             self.model_config.vocab_size)
        split = 0
        if key is None:
            self._step_key, key, split = _next_key(self._step_key,
                                                   temperature)
        rb, toks = self._ragged_step(
            [], [], sample={"key": key, "temperature": temperature,
                            "top_k": top_k, "top_p": top_p},
            programs=1 + split)
        if rb is None:
            return None
        mgr = self.state_manager
        self._flight = StepInFlight(
            toks, rb.key, rb.compiled,
            {uid: (slot, len(mgr.get(uid).tokens))
             for slot, uid in rb.uids_by_slot.items()})
        return self._flight

    def fetch(self, flight: StepInFlight) -> Dict[int, int]:
        """The second half of a sampled :meth:`step`: ``{uid: token}`` of
        a launched step, after the wait for the device and the copy back
        (span ``v2.fetch``).  Where the caller kept a token's place with
        ``IN_FLIGHT``, the token is written there."""
        toks_np = self._fetch(flight.out)
        if self._flight is flight:
            self._flight = None
        mgr = self.state_manager
        result = {}
        for uid, (slot, at) in flight.places.items():
            tok = result[uid] = int(toks_np[slot])
            if uid in mgr:          # not flushed meanwhile (a dead row's)
                tokens = mgr.get(uid).tokens
                if at < len(tokens) and tokens[at] == IN_FLIGHT:
                    tokens[at] = tok
        return result

    def forget(self) -> None:
        """Give up a launched step unfetched (the caller is flushing
        every sequence: a server's shutdown or crash path)."""
        self._flight = None

    def _refuse_in_flight(self, what: str) -> None:
        if self._flight is not None:
            raise RuntimeError(
                f"{what}: a launched step has not been fetched (its "
                "sequences' tokens, positions and pages are a step ahead "
                "of what the host knows): fetch() it first")

    @contextmanager
    def stepping(self):
        """One ``v2.ragged_step`` span (and one ``engine.step`` injection
        point) over what the caller does inside: a launch and a fetch,
        which need not be the same step's."""
        sp = self._begin_step()
        try:
            yield
        finally:
            self._end_step(sp)

    def step_bursts(self) -> Dict[int, List[int]]:
        """ONE greedy step of a self-drafting engine (``self_draft``: a
        latent model with a multi-token-prediction module, every layer
        full): ``ragged_draft_step``, one program, one transfer and one
        fetch as any step.  A decoding sequence brings its pending token
        and its draft, a prompt its chunk, under the one token budget.
        Returns ``{uid: tokens delivered}``: one, or two where the draft
        equalled the trunk's argmax; the stream is, token for token,
        plain greedy decoding's.  As after any step the caller extends
        the sequence with the LAST token (or flushes it); the engine has
        appended a burst's first token itself, given a refused draft's
        position back (host bookkeeping, ``SequenceDescriptor.settle``)
        and kept the module's next draft."""
        if not self.self_draft:
            raise ValueError("step_bursts: the engine's configuration "
                             "has no self_draft")
        self._refuse_in_flight("step_bursts")
        with self.stepping():
            flight = self.launch()
            return {} if flight is None else self.fetch(flight)

    def _launch_drafting(self, temperature: float = 0.0,
                         key: Optional[Any] = None, top_k: int = 0,
                         top_p: float = 1.0) -> Optional[StepInFlight]:
        """:meth:`launch` of a self-drafting engine: a greedy step is the
        drafting program (``ragged_draft_step``), and it too may be
        launched before the step before it is fetched, once.  The
        sequences that step samples ride this one where the caller kept
        their place (``extend(uid, IN_FLIGHT)``): written for that
        step's draft refused, moved a position on by the device where it
        stood (``DSStateManager.keep_place``)."""
        if temperature > 0:
            self._refuse_in_flight("a sampled launch of a self-drafting "
                                   "engine")
            return InferenceEngineV2.launch(self, temperature, key, top_k,
                                            top_p)
        rb, out = self._ragged_step([], [], sample=_DRAFT)
        if rb is None:
            return None
        self._prev_draft = out
        mgr = self.state_manager
        self._flight = StepInFlight(
            out, rb.key, rb.compiled,
            {uid: (slot, len(mgr.get(uid).tokens))
             for slot, uid in rb.uids_by_slot.items()},
            frozenset(seq.uid for seq in rb.verified))
        return self._flight

    def _fetch_drafting(self, flight: StepInFlight) -> Dict[int, Any]:
        """:meth:`fetch` of a self-drafting engine: of a drafting step,
        ``{uid: tokens delivered}`` (:meth:`step_bursts`) after its ONE
        fetch (span ``v2.fetch``, with the drafts it verified and
        accepted) and the host's bookkeeping: every sampled sequence
        takes in its burst (over the place kept for it, if one was),
        settles the position its verify run ran past the tokens known,
        and keeps the module's next draft unless it rides the step
        launched meanwhile, whose draft row read it on the device."""
        if flight.key[0] != self._draft.__name__:
            return InferenceEngineV2.fetch(self, flight)
        sp = self._step_span
        fetch = (self.tracer.span("v2.fetch", self.trace_id, sp)
                 if sp is not None else None)
        first, second, accepted, draft = np.asarray(flight.out).tolist()
        ahead = self._flight
        if ahead is flight:
            ahead = self._flight = None
        mgr = self.state_manager
        room = mgr.max_blocks_per_seq * mgr.block_size
        n_accepted = 0
        result: Dict[int, List[int]] = {}
        for uid, (slot, _) in flight.places.items():
            ok = accepted[slot]             # never set but on a verify run
            n_accepted += ok
            burst = result[uid] = ([first[slot], second[slot]] if ok
                                   else [first[slot]])
            if uid not in mgr:      # flushed meanwhile (a dead row's)
                continue
            seq = mgr.get(uid)
            seq.settle(burst, uid in flight.verified)
            if ahead is None or uid not in ahead.places:
                # a draft sits one position past the pending token's,
                # the burst's last, which is the next to be run
                seq.draft = (draft[slot] if seq.draftable
                             and seq.num_cached + 1 < room else None)
        self.drafts_verified += len(flight.verified)
        self.drafts_accepted += n_accepted
        if fetch is not None:
            fetch.end(drafts=len(flight.verified), accepted=n_accepted)
        return result

    def _extend_drafting(self, uid: int, token: int) -> None:
        """:meth:`extend` of a self-drafting engine: ``IN_FLIGHT`` for a
        sequence the unfetched step samples keeps more than the token's
        place (``DSStateManager.keep_place``)."""
        flight = self._flight
        if token == IN_FLIGHT and flight is not None \
                and uid in flight.places:
            self.state_manager.keep_place(self.state_manager.get(uid),
                                          uid in flight.verified)
        else:
            self.state_manager.extend(uid, int(token))

    def _begin_step(self):
        """The ``engine.step`` injection point and the step's span
        (None unless spans are recorded)."""
        if self.chaos is not None:
            # "engine.step" injection point: specs pinned here (see
            # resilience/chaos.py FaultSpec.point) delay or kill the
            # ragged dispatch itself rather than the serve loop around it
            for f in self.chaos.fire("engine.step"):
                if f.kind == "slow_replica":
                    time.sleep(float(f.params.get("delay_ms", 50.0)) / 1e3)
                elif f.kind == "replica_crash":
                    from deepspeed_tpu.resilience.chaos import ChaosError
                    raise ChaosError("injected replica_crash (engine.step)")
        tr = self.tracer
        sp = None
        if tr is not None and tr.enabled:
            if not self.trace_id:   # standalone use: one stable id
                self.trace_id = tr.new_trace_id()
            sp = tr.span("v2.ragged_step", self.trace_id)
        self._step_span = sp
        return sp

    def _end_step(self, sp) -> None:
        if sp is not None:
            self._step_span = None
            sp.end()

    def _step_logits(self) -> Dict[int, Any]:
        self._refuse_in_flight("step(return_logits=True)")
        rb, logits = self._ragged_step([], [])
        if rb is None:
            return {}
        logits_np = self._fetch(logits)
        return {uid: logits_np[slot]
                for slot, uid in rb.uids_by_slot.items()}

    def _fetch(self, out) -> np.ndarray:
        """The step's result on the host: the wait for the device and for
        the copy back asked for at the dispatch (span ``v2.fetch``)."""
        sp = self._step_span
        if sp is None:
            return np.asarray(out)
        with self.tracer.span("v2.fetch", self.trace_id, sp):
            return np.asarray(out)

    def extend(self, uid: int, token: int) -> None:
        """Append a sampled token so the next step decodes it."""
        self.state_manager.extend(uid, int(token))

    def flush(self, uid: int) -> None:
        """Free a finished sequence's slot and KV pages (ref flush)."""
        self.scheduler.retire(uid)
        self.state_manager.flush(uid)

    def preempt(self, uid: int) -> List[int]:
        """Evict a live sequence, returning every token it knows
        (prompt + generated-so-far, including any still-uncached sampled
        token).  Recompute-style preemption: the caller requeues the
        returned list as a fresh prompt; re-prefill rebuilds the KV and
        greedy decoding continues bit-identically.  Slot and pages are
        freed immediately."""
        self._refuse_in_flight("preempt")
        seq = self.state_manager.get(uid)
        tokens = list(seq.tokens)
        self.flush(uid)
        return tokens

    # -- disaggregated serving: KV-block handoff -----------------------
    def kv_geometry(self) -> tuple:
        """Layout fingerprint a handoff payload must match to be
        importable: two engines with the same geometry (and the shared
        same-seed weight contract) hold interchangeable KV pages."""
        mc = self.model_config
        geom = (mc.num_layers, mc.kv_heads, self.cfg.block_size,
                mc.dim_per_head, str(self.cfg.kv_dtype),
                str(self.model_config.dtype))
        if self.state is not None:
            # pages alone do not make a sequence here: the fingerprint
            # says so, with the bytes of one sequence's recurrent slot
            geom += (("recurrent_state_bytes_per_seq",
                      self.state_bytes // (self.cfg.max_tracked_sequences
                                           + 1)),)
        return geom

    def export_kv_chain(self, uid: int) -> Optional[Dict[str, Any]]:
        """Read the FULL KV pages of a live sequence's written prefix —
        the prefill half of a prefill→decode handoff.

        Returns a host payload {tokens, k, v, geom, nbytes, export_ms}
        covering ``num_cached // block_size`` full blocks (a partial
        last block is never transferable: adopted pages are read-only
        and the adopter would have to append into it), or None when not
        even one full block is written.  Must run on the thread that
        owns the engine — the gather reads the live donated caches.
        """
        import time as _time

        self._refuse_in_flight("export_kv_chain")
        self._refuse_recurrent("exporting a sequence's KV pages for "
                               "hand-off")
        self._refuse_latent_handoff()
        t0 = _time.perf_counter()
        seq = self.state_manager.get(uid)
        bs = self.cfg.block_size
        n_full = min(seq.num_cached // bs, len(seq.blocks))
        if n_full < 1:
            return None
        rows = np.concatenate(
            [np.arange(b * bs, (b + 1) * bs, dtype=np.int32)
             for b in seq.blocks[:n_full]])
        rows = self._put(rows)
        k = _kv_gather(self.cache_k, rows)
        v = _kv_gather(self.cache_v, rows)
        return {"tokens": list(seq.tokens[:n_full * bs]), "k": k, "v": v,
                "geom": self.kv_geometry(),
                "nbytes": _payload_nbytes(k) + _payload_nbytes(v),
                "export_ms": (_time.perf_counter() - t0) * 1e3}

    def import_kv_chain(self, payload: Dict[str, Any],
                        skip_blocks: int = 0) -> tuple:
        """Write a handoff payload's pages into THIS engine's cache — the
        decode half of the handoff.  ``skip_blocks`` leading blocks are
        already covered locally (a prefix-cache hit on the same chain:
        the zero-copy ref acquire); only the tail is allocated and
        written.  Returns ``(blocks, n_tokens, bytes_moved)`` where
        ``blocks`` are freshly-allocated pages (refcount 1, ownership
        passes to the caller) holding tokens ``[skip·bs, n_tokens)``.
        Raises ``ValueError`` on a geometry mismatch (caller falls back
        to re-running prefill) and ``KVCacheExhausted`` when the pool
        cannot host the tail.  Engine-owning thread only.
        """
        self._refuse_in_flight("import_kv_chain")
        self._refuse_recurrent("importing handed-off KV pages")
        self._refuse_latent_handoff()
        if tuple(payload["geom"]) != self.kv_geometry():
            raise ValueError(
                f"handoff payload geometry {payload['geom']} does not "
                f"match this engine's {self.kv_geometry()}; the decode "
                "tier must share the prefill tier's model + KV layout")
        bs = self.cfg.block_size
        n_total = len(payload["tokens"]) // bs
        n_new = n_total - int(skip_blocks)
        if n_new <= 0:
            return [], n_total * bs, 0
        blocks = self.state_manager.allocator.allocate(n_new)
        # pow2-bucket the scatter width so a serve lifetime compiles a
        # handful of import shapes; padding rows land in garbage block 0
        nb_bucket = 1
        while nb_bucket < n_new:
            nb_bucket *= 2
        rows = np.zeros((nb_bucket * bs,), np.int32)
        for i, b in enumerate(blocks):
            rows[i * bs:(i + 1) * bs] = np.arange(b * bs, (b + 1) * bs)
        lo, hi = skip_blocks * bs, (skip_blocks + n_new) * bs

        def _cut(part):
            if isinstance(part, dict):
                return {key: _pad(a[:, :, lo:hi]) for key, a in part.items()}
            return _pad(part[:, :, lo:hi])

        def _pad(a):
            width = nb_bucket * bs
            if a.shape[2] == width:
                return a
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, width - a.shape[2])
            return np.pad(a, pad)

        try:
            k, v = _cut(payload["k"]), _cut(payload["v"])
            self.cache_k, self.cache_v = self._kv_write(
                self.cache_k, self.cache_v, self._put(rows), k, v)
        except BaseException:
            # a failed scatter must not leak the freshly-allocated pages
            # (the donated caches are only rebound on success)
            self.state_manager.allocator.free(blocks)
            raise
        moved = _payload_nbytes(k) + _payload_nbytes(v)
        return blocks, n_total * bs, moved

    # -- speculative decoding: verify-k + draft rewind -----------------
    def verify_step(self, proposals: Dict[int, Sequence[int]]
                    ) -> Dict[int, List[int]]:
        """One ragged verify-k step (greedy only).

        Each uid must be a live sequence with exactly one pending
        sampled token (``uncached == 1``); its ``proposals`` are the
        draft model's guesses for the next k tokens (k may vary per uid,
        and may be 0 — the degenerate case is a plain greedy step).  The
        pending token plus the proposals run as one prefill-style chunk;
        the per-row argmax accepts the longest agreeing proposal prefix
        and appends the target's own argmax after it (the bonus token),
        so the returned ``{uid: accepted_tokens}`` — always ≥ 1 token —
        is bit-identical to one-at-a-time greedy decoding.

        Sequence state advances by the accepted tokens only; KV rows
        written for rejected proposals are dead weight that the next
        write to those positions overwrites (destinations are derived
        from absolute positions, and attention masks by ``ctx_lens``).
        Raises ``KVCacheExhausted`` with every sequence rolled back.
        """
        self._refuse_in_flight("verify_step")
        self._refuse_recurrent("verify_step (speculative decoding)")
        self._refuse_external_draft("verify_step")
        mgr = self.state_manager
        # validate the WHOLE batch before touching any state: a bad
        # entry must not leave earlier sequences carrying unverified
        # draft tokens (same discipline as _ragged_step admission)
        total = 0
        for uid, props in proposals.items():
            if mgr.get(uid).uncached != 1:
                raise ValueError(
                    f"verify_step: uid {uid} has "
                    f"{mgr.get(uid).uncached} uncached tokens; "
                    "speculative verification needs exactly the one "
                    "pending sampled token")
            total += 1 + len(props)
        if total > self.scheduler.token_budget:
            raise ValueError(
                f"verify_step: {total} tokens exceed the ragged budget "
                f"{self.scheduler.token_budget}; lower spec_k")
        schedule = []
        saved: Dict[int, tuple] = {}
        for uid, props in proposals.items():
            seq = mgr.get(uid)
            saved[uid] = (len(seq.tokens), seq.num_cached)
            seq.tokens.extend(int(t) for t in props)
            schedule.append((seq, 1 + len(props)))
        try:
            rb = build_ragged_batch(schedule, mgr,
                                    self.scheduler.token_budget)
        except KVCacheExhausted:
            for uid, (n_tok, _nc) in saved.items():
                del mgr.get(uid).tokens[n_tok:]
            raise
        index, sp, shape = self._ship(rb, self._verify)
        try:
            nxt = self._carried(self._verify(
                self.params, self.cache_k, self.cache_v, index))
        finally:
            if sp is not None:
                sp.end(**shape)
        nxt = np.asarray(nxt)
        out: Dict[int, List[int]] = {}
        cursor = 0
        for seq, n_new in schedule:
            rows = nxt[cursor:cursor + n_new]
            cursor += n_new
            n_tok, nc0 = saved[seq.uid]
            props = seq.tokens[n_tok:]
            m = 0
            while m < len(props) and int(props[m]) == int(rows[m]):
                m += 1
            accepted = [int(t) for t in props[:m]] + [int(rows[m])]
            # rewind: keep the accepted prefix + bonus; positions
            # nc0..nc0+m ran with correct inputs, the rest is garbage
            del seq.tokens[n_tok + m:]
            seq.tokens.append(int(rows[m]))
            seq.num_cached = nc0 + m + 1
            out[seq.uid] = accepted
        return out

    def rewind(self, uid: int, tokens: Sequence[int],
               num_cached: int) -> None:
        """Reset a live sequence's host-side view (draft-model rewind
        after speculative rejection): ``tokens`` becomes the full known
        stream and ``num_cached`` the count of leading positions whose
        KV was computed from correct inputs.  ``num_cached`` may only
        shrink — garbage KV beyond it is overwritten when those
        positions are legitimately re-run.  Allocated pages stay with
        the sequence (capacity, not content)."""
        self._refuse_in_flight("rewind")
        self._refuse_recurrent("rewind")
        self._refuse_external_draft("rewind")
        seq = self.state_manager.get(uid)
        if num_cached > seq.num_cached:
            raise ValueError(
                f"rewind: num_cached {num_cached} > written "
                f"{seq.num_cached} — rewind cannot invent KV")
        seq.tokens = [int(t) for t in tokens]
        seq.num_cached = int(num_cached)
        if seq.uncached > 1:
            # more than one pending token decodes 1/step from the decode
            # set; chunked prefill catches the stream up in one step
            self.scheduler.demote(uid)

    @property
    def free_blocks(self) -> int:
        return self.state_manager.allocator.free_blocks

    @property
    def free_window_blocks(self) -> int:
        """Free pages of the window layers' own pool (0 without one)."""
        wa = self.state_manager.window_allocator
        return wa.free_blocks if wa is not None else 0

    def window_seq_blocks(self, n_tokens: int) -> int:
        """Pages of the window layers' pool a sequence of ``n_tokens``
        holds at most: its context's, until the window and a step's rows
        span fewer (0 for a model without that pool)."""
        mgr = self.state_manager
        if not mgr.window:
            return 0
        bs = self.cfg.block_size
        return min(self.seq_blocks(n_tokens),
                   -(-(mgr.window + self.scheduler.token_budget) // bs) + 1)

    def window_admissible(self, n_tokens: int) -> bool:
        """Whether the window layers' pool has the pages a new sequence
        of ``n_tokens`` can come to hold (always, without that pool):
        admission counts both pools, this one by what a sequence holds at
        its widest, since it gives pages back as it goes."""
        return self.window_seq_blocks(n_tokens) <= self.free_window_blocks

    def seq_blocks(self, n_tokens: int) -> int:
        """KV pages a sequence of ``n_tokens`` tokens occupies — THE page
        accounting rule; admission layers must use it rather than re-derive
        it so engine and admission can never disagree."""
        return -(-int(n_tokens) // self.cfg.block_size)

    @property
    def max_seq_blocks(self) -> int:
        """Hard per-sequence page cap (pool size and block-table width)."""
        return min(self.cfg.num_blocks - 1,
                   self.state_manager.max_blocks_per_seq)

    # ------------------------------------------------------------------
    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 eos_token_id: Optional[int] = None, top_k: int = 0,
                 top_p: float = 1.0) -> List[List[int]]:
        """Continuous-batching generation loop over token prompts.
        ``top_k``/``top_p`` restrict temperature sampling to the top-k
        logits / the top-p nucleus (ref FastGen logits processors);
        0 / 1.0 disable them."""
        self._refuse_in_flight("generate")
        top_k, top_p = check_sampling_params(top_k, top_p,
                                             self.model_config.vocab_size)
        uids = list(range(len(prompts)))
        remaining = {u: max_new_tokens for u in uids}
        outputs: Dict[int, List[int]] = {u: [] for u in uids}
        pending = list(zip(uids, prompts))
        step_key = jax.random.PRNGKey(seed)

        decode_key = jax.random.PRNGKey(seed ^ 0x5EED)
        while pending or any(u in self.state_manager for u in uids):
            # Pure-decode phase: every live sequence is waiting on exactly
            # its one pending sampled token -> run a fused multi-step decode
            # on device (one dispatch + one [chunk, S] int32 fetch instead
            # of a full-logits transfer per token).
            active_uids = [u for u in uids if u in self.state_manager]
            if (not pending and active_uids
                    and not self.state_manager.window
                    and all(self.state_manager.get(u).uncached == 1
                            for u in active_uids)):
                decode_key, sub, _ = _next_key(decode_key, temperature)
                self._fused_decode(active_uids, remaining, outputs,
                                   temperature, sub, eos_token_id,
                                   top_k=top_k, top_p=top_p)
                continue
            admit_uids, admit_toks = [], []
            # Active sequences will still claim pages as they decode: reserve
            # their remaining future blocks so admission never overcommits.
            reserved = reserved_window = 0
            for u in uids:
                if u in self.state_manager:
                    seq = self.state_manager.get(u)
                    final = self.seq_blocks(len(seq.tokens) + remaining[u])
                    reserved += max(0, final - len(seq.blocks))
                    reserved_window += max(0, self.window_seq_blocks(
                        len(seq.tokens) + remaining[u])
                        - (len(seq.window_blocks) - seq.window_freed))
            # Admit while slots and KV pages allow (continuous batching).
            while pending and (self.state_manager.n_active + len(admit_uids)
                               < self.state_manager.max_seqs):
                u, toks = pending[0]
                need = self.seq_blocks(len(toks) + max_new_tokens)
                if need > self.max_seq_blocks:
                    raise RuntimeError(
                        f"prompt uid {u} needs {need} KV blocks but the cache "
                        f"allows {self.max_seq_blocks} per sequence; "
                        "raise num_blocks/max_context or shorten the prompt")
                need_window = self.window_seq_blocks(len(toks)
                                                     + max_new_tokens)
                if need + reserved > self.state_manager.allocator.free_blocks \
                        or (need_window + reserved_window
                            > self.free_window_blocks):
                    break
                pending.pop(0)
                reserved += need
                reserved_window += need_window
                admit_uids.append(u)
                admit_toks.append(toks)
            if pending and not admit_uids and self.state_manager.n_active == 0:
                raise RuntimeError("cannot admit any pending prompt: KV cache "
                                   "too fragmented/small for the workload")
            # mixed prefill/decode step with ON-DEVICE sampling: only
            # [max_seqs] int32 tokens cross to the host, not [seqs, V]
            # logits (the decode-phase discipline applied to prefill too)
            step_key, sub, split = _next_key(step_key, temperature)
            rb, toks = self._ragged_step(
                admit_uids, admit_toks,
                sample={"key": sub, "temperature": temperature,
                        "top_k": top_k, "top_p": top_p},
                programs=1 + split)
            toks_np = np.asarray(toks) if rb is not None else None
            results = ({} if rb is None
                       else {uid: int(toks_np[slot])
                             for slot, uid in rb.uids_by_slot.items()})
            for uid, nxt in results.items():
                outputs[uid].append(nxt)
                remaining[uid] -= 1
                done = remaining[uid] <= 0 or (eos_token_id is not None
                                               and nxt == eos_token_id)
                if done:
                    self.flush(uid)
                else:
                    self.extend(uid, nxt)
        return [outputs[u] for u in uids]

    # ------------------------------------------------------------------
    def _fused_decode(self, uids: List[int], remaining: Dict[int, int],
                      outputs: Dict[int, List[int]], temperature: float,
                      key, eos_token_id: Optional[int], top_k: int = 0,
                      top_p: float = 1.0) -> None:
        """One fused on-device decode chunk for all live sequences
        (ragged_decode_loop): chunk sizes are power-of-two bucketed so a
        generation run compiles at most a handful of loop lengths."""
        mgr = self.state_manager
        chunk = min(min(remaining[u] for u in uids),
                    self.cfg.max_decode_chunk)
        if chunk > 1:  # round UP to a power of two (compile-cache bound).
            # Up, not down: a 31-token budget then costs one 32-step
            # dispatch instead of a 16/8/4/2/1 ladder — each dispatch is a
            # host round-trip, and overshot tokens are just masked off
            # below (their KV writes die with the flushed sequence).
            chunk = 1 << (chunk - 1).bit_length()
        # ...but the overshoot must stay within every sequence's block
        # table: a prompt near max_context has fewer than `chunk` KV slots
        # left, and ensure_capacity raises rather than clamps.
        cap_tokens = mgr.max_blocks_per_seq * mgr.block_size
        headroom = min(cap_tokens - mgr.get(u).num_cached for u in uids)
        chunk = max(1, min(chunk, headroom))
        # ...and within the shared POOL: the round-up would allocate pages
        # past the admission reservation (overshot tokens are masked, but
        # their pages are real) — on a tight cache that's an exhaustion
        # crash mid-decode.  Halve back until the whole chunk's new pages
        # fit; chunk=1 always fits the reservation.
        bs2 = mgr.block_size

        def _pages_needed(c: int) -> int:
            return sum(max(0, -(-(mgr.get(u).num_cached + c) // bs2)
                           - len(mgr.get(u).blocks)) for u in uids)

        while chunk > 1 and _pages_needed(chunk) > mgr.allocator.free_blocks:
            chunk //= 2
        s_rows = mgr.max_seqs
        tokens0 = np.zeros((s_rows,), np.int32)
        ctx0 = np.zeros((s_rows,), np.int32)
        active = np.zeros((s_rows,), bool)
        nb_needed = 1
        for u in uids:
            seq = mgr.get(u)
            mgr.ensure_capacity(seq, seq.num_cached + chunk)
            tokens0[seq.slot] = seq.tokens[-1]
            ctx0[seq.slot] = seq.num_cached
            active[seq.slot] = True
            nb_needed = max(nb_needed, len(seq.blocks))
        nb_bucket = 1
        while nb_bucket < nb_needed:
            nb_bucket *= 2
        nb_bucket = min(nb_bucket, mgr.max_blocks_per_seq)
        tables = np.zeros((s_rows, nb_bucket), np.int32)
        for u in uids:
            seq = mgr.get(u)
            tables[seq.slot, :len(seq.blocks)] = seq.blocks

        sampled, _ = self._carried(self._decode_loop(
            self.params, self.cache_k, self.cache_v,
            self._put(tokens0), self._put(ctx0), self._put(active),
            self._put(tables), key, np.float32(max(temperature, 1e-6)),
            n_steps=chunk, greedy=(temperature <= 0),
            top_k=top_k, top_p=top_p, **self._state_kw()))
        sampled = np.asarray(sampled)  # [chunk, s_rows]
        for u in uids:
            seq = mgr.get(u)
            toks = [int(x) for x in sampled[:, seq.slot]]
            take = min(chunk, remaining[u])  # overshoot from round-up
            cut = take
            if eos_token_id is not None and eos_token_id in toks[:take]:
                cut = toks.index(eos_token_id) + 1
            seq.tokens.extend(toks)
            seq.num_cached += chunk
            outputs[u].extend(toks[:cut])
            remaining[u] -= cut
            if cut < take or remaining[u] <= 0:
                self.flush(u)


def build_engine(model: TransformerConfig, engine_config: Optional[Dict] = None,
                 model_params: Optional[Any] = None, **kw) -> InferenceEngineV2:
    """Factory (ref build_hf_engine, inference/v2/engine_factory.py:69)."""
    return InferenceEngineV2(model, engine_config, model_params=model_params, **kw)
