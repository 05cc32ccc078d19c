"""Disaggregated serving: prefill/decode replica tiers + speculation.

The homogeneous :class:`~.router.Router` treats replicas as
interchangeable, but the two phases of a generation live in different
roofline regimes: prefill is compute-bound (one big ragged batch over
the prompt), decode is HBM-bandwidth-bound (one token per sequence per
step, the KV cache streaming past the MXU).  At fleet scale they fight
for the same chips — the reference stack's MII/FastGen layer specializes
the fleet instead, and splitting the pools is a placement decision in
the sense of arXiv:2601.02311: different regimes deserve different
replica shapes, admission policies, and routing scores.

This module turns the replica tier into that fleet:

* **Tiers.**  ``ReplicaSet.build(..., disagg=...)`` splits the set into
  a *prefill tier* and a *decode tier* on disjoint device slices; the
  :class:`DisaggRouter` scores prefill legs by compute queue depth and
  decode legs by evictable KV headroom
  (``AdmissionController.evictable_headroom``), and falls back across
  tiers — a leg that finds no live replica in its tier re-runs on a
  unified (or any surviving) replica.

* **KV-block handoff.**  A prefill replica runs ``prompt → first
  token`` with ``handoff=True``: at completion the serve loop exports
  the sequence's FULL KV pages (``engine.export_kv_chain``) onto the
  stream.  The router then submits ``prompt + first_token`` to a decode
  replica with the payload attached; admission adopts it through the
  refcounted allocator — the same chain-keyed identity the prefix cache
  uses, so when the decode replica's cache already holds the chain the
  handoff is a **zero-copy ref acquire**, and otherwise only the
  uncovered tail moves as an explicit device-to-device block transfer
  (``handoff_ms``/``handoff_bytes`` are measured per request).  Both
  sides share the same-seed weight contract, so the decode continuation
  is bit-identical to a single-replica run — and a replica killed
  mid-handoff degrades to the ordinary fail-over recompute.

* **Speculative decoding.**  A small draft model lives in the decode
  replica's serve loop (:class:`SpeculativeDecoder`): it proposes up to
  ``spec_k`` greedy tokens per sequence, the target verifies the whole
  batch of proposals in ONE ragged verify-k step
  (``engine.verify_step``), and acceptance is **bit-identical to
  greedy** — every emitted token is the target's own argmax after its
  prefix, the draft only decides how many land per dispatch.  Opt-in is
  per request (``SamplingParams(speculative=True)``).

Like the rest of ``serving/``, this module imports no jax at module
scope — engines are built by ``ReplicaSet.build``.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

from deepspeed_tpu.serving.request import (DeadlineExceeded,
                                           GenerationRequest, ServingError)
from deepspeed_tpu.serving.router import _RETRY, Router, _RoutedRequest
from deepspeed_tpu.utils.logging import log_dist

#: replica tier vocabulary (ServingReplica.tier)
REPLICA_TIERS = ("prefill", "decode", "unified")

#: frozen key set of one RequestTimeline row — the per-request phase
#: breakdown the DisaggRouter stamps onto ``stream.timeline`` at finish
#: and keeps in its bounded ring (``DisaggRouter.timelines()``); linted
#: by tools/telemetry_check.py against docs/OBSERVABILITY.md
REQUEST_TIMELINE_KEYS = ("decode_ms", "failovers", "handoff_bytes",
                        "handoff_ms", "prefill_ms", "total_ms",
                        "trace_id", "uid")

#: RequestTimeline ring bound (oldest dropped)
_TIMELINE_RING = 1024


class SpeculativeConfig:
    """``serving.disagg.speculative`` block, serving-side parser: an
    EXTERNAL draft (a second engine, ``spec_k`` proposals a round, every
    active request opted in and decoding).  A model that drafts for
    itself through its own multi-token-prediction module needs none of
    this: ``engine_config`` ``self_draft`` (docs/SERVING.md), one
    program a step, verify runs beside prompts' chunks, no gate."""

    def __init__(self, d: Optional[dict] = None, **kw):
        d = {**(d or {}), **kw}
        self.enabled = bool(d.get("enabled", False))
        # models.get_model_config name (or a TransformerConfig passed
        # programmatically) for the draft; must share the target's
        # tokenizer/vocab — the proposals are target-vocabulary ids
        self.draft_model = d.get("draft_model", "")
        self.spec_k = int(d.get("spec_k", 4))
        if self.spec_k < 1:
            raise ValueError(f"speculative.spec_k={self.spec_k}: "
                             "must be >= 1")
        if self.enabled and not self.draft_model:
            raise ValueError("speculative.enabled requires a draft_model")


class DisaggConfig:
    """``serving.disagg`` block, serving-side parser (the runtime-config
    twin, ``runtime.config.DisaggServingConfig``, round-trips through
    this class at validation — the PR 9 drift tripwire)."""

    def __init__(self, d: Optional[dict] = None, **kw):
        d = {**(d or {}), **kw}
        self.enabled = bool(d.get("enabled", False))
        self.prefill_replicas = int(d.get("prefill_replicas", 1))
        self.decode_replicas = int(d.get("decode_replicas", 1))
        spec = d.get("speculative", {})
        self.speculative = (spec if isinstance(spec, SpeculativeConfig)
                            else SpeculativeConfig(spec))
        if self.enabled:
            if self.prefill_replicas < 1 or self.decode_replicas < 1:
                raise ValueError(
                    f"disagg tiers need >= 1 replica each, got prefill="
                    f"{self.prefill_replicas} decode={self.decode_replicas}")

    @property
    def n_replicas(self) -> int:
        return self.prefill_replicas + self.decode_replicas

    def tier_of(self, index: int) -> str:
        return "prefill" if index < self.prefill_replicas else "decode"


class SpeculativeDecoder:
    """Draft-propose / target-verify speculation inside one serve loop.

    The draft is a full :class:`InferenceEngineV2` (small model, same
    device slice) whose sequences MIRROR the target's: each round it
    greedily proposes up to ``spec_k`` tokens per sequence, the target
    scores every proposal in one ragged ``verify_step``, and the draft
    is rewound to the accepted stream (its KV rows for rejected
    positions are dead weight that the re-run overwrites — same
    position-addressed contract as the target's own rewind).  The
    mirror is self-healing: a missing or diverged draft sequence is
    flushed and re-admitted (a cheap draft-model re-prefill), so draft
    KV exhaustion, preemption, and fail-over all degrade to plain
    greedy decoding rather than to an error.

    Left as it was by PR 39: the target's ``verify_step`` still takes
    the argmax at every row of its own program (``ragged_verify``), not
    the self-drafting step's two rows a run (that step knows its runs
    are two rows long and fuses accept, module and next draft; this one
    serves ``spec_k`` rows a run and a draft engine's rewind).  The
    target may now be a latent model whose layers are all full; a
    self-drafting engine refuses to be one.
    """

    def __init__(self, target: Any, draft: Any, spec_k: int = 4):
        self.target = target
        self.draft = draft
        self.spec_k = int(spec_k)
        self.tracer = None
        self.trace_id = ""
        self.metrics = None

    def bind(self, tracer, trace_id: str, metrics) -> None:
        """Called by the owning server at start(): spans + accept-rate
        counters land in its trace/registry."""
        self.tracer = tracer
        self.trace_id = trace_id
        self.metrics = metrics

    # -- serve-loop API (the engine-owning thread only) -----------------
    def flush(self, uid: int) -> None:
        """Drop a draft mirror (target finished/preempted/failed)."""
        if uid in self.draft.state_manager:
            self.draft.flush(uid)

    def round(self, active: Dict[int, GenerationRequest]
              ) -> Dict[int, List[int]]:
        """One speculative round for the whole active set.

        Every request must be greedy/speculative with exactly one
        pending sampled token (the server's ``_spec_eligible`` gate).
        Returns ``{uid: accepted_tokens}`` (each >= 1 token, the burst
        the serve loop fans out); the target sequences already carry
        them.  Raises ``KVCacheExhausted`` only for TARGET pressure —
        draft pressure degrades to fewer (or zero) proposals.
        """
        tr = self.tracer
        uids = list(active)
        budget = self.target.scheduler.token_budget
        k_cap = max(0, budget // max(1, len(uids)) - 1)
        want = {uid: min(self.spec_k, k_cap,
                         max(0, active[uid].remaining - 1))
                for uid in uids}
        sp = (tr.span("spec.draft", self.trace_id) if tr is not None
              and tr.enabled else None)
        proposals = self._propose(uids, want)
        if sp is not None:
            sp.end(n_seqs=len(uids),
                   proposed=sum(len(p) for p in proposals.values()))
        sp = (tr.span("spec.verify", self.trace_id) if tr is not None
              and tr.enabled else None)
        try:
            accepted = self.target.verify_step(proposals)
        except BaseException:
            # target rolled back to the pre-round state; the draft
            # mirrors consumed proposals the target never saw — drop
            # them and re-admit lazily next round
            for uid in uids:
                self.flush(uid)
            if sp is not None:
                sp.end(kv_exhausted=True)
            raise
        n_prop = sum(len(p) for p in proposals.values())
        n_acc = sum(len(a) - 1 for a in accepted.values())
        if sp is not None:
            sp.end(proposed=n_prop, accepted=n_acc)
            tr.instant("spec.accept", self.trace_id, proposed=n_prop,
                       accepted=n_acc)
        if self.metrics is not None:
            self.metrics.record_spec_round(n_prop, n_acc)
        self._rewind_drafts(uids, proposals, accepted)
        return accepted

    # -- internals ------------------------------------------------------
    def _propose(self, uids: Sequence[int],
                 want: Dict[int, int]) -> Dict[int, List[int]]:
        """Greedy draft proposals, ``want[uid]`` tokens each.  A fresh
        (or diverged) mirror is re-admitted first and catches up through
        the draft's own chunked prefill; its completing step yields its
        first proposal.  Sequences done proposing idle (uncached 0) —
        the scheduler skips them — while slower peers finish."""
        from deepspeed_tpu.inference.v2.ragged import KVCacheExhausted

        mgr = self.draft.state_manager
        for uid in uids:
            seq_t = self.target.state_manager.get(uid)
            if uid in mgr:
                if list(mgr.get(uid).tokens) != list(seq_t.tokens):
                    self.draft.flush(uid)      # diverged: self-heal
            if uid not in mgr:
                try:
                    self.draft.admit(uid, list(seq_t.tokens))
                except (KVCacheExhausted, RuntimeError):
                    continue   # no draft room: propose nothing this round
        proposals: Dict[int, List[int]] = {u: [] for u in uids}
        max_iters = max(list(want.values()) or [0]) + 8
        for _ in range(max_iters):
            if all(len(proposals[u]) >= want[u] or u not in mgr
                   for u in uids):
                break
            try:
                out = self.draft.step(temperature=0.0)
            except KVCacheExhausted:
                # draft pool pressure: free EVERYTHING (mirrors rebuild
                # lazily) and run with the proposals gathered so far
                for uid in list(uids):
                    self.flush(uid)
                log_dist("speculative: draft KV exhausted; degrading to "
                         "plain greedy this round", level="warning")
                break
            if not out and not self.draft.scheduler.has_work:
                break
            for uid, tok in out.items():
                if uid in proposals and len(proposals[uid]) < want[uid]:
                    proposals[uid].append(int(tok))
                    if len(proposals[uid]) < want[uid]:
                        self.draft.extend(uid, int(tok))
        return proposals

    def _rewind_drafts(self, uids, proposals, accepted) -> None:
        """Align every draft mirror with the target's post-verify stream:
        the draft's KV is valid up to the longest common prefix of what
        it consumed (its own proposals) and what the target accepted."""
        mgr = self.draft.state_manager
        for uid in uids:
            if uid not in mgr:
                continue
            acc = accepted.get(uid)
            if acc is None:
                continue
            m = len(acc) - 1           # accepted proposals (sans bonus)
            seq_t = self.target.state_manager.get(uid)
            dseq = mgr.get(uid)
            base = len(seq_t.tokens) - len(acc)   # pre-round stream len
            self.draft.rewind(uid, list(seq_t.tokens),
                              num_cached=min(dseq.num_cached, base + m))


class DisaggRouter(Router):
    """Tier-aware router: prefill leg → KV handoff → decode leg.

    The ``submit()/generate()`` surface is unchanged.  Each request runs
    a **prefill leg** (``max_new_tokens=1`` + ``handoff=True`` on the
    prefill tier — TTFT is paid where the compute is) and, unless one
    token was all it wanted, a **decode leg** on the decode tier whose
    admission adopts the exported KV chain.  Fail-over is per leg and
    tier-local first: a dead prefill replica's leg re-runs on another
    prefill (or any surviving) replica, a dead decode replica's leg
    re-submits prompt+delivered WITH the payload (the chain is still a
    prefix of the stream), and when a tier is empty the other tier's
    replicas serve as unified stand-ins re-running prefill — greedy
    continuations stay bit-identical throughout.
    """

    def __init__(self, replicas, config: Optional[dict] = None,
                 telemetry=None):
        super().__init__(replicas, config, telemetry)
        tiers = {r.tier for r in replicas}
        if "prefill" not in tiers or "decode" not in tiers:
            raise ValueError(
                "DisaggRouter needs at least one prefill-tier and one "
                f"decode-tier replica (got tiers {sorted(tiers)}); build "
                "the ReplicaSet with disagg={'enabled': True, ...}")
        # finished-request phase breakdowns (REQUEST_TIMELINE_KEYS),
        # newest last; appended under self._lock by the pump threads
        self._timelines: deque = deque(maxlen=_TIMELINE_RING)
        # degraded homogeneous mode: True while a whole tier is gone
        # (fleet supervisor actuation) — requests run ONE full leg on
        # any survivor instead of the prefill→handoff→decode split
        self._collapsed = False

    def timelines(self) -> List[Dict[str, Any]]:
        """Recent per-request phase timelines (oldest first) — each row
        carries exactly :data:`REQUEST_TIMELINE_KEYS`."""
        with self._lock:
            return list(self._timelines)

    # -- degraded homogeneous mode --------------------------------------
    @property
    def collapsed(self) -> bool:
        with self._lock:
            return self._collapsed

    def collapse_tiers(self) -> None:
        """Fold the prefill/decode split into homogeneous routing: new
        requests run a single full leg on whichever replicas survive.
        The fleet supervisor calls this when a tier's dispatchable pool
        empties; in-flight two-leg requests finish through the ordinary
        cross-tier fallback.  Greedy outputs are unchanged — a unified
        leg is just prefill+decode on one replica."""
        with self._lock:
            if self._collapsed:
                return
            self._collapsed = True
        log_dist("disagg: tier collapsed — routing homogeneous until "
                 "the fleet heals", level="warning")

    def restore_tiers(self) -> None:
        """Re-enable tiered prefill→decode routing (both tiers have
        dispatchable replicas again)."""
        with self._lock:
            if not self._collapsed:
                return
            self._collapsed = False
        log_dist("disagg: tiers restored — prefill/decode routing back",
                 level="warning")

    # -- tier-aware dispatch --------------------------------------------
    def _candidates(self, tier: Optional[str],
                    exclude: Sequence[int]) -> List[Any]:
        masked = self.masked_indices()
        alive = [r for r in self.replicas.alive if r.index not in exclude]
        clean = [r for r in alive if r.index not in masked]
        if tier is None or self.collapsed:
            # homogeneous: prefer unmasked survivors, but availability
            # beats cleanliness when the mask covers everyone
            return clean or alive
        pool = [r for r in clean if r.tier == tier]
        if pool:
            return pool
        uni = [r for r in clean if r.tier == "unified"]
        if uni:
            return uni
        # last resort: any unmasked survivor serves the leg (a decode
        # leg landing on a prefill replica just re-runs prefill — the
        # recompute contract fail-over already rests on); a fully-masked
        # fleet still dispatches rather than failing the request
        return clean or alive

    def _score(self, rep, tier: Optional[str] = None) -> float:
        if tier == "prefill":
            # prefill is compute-bound: the only thing that matters is
            # how much prompt work is already queued on the replica
            with self._lock:
                inflight = self._inflight.get(rep.index, 0)
            return -float(rep.queue_load + inflight)
        # decode legs (and the unified fallback) score by evictable KV
        # headroom — the base rule
        return super()._score(rep, tier)

    # -- the two-leg pump -----------------------------------------------
    def submit(self, prompt, params=None, priority: int = 0,
               deadline_s: Optional[float] = None,
               session: Optional[str] = None):
        from deepspeed_tpu.serving.request import SamplingParams

        # validate the WHOLE request up front: the prefill leg's 1-token
        # shape would sail past the per-sequence KV cap that the decode
        # leg then hits mid-flight (replicas share one geometry, so any
        # live engine speaks for the fleet)
        params = params or SamplingParams()
        rep = next(iter(self.replicas.alive), None)
        if rep is not None and prompt is not None:
            eng = rep.engine
            need = eng.seq_blocks(len(prompt) + params.max_new_tokens)
            if need > eng.max_seq_blocks:
                raise ValueError(
                    f"prompt+output needs {need} KV blocks but the "
                    f"engines allow {eng.max_seq_blocks} per "
                    "sequence; raise num_blocks/max_context or "
                    "shorten the request")
        return super().submit(prompt, params, priority=priority,
                              deadline_s=deadline_s, session=session,
                              phase=None if self.collapsed else "prefill")

    def _request_complete(self, rr: _RoutedRequest) -> bool:
        eos = rr.params.eos_token_id
        return (len(rr.delivered) >= rr.params.max_new_tokens
                or (eos is not None and rr.delivered
                    and rr.delivered[-1] == eos))

    def _leg_done(self, rr: _RoutedRequest) -> None:
        # bank the leg's wall time under its phase BEFORE releasing the
        # inflight slot; failed-over legs accumulate (the timeline shows
        # total time spent in each phase, retries included)
        phase = rr.phase or "unified"
        rr.legs[phase] = (rr.legs.get(phase, 0.0)
                          + (time.monotonic() - rr.leg_t0) * 1e3)
        super()._leg_done(rr)

    def _pump_loop(self, rr: _RoutedRequest,
                   session: Optional[str]) -> None:
        out = rr.stream
        while True:
            leg = (self.tracer.span("router.leg", rr.trace_id, rr.span)
                   .set(uid=rr.uid, replica=rr.replica.index,
                        tier=rr.phase)
                   if self.tracer.enabled else None)
            try:
                for tok in rr.inner:
                    rr.delivered.append(tok)
                    out._put_token(tok)
                self._leg_done(rr)
                if leg is not None:
                    leg.end(outcome="completed")
                if rr.phase == "prefill" and not self._request_complete(rr):
                    # leg 2: hand the chain to the decode tier.  A lost
                    # payload (export failed, replica died between token
                    # and export) is fine — admission just re-prefills.
                    rr.payload = getattr(rr.inner, "handoff_payload", None)
                    if (rr.deadline is not None
                            and time.monotonic() >= rr.deadline):
                        # deadline died BETWEEN legs: surface the typed
                        # terminal error here rather than burning a
                        # decode admission that would only expire in
                        # queue.  The un-adopted payload is dropped —
                        # its exported chain was released with the
                        # prefill request, so no blocks leak.
                        rr.payload = None
                        self._finish(rr, DeadlineExceeded(
                            f"request {rr.uid}: deadline exceeded after "
                            f"prefill leg ({len(rr.delivered)} tokens out)"))
                        return
                    rr.phase = "decode"
                    try:
                        self._dispatch(rr, session=session)
                    except ServingError as e:
                        self._finish(rr, e)
                        return
                    continue
                self._finish(rr, None)
                return
            except ServingError as e:
                self._leg_done(rr)
                if leg is not None:
                    leg.end(outcome=type(e).__name__)
                err = self._on_leg_error(rr, e, session)
                if err is not _RETRY:
                    self._finish(rr, err)
                    return

    def _finish(self, rr: _RoutedRequest, error) -> None:
        payload = rr.payload
        if payload is not None and "import_ms" in payload:
            # the decode server stamped the import half at admission;
            # export half rode the payload from the prefill server
            ms = payload.get("export_ms", 0.0) + payload["import_ms"]
            nbytes = payload["import_bytes"]
            self.metrics.record_handoff(nbytes, ms / 1e3)
            rr.stream.handoff_ms = round(ms, 3)
            rr.stream.handoff_bytes = int(nbytes)
            rr.payload = None     # exactly-once accounting
        # RequestTimeline: the cross-tier phase breakdown, stamped on the
        # caller's stream AND kept in the ring — terminal errors included
        # (a failed request's phase split is exactly what triage wants)
        tl: Dict[str, Any] = {
            "uid": rr.uid,
            "trace_id": rr.trace_id,
            "prefill_ms": round(rr.legs.get("prefill", 0.0), 3),
            "decode_ms": round(rr.legs.get("decode", 0.0)
                               + rr.legs.get("unified", 0.0), 3),
            "handoff_ms": rr.stream.handoff_ms or 0.0,
            "handoff_bytes": rr.stream.handoff_bytes or 0,
            "failovers": rr.failovers,
            "total_ms": round((time.monotonic() - rr.t_submit) * 1e3, 3),
        }
        rr.stream.timeline = tl
        with self._lock:
            self._timelines.append(tl)
        super()._finish(rr, error)
