"""Chaos harness + fleet supervisor + graceful-degradation ladder.

Three layers, bottom-up: the seeded fault-injection vocabulary
(resilience/chaos.py) must be deterministic and exactly-once; the
FleetSupervisor's health state machine must walk the frozen states —
quarantine, respawn within budget, tier collapse/restore — against
scripted replica failures; and the brownout ladder must be monotone
with hysteresis, shedding STRICTLY the lowest-priority class while
accepted requests keep their exact greedy outputs.  Supervisor tests
run against fake replicas (the supervisor only touches public probe
surfaces); the shedding tests drive a real serve loop.
"""

import json
import time
import types

import numpy as np
import pytest

from deepspeed_tpu.resilience.chaos import (CHAOS_SENTINEL, FAULT_KINDS,
                                            INJECTION_POINTS, ChaosError,
                                            ChaosInjector, FaultPlan,
                                            FaultSpec, TrainChaos,
                                            attach_chaos)
from deepspeed_tpu.serving import (BROWNOUT_LEVELS, HEALTH_STATES,
                                   BrownoutConfig, BrownoutController,
                                   FleetHealFailed, FleetSupervisor,
                                   RequestShed, ServingError,
                                   brownout_index)

ENG_CFG = {"dtype": "float32",
           "memory_config": {"num_blocks": 64, "block_size": 4},
           "max_context": 64}


# ---------------------------------------------------------------------------
# chaos module: plans, injectors, the training contract
# ---------------------------------------------------------------------------

def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(kind="meteor_strike")
    with pytest.raises(ValueError, match="unknown injection point"):
        FaultSpec(kind="replica_crash", point="kitchen.sink")
    # every kind resolves to a legal default point
    for kind in FAULT_KINDS:
        assert FaultSpec(kind=kind).point in INJECTION_POINTS


def test_fault_plan_sorted_and_targeted():
    plan = FaultPlan([
        {"kind": "replica_hang", "at": 2.0, "target": "r1"},
        {"kind": "replica_crash", "at": 0.5, "target": "r0"},
        {"kind": "slow_replica", "at": 1.0},          # broadcast
    ], seed=3)
    assert [f.at for f in plan.faults] == [0.5, 1.0, 2.0]
    # a target sees its own specs plus the broadcast ones, in order
    assert [f.kind for f in plan.for_target("r1")] == ["slow_replica",
                                                       "replica_hang"]
    assert len(plan.for_target(None)) == 1


def test_injector_one_shot_fires_exactly_once():
    plan = FaultPlan([{"kind": "replica_crash", "at": 0.5,
                       "target": "r0"}])
    inj = ChaosInjector(plan, target="r0").arm(now=100.0)
    assert inj.fire("server.step", now=100.4) == []
    due = inj.fire("server.step", now=100.6)
    assert [f.kind for f in due] == ["replica_crash"]
    # consumed: never again, regardless of how often the loop polls
    assert inj.fire("server.step", now=100.7) == []
    assert inj.fire("server.step", now=200.0) == []
    assert inj.injected == 1 and inj.fired_kinds == {"replica_crash"}
    # the wrong point never sees it
    assert inj.fire("engine.step", now=100.6) == []


def test_injector_durational_window_and_delay():
    plan = FaultPlan([{"kind": "slow_replica", "at": 0.0,
                       "duration_s": 1.0, "params": {"delay_ms": 20.0}}])
    inj = ChaosInjector(plan, target="r0").arm(now=50.0)
    assert len(inj.fire("server.step", now=50.2)) == 1
    due = inj.fire("server.step", now=50.9)     # re-fires inside window
    assert len(due) == 1
    assert inj.delay_s(due) == pytest.approx(0.02)
    assert inj.fire("server.step", now=51.5) == []     # window closed
    assert inj.injected == 1        # ONE activation (one instant), many fires


def test_injector_unarmed_is_free():
    plan = FaultPlan([{"kind": "replica_crash", "at": 0.0}])
    inj = ChaosInjector(plan)
    assert not inj.armed and inj.fire("server.step") == []


def test_attach_chaos_wires_fleet_against_one_origin():
    reps = [types.SimpleNamespace(name=f"r{i}",
                                  server=types.SimpleNamespace(tracer=None),
                                  engine=types.SimpleNamespace())
            for i in range(2)]
    router = types.SimpleNamespace(tracer=None)
    plan = FaultPlan([{"kind": "replica_crash", "at": 1.0}])
    injs = attach_chaos(reps, plan, router=router)
    assert set(injs) == {"r0", "r1", "router"}
    assert all(i.armed for i in injs.values())
    assert len({i._t0 for i in injs.values()}) == 1    # shared clock
    for rep in reps:
        assert rep.server._chaos is injs[rep.name]
        assert rep.engine.chaos is injs[rep.name]
    assert router._chaos is injs["router"]


def test_chaos_error_is_not_a_typed_serving_outcome():
    # a ChaosError must ride the "unexpected crash" paths, not the typed
    # request-outcome classes
    assert issubclass(ChaosError, RuntimeError)
    assert not issubclass(ChaosError, ServingError)


def test_train_chaos_env_contract(tmp_path):
    env = {"DSTPU_CHAOS": json.dumps({"rank": 1, "die_at": 3})}
    ckpt = str(tmp_path)
    assert TrainChaos.from_env(0, ckpt, env=env) is None   # other rank
    tc = TrainChaos.from_env(1, ckpt, env=env)
    assert tc is not None and tc.cfg["die_at"] == 3
    # the sentinel disarms every later incarnation (exactly-once)
    (tmp_path / CHAOS_SENTINEL).write_text("999")
    assert TrainChaos.from_env(1, ckpt, env=env) is None
    assert TrainChaos.from_env(1, ckpt, env={}) is None    # chaos off


# ---------------------------------------------------------------------------
# fleet supervisor state machine (fake replicas: public probe surface only)
# ---------------------------------------------------------------------------

class _FakeAdmission:
    def __init__(self):
        self.depth = 0
        self.cfg = types.SimpleNamespace(max_queue_size=8)

    def __len__(self):
        return self.depth


class _FakeServer:
    def __init__(self):
        self.loop_beat_t = time.monotonic()
        self.step_ema_s = 0.0
        self.admission = _FakeAdmission()
        self.brownout_level = "normal"

    def set_brownout(self, level):
        self.brownout_level = level


class _FakeReplica:
    def __init__(self, index, tier="unified"):
        self.index = index
        self.name = f"r{index}"
        self.tier = tier
        self.alive = True
        self.killed = False
        self.queue_load = 0
        self.kv_headroom = 1.0
        self.server = _FakeServer()

    def kill(self):
        self.alive = False
        self.killed = True


class _FakeSet(list):
    def __init__(self, reps, fail_respawn=False):
        super().__init__(reps)
        self.respawns = []
        self.fail_respawn = fail_respawn

    def respawn(self, index):
        if self.fail_respawn:
            raise RuntimeError("no capacity")
        if self[index].alive:
            raise RuntimeError(f"replica {index} still alive")
        fresh = _FakeReplica(index, self[index].tier)
        self[index] = fresh
        self.respawns.append(index)
        return fresh


class _FakeRouter:
    # no collapse_tiers: a plain (non-disagg) router has no tiers, and
    # the supervisor keys tier management off that attribute
    def __init__(self):
        self._mask = {}
        self.brownout = None

    def mask(self, index, cooldown_s=None):
        self._mask[index] = cooldown_s

    def unmask(self, index):
        self._mask.pop(index, None)

    def masked_indices(self):
        return set(self._mask)

    def set_brownout(self, level):
        self.brownout = level


class _FakeDisaggRouter(_FakeRouter):
    def __init__(self):
        super().__init__()
        self.collapsed = False
        self.collapse_calls = 0
        self.restore_calls = 0

    def collapse_tiers(self):
        self.collapsed = True
        self.collapse_calls += 1

    def restore_tiers(self):
        self.collapsed = False
        self.restore_calls += 1


def _sup(reps, router=None, **cfg):
    cfg.setdefault("suspect_ticks", 1)
    cfg.setdefault("manage_brownout", False)
    return FleetSupervisor(reps, router=router, config=cfg)


def test_supervisor_dead_replica_quarantined_and_respawned():
    reps = _FakeSet([_FakeReplica(0), _FakeReplica(1)])
    router = _FakeRouter()
    sup = _sup(reps, router, suspect_ticks=2)
    assert sup.tick() == {"r0": "healthy", "r1": "healthy"}
    reps[0].kill()
    assert sup.tick()["r0"] == "suspect"      # one miss is a race...
    states = sup.tick()                        # ...two is a corpse
    assert states["r0"] == "respawned"         # dead→quarantined→respawned
    seq = [e["state"] for e in sup.events if e["replica"] == "r0"]
    assert seq == ["suspect", "dead", "quarantined", "respawned"]
    assert all(s in HEALTH_STATES for s in seq)
    assert reps.respawns == [0] and reps[0].alive
    assert router.masked_indices() == set()    # unmasked after the heal
    assert sup.tick()["r0"] == "healthy"       # one clean tick closes it
    assert sup.heals == 1
    heal = next(e for e in sup.events if e["state"] == "respawned")
    assert heal["heal_s"] <= heal["deadline_s"]


def test_supervisor_stuck_probe_needs_queued_work():
    reps = _FakeSet([_FakeReplica(0), _FakeReplica(1)])
    sup = _sup(reps, stuck_after_s=5.0)
    now = time.monotonic()
    # idle replica with an ancient beat is NOT stuck (blocked in
    # wait_for_work is legitimate)...
    reps[0].server.loop_beat_t = now - 60.0
    assert sup.tick(now=now)["r0"] == "healthy"
    # ...but a stale beat WITH queued work is a wedge
    reps[0].queue_load = 3
    assert sup.tick(now=now)["r0"] == "respawned"
    assert [e["state"] for e in sup.events] == ["stuck", "quarantined",
                                                "respawned"]
    # the quarantine killed the hung thread before respawning
    assert reps.respawns == [0]


def test_supervisor_straggler_needs_sustained_evidence_and_peers():
    reps = _FakeSet([_FakeReplica(i) for i in range(4)])
    for r in reps:
        r.server.step_ema_s = 0.1
    reps[0].server.step_ema_s = 1.0            # 10x the peer median
    sup = _sup(reps, straggler_factor=4.0, straggler_ticks=2)
    assert sup.tick()["r0"] == "healthy"       # tick 1: evidence, no verdict
    assert sup.tick()["r0"] == "respawned"     # tick 2: sustained
    assert any(e["state"] == "straggler" for e in sup.events)


def test_supervisor_max_heals_fails_loudly():
    reps = _FakeSet([_FakeReplica(0), _FakeReplica(1)])
    sup = _sup(reps, max_heals=1)
    reps[0].kill()
    sup.tick()                                  # heal 1: within budget
    reps[1].kill()
    with pytest.raises(FleetHealFailed, match="budget exhausted"):
        sup.tick()
    with pytest.raises(FleetHealFailed):
        sup.check()                             # sticky, caller-visible
    assert any(e["state"] == "retired" for e in sup.events)


def test_supervisor_respawn_failure_retires():
    reps = _FakeSet([_FakeReplica(0), _FakeReplica(1)], fail_respawn=True)
    sup = _sup(reps)
    reps[0].kill()
    assert sup.tick()["r0"] == "retired"
    sup.check()                                 # retirement is not a raise


def test_supervisor_tier_collapse_and_restore():
    reps = _FakeSet([_FakeReplica(0, "prefill"), _FakeReplica(1, "prefill"),
                     _FakeReplica(2, "decode"), _FakeReplica(3, "decode")])
    router = _FakeDisaggRouter()
    sup = _sup(reps, router)
    reps[2].kill()
    reps[3].kill()
    states = sup.tick()
    # the tick that emptied the decode pool collapsed BEFORE healing
    # (the degraded window is real), then healing restored the tiers
    assert router.collapse_calls == 1 and router.restore_calls == 1
    assert sup.collapses == 1 and sup.restores == 1
    assert not router.collapsed
    assert states["r2"] == states["r3"] == "respawned"
    # one casualty does NOT collapse a tier that still has a survivor
    reps[0].kill()
    sup.tick()
    assert router.collapse_calls == 1


def test_supervisor_brownout_actuation_and_pressure():
    reps = _FakeSet([_FakeReplica(0), _FakeReplica(1)])
    router = _FakeRouter()
    sup = FleetSupervisor(reps, router=router, config={
        "suspect_ticks": 1,
        "brownout": {"enter": 0.8, "exit": 0.3, "dwell_s": 0.0}})
    assert sup.fleet_pressure() == 0.0
    reps[0].server.admission.depth = 8          # queue fraction 1.0
    assert sup.fleet_pressure() == 1.0
    sup.tick()
    assert router.brownout == "shed_speculation"    # one level per tick
    sup.tick()
    assert router.brownout == "cap_decode"
    # inside the hysteresis band the ladder holds
    reps[0].server.admission.depth = 4          # pressure 0.5
    sup.tick()
    assert router.brownout == "cap_decode"
    reps[0].server.admission.depth = 0
    sup.tick()
    assert router.brownout == "shed_speculation"
    sup.tick()
    assert router.brownout == "normal"
    levels = [e["level"] for e in sup.events if e["state"] == "brownout"]
    assert levels == ["shed_speculation", "cap_decode",
                      "shed_speculation", "normal"]


def test_supervisor_snapshot_shape():
    reps = _FakeSet([_FakeReplica(0)])
    sup = _sup(reps)
    sup.tick()
    snap = sup.snapshot()
    assert snap["states"] == {"r0": "healthy"}
    assert snap["brownout_level"] == "normal" and not snap["failed"]


# ---------------------------------------------------------------------------
# brownout ladder: monotone, hysteresis, no flapping
# ---------------------------------------------------------------------------

def test_brownout_controller_walks_one_level_with_dwell():
    bc = BrownoutController(BrownoutConfig(enter=0.8, exit=0.3,
                                           dwell_s=1.0))
    assert bc.level == "normal"
    assert bc.observe(0.95, now=0.0) == "shed_speculation"
    assert bc.observe(0.95, now=0.5) is None        # dwell holds
    assert bc.observe(0.95, now=1.1) == "cap_decode"
    assert bc.observe(0.95, now=2.2) == "shed_low_priority"
    assert bc.observe(0.95, now=3.3) == "reject_new"
    assert bc.observe(0.95, now=4.4) is None        # top of the ladder
    assert bc.level == "reject_new"
    # descent: one level per dwell once pressure clears the EXIT line
    assert bc.observe(0.5, now=5.5) is None          # hysteresis band
    for i, want in enumerate(["shed_low_priority", "cap_decode",
                              "shed_speculation", "normal"]):
        assert bc.observe(0.1, now=6.6 + i * 1.1) == want
    assert bc.observe(0.1, now=20.0) is None         # floor


def test_brownout_no_flap_around_one_threshold():
    bc = BrownoutController(BrownoutConfig(enter=0.8, exit=0.3,
                                           dwell_s=0.0))
    bc.observe(0.9, now=0.0)
    # pressure oscillating around the ENTER threshold inside the band
    # must not move the ladder in either direction
    for i in range(20):
        assert bc.observe(0.79 if i % 2 else 0.31, now=1.0 + i) is None
    assert bc.level == "shed_speculation"


def test_brownout_config_validates_band():
    with pytest.raises(ValueError, match="exit"):
        BrownoutConfig(enter=0.5, exit=0.6)
    assert [brownout_index(l) for l in BROWNOUT_LEVELS] == [0, 1, 2, 3, 4]


# ---------------------------------------------------------------------------
# shedding on a real serve loop: strictly the lowest-priority class
# ---------------------------------------------------------------------------

def _server(srv_cfg=None):
    from deepspeed_tpu.inference.v2 import build_engine
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.serving import InferenceServer

    model = get_model_config("llama-tiny", num_layers=1)
    eng = build_engine(model, ENG_CFG, seed=0)
    return model, InferenceServer(eng, srv_cfg or {})


def test_shed_low_priority_sheds_strictly_below_floor():
    from deepspeed_tpu.serving import SamplingParams

    model, srv = _server({"brownout": {"priority_floor": 0}})
    rng = np.random.default_rng(5)
    p = rng.integers(1, model.vocab_size, size=8).tolist()
    with srv:
        want = srv.generate([p], max_new_tokens=4)[0]
        srv.set_brownout("shed_low_priority")
        with pytest.raises(RequestShed):
            srv.submit(p, SamplingParams(max_new_tokens=4), priority=-1)
        # AT the floor is accepted — and the accepted request's greedy
        # output is exactly the fault-free one (degradation never
        # touches correctness)
        s = srv.submit(p, SamplingParams(max_new_tokens=4), priority=0)
        assert s.result(timeout=300) == want
        srv.set_brownout("reject_new")
        with pytest.raises(RequestShed):       # even high priority
            srv.submit(p, SamplingParams(max_new_tokens=4), priority=99)
        srv.set_brownout("normal")
        s = srv.submit(p, SamplingParams(max_new_tokens=4), priority=-1)
        assert s.result(timeout=300) == want
        m = srv.metrics.snapshot()
        assert m["shed"] == 2 and m["completed"] == 3


def test_queue_sweep_sheds_only_below_floor():
    from deepspeed_tpu.serving import SamplingParams

    model, srv = _server({"brownout": {"priority_floor": 0,
                                       "decode_cap": 1}})
    rng = np.random.default_rng(6)
    p = rng.integers(1, model.vocab_size, size=8).tolist()
    with srv:
        srv.generate([p], max_new_tokens=2)     # pay the compile
        # cap_decode holds admissions behind the filler, so the two
        # probes sit IN QUEUE when the ladder reaches shed_low_priority
        srv.set_brownout("cap_decode")
        filler = srv.submit(p, SamplingParams(max_new_tokens=24))
        deadline = time.monotonic() + 60
        while not srv._active and time.monotonic() < deadline:
            time.sleep(0.01)
        keep = srv.submit(p, SamplingParams(max_new_tokens=4), priority=0)
        low = srv.submit(p, SamplingParams(max_new_tokens=4), priority=-1)
        srv.set_brownout("shed_low_priority")
        with pytest.raises(RequestShed):        # swept from the queue
            low.result(timeout=300)
        srv.set_brownout("normal")
        assert len(filler.result(timeout=300)) == 24
        assert len(keep.result(timeout=300)) == 4    # survived the sweep
