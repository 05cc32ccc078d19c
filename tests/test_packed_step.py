"""The launch path of a ragged step (ISSUE 32): the seven index arrays as
one packed buffer and one transfer, no key split on a greedy step, one
program a step.  The reference is the formulation it replaced, kept here:
eight fresh arrays a step, cut to the buckets after the build, shipped one
by one to the programs that take them one by one."""

import collections
import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import model as m2
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.ragged import (DSStateManager, PackedIndex,
                                               build_ragged_batch)
from deepspeed_tpu.models import get_model_config
from deepspeed_tpu.telemetry.tracing import Tracer

# token buckets 16 and 32 (the budget), block buckets 1, 2, 4, 8, 16
ENGINE = {"dtype": "float32",
          "memory_config": {"num_blocks": 96, "block_size": 4},
          "max_context": 64,
          "state_manager": {"max_tracked_sequences": 8,
                            "max_ragged_batch_size": 32}}


# -- the formulation this PR replaced ---------------------------------------
def _unpacked_build(schedule, mgr, token_budget):
    """``build_ragged_batch`` as it was: fresh arrays at the full budget
    and the full table width, a sequence at a time; then the engine's cut
    to the step's buckets.  Returns (the seven arrays, uids_by_slot)."""
    bs, t, pad_slot = mgr.block_size, token_budget, mgr.max_seqs
    token_ids = np.zeros((t,), np.int32)
    token_slot = np.full((t,), pad_slot, np.int32)
    token_pos = np.zeros((t,), np.int32)
    token_dest = np.zeros((t,), np.int32)
    block_tables = np.zeros((mgr.max_seqs + 1, mgr.max_blocks_per_seq),
                            np.int32)
    ctx_lens = np.zeros((mgr.max_seqs + 1,), np.int32)
    logits_idx = np.zeros((mgr.max_seqs + 1,), np.int32)
    uids_by_slot = {}
    for seq, n_new in schedule:
        mgr.ensure_capacity(seq, seq.num_cached + n_new)
    cursor = 0
    for seq, n_new in schedule:
        start, sl = seq.num_cached, seq.slot
        end = start + n_new
        rows = np.arange(start, end, dtype=np.int32)
        dest = np.asarray(seq.blocks, np.int32)[rows // bs] * bs + rows % bs
        token_ids[cursor:cursor + n_new] = seq.tokens[start:end]
        token_slot[cursor:cursor + n_new] = sl
        token_pos[cursor:cursor + n_new] = rows
        token_dest[cursor:cursor + n_new] = dest
        block_tables[sl, :len(seq.blocks)] = seq.blocks
        ctx_lens[sl] = end
        logits_idx[sl] = cursor + n_new - 1
        if end == len(seq.tokens):
            uids_by_slot[sl] = seq.uid
        cursor += n_new
        seq.num_cached = end
    t_bucket = 16
    while t_bucket < cursor:
        t_bucket *= 2
    t_bucket = min(t_bucket, token_budget)
    nb_bucket = 1
    while nb_bucket < max(1, -(-int(ctx_lens.max()) // bs)):
        nb_bucket *= 2
    nb_bucket = min(nb_bucket, mgr.max_blocks_per_seq)
    return (token_ids[:t_bucket], token_slot[:t_bucket],
            token_pos[:t_bucket], token_dest[:t_bucket],
            block_tables[:, :nb_bucket], ctx_lens, logits_idx), uids_by_slot


class _Unpacked:
    """An engine's state driven the old way: the programs that take the
    seven arrays one by one, jitted as the engine jitted them."""

    def __init__(self, eng):
        self.eng = eng
        static = dict(cfg=eng.model_config, block_size=eng.cfg.block_size)
        donate = dict(donate_argnums=(1, 2))
        if eng.state is not None:
            donate["donate_argnames"] = ("state",)
        self.forward = jax.jit(functools.partial(m2.ragged_forward, **static),
                               **donate)
        self.sampled = jax.jit(
            functools.partial(m2.ragged_forward_sampled, **static),
            static_argnames=("greedy", "top_k"), **donate)
        self.verify = jax.jit(
            functools.partial(m2.ragged_forward_verify, **static),
            donate_argnums=(1, 2))

    def step(self, program, **kw):
        eng = self.eng
        schedule = eng.scheduler.next_schedule()
        host, uids_by_slot = _unpacked_build(
            schedule, eng.state_manager, eng.scheduler.token_budget)
        out = eng._carried(program(
            eng.params, eng.cache_k, eng.cache_v,
            *[jnp.asarray(a) for a in host], **kw, **eng._state_kw()))
        return np.asarray(out), uids_by_slot


def _same_bits(a, b):
    a, b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x.astype(jnp.float32)),
                                      np.asarray(y.astype(jnp.float32)))


def _engine(name, seed=0, **cfg):
    over = {"falcon-h1-tiny": {}}.get(name, {"num_layers": 1})
    model = get_model_config(name, **over)
    return model, InferenceEngineV2(model, dict(ENGINE, **cfg), seed=seed)


# -- the build ---------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1])
def test_packed_build_matches_the_unpacked_build(seed):
    """Random mixes of prefill chunks and decode rows, step after step, so
    that every bucket's buffer is rewritten over what an earlier step left
    in it; a sequence that holds pages past the step's block bucket."""
    rng = np.random.default_rng(seed)
    mgrs = [DSStateManager(max_seqs=8, num_blocks=160, block_size=4,
                           max_blocks_per_seq=16) for _ in range(2)]
    seen, uid, last = set(), 0, None
    for _ in range(150):
        for _ in range(int(rng.integers(0, 3))):
            if mgrs[0].n_active < 8:
                toks = rng.integers(1, 99, size=int(rng.integers(1, 40)))
                for m in mgrs:
                    m.open(uid, toks.tolist())
                uid += 1
        live = sorted(mgrs[0]._seqs)
        if not live:
            continue
        # a decode row for a caught-up sequence, a chunk for the others,
        # within a budget of 32; now and then a sequence is left out
        picks, left = [], 32
        for u in live:
            seq = mgrs[0].get(u)
            if not seq.uncached:
                tok = int(rng.integers(1, 99))
                for m in mgrs:
                    m.extend(u, tok)
            n = min(seq.uncached, left, int(rng.integers(1, 33)))
            if n and rng.random() < 0.85:
                picks.append((u, n))
                left -= n
        if rng.random() < 0.2 and picks:
            # pages held past this step's context (a fused decode's
            # horizon): the bucket must cut them off the table row
            u = picks[0][0]
            for m in mgrs:
                m.ensure_capacity(m.get(u), min(64, len(m.get(u).tokens)
                                                + 24))
        if not picks:
            continue
        rb = build_ragged_batch([(mgrs[0].get(u), n) for u, n in picks],
                                mgrs[0], 32)
        want, want_uids = _unpacked_build(
            [(mgrs[1].get(u), n) for u, n in picks], mgrs[1], 32)
        got = rb.index.arrays()
        for g, w in zip(got, want):
            assert g.dtype == np.int32 and np.shares_memory(g, rb.index.buf)
            np.testing.assert_array_equal(g, w)
        assert rb.uids_by_slot == want_uids
        assert list(rb.uids_by_slot) == list(want_uids)     # the order too
        assert rb.n_tokens == sum(n for _, n in picks)
        # two buffers a bucket, in turn: the step before this one may
        # still be on the device, its transfer not yet read
        assert rb.index is mgrs[0]._index[rb.index.rows, rb.index.blocks,
                                          mgrs[0]._turn]
        assert last is None or not np.shares_memory(rb.index.buf, last)
        last = rb.index.buf
        seen.add((rb.index.rows, rb.index.blocks))
        for u in live:
            seq = mgrs[0].get(u)
            if not seq.uncached and len(seq.tokens) > rng.integers(2, 45):
                for m in mgrs:
                    m.flush(u)
    assert len(seen) >= 7, seen
    # two buffers a bucket at most, however many steps
    assert {k[:2] for k in mgrs[0]._index} == seen
    assert len(mgrs[0]._index) <= 2 * len(seen)


def test_packed_index_is_a_pytree_of_one_leaf():
    sizes = (16, 9, 4)
    buf = np.arange(PackedIndex.size(*sizes), dtype=np.int32)
    index = PackedIndex(buf, *sizes)
    leaves, tree = jax.tree.flatten(index)
    assert len(leaves) == 1 and leaves[0] is buf
    back = jax.tree.unflatten(tree, [jnp.asarray(buf)])
    assert (back.rows, back.slots, back.blocks) == sizes
    # the same cut on the host and inside a program, by static slices
    cut = jax.jit(lambda ix: ix.arrays())
    for host, dev in zip(index.arrays(), cut(back)):
        np.testing.assert_array_equal(host, np.asarray(dev))
    text = cut.lower(back).as_text()
    assert "dynamic_slice" not in text and "gather" not in text
    shapes = [a.shape for a in index.arrays()]
    assert shapes == [(16,)] * 4 + [(9, 4), (9,), (9,)]
    assert sum(int(np.prod(s)) for s in shapes) == buf.size
    # another bucket is another program: the sizes are static
    other = PackedIndex(jnp.zeros((PackedIndex.size(32, 9, 4),), jnp.int32),
                        32, 9, 4)
    assert jax.tree.structure(other) != tree


# -- whole steps against the unpacked programs ------------------------------
_CASES = {
    "llama": ("llama-tiny", {}),
    "falcon_h1_state": ("falcon-h1-tiny", {}),
    "llama_int8_kv": ("llama-tiny", {"memory_config": dict(
        ENGINE["memory_config"], kv_dtype="int8")}),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_steps_same_bits_as_the_unpacked_programs(case):
    """``put()``, ``step()`` and ``step(return_logits=True)`` over mixed
    prefill and decode, through every token and block bucket: tokens,
    logits, both pools and a mixer's recurrent state, bit for bit."""
    name, cfg = _CASES[case]
    model, eng = _engine(name, **cfg)
    _, twin = _engine(name, **cfg)
    old = _Unpacked(twin)
    eng.tracer = Tracer(enabled=True)
    rng = np.random.default_rng(7)
    arrivals = {0: [3], 1: [7], 2: [12], 3: [20], 5: [50], 9: [2, 6]}
    live, uid = [], 0
    for i in range(16):
        prompts = [rng.integers(1, model.vocab_size, size=n).tolist()
                   for n in arrivals.get(i, [])]
        uids = list(range(uid, uid + len(prompts)))
        uid += len(prompts)
        for u, p in zip(uids, prompts):
            twin.admit(u, p)
        kind = ("put", "sampled", "logits")[i % 3]
        if kind == "put":
            got = eng.put(uids, prompts)
        else:
            for u, p in zip(uids, prompts):
                eng.admit(u, p)
            got = eng.step(return_logits=(kind == "logits"))
        if kind == "sampled":
            want, by_slot = old.step(
                old.sampled, key=jax.random.PRNGKey(1),
                temperature=jnp.float32(1e-6), greedy=True, top_k=0,
                top_p=None)
        else:
            want, by_slot = old.step(old.forward)
        assert sorted(got) == sorted(by_slot.values())
        for slot, u in by_slot.items():
            if kind == "sampled":
                assert got[u] == int(want[slot])
            else:
                np.testing.assert_array_equal(got[u], want[slot])
        _same_bits((eng.cache_k, eng.cache_v, eng.state),
                   (twin.cache_k, twin.cache_v, twin.state))
        live += uids
        for u in list(got):
            nxt = got[u] if kind == "sampled" else int(np.argmax(got[u]))
            seq = eng.state_manager.get(u)
            if len(seq.tokens) >= 56 or (u == 0 and i == 8):
                live.remove(u)
                eng.flush(u)
                twin.flush(u)
            else:
                eng.extend(u, nxt)
                twin.extend(u, nxt)
    spans = [e for e in eng.tracer.snapshot() if e["ph"] == "X"]
    shapes = {(e["args"]["t_bucket"], e["args"]["nb_bucket"])
              for e in spans if e["name"] == "v2.dispatch"}
    assert {t for t, _ in shapes} == {16, 32}
    assert {nb for _, nb in shapes} == {1, 2, 4, 8, 16}
    slots = eng.state_manager.max_seqs + 1
    h2d = [e["args"] for e in spans if e["name"] == "v2.h2d"]
    sizes = {4 * PackedIndex.size(t, slots, nb) for t, nb in shapes}
    assert {a["arrays"] for a in h2d} == {1}
    assert {a["bytes"] for a in h2d} == sizes


def test_verify_step_same_bits_as_the_unpacked_program():
    """``verify_step`` goes through the same launch: every row's argmax
    and the pools against ``ragged_forward_verify`` on separate arrays."""
    model, eng = _engine("llama-tiny")
    _, twin = _engine("llama-tiny")
    old = _Unpacked(twin)
    rng = np.random.default_rng(3)
    rows, verify = [], eng._verify

    def captured(*args):
        out = verify(*args)
        rows.append(out[0])
        return out

    captured.__name__ = verify.__name__
    eng._verify = captured
    eng.tracer = Tracer(enabled=True)
    for u, n in ((0, 9), (1, 5)):
        prompt = rng.integers(1, model.vocab_size, size=n).tolist()
        eng.admit(u, prompt)
        twin.admit(u, prompt)
    out = eng.step()
    old.step(old.forward)
    for u, tok in out.items():
        eng.extend(u, tok)
        twin.extend(u, tok)
    for round_ in range(3):
        props = {0: rng.integers(1, model.vocab_size, size=3).tolist(),
                 1: rng.integers(1, model.vocab_size,
                                 size=round_).tolist()}
        # the old way: proposals appended, one chunk a sequence
        schedule = []
        for u, p in props.items():
            seq = twin.state_manager.get(u)
            seq.tokens.extend(p)
            schedule.append((seq, 1 + len(p)))
        twin.scheduler.next_schedule = lambda s=schedule: s
        want, _ = old.step(old.verify)
        del twin.scheduler.next_schedule
        accepted = eng.verify_step(props)
        np.testing.assert_array_equal(np.asarray(rows[-1]), want)
        _same_bits((eng.cache_k, eng.cache_v), (twin.cache_k, twin.cache_v))
        for u, acc in accepted.items():     # rewind the twin as verify did
            a, b = eng.state_manager.get(u), twin.state_manager.get(u)
            b.tokens, b.num_cached = list(a.tokens), a.num_cached
    spans = [e for e in eng.tracer.snapshot() if e["ph"] == "X"]
    assert [e["name"] for e in spans[-2:]] == ["v2.h2d", "v2.dispatch"]
    assert spans[-2]["args"]["arrays"] == 1
    assert ("ragged_verify", 16, 4) in eng._dispatched


# -- one transfer, one program ----------------------------------------------
def _programs_run(trace_dir):
    """Jitted programs the process called while it was traced, by name."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    names = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("PjitFunction("):
                    names[ev.name[len("PjitFunction("):-1]] += 1
    return names


def test_greedy_step_is_one_transfer_and_one_program(tmp_path):
    # pages of 16 rows: the traced steps stay in one bucket (contexts 21
    # to 30), so nothing is traced anew while the profiler listens
    model, eng = _engine("llama-tiny", memory_config={"num_blocks": 24,
                                                      "block_size": 16})
    eng.tracer = Tracer(enabled=True)
    puts, scalars = [], []
    put, program = eng._put, eng._step_sampled
    eng._put = lambda a: puts.append(a.buf.nbytes) or put(a)

    def sampled(*args, **kw):
        assert len(args) == 4       # weights, two pools, the index buffer
        scalars.append({k: type(v) for k, v in kw.items()
                        if not isinstance(v, (bool, int, jax.Array))})
        return program(*args, **kw)

    sampled.__name__ = program.__name__
    eng._step_sampled = sampled
    eng.admit(0, list(range(1, 12)))
    eng.admit(1, list(range(5, 8)))

    def steps(n, **kw):
        for _ in range(n):
            for u, tok in eng.step(**kw).items():
                eng.extend(u, tok)

    steps(8)
    steps(1, temperature=0.7, top_p=0.9)
    del puts[:], scalars[:]
    eng.tracer = Tracer(enabled=True)
    with jax.profiler.trace(str(tmp_path / "greedy")):
        steps(5)
    assert _programs_run(tmp_path / "greedy").keys() == {
        "ragged_step_sampled"}
    with jax.profiler.trace(str(tmp_path / "sampled")):
        steps(5, temperature=0.7, top_p=0.9)
    ran = _programs_run(tmp_path / "sampled")
    assert set(ran) == {"ragged_step_sampled", "_threefry_split", "_unstack"}
    assert len(set(ran.values())) == 1      # one of each a step
    # what is not a device array already is a host scalar of the call's
    assert scalars == [{"temperature": np.float32, "top_p": type(None)}] * 5 \
        + [{"temperature": np.float32, "top_p": np.float32}] * 5
    assert len(puts) == 10                  # one transfer a step
    spans = [e for e in eng.tracer.snapshot() if e["ph"] == "X"]
    assert [e["args"]["arrays"] for e in spans
            if e["name"] == "v2.h2d"] == [1] * 10
    assert [e["args"]["programs"] for e in spans
            if e["name"] == "v2.dispatch"] == [1] * 5 + [3] * 5
    assert [e["args"]["bytes"] for e in spans
            if e["name"] == "v2.h2d"] == puts


def test_greedy_step_keeps_the_key_and_compiles_no_second_variant():
    model, eng = _engine("llama-tiny")
    key0 = np.asarray(eng._step_key)
    # the key lives on the engine's mesh slice, not on the default device
    assert eng._step_key.sharding.mesh == eng.topology.mesh
    eng.admit(0, [4, 5, 6])
    for _ in range(3):
        eng.extend(0, eng.step()[0])
    np.testing.assert_array_equal(np.asarray(eng._step_key), key0)
    # one program a bucket: the signature is the sampled step's own
    assert eng._dispatched == {("ragged_step_sampled", 16, 1, True, 0, True),
                               ("ragged_step_sampled", 16, 2, True, 0, True)}
    assert eng._step_sampled._cache_size() == 2
    # a key of the caller's is still taken as it is, greedy or not
    eng.extend(0, eng.step(key=jax.random.PRNGKey(9))[0])
    np.testing.assert_array_equal(np.asarray(eng._step_key), key0)


def _draws(seed, n=6, **kw):
    model, eng = _engine("llama-tiny", seed=seed)
    eng.admit(0, [4, 5, 6, 7])
    keys, toks = [], []
    for _ in range(n):
        tok = eng.step(temperature=1.5, **kw)[0]
        keys.append(tuple(np.asarray(eng._step_key).tolist()))
        toks.append(tok)
        eng.extend(0, tok)
    return keys, toks


def test_non_greedy_steps_split_a_fresh_key_each():
    """Two non-greedy steps never draw from one key; an engine seed gives
    the draws it gave (the split is the parent's: ``PRNGKey(seed ^
    0x57E9)`` split once a step, the second half drawn from)."""
    keys, toks = _draws(seed=0)
    assert len(set(keys)) == len(keys)
    assert _draws(seed=0) == (keys, toks)
    other_keys, other = _draws(seed=1)
    assert other_keys != keys
    carry = jax.random.PRNGKey(0 ^ 0x57E9)
    for key in keys:
        carry, _ = jax.random.split(carry)
        assert tuple(np.asarray(carry).tolist()) == key
    # flat logits at a high temperature: six draws are not one token
    assert len(set(toks)) > 1
    # top-p rides the same call as a host scalar
    assert _draws(seed=0, n=3, top_p=0.9)[0] == keys[:3]


# -- the audit's view of the step ------------------------------------------
@pytest.mark.parametrize("phase", ["decode", "prefill", "verify"])
def test_audit_step_args_lower_the_packed_programs(phase):
    from deepspeed_tpu.analysis.auditor import audit_v2_engine

    model, eng = _engine("llama-tiny")
    fn, args = eng.audit_step_args(phase)
    assert fn is (eng._verify if phase == "verify" else eng._step)
    assert len(args) == 4 == len(eng.audit_arg_categories())
    index = args[3]
    slots, nb = eng.state_manager.max_seqs + 1, 16
    rows = 16 if phase == "decode" else 32
    assert (index.rows, index.slots, index.blocks) == (rows, slots, nb)
    text = fn.lower(*args).as_text()
    name = "ragged_verify" if phase == "verify" else "ragged_step"
    assert f"module @jit_{name} " in text
    # the index buffer is ONE parameter of the program, beside the pools
    assert f"tensor<{PackedIndex.size(rows, slots, nb)}xi32>" in text
    report = audit_v2_engine(eng, phase)
    assert report.label == f"v2_{phase}"


def test_audit_step_args_with_a_recurrent_state():
    model, eng = _engine("falcon-h1-tiny")
    fn, args = eng.audit_step_args("prefill")
    assert len(args) == 5 == len(eng.audit_arg_categories())
    assert args[4] is eng.state
    lowered = fn.lower(*args)
    # the pools and the state are donated where they now stand: 1, 2, 4
    donated = [i for i, a in enumerate(lowered.args_info[0])
               if any(leaf.donated for leaf in jax.tree.leaves(a))]
    assert donated == [1, 2, 4]


# -- the sampled tokens fed back on the device (ISSUE 50) -------------------
_FED = {"llama-tiny": {}, "falcon-h1-tiny": {},
        "trinity-tiny": {"memory_config": {"num_blocks": 96, "block_size": 4,
                                           "window_blocks": 48}}}


@pytest.mark.parametrize("name", list(_FED))
def test_the_fed_back_row_equals_the_row_fed_from_the_host(name):
    """A step whose rows read the tokens of the step before where its
    program left them (``launch`` twice, ``IN_FLIGHT`` between) against
    the same step given them by the host (``step``, ``extend``): tokens,
    both pools and whatever state the model carries, to the bit.  Prompts
    mid-chunk beside decoding sequences, and a sequence that rides no
    further step."""
    from deepspeed_tpu.inference.v2.ragged import IN_FLIGHT

    def fresh():
        model, eng = _engine(name, **_FED[name])
        rng = np.random.default_rng(5)
        for uid, n in enumerate((3, 30, 41, 7)):
            eng.admit(uid, rng.integers(1, model.vocab_size, n).tolist())
        return eng

    def held(eng):
        return [np.asarray(a) for a in jax.tree.leaves(
            (eng.cache_k, eng.cache_v, eng.state))]

    host, dev = fresh(), fresh()
    stop_at = {0: 4}                # uid 0 rides no step after its fourth
    got_host, got_dev = [], []
    done = set()
    flight = dev.launch()
    for step in range(12):
        toks = host.step()
        for uid, tok in toks.items():
            if uid in done:
                continue
            if sum(uid in t for t in got_host) + 1 >= stop_at.get(uid, 99):
                done.add(uid)
            else:
                host.extend(uid, tok)
        got_host.append(toks)
        # the same step on the other engine: its tokens stay on the device
        for uid in flight.uids:
            if uid not in done:
                dev.extend(uid, IN_FLIGHT)
        ahead = dev.launch()
        got_dev.append(dev.fetch(flight))
        flight = ahead
        assert got_dev[-1] == toks, step
        if flight is None:
            break
    assert flight is not None and len(got_host) == 12
    dev.fetch(flight)
    host.step()
    for a, b in zip(held(host), held(dev)):
        np.testing.assert_array_equal(a, b)
    for uid in range(4):
        assert host.state_manager.get(uid).tokens[:-1] == \
            dev.state_manager.get(uid).tokens[:-1] or uid in done
        assert IN_FLIGHT not in dev.state_manager.get(uid).tokens[:-1]
    # one program a bucket on both, and the same ones
    assert dev._dispatched == host._dispatched
    assert dev._step_sampled._cache_size() == len(dev._dispatched)


def test_the_sampled_step_lowers_with_the_fed_back_operand():
    """The program the serve loop runs: the tokens of the step before are
    ONE more parameter, ``[slots]`` int32, read before the embedding;
    what is donated stays where it was (the pools, and a mixer's state by
    name); the audited program (``ragged_step``: logits to the host)
    takes no such operand."""
    model, eng = _engine("falcon-h1-tiny")
    slots = eng.state_manager.max_seqs + 1
    assert eng._prev.shape == (slots,) and eng._prev.dtype == jnp.int32
    assert eng._prev.sharding.mesh == eng.topology.mesh
    fn, args = eng.audit_step_args("decode")
    assert fn is eng._step and len(args) == 5
    lowered = eng._step_sampled.lower(
        *args[:4], prev=eng._prev, key=eng._step_key,
        temperature=np.float32(1.0), greedy=True, top_k=0, top_p=None,
        state=eng.state)
    text = lowered.as_text()
    assert "module @jit_ragged_step_sampled " in text
    assert f"tensor<{slots}xi32>" in text
    donated = [i for i, a in enumerate(lowered.args_info[0])
               if any(leaf.donated for leaf in jax.tree.leaves(a))]
    assert donated == [1, 2]
    assert all(leaf.donated
               for leaf in jax.tree.leaves(lowered.args_info[1]["state"]))
    assert not lowered.args_info[1]["prev"].donated


# -- the drafting step's fed-back operand (ISSUE 55) -------------------------
def _lowered_sampled_step(name):
    model, eng = _engine(name)
    _, args = eng.audit_step_args("decode")
    return eng._step_sampled.lower(
        *args[:4], prev=eng._prev, key=eng._step_key,
        temperature=np.float32(1.0), greedy=True, top_k=0, top_p=None,
        **eng._state_kw()).as_text()


# sha256 of the lowered text at the commit before the drafting step took
# its operand (f9594be), by the same call under this suite's settings
_PARENT_TEXT = {
    "llama-tiny":
        "377a2432af433de2cac8f7b2cf217458dbd026a2875f6d558183111a1a1a83cf",
    "falcon-h1-tiny":
        "b70c624e77388f7ad688d437d637493fd66996c53ebb9291b5e62ab679747fc2",
}


@pytest.mark.parametrize("name", list(_PARENT_TEXT))
def test_a_step_that_drafts_nothing_lowers_to_the_parents_text(name):
    """What a self-drafting step reads of the step before it is the
    drafting program's alone: the sampled step of an engine that does
    not draft is, to the letter, the program it was."""
    import hashlib

    text = _lowered_sampled_step(name)
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_TEXT[name]


def test_a_drafting_engine_compiles_one_program_a_bucket():
    """Steps fetched before the next is launched (``step_bursts``) and
    steps launched ahead (``launch``, ``IN_FLIGHT``, ``launch``,
    ``fetch``) run the SAME programs, one a (token, block) bucket: the
    ``out`` of the step before is one more operand, ``[4, slots]`` int32,
    zeros before any step, read or not."""
    from deepspeed_tpu.inference.v2.ragged import IN_FLIGHT

    model = get_model_config("glm-5-tiny")
    eng = InferenceEngineV2(model, dict(
        ENGINE, self_draft=True, max_context=96,
        memory_config={"num_blocks": 96, "block_size": 4}), seed=3)
    slots = eng.state_manager.max_seqs + 1
    assert eng._prev_draft.shape == (4, slots)
    assert eng._prev_draft.sharding.mesh == eng.topology.mesh
    prompt = np.random.default_rng(2).integers(1, 512, 21).tolist()

    def serve(ahead, new=24):
        eng.admit(7, prompt)
        out, flight = [], None
        while len(out) < new:
            if not ahead:
                bursts = eng.step_bursts()
                if 7 in bursts:
                    out += bursts[7]
                    eng.extend(7, bursts[7][-1])
                continue
            nxt = eng.launch()
            if flight is not None:
                out += eng.fetch(flight).get(7, [])
            flight = nxt
            if flight is not None and 7 in flight.uids:
                eng.extend(7, IN_FLIGHT)
        if flight is not None:
            eng.fetch(flight)
        eng.flush(7)
        return out[:new]

    behind = serve(ahead=False)
    warm = set(eng._dispatched)
    assert eng._draft._cache_size() == len(warm) > 1
    assert serve(ahead=True) == behind
    assert eng._dispatched == warm
    assert eng._draft._cache_size() == len(warm)
    assert eng.drafts_accepted > 0 and eng._flight is None
    sizes = (16, slots, 4, True)
    index = PackedIndex(jnp.zeros((PackedIndex.size(*sizes),), jnp.int32),
                        *sizes)
    lowered = eng._draft.lower(eng.params, eng.cache_k, eng.cache_v, index,
                               eng._prev_draft)
    assert f"tensor<4x{slots}xi32>" in lowered.as_text()
    donated = [i for i, a in enumerate(lowered.args_info[0])
               if any(leaf.donated for leaf in jax.tree.leaves(a))]
    assert donated == [1, 2]
