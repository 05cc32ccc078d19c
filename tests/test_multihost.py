"""Multi-host correctness: 2 real ``jax.distributed`` CPU processes train,
checkpoint, barrier, and convert to a universal checkpoint; a separate
1-process run reloads it at the different world size.

This is the analog of the reference's ``DistributedExec`` harness
(``tests/unit/common.py:134``, file-store rendezvous at ``:331``) with the
rendezvous replaced by a jax.distributed coordinator, and of
``checkpoint/ds_to_universal.py:112`` elasticity coverage.

Each worker runs in a fresh subprocess (its own JAX runtime): 2 processes
x 2 local CPU devices = a 4-device global mesh, dp=4.
"""

import json
import os
import pickle
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import os, sys, json, pickle
    import numpy as np

    rank = int(sys.argv[1]); world = int(sys.argv[2])
    port = sys.argv[3]; out_dir = sys.argv[4]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["DSTPU_COORDINATOR"] = f"localhost:{port}"
    os.environ["DSTPU_NUM_PROCS"] = str(world)
    os.environ["DSTPU_PROC_ID"] = str(rank)
    sys.path.insert(0, os.environ["DSTPU_TEST_REPO"])

    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.models import get_model_config

    topo = comm.init_distributed(mesh_sizes={"data": 4})
    assert jax.process_count() == world, jax.process_count()
    assert len(jax.devices()) == 4, jax.devices()
    assert comm.get_world_size() == 4  # world = devices (2 procs x 2 local)
    assert comm.get_rank() == rank     # host-level rank = process index
    comm.barrier()

    # host-object collectives across REAL processes (ref
    # dist.all_gather_object/broadcast_object_list, comm.py:247/:229)
    gathered = comm.all_gather_object({"rank": rank, "tag": "x" * (rank + 1)})
    assert gathered == [{"rank": 0, "tag": "x"}, {"rank": 1, "tag": "xx"}], gathered
    objs = [f"from-{rank}", rank * 10]
    comm.broadcast_object_list(objs, src=1)
    assert objs == ["from-1", 10], objs
    # src is a GLOBAL rank (reference semantics): with the reversed group
    # (1, 0), src=1 must still pick process 1's payload, not index 1.
    objs = [f"from-{rank}"]
    comm.broadcast_object_list(objs, src=1, group=(1, 0))
    assert objs == ["from-1"], objs
    try:
        comm.broadcast_object_list([0], src=5, group=(1, 0))
        raise AssertionError("src outside group must raise")
    except ValueError:
        pass
    comm.monitored_barrier(timeout=60.0)

    model = get_model_config("gpt2-tiny")
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
        "mesh": {"data": 4},
        "checkpoint": {"writer": {"type": "fast"}},
    }
    engine, _, _, _ = ds.initialize(model=model, config=cfg, seed=17)
    rng = np.random.default_rng(0)  # identical data on both processes
    ids = rng.integers(0, model.vocab_size, size=(8, 33), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    losses = [float(np.asarray(engine.train_batch(batch))) for _ in range(3)]
    assert all(np.isfinite(losses)), losses

    # ---- fast engine save: per-process files, rank-0 commit --------------
    engine.save_checkpoint(out_dir, tag="t1")
    comm.barrier()

    # perturb, then reload and check the roundtrip restores training state
    before = np.asarray(
        jax.experimental.multihost_utils.process_allgather(
            engine.params["embed"]["tokens"] if isinstance(engine.params["embed"], dict) else engine.params["embed"], tiled=True))
    engine.params = jax.tree.map(lambda x: x * 0, engine.params)
    engine.load_checkpoint(out_dir, tag="t1")
    after = np.asarray(
        jax.experimental.multihost_utils.process_allgather(
            engine.params["embed"]["tokens"] if isinstance(engine.params["embed"], dict) else engine.params["embed"], tiled=True))
    np.testing.assert_array_equal(before, after)
    loss_after = float(np.asarray(engine.train_batch(batch)))
    assert np.isfinite(loss_after)

    # ---- pickle engine save (per-process mp_rank files) + universal ------
    from deepspeed_tpu.checkpoint.engine import save_checkpoint
    from deepspeed_tpu.checkpoint.universal import ds_to_universal
    pik_dir = os.path.join(out_dir, "pickle_ckpt")
    save_checkpoint(engine, pik_dir, tag="u1")
    comm.barrier()
    uni = ds_to_universal(pik_dir, tag="u1")
    comm.barrier()

    if rank == 0:
        # snapshot of the weights the u1/universal checkpoint contains
        final = np.asarray(
            jax.experimental.multihost_utils.process_allgather(
                engine.params["embed"]["tokens"], tiled=True))
        with open(os.path.join(out_dir, "result.json"), "w") as f:
            json.dump({"losses": losses, "loss_after": loss_after,
                        "universal_dir": uni}, f)
        np.save(os.path.join(out_dir, "final_wte.npy"), final)
    comm.barrier()
    print(f"worker {rank} OK", flush=True)
""")

RELOADER = textwrap.dedent("""
    import os, sys, json
    import numpy as np

    out_dir = sys.argv[1]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.pop("DSTPU_COORDINATOR", None)
    os.environ.pop("DSTPU_NUM_PROCS", None)
    sys.path.insert(0, os.environ["DSTPU_TEST_REPO"])

    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.checkpoint.universal import (load_universal,
                                                    resolve_universal_dir)
    from deepspeed_tpu.models import get_model_config

    with open(os.path.join(out_dir, "result.json")) as f:
        res = json.load(f)

    # DIFFERENT topology than the save: 1 process, dp=2 x tp=2 over 4 devices
    model = get_model_config("gpt2-tiny")
    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
        "mesh": {"data": 2, "tensor": 2},
    }
    engine, _, _, _ = ds.initialize(model=model, config=cfg, seed=99)
    load_universal(engine, resolve_universal_dir(res["universal_dir"]))

    saved = np.load(os.path.join(out_dir, "final_wte.npy"))
    np.testing.assert_array_equal(np.asarray(engine.params["embed"]["tokens"] if isinstance(engine.params["embed"], dict) else engine.params["embed"]), saved)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size, size=(8, 33), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    loss = float(np.asarray(engine.train_batch(batch)))
    assert np.isfinite(loss)
    print(f"reloader OK loss={loss}", flush=True)
""")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_workers(script: str, args_per_proc, timeout=420):
    # log to files, not pipes: a full pipe buffer on one worker while the
    # harness blocks on another would deadlock the collective they share
    import tempfile

    procs, files = [], []
    for i, args in enumerate(args_per_proc):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("DSTPU_", "XLA_", "JAX_"))}
        env["DSTPU_TEST_REPO"] = REPO
        f = tempfile.NamedTemporaryFile("w+", suffix=f"_w{i}.log", delete=False)
        files.append(f)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script, *map(str, args)],
            stdout=f, stderr=subprocess.STDOUT, env=env))
    outs = []
    for p, f in zip(procs, files):
        try:
            p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
        f.flush()
        f.seek(0)
        outs.append(f.read())
        f.close()
        os.unlink(f.name)
    return procs, outs


@pytest.mark.slow
def test_two_process_train_checkpoint_universal(tmp_path):
    """2 jax.distributed processes: init, barrier, train dp=4, fast-engine
    save/load roundtrip, pickle save, universal conversion."""
    port = _free_port()
    out = str(tmp_path)
    procs, logs = _run_workers(
        WORKER, [(r, 2, port, out) for r in range(2)])
    for p, log in zip(procs, logs):
        assert p.returncode == 0, f"worker failed:\n{log[-3000:]}"

    # per-process fast-engine files exist (no clobbering)
    d = os.path.join(out, "t1")
    assert os.path.exists(os.path.join(d, "model_states_p000.bin"))
    assert os.path.exists(os.path.join(d, "model_states_p001.bin"))
    assert os.path.exists(os.path.join(d, "meta.json"))
    with open(os.path.join(d, "meta.json")) as f:
        assert json.load(f)["process_count"] == 2
    # per-process pickle files exist
    pd = os.path.join(out, "pickle_ckpt", "u1")
    assert os.path.exists(os.path.join(pd, "mp_rank_00_model_states.pt"))
    assert os.path.exists(os.path.join(pd, "mp_rank_01_model_states.pt"))

    # both processes trained identical losses (same data, dp replicas agree)
    with open(os.path.join(out, "result.json")) as f:
        res = json.load(f)
    assert res["losses"][-1] < res["losses"][0]

    # ---- elasticity: reload the universal ckpt at world_size=1, tp=2 -----
    procs, logs = _run_workers(RELOADER, [(out,)])
    assert procs[0].returncode == 0, f"reloader failed:\n{logs[0][-3000:]}"
    assert "reloader OK" in logs[0]
