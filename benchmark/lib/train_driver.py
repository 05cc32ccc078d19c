"""``train_steps``: ``ds.initialize`` and ``engine.train_batch`` on a new
seeded batch every step, fed by a host thread."""

from __future__ import annotations

import math
import queue
import threading
import time


from benchmark.lib import flops
from benchmark.lib import traffic as tr
from benchmark.lib.manifest import Cell
from benchmark.lib.model import build_model, fwd_flops_per_tok
from benchmark.lib.peaks import peaks_for
from benchmark.lib.profiler import DeviceTracer
from benchmark.lib.run import Run, log, seed32

QUEUE_DEPTH = 2         # batches the host thread keeps ahead of the step
WARMUP_STEPS = 2        # before the window: compile, then one from cache
TRACED_STEPS = 3        # a traced run traces this many steps

def _feeder(out: queue.Queue, stop: threading.Event, make) -> None:
    step = 0
    while not stop.is_set():
        batch = make(step)
        while not stop.is_set():
            try:
                out.put(batch, timeout=0.1)
                break
            except queue.Full:
                continue
        step += 1


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float, compiles) -> Run:
    import jax

    import deepspeed_tpu as ds

    cfg, traffic = cell.config, cell.traffic
    model = build_model(cfg)
    ds_config = dict(cfg["ds_config"])
    ds_config["mesh"] = dict(cfg["mesh"])
    t0 = time.perf_counter()
    engine, _, _, _ = ds.initialize(model=model, config=ds_config,
                                    seed=seed32(seed))
    feeder = stop = None
    tracer = DeviceTracer(cell.root, cell.name) if traced else None
    try:
        jax.block_until_ready(engine.params)
        dp = engine.topology.dp_size
        rows = (ds_config["train_micro_batch_size_per_gpu"]
                * ds_config["gradient_accumulation_steps"] * dp)
        seq = int(traffic["seq_len"])
        log(f"[train] {cfg['registry']['name']} layers={model.num_layers} "
            f"hidden={model.hidden_size} heads={model.num_heads} "
            f"vocab={model.vocab_size} seq={seq} rows/step={rows} "
            f"mesh={cfg['mesh']} zero="
            f"{ds_config['zero_optimization']['stage']}; engine init "
            f"{time.perf_counter() - t0:.1f} s")

        # correct: the engine's loss on seeded rows against the reference
        t1 = time.perf_counter()
        n_rows = int(traffic["correct_rows"])
        probe = tr.train_batch(traffic, seed, -1, n_rows, model.vocab_size)
        got = float(engine.eval_batch(probe))
        want = float(cell.reference().loss(
            engine.params, probe["input_ids"], probe["labels"], cfg,
            jax.devices()[0]))
        tol = float(cfg["loss_tolerance"])
        correct = math.isfinite(got) and abs(got - want) <= tol
        log(f"[train] loss on {n_rows} seeded row(s): engine {got:.5f}, "
            f"plain float32 reference {want:.5f}, |diff| "
            f"{abs(got - want):.5f} (tolerance {tol})")

        def make(step):
            return tr.train_batch(traffic, seed, step, rows,
                                  model.vocab_size)

        t2 = time.perf_counter()
        for i in range(WARMUP_STEPS):
            jax.block_until_ready(engine.train_batch(make(-2 - i)))
        log(f"[train] correctness {t2 - t1:.1f} s; "
            f"{WARMUP_STEPS} warm-up steps "
            f"{time.perf_counter() - t2:.1f} s; compile requests so far "
            f"{compiles.requests}, from the cache {compiles.hits}")
        stop = threading.Event()
        batches: queue.Queue = queue.Queue(QUEUE_DEPTH)
        feeder = threading.Thread(target=_feeder, name="bench-feeder",
                                  args=(batches, stop, make), daemon=True)
        feeder.start()
        while not batches.full():
            time.sleep(0.005)

        compiles.mark()
        n_traced = TRACED_STEPS if traced else 0
        losses, step_s, wait_s = [], [], []
        setup_s = time.perf_counter() - t_start
        w0 = time.perf_counter()
        if tracer is not None:
            tracer.start()
        while True:
            a = time.perf_counter()
            batch = batches.get()
            b = time.perf_counter()
            loss = jax.block_until_ready(engine.train_batch(batch))
            c = time.perf_counter()
            losses.append(float(loss))
            wait_s.append(b - a)
            step_s.append(c - b)
            if tracer is not None and len(step_s) == n_traced:
                tracer.stop()
            if c - w0 >= seconds:
                break
        window = c - w0
        in_window = compiles.since_mark
    finally:
        if tracer is not None:
            tracer.stop()
        if stop is not None:
            stop.set()
            feeder.join(timeout=10)
        engine.destroy()
    if feeder.is_alive():
        raise RuntimeError("the feeder thread did not stop")

    steps = len(step_s)
    tokens_per_s = steps * rows * seq / window
    finite = all(math.isfinite(x) for x in losses)
    if not finite:
        log(f"[train] non-finite loss in the window: {losses}")
    kind = jax.devices()[0].device_kind
    n_dev = len(jax.devices())
    f_tok = fwd_flops_per_tok(model, seq)
    try:
        mfu = flops.mfu(tokens_per_s, f_tok, n_dev,
                        peaks_for(kind)["flops_per_s_bf16"])
        log(f"[train] {steps} steps in {window:.3f} s: {tokens_per_s:.1f} "
            f"tokens/s on {n_dev} x {kind}; {3 * f_tok / 1e9:.3f} GFLOP a "
            f"token fwd+bwd, MFU {mfu:.4f}; loss {losses[0]:.4f} -> "
            f"{losses[-1]:.4f}; compiles in the window {in_window}")
    except KeyError:
        log(f"[train] {steps} steps in {window:.3f} s on {kind} (not in "
            f"the peaks table, so no MFU)")
    untraced = step_s[n_traced:] or step_s
    return Run(
        correct=bool(correct and finite), attempted=steps, failed=0,
        end_to_end={"train_tokens_per_s": tokens_per_s}, setup_s=setup_s,
        counters={"step_s": untraced, "input_wait_s": wait_s,
                  "traced_steps": n_traced, "rows": rows, "seq": seq,
                  "compiles_in_window": in_window, "model": model,
                  "micro_batch": ds_config["train_micro_batch_size_per_gpu"],
                  "gas": ds_config["gradient_accumulation_steps"],
                  "device_kind": kind, "chips": n_dev},
        trace=tracer.reduce() if tracer is not None else None)
