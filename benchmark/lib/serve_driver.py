"""``open_loop`` and ``closed_loop``: ``InferenceEngineV2`` behind
``InferenceServer``, requests through ``submit`` and the stream it
returns, timed at the client by ``lib.loadgen``."""

from __future__ import annotations

import statistics
import threading
import time

import numpy as np

from benchmark.lib import traffic as tr
from benchmark.lib.loadgen import run_load
from benchmark.lib.manifest import Cell
from benchmark.lib.model import build_model
from benchmark.lib.profiler import DeviceTracer
from benchmark.lib.run import Run, log, seed32
from benchmark.lib.stats import percentile

# correct: two seeded prompts, each longer than one prefill chunk, then
# this many decoded tokens through the cache
CORRECT_PROMPTS = (300, 290)
CORRECT_DECODE = 8
POLL_S = 0.002          # the client looks for new tokens this often
TRACED_S = 5.0          # a traced run traces this much, mid-window


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def logit_errors(cell: Cell, eng, model, seed: int,
                 reference_params=None) -> dict:
    """Prefill, then ``CORRECT_DECODE`` tokens through the cache, against
    the plain reference's full forward over the same tokens, for each of
    the seeded prompts: the logits of the last position at every step.
    ``rms``: root mean square of (logit - reference) over every checked
    position and the whole vocabulary, as a share of the reference
    logits' own root mean square; ``max``: the largest difference as a
    share of the largest reference |logit|; ``agree``: positions with the
    same argmax, of ``positions``.  The reference runs on the engine's
    own weights unless ``reference_params`` gives others."""
    import jax

    cfg = cell.config
    limit = eng.cfg.max_context - CORRECT_DECODE - 1
    rng = np.random.default_rng([seed % 2 ** 32, 77])
    sq_err = sq_ref = worst = 0.0
    agree = positions = 0
    for k, n in enumerate(CORRECT_PROMPTS):
        n = min(n, limit)
        uid = (1 << 30) + k
        prompt = rng.integers(0, model.vocab_size, size=n).tolist()
        out = eng.put([uid], [prompt])
        while uid not in out:
            out = eng.put([], [])
        rows, toks = [np.asarray(out[uid], np.float32)], []
        for _ in range(CORRECT_DECODE):
            toks.append(int(rows[-1].argmax()))
            eng.extend(uid, toks[-1])
            rows.append(np.asarray(eng.put([], [])[uid], np.float32))
        eng.flush(uid)
        ref = np.asarray(cell.reference().logits(
            eng.params if reference_params is None else reference_params,
            np.asarray([prompt + toks]), cfg, jax.devices()[0],
            last=CORRECT_DECODE + 1))[0].astype(np.float64)
        got = np.stack(rows).astype(np.float64)
        sq_err += float(((got - ref) ** 2).sum())
        sq_ref += float((ref ** 2).sum())
        worst = max(worst, float(np.abs(got - ref).max() / np.abs(ref).max()))
        agree += int((got.argmax(-1) == ref.argmax(-1)).sum())
        positions += len(got)
    return {"rms": (sq_err / sq_ref) ** 0.5, "max": worst, "agree": agree,
            "positions": positions}


def check_logits(cell: Cell, eng, model, seed: int) -> bool:
    """``logit_errors``' ``rms`` within the configuration's
    ``logit_rms_tolerance``: a mean over 18 positions x the vocabulary,
    which holds still from seed to seed where the largest single
    difference (logged beside it) does not."""
    tol = float(cell.config["logit_rms_tolerance"])
    e = logit_errors(cell, eng, model, seed)
    log(f"[serve] {len(CORRECT_PROMPTS)} prompts of {CORRECT_PROMPTS} + "
        f"{CORRECT_DECODE} decoded: rms(logit - reference) is "
        f"{e['rms']:.4%} of rms(reference) (tolerance {tol:.2%}); largest "
        f"difference {e['max']:.4%} of the largest |logit|; argmax agrees "
        f"at {e['agree']} of {e['positions']} positions")
    return bool(np.isfinite(e["rms"]) and e["rms"] <= tol)


def warm_up(eng, traffic: dict, vocab: int) -> int:
    """Run every step shape the cell's traffic can reach, through the
    calls the serve loop makes (``admit``, ``step``, ``extend``,
    ``flush``).  The engine compiles one program per (token bucket,
    context-blocks bucket), both powers of two: tokens 16 up to the step's
    budget, blocks up to the longest context.  For each blocks bucket one
    long sequence is prefilled (its chunks are shapes too) and kept
    decoding while short prompts beside it fill each token bucket."""
    bs = eng.cfg.block_size
    budget = eng.scheduler.token_budget
    longest = min(tr.max_context(traffic), eng.cfg.max_context)
    shortest = int(traffic["prompt_tokens"]["min"])
    nb = _pow2_at_least(-(-min(shortest, budget) // bs))
    nb_top = _pow2_at_least(-(-longest // bs))
    rng = np.random.default_rng(0)
    uid = 1 << 29
    steps = 0

    def ids(n):
        return rng.integers(0, vocab, size=n).tolist()

    while nb <= nb_top:
        long_uid, uid = uid, uid + 1
        # inside this bucket, with room for the few steps it decodes here
        ctx = max(2, min(nb * bs - 12, max(longest - 6, nb * bs // 2 + 1)))
        eng.admit(long_uid, ids(ctx))
        for _ in ("prefill", "decode alone"):
            out = {}
            while long_uid not in out:
                out = eng.step(temperature=0.0)
                steps += 1
            eng.extend(long_uid, out[long_uid])
        t = 32
        while t <= budget:
            fill = t // 2                # with the decode token: t/2 + 1
            piece = max(1, min(nb * bs - 12, fill))
            fillers = []
            while fill > 0:
                n = min(piece, fill)
                eng.admit(uid, ids(n))
                fillers.append(uid)
                uid += 1
                fill -= n
            out = eng.step(temperature=0.0)
            steps += 1
            eng.extend(long_uid, out[long_uid])
            for f in fillers:
                eng.flush(f)
            t *= 2
        eng.flush(long_uid)
        nb *= 2
    return steps


def build(cell: Cell, seed: int, compiles):
    """The engine with weights from ``seed``, checked against the
    reference and warmed for the cell's traffic: ``(engine, model,
    correct)``."""
    import jax

    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    cfg, traffic = cell.config, cell.traffic
    model = build_model(cfg)
    t0 = time.perf_counter()
    eng = InferenceEngineV2(model, dict(cfg["engine_config"]),
                            seed=seed32(seed))
    jax.block_until_ready(eng.params)
    log(f"[serve] {cfg['registry']['name']} layers={model.num_layers} "
        f"hidden={model.hidden_size} heads={model.num_heads}:"
        f"{model.kv_heads} ffn={model.intermediate_size} "
        f"vocab={model.vocab_size} attention={eng.attention_impl} pool="
        f"{eng.cfg.num_blocks}x{eng.cfg.block_size} rows; engine init "
        f"{time.perf_counter() - t0:.1f} s")
    want_impl = cfg.get("attention_impl")
    if want_impl and eng.attention_impl != want_impl:
        raise RuntimeError(f"the configuration names attention "
                           f"{want_impl!r}, the engine chose "
                           f"{eng.attention_impl!r}")
    if tr.max_context(traffic) > eng.cfg.max_context:
        raise ValueError("the traffic's longest request exceeds the "
                         "engine's max_context")
    t0 = time.perf_counter()
    correct = check_logits(cell, eng, model, seed)
    t1 = time.perf_counter()
    n_warm = warm_up(eng, traffic, model.vocab_size)
    log(f"[serve] correctness {t1 - t0:.1f} s; warm-up {n_warm} steps "
        f"{time.perf_counter() - t1:.1f} s; compile requests so far "
        f"{compiles.requests}, from the cache {compiles.hits}")
    return eng, model, correct


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float, compiles) -> Run:
    eng, model, correct = build(cell, seed, compiles)
    return serve(cell, eng, model, correct, seed, seconds, traced, t_start,
                 compiles)


def serve(cell: Cell, eng, model, correct: bool, seed: int, seconds: float,
          traced: bool, t_start: float, compiles) -> Run:
    """One measured window on a built engine, behind a new server."""
    import jax

    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    cfg, traffic = cell.config, cell.traffic
    plan = tr.serve_plan(traffic, seed, seconds, model.vocab_size)
    server_config = dict(cfg.get("server_config", {}))
    if traced:
        server_config["tracing"] = {"enabled": True, "max_events": 4_000_000}
    srv = InferenceServer(eng, server_config)

    def submit(prompt, max_new_tokens):
        return srv.submit(prompt, SamplingParams(max_new_tokens=max_new_tokens))

    tracer = DeviceTracer(cell.root, cell.name) if traced else None
    tracer_thread = None
    srv.start()
    try:
        compiles.mark()
        setup_s = time.perf_counter() - t_start
        mono0 = time.monotonic()
        if tracer is not None:
            span = TRACED_S

            def traced_stretch():
                time.sleep(max(0.0, (seconds - span) / 2))
                tracer.start()
                time.sleep(min(span, seconds))
                tracer.stop()
            tracer_thread = threading.Thread(target=traced_stretch,
                                             name="bench-tracer")
            tracer_thread.start()
        pool = eng.cfg.num_blocks - 1            # block 0 is reserved
        fewest_free = [pool]

        def watch_pool():
            fewest_free[0] = min(fewest_free[0], eng.free_blocks)
        records = run_load(submit, plan, seconds, float(traffic["drain_s"]),
                           POLL_S,
                           on_poll=watch_pool)
        in_window = compiles.since_mark
    finally:
        if tracer_thread is not None:
            tracer_thread.join()
        srv.stop(drain=False, timeout=60)
    spans = srv.tracer.snapshot() if traced else []

    ok = [r for r in records if r.ok]
    failed = len(records) - len(ok)
    shape_ok = all(len(r.tokens) == r.asked_tokens
                   and all(0 <= t < model.vocab_size for t in r.tokens)
                   for r in ok)
    if not shape_ok:
        log("[serve] a stream has another length than asked for, or a "
            "token outside the vocabulary")
    end = seconds + float(traffic["drain_s"])
    # a request with no first token waited at least until the drain's end
    ttft = [(r.token_s[0] if r.token_s else end) - r.due_s for r in records]
    gaps = [b - a for r in records
            for a, b in zip(r.token_s, r.token_s[1:])]
    delivered = sum(1 for r in records for t in r.token_s if t <= seconds)
    late = [r.sent_s - r.due_s for r in records]
    backlog = sum(1 for r in records
                  if r.done_s is None or r.done_s > seconds)
    # every request counts in each statistic; a cell reports those of
    # them that BENCHMARK.json lists for it
    e2e = {"serve_tokens_per_s": delivered / seconds}
    if ttft:
        e2e["ttft_p95_ms"] = percentile(ttft, 0.95) * 1e3
        e2e["ttft_mean_ms"] = statistics.fmean(ttft) * 1e3
    if gaps:
        e2e["token_gap_p95_ms"] = percentile(gaps, 0.95) * 1e3
        e2e["token_gap_mean_ms"] = statistics.fmean(gaps) * 1e3
    log(f"[serve] {traffic['driver']}: {len(records)} requests sent in "
        f"{seconds:g} s, {len(ok)} finished, {failed} failed, {backlog} "
        f"unfinished at the window's end; {delivered} tokens delivered in "
        f"the window; ttft mean {statistics.fmean(ttft) * 1e3:.1f} ms p50 "
        f"{percentile(ttft, 0.5) * 1e3:.1f} ms p95 "
        f"{percentile(ttft, 0.95) * 1e3:.1f} ms; token gap mean "
        f"{statistics.fmean(gaps) * 1e3:.2f} ms p50 "
        f"{percentile(gaps, 0.5) * 1e3:.2f} ms p95 "
        f"{percentile(gaps, 0.95) * 1e3:.2f} ms over {len(gaps)} gaps; "
        f"sent late p95 {percentile(late, 0.95) * 1e3:.2f} ms; compiles in "
        f"the window {in_window}")
    pool_peak = pool - fewest_free[0]
    log(f"[serve] KV pool: at most {pool_peak} of {pool} blocks of "
        f"{eng.cfg.block_size} rows in use at once "
        f"({pool_peak / pool:.1%}), sampled every {POLL_S * 1e3:g} ms")
    for r in records:
        if r.error:
            log(f"[serve] request {r.index} failed: {r.error}")
            break
    return Run(
        correct=bool(correct and shape_ok), attempted=len(records),
        failed=failed, end_to_end=e2e, setup_s=setup_s,
        counters={"late_s": late, "ttft_s": ttft, "gap_s": gaps,
                  "compiles_in_window": in_window, "window_s": seconds,
                  "window_mono_us": (mono0 * 1e6,
                                     (mono0 + seconds) * 1e6),
                  "backlog": backlog, "model": model,
                  "pool_peak_blocks": pool_peak, "pool_blocks": pool,
                  "prompt_tokens": sum(r.prompt_tokens for r in records),
                  "device_kind": jax.devices()[0].device_kind,
                  "chips": len(jax.devices())},
        spans=spans,
        trace=tracer.reduce() if tracer is not None else None)
