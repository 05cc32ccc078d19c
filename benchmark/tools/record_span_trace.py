#!/usr/bin/env python3
"""Record the second small trace ``tests/benchmark`` checks against: the
workload of ``record_small_trace.py`` (matmul programs, the Pallas flash
kernel forward and backward, a 30 ms host sleep, the matmuls again) with
the program's ``Tracer`` running beside the profiler: a span around each
dispatch, around each wait for the device and around the sleep, all under
one trace id, and a clock anchor at each end of the capture.

    chiprun -- python3 benchmark/tools/record_span_trace.py

Runs on the chip only.  Writes ``chiprun_out/span_trace/
span_trace.xplane.pb`` and ``span_trace.spans.json`` (the Tracer's Chrome
trace) and prints what ``lib.attribute`` makes of them.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.lib import attribute, trace
    from deepspeed_tpu.ops.flash_attention import flash_attention
    from deepspeed_tpu.telemetry.tracing import Tracer
    from deepspeed_tpu.utils.trace import write_clock_anchor

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: platform {devs[0].platform!r}", file=sys.stderr)
        return 1
    out = ROOT / "chiprun_out" / "span_trace"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)

    @jax.jit
    def matmuls(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    def attn_loss(q, k, v):
        return flash_attention(q, k, v, impl="pallas").astype(
            jnp.float32).sum()

    attn = jax.jit(jax.value_and_grad(attn_loss, argnums=(0, 1, 2)))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1024, 2048), jnp.bfloat16)
    w = jax.random.normal(key, (2048, 2048), jnp.bfloat16) * 0.02
    q = jax.random.normal(key, (2, 1024, 16, 64), jnp.bfloat16)
    jax.block_until_ready((matmuls(x, w), attn(q, q, q)))       # compile

    tracer = Tracer()
    tid = tracer.new_trace_id()

    def dispatch(fn, *args):
        with tracer.span("train.dispatch", tid):
            return fn(*args)

    def sync(value):
        with tracer.span("train.sync", tid):
            return jax.block_until_ready(value)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out / "raw"), profiler_options=opts)
    write_clock_anchor("start")
    for _ in range(3):
        r = dispatch(matmuls, x, w)
    for _ in range(2):
        g = dispatch(attn, q, q, q)
    sync((r, g))
    with tracer.span("serve.idle_wait", tid):     # the host idles, as a
        time.sleep(0.03)                          # serve loop without work
    for _ in range(3):
        r = dispatch(matmuls, x, w)
    sync(r)
    write_clock_anchor("stop")
    jax.profiler.stop_trace()

    pb = out / "span_trace.xplane.pb"
    shutil.copy(trace.find_xplane(str(out / "raw")), pb)
    shutil.rmtree(out / "raw")
    spans = out / "span_trace.spans.json"
    tracer.export_chrome_trace(str(spans))
    cap = attribute.load_capture(str(pb))
    events = json.loads(spans.read_text())["traceEvents"]
    att = attribute.attribute_capture(cap, events)
    print(f"trace {pb} {pb.stat().st_size} bytes; anchors {cap.anchors}; "
          f"{len(cap.enqueues)} enqueues, {len(cap.dones)} dones")
    for ordinal, runs in cap.runs.items():
        print("bracket", ordinal, attribute.device_offset(
            runs, [(t, r) for t, r, d in cap.enqueues if d == ordinal],
            [t for t, c in cap.dones if c == ordinal]))
    print(json.dumps({
        "ok": True, "kind": devs[0].device_kind,
        "clock_error_ms": (att.clock_error_s or 0) * 1e3,
        "drift_us": att.drift_s * 1e6, "idle_gaps": att.idle_gaps,
        "pallas": att.pallas, "named_s": att.named_s,
        "long_idle_s": att.long_idle_s}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
