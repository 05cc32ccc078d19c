#!/usr/bin/env python3
"""Record the small trace ``tests/benchmark/test_benchmark_stages.py``
checks ``lib.stages`` against: a tiny served model on the kernels' path
(``mistral-tiny`` with heads of 128: ``paged_qblock`` and ``kv_append`` in
every layer), one ``generate`` call of three prompts, so a prefill step of
``ragged_step_sampled`` and the fused decode loop, with the program's
stage scopes on every operation's name stack.

    chiprun -- python3 benchmark/tools/record_stage_trace.py

Runs on the chip only.  Writes ``chiprun_out/stage_trace/
stage_trace.xplane.pb`` and prints what ``lib.stages`` and the program's
``build_capture_report`` make of it.  The file is the profiler's, cut to
what the readers read (it has to stay under 150 KB in the repository):
the device planes and ``/host:metadata`` only; of an instruction's stats
``tf_op``, ``program_id`` and ``hlo_category``; of a program's
``HloProto`` the ``fusion`` instructions by name and, of each fused
computation, one instruction for every ``op_name`` in it (no shapes,
operands or kernel bodies).
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

KEPT_STATS = ("tf_op", "program_id", "hlo_category")


def _fusions_only(proto, kept):
    """What ``lib.stages.mixed_fusions_of`` reads of a program: every
    ``fusion`` instruction by name with the computation it calls, and of a
    fused computation one instruction for each ``op_name`` its instructions
    carry."""
    module = proto.hlo_module
    kept.hlo_module.name, kept.hlo_module.id = module.name, module.id
    fused = {cid for comp in module.computations
             for ins in comp.instructions if ins.opcode == "fusion"
             for cid in ins.called_computation_ids}
    for comp in module.computations:
        c = kept.hlo_module.computations.add(name=comp.name, id=comp.id)
        seen = set()
        for ins in comp.instructions:
            name = ins.metadata.op_name
            if comp.id in fused and name and name not in seen:
                seen.add(name)
            elif ins.opcode != "fusion":
                continue
            i = c.instructions.add(name=ins.name, opcode=ins.opcode,
                                   id=ins.id)
            i.called_computation_ids.extend(ins.called_computation_ids)
            i.metadata.op_name = name
    return kept


def slim(xs):
    """The capture cut to what ``lib.stages`` and ``utils/xplane.py``
    read, in place."""
    from tensorflow.compiler.xla.service import hlo_pb2

    for plane in [p for p in xs.planes
                  if not p.name.startswith("/device:TPU:")
                  and p.name != "/host:metadata"]:
        xs.planes.remove(plane)
    for plane in xs.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        if plane.name == "/host:metadata":
            for md in plane.event_metadata.values():
                for st in md.stats:
                    if names[st.metadata_id] != "Hlo Proto":
                        continue
                    proto = hlo_pb2.HloProto()
                    proto.ParseFromString(st.bytes_value)
                    kept = _fusions_only(proto, hlo_pb2.HloProto())
                    st.bytes_value = kept.SerializeToString()
            continue
        for md in plane.event_metadata.values():
            kept = [st for st in md.stats
                    if names[st.metadata_id] in KEPT_STATS]
            del md.stats[:]
            md.stats.extend(kept)
        for line in [ln for ln in plane.lines
                     if ln.name not in ("XLA Ops", "XLA Modules")]:
            plane.lines.remove(line)
    return xs


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import stages, trace
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.telemetry.capture import build_capture_report
    from deepspeed_tpu.utils.xplane import load_xspace

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: platform {devs[0].platform!r}", file=sys.stderr)
        return 1
    out = ROOT / "chiprun_out" / "stage_trace"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)

    # tiny in depth and vocabulary; wide enough that the products, not the
    # step's bookkeeping (a microsecond an operation), are the time
    cfg = get_model_config("mistral-tiny", num_layers=2, hidden_size=1024,
                           num_heads=8, num_kv_heads=2,
                           intermediate_size=4096,
                           param_dtype=jnp.bfloat16)
    engine = InferenceEngineV2(cfg, {
        "dtype": "bfloat16",
        "memory_config": {"num_blocks": 64, "block_size": 16},
        "max_context": 256,
        "state_manager": {"max_tracked_sequences": 4,
                          "max_ragged_batch_size": 256}})
    assert engine.attention_impl == "paged_pallas", engine.attention_impl
    rng = np.random.default_rng(42)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (120, 70, 33)]
    first = engine.generate(prompts, max_new_tokens=4)          # compile

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out / "raw"), profiler_options=opts)
    again = engine.generate(prompts, max_new_tokens=4)
    jax.profiler.stop_trace()
    assert again == first, (again, first)

    pb = out / "stage_trace.xplane.pb"
    xs = slim(load_xspace(trace.find_xplane(str(out / "raw"))))
    pb.write_bytes(xs.SerializeToString())
    shutil.rmtree(out / "raw")
    names = stages.stage_names()
    cap = stages.load_stage_capture(str(pb))
    tables = stages.stage_tables(cap, names)
    report = build_capture_report(str(out))
    print(f"trace {pb} {pb.stat().st_size} bytes; "
          f"{sum(len(p.ops) for p in cap.planes)} operations, "
          f"{len(cap.programs)} programs")
    print(json.dumps({
        "ok": True, "kind": devs[0].device_kind,
        "bytes": pb.stat().st_size, "stages": tables,
        "report": {"stages": report["stages"],
                   "top_ops": [dict(op, name=op["name"][:60])
                               for op in report["top_ops"]]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
