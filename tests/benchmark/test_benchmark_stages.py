"""``lib.stages`` on planes made by hand, on the capture recorded on a v5e
with the program's stage scopes on (``data/stage_trace.xplane.pb``, by
``benchmark/tools/record_stage_trace.py``: a tiny served model on the
kernels' path, one prefill step and the fused decode loop), and
``tools/stage_run.py`` through the tiny cells."""

import json
from pathlib import Path

import jax
import pytest

import bench_tiny
from benchmark.lib import device, manifest, trace
from benchmark.lib import stages as S

DATA = Path(__file__).parent / "data"
NAMES = ("attn.qkv", "attn.read", "attn.append", "mlp", "mtp", "head",
         "layers", "moe.router", "moe.experts")
US = 1e3        # ns


def _op(stack, pid, start_us, dur_us, name, opcode="fusion"):
    return (stack, pid, start_us * US, dur_us * US,
            f"%{name} = bf16[8,128]{{1,0}} {opcode}(bf16[8,128]{{1,0}} %p)")


# -- a name stack's stage ------------------------------------------------------
@pytest.mark.parametrize("stack,last,first", [
    ("jit(ragged_step_sampled)/while/body/attn.qkv/dot_general:",
     "attn.qkv", "attn.qkv"),
    ("jit(ragged_draft_step)/mtp/attn.read/paged_qblock/pallas_call:",
     "attn.read", "mtp"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/tanh:", "mlp", "mlp"),
    ("jit(train_step)/transpose(jvp(moe.experts))/mul:", "moe.experts",
     "moe.experts"),
    ("jit(step)/layers/while/body/mtp/moe.router/top_k:", "moe.router",
     "layers"),
    # a scope that is not in the vocabulary (the train names to come)
    ("jit(train_step)/optimizer/grad.norm/sqrt:", S.UNSCOPED, S.UNSCOPED),
    ("jit(loss)/add:", S.UNSCOPED, S.UNSCOPED),
    # a weight's copy carries the argument's name: no scope
    ("params['layers']['mlp']['wi']:", S.UNSCOPED, S.UNSCOPED),
    ("jit(step)/while/body/head:", S.UNSCOPED, S.UNSCOPED),
    ("", S.UNSCOPED, S.UNSCOPED),
])
def test_stage_of_a_name_stack(stack, last, first):
    assert S.stage_of(stack, NAMES) == last
    assert S.stage_of(stack, NAMES, first=True) == first


def test_the_benchmark_reads_the_programs_vocabulary():
    from deepspeed_tpu.telemetry.tracing import STAGE_NAMES

    assert S.stage_names() == tuple(STAGE_NAMES)
    assert {"attn.read", "mlp", "layers"} <= set(S.stage_names())


def test_a_program_without_the_vocabulary_reads_unscoped():
    """The parent of the PR that added the scopes: no name is a stage."""
    ops = [_op("jit(step)/while/body/attn.qkv/dot_general:", 1, 0, 5, "f.1")]
    assert S.by_stage(ops, ()) == {S.UNSCOPED: 5 * US}


# -- time by stage, by program ---------------------------------------------------
def _two_programs():
    """A decode program (id 11) run twice and a chunk program (id 12) run
    once; each run a loop over one layer."""
    ops = []
    for k, t0 in enumerate((0, 100)):                     # decode runs
        ops += [
            _op("jit(step)/while:", 11, t0, 30, "while.1", "while"),
            _op("jit(step)/while/body/attn.qkv/dot_general:", 11, t0, 10,
                "fusion.7"),
            _op("jit(step)/while/body/attn.read/paged_qblock/pallas_call:",
                11, t0 + 10, 5, "paged_qblock.1", "custom-call"),
            _op("jit(step)/while/body/mlp/dot_general:", 11, t0 + 15, 15,
                "fusion.9"),
            _op("jit(step)/head/argmax:", 11, t0 + 30, 4, "fusion.2"),
            _op("", 11, t0 + 34, 1, "copy.3", "copy"),
        ]
    ops += [
        _op("jit(step)/while:", 12, 200, 300, "while.1", "while"),
        _op("jit(step)/while/body/attn.qkv/dot_general:", 12, 200, 100,
            "fusion.7"),
        _op("jit(step)/while/body/mtp/mlp/dot_general:", 12, 300, 200,
            "fusion.8"),
    ]
    runs = [(11, 1, 0.0, 35 * US, "jit_step"),
            (11, 2, 100 * US, 135 * US, "jit_step"),
            (12, 3, 200 * US, 500 * US, "jit_step")]
    return ops, runs


def test_time_by_stage_leaves_loops_out_and_sums_to_the_leaf_time():
    ops, _ = _two_programs()
    got = S.by_stage(ops, NAMES)
    assert got == {"mlp": 230 * US, "attn.qkv": 120 * US,
                   "attn.read": 10 * US, "head": 8 * US,
                   S.UNSCOPED: 2 * US}
    assert list(got) == ["mlp", "attn.qkv", "attn.read", "head", S.UNSCOPED]
    assert sum(got.values()) == sum(op[3] for op in S.leaf(ops))
    outer = S.by_stage(ops, NAMES, first=True)
    assert outer["mtp"] == 200 * US and outer["mlp"] == 30 * US
    assert {pid: len(v) for pid, v in S.by_program(ops).items()} == {
        11: 12, 12: 3}


def test_the_ledgers_operations_get_their_stages():
    """``fusion.7`` is a name in both programs: its time is added up as
    ``reduce_planes`` adds it, and split by stage."""
    ops, _ = _two_programs()
    rows = S.top_ops_by_stage(ops, NAMES, top=3)
    assert rows[0] == ["fusion.8 fusion", 200 * US, {"mlp": 200 * US}]
    assert rows[1] == ["fusion.7 fusion", 120 * US, {"attn.qkv": 120 * US}]
    assert rows[2][0] == "fusion.9 fusion"
    assert S.unscoped_top(ops, NAMES) == [["copy.3 copy", "", 2 * US]]


def test_runs_are_labelled_with_what_their_steps_carried():
    """A run belongs to the last ``v2.schedule`` span that began before
    it; a run before the first span has none."""
    _, runs = _two_programs()
    steps = [(90 * US, 12, 0), (190 * US, 1024, 1012)]
    got = S.label_runs(runs, steps)
    assert got == {11: [(12, 0)], 12: [(1024, 1012)]}
    assert S.step_kind(got[11]) == "decode<=12"
    assert S.step_kind(got[12]) == "chunk<=1024"
    assert S.step_kind([(12, 0), (16, 0), (14, 2)]) == "mixed<=16"
    assert S.step_kind([]) == ""
    assert S.label_runs(runs, []) == {}


def test_fusions_that_mix_stages_are_counted_with_their_time():
    ops, _ = _two_programs()
    fusions = {(11, "fusion.7"): {"attn.qkv", S.LOOP},    # a weight's slice
               (11, "fusion.9"): {"mlp", "attn.qkv", S.UNSCOPED},
               (12, "fusion.7"): {"attn.qkv", S.UNSCOPED},
               (12, "fusion.8"): {"mlp", "head"},
               (12, "fusion.99"): {"mlp", "head"}}      # never ran
    got = S.mixed_fusions(fusions, ops)
    assert got == {"fusions": 5, "mixed": 3, "mixed_ns": 230 * US}


def test_stage_tables_of_a_hand_made_capture():
    ops, runs = _two_programs()
    cap = S.StageCapture(planes=[S.StagePlane("/device:TPU:0", ops, runs)])
    steps = {"/device:TPU:0": [(-10 * US, 12, 0), (90 * US, 16, 0),
                               (190 * US, 1024, 1012)]}
    t = S.stage_tables(cap, NAMES, steps)
    assert t["chips"] == 1 and t["ops_s"] == pytest.approx(370e-6)
    assert t["stages"]["mlp"] == [pytest.approx(230e-6),
                                  pytest.approx(230 / 370)]
    assert t["unscoped_share"] == pytest.approx(2 / 370)
    assert sum(v[1] for v in t["stages"].values()) == pytest.approx(1.0)
    assert "mixed_fusions" not in t          # the capture holds no program
    chunk, decode = t["programs"]
    assert chunk["kind"] == "chunk<=1024" and chunk["runs"] == 1
    assert chunk["mean_run_ms"] == pytest.approx(0.3)
    assert chunk["stages"]["mlp"][1] == pytest.approx(2 / 3)
    assert decode["kind"] == "decode<=16" and decode["runs"] == 2
    assert decode["tokens_p50"] == 14 and decode["program"] == "jit_step"
    assert decode["prefill_runs"] == 0 and chunk["prefill_runs"] == 1
    assert decode["mean_run_ms"] == pytest.approx(0.035)
    assert decode["stages"]["attn.read"] == [pytest.approx(10e-6),
                                            pytest.approx(10 / 70)]
    with pytest.raises(ValueError):
        S.stage_tables(S.StageCapture(planes=[]), NAMES)


# -- the recorded capture --------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    path = DATA / "stage_trace.xplane.pb"
    assert path.stat().st_size < 150_000
    return S.load_stage_capture(str(path))


def test_recorded_times_are_profile_datas(recorded):
    """The proto's ``timestamp_ns + offset_ps / 1000`` is what
    ``ProfileData`` calls ``start_ns``: the operations sit on the clock
    ``lib.attribute`` maps."""
    planes = trace.load_device_planes(str(DATA / "stage_trace.xplane.pb"))
    assert len(planes) == len(recorded.planes) == 1
    theirs, ours = planes[0].ops, recorded.planes[0].ops
    assert len(theirs) == len(ours) > 100
    for (name, start, dur), op in zip(theirs, ours):
        assert name == op[4]
        assert start == pytest.approx(op[2], abs=1) and dur == pytest.approx(
            op[3], abs=1)


def test_recorded_stages_sum_to_the_busy_time(recorded):
    names = S.stage_names()
    t = S.stage_tables(recorded, names)
    busy = trace.reduce_planes(trace.load_device_planes(
        str(DATA / "stage_trace.xplane.pb"))).busy_s
    assert sum(v[0] for v in t["stages"].values()) == pytest.approx(
        busy, rel=0.01)
    assert t["unscoped_share"] < 0.05, t["unscoped_top"]
    assert {"attn.qkv", "attn.read", "attn.append", "attn.out", "mlp",
            "head", "embed"} <= set(t["stages"])
    assert not {"verify", "ssm.scan", "latent.read", "moe.experts",
                "mtp"} & set(t["stages"])


def test_recorded_kernels_land_in_their_stages(recorded):
    names = S.stage_names()
    kernels = {}
    for stack, _, _, dur, text in recorded.planes[0].ops:
        if trace.is_mosaic(text):
            kernel = text.partition(" = ")[0].lstrip("%").split(".")[0]
            kernels.setdefault(kernel, set()).add(S.stage_of(stack, names))
    assert kernels == {"paged_qblock": {"attn.read"},
                       "kv_append": {"attn.append"}}


def test_recorded_programs_and_their_fusions(recorded):
    """Two programs ran (the prefill step, the decode loop); the capture
    carries both as ``HloProto``, from which the fusions that mix stages
    are counted."""
    names = S.stage_names()
    t = S.stage_tables(recorded, names)
    got = {p["program"]: p for p in t["programs"]}
    assert {"jit_ragged_step_sampled", "jit_ragged_decode_loop"} <= set(got)
    assert all(p["kind"] == "" and p["runs"] >= 1 for p in got.values())
    assert got["jit_ragged_decode_loop"]["stages"]["mlp"][0] > 0
    assert len(recorded.programs) >= 2
    mixed = t["mixed_fusions"]
    assert 0 < mixed["mixed"] < mixed["fusions"]
    assert 0 <= mixed["mixed_share"] < 1


def test_the_programs_report_reads_the_same_capture(tmp_path):
    """``build_capture_report`` on the recorded capture: a ``stages``
    block and a stage on every ``top_ops`` row, equal to the benchmark's
    reading of the same file."""
    import shutil

    from deepspeed_tpu.telemetry import build_capture_report

    shutil.copy(DATA / "stage_trace.xplane.pb", tmp_path / "t.xplane.pb")
    rep = build_capture_report(str(tmp_path))
    t = S.stage_tables(S.load_stage_capture(
        str(DATA / "stage_trace.xplane.pb")), S.stage_names())
    for stage, (seconds, _) in t["stages"].items():
        assert rep["stages"]["stages"][stage] == pytest.approx(
            seconds * 1e3, abs=1e-3)
    assert rep["stages"]["unscoped_share"] < 0.05
    assert all(op["stage"] in S.stage_names() + (S.UNSCOPED,)
               for op in rep["top_ops"])
    assert {"mlp", "attn.read"} & {op["stage"] for op in rep["top_ops"]}


# -- through the tiny cells ---------------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return bench_tiny.make_copy(tmp_path_factory.mktemp("bench_copy"))


@pytest.fixture
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(device, "setup_compile_cache", lambda: "(off)")
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield
    jax.config.update("jax_default_matmul_precision", was)


def test_a_drafted_steps_verify_rows_are_not_prompt_tokens(copy):
    """``v2.schedule`` counts a verify run's two rows among
    ``prefill_tokens``; the tool takes them out, so a self-drafting decode
    step is labelled ``decode`` and a chunk beside verify runs ``chunk``."""
    tool = manifest.load_code(copy, "tools", "stage_run")

    def span(ts, **args):
        return {"ph": "X", "name": "v2.schedule", "ts": ts, "args": args}

    events = [
        span(1.0, tokens=12, prefill_tokens=12, decode_tokens=0,
             verify_runs=6, draft_rows=6),
        span(2.0, tokens=1024, prefill_tokens=1022, decode_tokens=2,
             verify_runs=3, draft_rows=3),
        span(3.0, tokens=5, prefill_tokens=0, decode_tokens=5),
        {"ph": "X", "name": "v2.fetch", "ts": 4.0, "args": {}},
        {"ph": "i", "name": "v2.schedule", "ts": 5.0, "args": {}}]
    steps = tool.schedule_steps(events, lambda ns: ns + 7)
    assert steps == [(1007.0, 12, 0), (2007.0, 1024, 1016), (3007.0, 5, 0)]
    assert S.step_kind([s[1:] for s in steps[:1]]) == "decode<=12"


@pytest.mark.parametrize("cell", ["mistral-tiny.tiny_open",
                                  "gpt2-tiny.tiny_steps"])
def test_stage_run_rehearsed_on_the_cpu(copy, cell, no_persistent_cache,
                                        capsys):
    """``tools/stage_run.py`` end to end in the temporary copy: the cell
    runs with its spans on and a capture between two anchors; the CPU's
    capture has no device plane, so no device number comes out."""
    tool = manifest.load_code(copy, "tools", "stage_run")
    assert tool.main(["--workload", cell, "--seed", str(2 ** 31 + 9),
                      "--seconds", "1.5", "--out", "out/stages.json"],
                     root=copy, need_chip=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] and out["failed"] == 0 and out["span_events"] > 0
    assert out["stage_names"] == len(S.stage_names()) > 20
    assert "breakdown" not in out and "clock_error_ms" not in out
    assert "busy_s" not in out["device"]
    assert json.loads((copy / "out" / "stages.json").read_text()) == out
    assert not (copy / ".bench_trace" / f"{cell}.stages").exists()
