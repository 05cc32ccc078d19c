"""The trace reduction against a trace recorded on a v5e
(``data/small_trace.xplane.pb``, by ``benchmark/tools/
record_small_trace.py``: three matmul programs, two flash forward+backward
programs, 30 ms of host sleep, three matmul programs) and against
intervals worked by hand."""

from pathlib import Path

import pytest

from benchmark.lib import trace

PB = Path(__file__).parent / "data" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def planes():
    return trace.load_device_planes(str(PB))


def test_recorded_trace_has_one_chip_with_ops_and_programs(planes):
    assert [p.name for p in planes] == ["/device:TPU:0"]
    assert len(planes[0].ops) == 84
    names = [m[0].partition("(")[0] for m in planes[0].modules]
    assert names.count("jit_matmuls") == 6
    assert names.count("jit_attn_loss") == 2


def test_recorded_trace_reduces_to_the_numbers_read_by_hand(planes):
    r = trace.reduce_planes(planes)
    assert r.chips == 1
    # host saw 35.0 ms; on the device's clock first op to last op:
    assert r.window_s == pytest.approx(34.565e-3, rel=1e-3)
    # 24 fusions of ~46 us, 6 Pallas calls, copies and slices
    assert r.busy_s == pytest.approx(2.205e-3, rel=1e-3)
    assert r.mosaic_calls == 6          # forward, dq, dkv, twice
    assert r.mosaic_s == pytest.approx(0.9077e-3, rel=1e-3)
    assert r.collective_s == 0 and r.exposed_collective_s == 0
    assert r.top_ops[0][0].endswith("pallas")
    assert [n for n, _ in r.top_ops].count(
        "convolution_tanh_fusion fusion") == 1
    # the 30 ms sleep is the longest gap, between the two kinds of program
    what, seconds = r.idle_gaps[0]
    assert seconds > 0.030 and "jit_attn_loss" in what and \
        "jit_matmuls" in what and what.startswith("unattributed")
    assert 1 - r.busy_s / r.window_s == pytest.approx(0.936, abs=0.002)


def test_opcode_and_kinds():
    fusion = ("%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} fusion("
              "bf16[8,128]{1,0:T(8,128)(2,1)} %x), kind=kLoop")
    kernel = ('%jvp__.1 = (bf16[2,16]{1,0:T(8,128)(2,1)}, f32[2]{0}) '
              'custom-call(bf16[2,16]{1,0} %a), '
              'custom_call_target="tpu_custom_call"')
    loop = "%while.2 = (s32[], bf16[4]{0}) while((s32[], bf16[4]{0}) %t)"
    gather = ("%all-gather-start.1 = (bf16[4]{0}, bf16[16]{0}) "
              "all-gather-start(bf16[4]{0} %p), replica_groups={{0,1,2,3}}")
    assert trace.opcode(fusion) == "fusion"
    assert trace.opcode(kernel) == "custom-call" and trace.is_mosaic(kernel)
    assert trace.is_control(loop) and not trace.is_control(fusion)
    assert trace.is_collective(gather) and not trace.is_collective(fusion)
    assert trace.short_name(kernel) == "jvp__.1 pallas"
    assert trace.short_name(gather) == "all-gather-start.1 all-gather-start"


def test_intervals_by_hand():
    assert trace.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace.measure([(0, 2), (1, 3), (5, 7)]) == 5
    # a collective on 0..10, compute covers 2..4 and 6..12: 0..2 and 4..6
    # are exposed
    assert trace.subtract([(0, 10)], [(2, 4), (6, 12)]) == 4


def test_exposed_collective_and_loops_on_a_made_up_plane():
    f = "%f = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop"
    ag = "%ag = bf16[8]{0} all-gather(bf16[2]{0} %x), dimensions={0}"
    loop = "%w = (s32[]) while((s32[]) %t), body=%b"
    plane = trace.DevicePlane("/device:TPU:0", ops=[
        (loop, 0, 1000),            # spans its body: not counted
        (f, 0, 400), (ag, 300, 400), (f, 800, 100)],
        modules=[("jit_step(1)", 0, 1000)])
    r = trace.reduce_planes([plane, plane])
    assert r.chips == 2
    assert r.busy_s == pytest.approx(800e-9)       # 0..700 and 800..900
    assert r.collective_s == pytest.approx(400e-9)
    assert r.exposed_collective_s == pytest.approx(300e-9)   # 400..700
    assert r.idle_gaps[0] == ["unattributed: inside jit_step",
                              pytest.approx(100e-9)]   # 700..800, a chip
    with pytest.raises(ValueError):
        trace.reduce_planes([trace.DevicePlane("/device:TPU:0")])
