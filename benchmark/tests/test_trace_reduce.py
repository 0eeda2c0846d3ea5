"""The reduction from a trace to busy time, idle share, program times
and gaps: on hand-made planes whose answer is known, and on one small
trace recorded on the chip (``record_small_trace.py``)."""

import dataclasses
import os

import pytest

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: int
    duration_ns: int


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _planes():
    ms = 1_000_000
    ops = [Ev("fusion.1", 0, 2 * ms), Ev("fusion.2", 1 * ms, 2 * ms),     # overlap: union 0-3
           Ev("custom-call.7", 5 * ms, 1 * ms),                           # gap 3-5
           Ev("fusion.1", 8 * ms, 2 * ms)]                                # gap 6-8
    modules = [Ev("jit_step_a(123)", 0, 6 * ms), Ev("jit_step_b(9)", 8 * ms, 2 * ms)]
    return [
        Plane("/host:CPU", [Line("python3", [Ev("x", 0, 100 * ms)])]),
        Plane("/device:TPU:0", [Line("XLA Modules", modules), Line("XLA Ops", ops),
                                Line("Steps", [Ev("1", 0, 10 * ms)])]),
    ]


def test_busy_is_the_union_and_idle_the_rest():
    s = trace_reduce.reduce_planes(_planes())
    assert len(s.planes) == 1                      # the host plane is not a device
    assert s.busy_s == pytest.approx(0.006)        # 3 + 1 + 2 ms
    assert s.window_s == pytest.approx(0.010)
    assert s.idle_share == pytest.approx(0.4)
    s.host_window_s = 0.012                        # the host saw a longer window
    assert s.idle_share == pytest.approx(0.5)


def test_programs_ops_and_gaps_by_name():
    s = trace_reduce.reduce_planes(_planes())
    assert s.module_durations(r"step_a") == [pytest.approx(0.006)]
    assert s.module_durations(r"jit_step") == [pytest.approx(0.006), pytest.approx(0.002)]
    assert s.op_seconds(r"^fusion\.1$") == (2, pytest.approx(0.004))
    assert s.top_ops(2) == [["fusion", pytest.approx(0.006)], ["custom-call", pytest.approx(0.001)]]
    hlo = "%attn.60 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[192,1024,64]{2,1,0}) custom-call(bf16[1"
    assert trace_reduce.op_family(hlo) == "attn custom-call"
    assert trace_reduce.op_family("%fusion.40.remat_uncompressed = f32[12,3072]{1,0} copy(f32[") == "fusion.remat_uncompressed copy"
    assert trace_reduce.op_family("%fusion.136 = bf16[16,1024,50257]{2,1,0:T(8,128)(2,1)} fusion(bf16") == "fusion fusion"
    gaps = dict((n, v) for n, v in s.top_gaps())
    assert gaps == {"before jit_step_a": pytest.approx(0.002), "before jit_step_b": pytest.approx(0.002)}
    assert s.lines_seen["/device:TPU:0"] == ["XLA Modules", "XLA Ops", "Steps"]


def test_no_device_plane_means_nothing_to_read():
    s = trace_reduce.reduce_planes(_planes()[:1])
    assert s.planes == [] and s.busy_s == 0.0 and s.idle_share is None


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace in this tree")
def test_recorded_trace_from_the_chip():
    from jax.profiler import ProfileData

    s = trace_reduce.reduce_planes(ProfileData.from_file(DATA).planes)
    runs = s.module_durations(r"small_step")
    assert len(runs) == 3                                  # three executions were recorded
    assert all(d == pytest.approx(11.9e-6, rel=0.01) for d in runs)   # 2.1 GFLOP at 180 TFLOP/s
    assert 0 < s.busy_s < s.window_s
    assert s.idle_share > 0.5                              # 5 ms pauses between ~0.1 ms programs
    assert sum(v for _, v in s.top_gaps()) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
