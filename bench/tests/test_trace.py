"""The trace reduction (``bench/trace.py``) on traces built by hand."""
import re

import pytest

from bench import trace as T

MS = 1_000_000   # ns


def _devices():
    """Two devices over a 100 ms window.  dev0: a kernel 10-30 ms, a fusion
    20-40 ms (overlapping it), an all-reduce 60-70 ms; dev1: the kernel
    0-50 ms and an all-gather 90-110 ms (past the window's end)."""
    d0 = T.Device("/device:TPU:0", ops=[
        (10 * MS, 30 * MS, "paged_gather_attend"),
        (20 * MS, 40 * MS, "fusion"),
        (60 * MS, 70 * MS, "all-reduce"),
    ], modules=[(10 * MS, 40 * MS, "jit_fused_decode_run"),
                (60 * MS, 70 * MS, "jit_sampled_decode_step")])
    d1 = T.Device("/device:TPU:1", ops=[
        (0, 50 * MS, "paged_gather_attend"),
        (90 * MS, 110 * MS, "all-gather"),
    ], modules=[(0, 50 * MS, "jit_fused_decode_run")])
    return [d0, d1]


SPANS = [(0, 100 * MS, "tick"), (0, 45 * MS, "step"),
         (45 * MS, 80 * MS, "admit")]


@pytest.fixture
def summary():
    return T.reduce(_devices(), SPANS, (0, 100 * MS))


def test_busy_is_the_union_of_device_intervals(summary):
    # dev0: 10-40 (overlap counted once) + 60-70 = 40 ms; dev1: 50 + 10
    assert T.busy_ns(_devices()[0].ops) == 40 * MS
    assert summary.busy_s == pytest.approx((0.040 + 0.060) / 2)
    assert summary.window_s == pytest.approx(0.1)
    assert summary.n_devices == 2


def test_idle_share(summary):
    assert summary.idle_share == pytest.approx(1 - 0.05 / 0.1)


def test_kernel_time_by_name(summary):
    assert summary.kernel_s("paged_gather_attend") == pytest.approx(
        (0.020 + 0.050) / 2)
    assert summary.kernel_s("paged_kv_write") == 0
    assert summary.program_s(["fused_decode_run"]) == pytest.approx(
        (0.030 + 0.050) / 2)
    assert summary.program_s(["fused_decode_run", "sampled_decode_step"]) \
        == pytest.approx((0.040 + 0.050) / 2)


def test_collective_time_clipped_to_the_window(summary):
    # all-reduce 10 ms on dev0; all-gather 10 of its 20 ms inside
    assert summary.collective_s == pytest.approx((0.010 + 0.010) / 2)


def test_idle_gaps_go_to_the_innermost_span(summary):
    # dev0 gaps: 0-10 (step), 40-60 (admit: midpoint 50), 70-100 (tick);
    # dev1 gaps: 50-90 (midpoint 70, admit)
    assert summary.idle_s["step"] == pytest.approx(0.010 / 2)
    assert summary.idle_s["admit"] == pytest.approx((0.020 + 0.040) / 2)
    assert summary.idle_s["tick"] == pytest.approx(0.030 / 2)
    assert sum(summary.idle_s.values()) == pytest.approx(
        summary.window_s - summary.busy_s)


def test_gap_outside_every_span():
    dev = T.Device("/device:TPU:0", ops=[(0, 10 * MS, "fusion")], modules=[])
    s = T.reduce([dev], [(0, 5 * MS, "step")], (0, 20 * MS))
    assert s.idle_s == {T.OUTSIDE: pytest.approx(0.010)}


def test_breakdown(summary):
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert b["device_ops"][0] == ["paged_gather_attend",
                                  pytest.approx(0.035)]
    assert [k for k, _ in b["idle_gaps"]] == ["admit", "tick", "step"]
    many = T.Device("/device:TPU:0", modules=[], ops=[
        (i * MS, i * MS + 1000, f"op{i}") for i in range(30)])
    assert len(T.reduce([many], SPANS, (0, 100 * MS))
               .breakdown()["device_ops"]) == 10


def test_gaps_and_union_edges():
    assert T.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert T.gaps([(2, 3)], 0, 5) == [(0, 2), (3, 5)]
    assert T.gaps([], 0, 5) == [(0, 5)]
    with pytest.raises(ValueError):
        T.reduce([], SPANS, (0, 1))


def test_op_label_strips_the_instance_number():
    assert T.op_label("fusion.123") == "fusion"
    assert T.op_label("paged_gather_attend.1") == "paged_gather_attend"
    assert T.op_label("all-reduce") == "all-reduce"
    assert T.op_label("jit_fused_decode_run(1046502977)") == \
        "jit_fused_decode_run"


#: operation names as a v5e trace gives them (HLO instruction text)
GATHER = ("%closed_call.31 = (f32[16,8,2,128]{3,2,1,0:T(2,128)S(1)}, "
          "f32[16,8,2,1]{3,2,1,0:T(2,128)}, f32[16,8,2,1]{3,2,1,0:T(2,128)"
          "S(1)}) custom-call(s32[128]{0:T(128)S(1)} %copy-done.15)")
WRITE = ("%closed_call.30 = (bf16[128,8,256,128]{3,2,1,0:T(8,128)(2,1)S(1)}, "
         "bf16[128,8,256,128]{3,2,1,0:T(8,128)(2,1)}) custom-call(s32[128]"
         "{0:T(128)S(1)} %get-tuple-element.602)")
LOOP = ("%while.3 = (s32[]{:T(128)}, bf16[16,1,1024]{2,0,1:T(8,128)(2,1)"
        "S(1)}) while(%tuple.96), condition=%wide.cond")
SLICE = ("%dynamic-slice_bitcast_fusion.7 = bf16[128,8,256,128]{3,2,1,0:T(8,"
         "128)(2,1)} fusion(bf16[28,128,8,256,128]{4,3,2,1,0:T(8,128)(2,1)} "
         "%get-tuple-element.597, s32[")


def test_op_label_of_tpu_instructions():
    assert T.op_label(GATHER) == \
        "custom-call (f32[16,8,2,128],f32[16,8,2,1],f32[16,8,2,1])"
    assert T.op_label(WRITE) == \
        "custom-call (bf16[128,8,256,128],bf16[128,8,256,128])"
    assert T.op_label(LOOP) == "while"
    assert T.op_label(SLICE) == "dynamic-slice_bitcast_fusion"


def test_kernel_patterns_of_the_metric_readers():
    """Each kernel reader's pattern picks its kernel's label alone."""
    from bench import harness
    labels = [T.op_label(n) for n in (GATHER, WRITE, LOOP, SLICE)]
    for name, want in (("gather_attend_roofline", labels[0]),
                       ("kv_write_roofline", labels[1])):
        pattern = harness.load_metric(name).KERNEL
        assert [lb for lb in labels if re.search(pattern, lb)] == [want]


def test_containers_count_as_busy_not_as_op_time():
    dev = T.Device("/device:TPU:0", modules=[], ops=[
        (0, 10 * MS, T.op_label(LOOP)), (2 * MS, 4 * MS, T.op_label(SLICE))])
    s = T.reduce([dev], [], (0, 10 * MS))
    assert s.busy_s == pytest.approx(0.010)
    assert s.op_s == {"dynamic-slice_bitcast_fusion": pytest.approx(0.002)}
