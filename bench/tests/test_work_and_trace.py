"""The work counts and the trace reduction, on hand-counted inputs and on
a small trace recorded on a TPU v5e."""
import os

import numpy as np
import pytest

import bench_helpers  # noqa: F401  (puts the repository on the path)
from bench import trace, work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_least_step_work_by_hand():
    # 3 neurons, 2 steps: neuron 0 spikes at step 0, neurons 0 and 2 at 1
    raster = np.array([[1, 0, 0], [1, 0, 1]], np.uint8)
    out_deg = np.array([4, 1, 2])
    nbytes, ops = work.least_step_work(raster, out_deg)
    events = 4 + 4 + 2
    assert nbytes == 8 * events + 28 * 3 * 2
    assert ops == events + work.LIF_OPS * 3 * 2
    p_out, p_in = np.array([1, 0, 0]), np.array([0, 0, 3])
    nbytes2, ops2 = work.least_step_work(raster, out_deg, p_out, p_in)
    touched = 1 + 1 + 3
    assert nbytes2 == nbytes + 16 * 3 * 2 + 12 * touched
    assert ops2 == ops + 4 * 3 * 2 + work.STDP_OPS * touched


def test_degrees_by_hand():
    # rows are targets: row 0 <- {1, 2}, row 1 <- {}, row 2 <- {0, 1, 1}
    row_ptr = np.array([0, 2, 2, 5])
    col = np.array([1, 2, 0, 1, 1])
    plastic = np.array([True, False, False, True, True])
    out, p_out, p_in = work.degrees(row_ptr, col, 3, plastic)
    assert out.tolist() == [1, 3, 1]
    assert p_out.tolist() == [0, 3, 0]
    assert p_in.tolist() == [1, 0, 2]


def test_least_time_takes_the_larger_bound():
    peak = {"hbm_bytes_per_s": 100.0, "flops_per_s": 10.0}
    assert work.least_time(1000.0, 5.0, peak) == 10.0
    assert work.least_time(100.0, 50.0, peak) == 5.0


def test_reduce_events_by_hand():
    # times in microseconds (the reduction takes ns): window 0..100; ops
    # at 10-30 and 20-40 (overlap) and 60-70, one that starts before the
    # window, and a loop op around them all that does not count as busy;
    # spans name what the host did
    us = 1000
    ops = [(-5, 5, "%a = f32[8] add(f32[8] %x, f32[8] %y)"),
           (10, 30, "%b = f32[8] multiply(f32[8] %x, f32[8] %y)"),
           (20, 40, "%b = f32[8] multiply(f32[8] %x, f32[8] %y)"),
           (60, 70, "%c = f32[8] negate(f32[8] %x)"),
           (0, 100, "%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t)")]
    ops = [(a * us, b * us, n) for a, b, n in ops]
    spans = [(a * us, b * us, n) for a, b, n in (
        (0, 100, trace.WINDOW_SPAN), (0, 45, "bench.chunk"),
        (45, 60, "bench.save"), (70, 100, "bench.monitor"))]
    s = trace.reduce_events({"/device:TPU:0": ops}, spans)
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx((5 + 30 + 10) * 1e-6)
    b = "%b = f32[8] multiply(f32[8] %x, f32[8] %y)"
    assert s.op_seconds[b] == pytest.approx(40e-6)
    assert sorted(s.op_counts.values()) == [1, 1, 2]
    assert s.idle_gaps[0] == ("bench.monitor", pytest.approx(30e-6))
    assert s.idle_gaps[1] == ("bench.save", pytest.approx(20e-6))
    assert s.idle_gaps[2] == ("bench.chunk", pytest.approx(5e-6))
    assert len(s.idle_gaps) == 3


def test_reduce_events_averages_chips():
    spans = [(0, 100, trace.WINDOW_SPAN)]
    s = trace.reduce_events(
        {"/device:TPU:0": [(0, 100, "%x = f32[] negate(f32[] %y)")],
         "/device:TPU:1": [(0, 50, "%x = f32[] negate(f32[] %y)")]},
        spans,
    )
    assert s.busy_s == pytest.approx(75e-9)
    assert s.chips == 2


def test_recorded_tpu_trace():
    """A trace of three calls of a gather-multiply-sum program on a TPU
    v5e (512 x 128 ids into a 4,096-vector), inside the benchmark's
    window span."""
    s = trace.reduce_xplane(os.path.join(DATA, "tpu_small.xplane.pb"))
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    secs, nbytes = work.gather_traffic(s.op_seconds, s.op_counts, [512 * 128])
    assert secs > 0
    (gather,) = [n for n in s.op_counts if "kind=kCustom" in n]
    # each execution: 65,536 int32 ids, the 4,096-float vector, and
    # 65,536 gathered floats
    assert nbytes == s.op_counts[gather] * (65536 * 4 + 4096 * 4 + 65536 * 4)
    assert secs == s.op_seconds[gather]
    assert work.gather_traffic(s.op_seconds, s.op_counts, [1000]) == (0.0, 0.0)
    # the host ran the monitor span between the calls
    assert s.idle_gaps and s.idle_gaps[0][0] == "bench.monitor"
    assert all(g >= trace.MIN_GAP_NS * 1e-9 for _, g in s.idle_gaps)


# a Pallas delivery kernel as the trace names it: packed spike bits, the
# panel's int32 ids and its weights in, one input current per row out
PALLAS_DELIVERY = (
    "%spike_gather_pallas.21 = f32[77176,1]{1,0} custom-call(s32[19,128]{1,0} "
    "%bits, s32[77176,4736]{1,0:T(8,128)} %cols, f32[77176,4736]{1,0:T(8,128)} "
    "%w), custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
    "{s32[19,128]{1,0}, s32[77176,4736]{1,0}, f32[77176,4736]{1,0}}"
)
XLA_GATHER = (
    "%fusion.45 = f32[365505536]{0} fusion(f32[77169]{0} %v, "
    "s32[365505536]{0} %ids), kind=kCustom, calls=%fused_computation.45"
)


def test_gather_traffic_counts_a_delivery_custom_call():
    """A custom-call reading a panel-sized int32 operand is a delivery
    gather: its bytes are its result's and every operand's, the layout
    constraints after them not counted again."""
    panel = 77176 * 4736
    secs, nbytes = work.gather_traffic({PALLAS_DELIVERY: 0.5},
                                       {PALLAS_DELIVERY: 3}, [77176 * 1536, panel])
    assert secs == 0.5
    assert nbytes == 3 * (77176 * 4 + 19 * 128 * 4 + panel * 4 + panel * 4)
    # another panel size, or a custom-call with no panel-sized ids: none
    assert work.gather_traffic({PALLAS_DELIVERY: 0.5}, {PALLAS_DELIVERY: 3},
                               [77176 * 1536]) == (0.0, 0.0)
    other = PALLAS_DELIVERY.replace("s32[77176,4736]{1,0:T", "f32[77176,4736]{1,0:T")
    assert work.gather_traffic({other: 0.5}, {other: 3}, [panel]) == (0.0, 0.0)


def test_gather_traffic_adds_both_kinds():
    """XLA's gather fusion reads as before beside a delivery custom-call."""
    n = 365505536
    ops = {XLA_GATHER: 2.0, PALLAS_DELIVERY: 0.5}
    counts = {XLA_GATHER: 5, PALLAS_DELIVERY: 3}
    secs, nbytes = work.gather_traffic(ops, counts, [n, 77176 * 4736])
    alone = work.gather_traffic({XLA_GATHER: 2.0}, {XLA_GATHER: 5}, [n])
    assert alone == (2.0, 5 * (n * 4 + 77169 * 4 + n * 4))
    assert secs == 2.5
    assert nbytes == alone[1] + work.gather_traffic(
        {PALLAS_DELIVERY: 0.5}, {PALLAS_DELIVERY: 3}, [77176 * 4736])[1]
