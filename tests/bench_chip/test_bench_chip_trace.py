"""The chip benchmark's reduction from trace and compiled program to
per-layer numbers, on hand-built traces (nanoseconds)."""
from __future__ import annotations

import base64

import pytest

import tiny  # noqa: F401  (puts the repository on the path)
from benchmarks.chip import trace as T


def make_trace():
    # device 0: ops at [0,10) [5,20) [30,40), a collective in flight
    # [40,70) with compute [50,60) under it; device 1 idle but [0,50)
    return T.Trace(
        devices={
            0: [("fusion.1", 0, 10), ("fusion.2", 5, 20),
                ("_lambda_.1", 30, 40),
                ("collective-permute-start.3", 40, 42),
                ("fusion.4", 50, 60),
                ("collective-permute-done.3", 68, 70)],
            1: [("fusion.1", 0, 50)],
        },
        spans=[("bench.dispatch", 0, 2), ("bench.loss_fetch", 2, 100)])


def test_union_and_cover():
    assert T.union([(0, 10), (5, 20), (30, 40)]) == 30
    assert T.union([]) == 0
    assert T.clip([(40, 70), (80, 90)], 50, 85) == [(50, 70), (80, 85)]


def test_window_busy_and_idle_share():
    tr = make_trace()
    assert tr.window() == (0, 100)
    # device 0 busy 20 + 10 + 2 + 10 + 2 = 44 ns, device 1 50 ns
    assert T.busy_s(tr) == pytest.approx((44 + 50) / 2 / 1e9)
    # without host spans the window is the devices' first op to last
    assert T.Trace(devices=tr.devices).window() == (0, 70)


def test_kernel_sum_is_summed_device_time_averaged_over_chips():
    tr = make_trace()
    assert T.op_seconds(tr, {"_lambda_.1"}.__contains__) == \
        pytest.approx(10 / 2 / 1e9)
    assert T.op_seconds(tr, lambda n: n.startswith("fusion")) == \
        pytest.approx((10 + 15 + 10 + 50) / 2 / 1e9)


def test_breakdown():
    tr = make_trace()
    top = T.top_ops(tr, k=2)
    assert top[0] == ["fusion.1", pytest.approx((10 + 50) / 2 / 1e9)]
    assert len(top) == 2
    gaps = T.idle_gaps(tr, k=3)
    # device 0's gaps: [70,100) [20,30) then two of 8 ns, longest first,
    # each named by the host span over it
    assert gaps[0] == ["bench.loss_fetch", pytest.approx(30 / 1e9)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_custom_calls_and_shape_bytes():
    body = base64.b64encode(b"...sgd_momentum_flat...stream_call").decode()
    hlo = (
        '  %_lambda_.1 = (f32[8192,128]{1,0:T(8,128)}, '
        'f32[8192,128]{1,0:T(8,128)}) custom-call(%constant.1, %bitcast.5, '
        '%bitcast.7, %bitcast.9), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={f32[2]{0}, f32[8192,128]{1,0}, '
        'f32[8192,128]{1,0}, f32[8192,128]{1,0}}, output_to_operand_'
        'aliasing={{0}: (1, {})}, backend_config={"custom_call_config":'
        '{"body":"' + body + '","needs_layout_passes":true}}\n'
        '  %fusion.2 = bf16[4,4]{1,0} fusion(%p), kind=kLoop\n')
    calls = T.custom_calls(hlo)
    assert [c["name"] for c in calls] == ["_lambda_.1"]
    assert b"sgd_momentum_flat" in calls[0]["body"]
    n = 8192 * 128
    assert T.shapes_bytes(calls[0]["results"]) == (n, 2 * 4 * n)
    assert T.shapes_bytes(calls[0]["operands"]) == (2, 8 + 3 * 4 * n)
    assert T.shapes_bytes("bf16[3,5] s8[7]") == (15, 37)


def test_op_names_and_nesting_as_the_chip_records_them():
    # the TPU trace names each op by its HLO text, and a while loop's
    # event holds its body's ops on the same line
    assert T.op_name("%step_c1.1 = (f32[8,128]{1,0}, f32[8,128]{1,0}) "
                     "custom-call(f32[2]{0} %c), custom_call_target=\"x\"") \
        == "step_c1.1"
    assert T.op_name("fusion.3") == "fusion.3"
    ops = [("while.1", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 30, 90),
           ("step_c1.1", 100, 120), ("copy.1", 130, 140)]
    leaves = T.innermost(ops)
    assert [n for n, _, _ in leaves] == ["fusion.1", "fusion.2",
                                         "step_c1.1", "copy.1"]
    tr = T.Trace(devices={0: leaves},
                 busy={0: T.merged([(a, b) for _, a, b in ops])},
                 spans=[("bench.dispatch", 0, 1), ("bench.loss_fetch", 1, 150)])
    # busy counts the loop's own time; the kernel is left out on request
    assert T.busy_s(tr) == pytest.approx(130 / 1e9)
    assert T.busy_s(tr, leave_out={"step_c1.1"}.__contains__) == \
        pytest.approx(110 / 1e9)
    assert T.op_seconds(tr, lambda n: n.startswith("fusion")) == \
        pytest.approx(80 / 1e9)
    assert T.idle_gaps(tr, k=1) == [["bench.loss_fetch",
                                     pytest.approx(10 / 1e9)]]
