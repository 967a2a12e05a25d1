"""The attention kernels' device time, read from a hand-built compiled
program and trace (nanoseconds) through the run's own context."""
from __future__ import annotations

import base64
from types import SimpleNamespace

import pytest

import tiny
from benchmarks.chip import harness, run, trace as T

MS = 1_000_000      # ns


def _call(name: str, body: bytes) -> str:
    b64 = base64.b64encode(body).decode()
    return (f'  %{name} = bf16[8,2,7168,64]{{3,2,1,0}} custom-call('
            f'%p.0), custom_call_target="tpu_custom_call", '
            f'operand_layout_constraints={{bf16[8,2,7168,64]{{3,2,1,0}}}}, '
            f'backend_config={{"custom_call_config":{{"body":"{b64}"}}}}\n')


# the names the compiled step gives the forward, dK/dV and dQ kernels and
# the update; each body holds its pallas_call's name, and may hold the
# Python function names of another kernel's frames (here the update's)
HLO = (_call("gqa_causal_fwd.17", b"..gqa_causal_fwd..causal_flash..")
       + _call("gqa_causal_dkv.3", b"..gqa_causal_dkv")
       + _call("gqa_causal_dq.10", b"gqa_causal_dq..")
       + _call("sync.update.1", b"..sgd_momentum_flat..causal_flash..")
       + '  %fusion.1 = bf16[4,4]{1,0} fusion(%p), kind=kLoop\n')


def ctx_for(hlo: str, steps: int = 2) -> dict:
    # per step: forward 3 ms, its recomputation 3, dK/dV 4, dQ 2, the
    # update 5 and an XLA fusion 10
    plan = [("gqa_causal_fwd.17", 3), ("fusion.1", 10),
            ("gqa_causal_fwd.17", 3),
            ("gqa_causal_dkv.3", 4),
            ("gqa_causal_dq.10", 2), ("sync.update.1", 5)]
    ops, t = [], 0
    for _ in range(steps):
        for name, ms in plan:
            ops.append((name, t, t + ms * MS))
            t += ms * MS
    prof = T.Trace(devices={0: ops}, spans=[("bench.dispatch", 0, 1),
                                            ("bench.loss_fetch", 1, t)])
    cell = SimpleNamespace(chips=1, traffic={"batch_per_chip": 8,
                                             "seq_len": 1024})
    res = {"profile": prof, "hlo": hlo, "stamps": [0.0] * steps}
    device = SimpleNamespace(device_kind="TPU v5 lite")
    return run.context(cell, res, [device])


def read(name: str, ctx: dict):
    return harness.metric_reader(name)(ctx)


def test_attention_kernels_are_found_by_their_mark():
    ctx = ctx_for(HLO)
    assert ctx["kernels"]("flash_attention") == {
        "gqa_causal_fwd.17", "gqa_causal_dkv.3",
        "gqa_causal_dq.10"}
    assert ctx["kernels"]("fused_sgd") == {"sync.update.1"}


def test_attention_time_is_its_kernels_time_per_step():
    ctx = ctx_for(HLO)
    assert read("kernel.attention.ms", ctx) == pytest.approx(3 + 3 + 4 + 2)
    assert read("kernel.fused_update.ms", ctx) == pytest.approx(5)
    # like every kernel, attention is left out of the XLA ops' time
    assert read("step.xla_ms", ctx) == pytest.approx(10)


def test_attention_time_is_silent_where_no_call_took_the_kernel():
    # a program whose attention ran as XLA ops: nothing to read
    plain = "".join(line + "\n" for line in HLO.splitlines()
                    if "gqa_causal" not in line)
    assert read("kernel.attention.ms", ctx_for(plain)) is None
    assert read("kernel.fused_update.ms", ctx_for(plain)) == \
        pytest.approx(5)
    assert read("kernel.attention.ms", ctx_for(HLO, steps=0)) is None
