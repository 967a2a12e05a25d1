"""The chip benchmark's operation and byte counts against hand-worked
numbers for both configurations."""
from __future__ import annotations

import json

import pytest

import tiny
from benchmarks.chip import harness, trace as T

CONFIGS = tiny.REPO / "benchmarks" / "chip" / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name, params, flops_per_step", [
    # 24 x (896*896 + 2*896*128 + 896*896 + 896 + 2*128   attention
    #       + 3*896*4864 + 2*896)                          mlp, norms
    # + 151936*896 + 896 = 494,032,768 (Qwen2-0.5B's published count);
    # per token 6 N + 12 * 24 * 896 * 1024, times 8 x 1024 tokens
    ("qwen2-0.5b", 494_032_768,
     (6 * 494_032_768 + 12 * 24 * 896 * 1024) * 8192),
    # 6 x (2048*2048 + 2*2048*256 + 2048*2048 + 2048 + 2*256
    #      + 3*2048*11008 + 2*2048) + 151936*2048 + 2048
    ("qwen2.5-3b-l6", 773_628_928,
     (6 * 773_628_928 + 12 * 6 * 2048 * 1024) * 8192),
])
def test_model_flops(name, params, flops_per_step):
    costs = harness.cost_module("dense_decoder")
    m = model(name)
    assert costs.params(m) == params
    assert costs.flops_per_token(m, 1024) * 8192 == flops_per_step
    assert json.loads((CONFIGS / f"{name}.json").read_text())["params"] \
        == params


def test_fused_sgd_bytes_and_operations():
    # qwen2-0.5b's flat buffer on one chip: 494,147,584 f32 elements,
    # (rows, 128) slabs; reads p, v, g and writes p, v: 20 B an element
    rows = 494_147_584 // 128
    call = {"results": f"(f32[{rows},128], f32[{rows},128])",
            "operands": f"f32[2]{{0}}, f32[{rows},128]{{1,0}}, "
                        f"f32[{rows},128]{{1,0}}, f32[{rows},128]{{1,0}}"}
    flops, nbytes = harness.cost_module("fused_sgd").cost(
        call, T.shapes_bytes)
    assert flops == 4 * 494_147_584
    assert nbytes == 20 * 494_147_584 + 8
    # at v5e's 819 GB/s the least time is about 12.07 ms
    peaks = harness.peaks("TPU v5 lite")
    assert nbytes / peaks["hbm_bytes_per_s"] == pytest.approx(12.07e-3,
                                                              rel=1e-3)


def test_peaks_refuse_an_unknown_chip():
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(harness.HarnessError, match="no entry"):
        harness.peaks("TPU v9 imaginary")
