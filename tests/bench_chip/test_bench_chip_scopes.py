"""The chip benchmark's reduction from compiled HLO to the program's
named scopes, and the per-layer device times read from them, on
hand-built HLO and traces (nanoseconds)."""
from __future__ import annotations

import pytest

import tiny  # noqa: F401  (puts the repository on the path)
from benchmarks.chip import harness, scopes, trace as T

LAYERS = "jit(step_c1)/transpose(jvp(model.layers))/while/body/closed_call"

HLO = f"""HloModule jit_step_c1, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[8,64]) -> bf16[8,64] {{
  %param_0.1 = bf16[8,64]{{1,0}} parameter(0)
  %constant.1 = bf16[] constant(1), metadata={{op_name="jit(step_c1)"}}
  ROOT %multiply.1 = bf16[8,64]{{1,0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{LAYERS}/checkpoint/model.mlp/mul"}}
}}

%body.2 (arg.2: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {{
  %arg.2 = (s32[], bf16[8,64]{{1,0}}) parameter(0)
  %gte.2 = bf16[8,64]{{1,0}} get-tuple-element(%arg.2), index=1
  %gte.3 = s32[] get-tuple-element(%arg.2), index=0
  %fusion.1 = bf16[8,64]{{1,0}} fusion(%gte.2), kind=kLoop, calls=%fused_computation.1
  %dot.2 = bf16[8,64]{{1,0}} dot(%fusion.1, %gte.2), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{LAYERS}/checkpoint/model.attention/bsd,dh->bsh/dot_general"}}
  %bitcast_dynamic-update-slice_fusion.26 = bf16[8,64]{{1,0}} fusion(%dot.2, %gte.3), kind=kLoop, calls=%fused_computation.9
  ROOT %tuple.2 = (s32[], bf16[8,64]{{1,0}}) tuple(%gte.3, %bitcast_dynamic-update-slice_fusion.26)
}}

ENTRY %main.3 (p.0: bf16[8,64], p.1: s32[8]) -> (bf16[8,64], f32[]) {{
  %p.0 = bf16[8,64]{{1,0}} parameter(0), metadata={{op_name="state['params']"}}
  %p.1 = s32[8]{{0}} parameter(1)
  %gather.1 = bf16[8,64]{{1,0}} gather(%p.0, %p.1), offset_dims={{1}}, metadata={{op_name="jit(step_c1)/jvp(model.embed)/gather"}}
  %while.1 = (s32[], bf16[8,64]{{1,0}}) while(%tuple.0), condition=%cond.2, body=%body.2, metadata={{op_name="{LAYERS}"}}
  %fusion.5 = bf16[8,64]{{1,0}} fusion(%gather.1), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="jit(step_c1)/jvp(model.layers2)/add"}}
  %fusion.head = f32[8,64]{{1,0}} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.6, metadata={{op_name="jit(step_c1)/transpose(jvp(model.head_loss))/while/body/closed_call/checkpoint/bsd,vd->bsv/dot_general"}}
  %copy.7 = f32[8,64]{{1,0}} copy(%fusion.head)
  %convert_bitcast_fusion.5 = f32[8,64]{{1,0}} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.7
  %pack.1 = f32[512]{{0}} fusion(%convert_bitcast_fusion.5), kind=kLoop, calls=%fused_computation.8, metadata={{op_name="jit(step_c1)/sync.pack/scatter"}}
  %pack.2 = f32[512]{{0}} fusion(%convert_bitcast_fusion.5, %pack.1), kind=kLoop, calls=%fused_computation.8, metadata={{op_name="jit(step_c1)/sync.pack/scatter"}}
  %step_c1.1 = f32[512]{{0}} custom-call(%pack.2), custom_call_target="tpu_custom_call", metadata={{op_name="jit(step_c1)/sync.update/pallas_call"}}
  %unpack.1 = bf16[8,64]{{1,0}} fusion(%step_c1.1), kind=kLoop, calls=%fused_computation.10, metadata={{op_name="jit(step_c1)/sync.unpack/convert_element_type"}}
  %mixed.1 = bf16[8,64]{{1,0}} fusion(%copy.7, %unpack.1), kind=kLoop, calls=%fused_computation.11
  %emb.2 = bf16[8,64]{{1,0}} fusion(%mixed.1), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="jit(step_c1)/transpose(jvp(model.embed))/scatter-add"}}
  %pk.3 = f32[512]{{0}} fusion(%mixed.1), kind=kLoop, calls=%fused_computation.8, metadata={{op_name="jit(step_c1)/sync.pack/scatter"}}
  %iota.9 = s32[8]{{0}} iota(), iota_dimension=0
  %lonely.1 = s32[8]{{0}} fusion(%iota.9), kind=kLoop, calls=%fused_computation.12
  %loss.1 = f32[] fusion(%lonely.1), kind=kLoop, calls=%fused_computation.13, metadata={{op_name="jit(step_c1)/add"}}
  ROOT %tuple.9 = (bf16[8,64]{{1,0}}, f32[]) tuple(%emb.2, %loss.1)
}}
"""


def test_a_scope_is_a_whole_component_bare_or_wrapped():
    assert scopes.in_scope(f"{LAYERS}/checkpoint/model.mlp/mul", "model.mlp")
    assert scopes.in_scope(f"{LAYERS}/checkpoint/model.mlp/mul",
                           "model.layers")
    assert scopes.in_scope("jit(step_c1)/jvp(model.mlp)/add", "model.mlp")
    assert scopes.in_scope(
        "jit(step_c1)/transpose(jvp(model.mlp))/add", "model.mlp")
    assert not scopes.in_scope("jit(step_c1)/model.layers2/add",
                               "model.layers")
    assert not scopes.in_scope("jit(step_c1)/model.layers_x/add",
                               "model.layers")
    assert not scopes.in_scope(None, "model.layers")
    # an op_name that XLA joins from two ops
    assert scopes.in_scope("a/reshape;b/model.attention/transpose",
                           "model.attention")


def test_op_scopes_of_fusions_loop_bodies_and_transforms():
    names = scopes.op_scopes(HLO)
    # a fusion without metadata takes its fused computation's name
    assert scopes.signature(names["fusion.1"]) == ("model.layers",
                                                   "model.mlp")
    # an op of a while body, under transpose(jvp(...))
    assert scopes.signature(names["dot.2"]) == ("model.layers",
                                                "model.attention")
    assert scopes.signature(names["gather.1"]) == ("model.embed",)
    # model.layers2 is not model.layers: a name in none of the program's
    # scopes, so the op inherits (its users disagree; its operand is the
    # embedding gather)
    assert not scopes.in_scope(names["fusion.5"], "model.layers")
    assert scopes.signature(names["fusion.5"]) == ("model.embed",)
    # an op with no metadata whose neighbours have none stays unscoped
    assert scopes.signature(names["iota.9"]) == ()
    assert scopes.signature(names["lonely.1"]) == ()
    assert set(scopes.compute_ops(HLO)) >= {"fusion.1", "dot.2", "gather.1",
                                            "step_c1.1", "lonely.1"}
    assert "iota.9" not in scopes.compute_ops(HLO)


def test_inheritance_from_users_then_operands():
    names = scopes.op_scopes(HLO)
    got = scopes.inherited(HLO)
    # users agree (two sync.pack fusions)
    assert scopes.signature(names["convert_bitcast_fusion.5"]) == \
        ("sync.pack",)
    # the only user has no name (a tuple): the operand's name
    assert scopes.signature(names["copy.7"]) == ("model.head_loss",)
    # users (model.embed, sync.pack) and operands (model.head_loss,
    # sync.unpack) both disagree: unscoped
    assert names["mixed.1"] is None
    # the loop body's stacking fusion: its only user is the root tuple,
    # its operands the attention dot and the loop counter (no name)
    assert scopes.signature(
        names["bitcast_dynamic-update-slice_fusion.26"]) == \
        ("model.layers", "model.attention")
    assert {"convert_bitcast_fusion.5", "copy.7", "fusion.1",
            "bitcast_dynamic-update-slice_fusion.26"} <= got
    assert not {"dot.2", "gather.1", "mixed.1", "lonely.1"} & got


def test_shared_scopes_and_scopeless_names():
    hlo = f"""HloModule m

ENTRY %main.1 (p.0: f32[4]) -> (f32[4], f32[4]) {{
  %p.0 = f32[4]{{0}} parameter(0)
  %a.1 = f32[4]{{0}} fusion(%p.0), kind=kLoop, calls=%f.1, metadata={{op_name="{LAYERS}/model.mlp/mul"}}
  %b.1 = f32[4]{{0}} fusion(%p.0), kind=kLoop, calls=%f.1, metadata={{op_name="{LAYERS}/dynamic_update_slice"}}
  %c.1 = f32[4]{{0}} fusion(%a.1, %b.1), kind=kLoop, calls=%f.1, metadata={{op_name="jit(step)/shard_map/broadcast.67"}}
  %d.1 = f32[4]{{0}} fusion(%c.1), kind=kLoop, calls=%f.1, metadata={{op_name="jit(step)/sync.reduce_scatter/add"}}
  ROOT %t.1 = (f32[4]{{0}}, f32[4]{{0}}) tuple(%c.1, %d.1)
}}
"""
    names = scopes.op_scopes(hlo)
    # an op_name in none of the program's scopes (one an XLA pass gave)
    # inherits like a missing one: here from its one named user
    assert scopes.signature(names["c.1"]) == ("sync.reduce_scatter",)
    assert "c.1" in scopes.inherited(hlo)
    # operands in model.layers, one also in model.mlp: what they share
    del_user = hlo.replace("%d.1 = f32[4]{0} fusion(%c.1)",
                           "%d.1 = f32[4]{0} fusion(%p.0)")
    assert scopes.signature(scopes.op_scopes(del_user)["c.1"]) == \
        ("model.layers",)


# -- the readers -------------------------------------------------------------

READERS = ("model.embed.ms", "model.layers.ms", "model.attention.ms",
           "model.mlp.ms", "model.head_loss.ms", "sync.flatbuf.ms",
           "step.unscoped.ms")
MS = 1_000_000      # ns


def ctx_for(hlo=HLO, trace=True, steps=2):
    # two steps' ops, back to back, in ms; the kernel and the collective
    # are left out of every reading, as step.xla_ms leaves them out
    plan = [("gather.1", 1), ("fusion.1", 30), ("dot.2", 20),
            ("bitcast_dynamic-update-slice_fusion.26", 4),
            ("fusion.5", 5), ("fusion.head", 12), ("copy.7", 2),
            ("convert_bitcast_fusion.5", 3), ("pack.1", 6), ("pack.2", 6),
            ("step_c1.1", 15), ("collective-permute-done.1", 9),
            ("unpack.1", 7), ("mixed.1", 1), ("emb.2", 8), ("pk.3", 2),
            ("lonely.1", 1), ("loss.1", 1), ("not_in_hlo.1", 3)]
    ops, t = [], 0
    for _ in range(steps):
        for name, ms in plan:
            ops.append((name, t, t + ms * MS))
            t += ms * MS
    tr = T.Trace(devices={0: ops}, spans=[("bench.dispatch", 0, 1),
                                          ("bench.loss_fetch", 1, t)])
    return {"trace": tr if trace else None, "run": {"hlo": hlo},
            "steps": steps if trace else 0, "lib": T,
            "kernel_ops": {"step_c1.1"}}


def read(name, ctx):
    return harness.metric_reader(name)(ctx)


def test_readers_return_the_planted_times():
    ctx = ctx_for()
    got = {n: read(n, ctx) for n in READERS}
    # the gather, its backward, and fusion.5 (model.layers2, inherited)
    assert got["model.embed.ms"] == pytest.approx(1 + 8 + 5)
    # the layer scan: its ops, attention and MLP
    assert got["model.layers.ms"] == pytest.approx(30 + 20 + 4)
    assert got["model.attention.ms"] == pytest.approx(20 + 4)
    assert got["model.mlp.ms"] == pytest.approx(30)
    assert got["model.head_loss.ms"] == pytest.approx(12 + 2)
    # pack and unpack; the kernel under sync.update is not counted
    assert got["sync.flatbuf.ms"] == pytest.approx(3 + 6 + 6 + 7 + 2)
    # mixed.1, lonely.1, loss.1 and an op the compiled text does not name
    assert got["step.unscoped.ms"] == pytest.approx(1 + 1 + 1 + 3)
    # the parts add up to step.xla_ms, which no other sync time joins here
    parts = sum(got[n] for n in ("model.embed.ms", "model.layers.ms",
                                 "model.head_loss.ms", "sync.flatbuf.ms",
                                 "step.unscoped.ms"))
    assert parts == pytest.approx(read("step.xla_ms", ctx))


def test_readers_are_silent_without_a_trace_or_scopes():
    for name in READERS:
        assert read(name, ctx_for(trace=False)) is None
        assert read(name, ctx_for(hlo="")) is None
    # a program that names no scope: nothing to read, nothing raised
    bare = "\n".join(line for line in HLO.splitlines()
                     if "model." not in line and "sync." not in line)
    for name in READERS:
        assert read(name, ctx_for(hlo=bare)) is None


def test_loop_time_without_inner_ops_is_unscoped():
    # the trace keeps no name for an op that holds others: a loop's own
    # time outside its body's ops joins step.unscoped.ms, so the parts
    # still sum to step.xla_ms
    raw = [("while.1", 0, 25 * MS), ("fusion.1", 0, 10 * MS),
           ("dot.2", 10 * MS, 20 * MS), ("gather.1", 25 * MS, 26 * MS),
           ("step_c1.1", 26 * MS, 30 * MS)]
    tr = T.Trace(devices={0: T.innermost(raw)},
                 busy={0: T.merged([(a, b) for _, a, b in raw])},
                 spans=[("bench.dispatch", 0, 1),
                        ("bench.loss_fetch", 1, 31 * MS)])
    ctx = {"trace": tr, "run": {"hlo": HLO}, "steps": 1, "lib": T,
           "kernel_ops": {"step_c1.1"}}
    got = {n: read(n, ctx) for n in READERS}
    assert got["model.layers.ms"] == pytest.approx(10 + 10)
    assert got["model.embed.ms"] == pytest.approx(1)
    assert got["step.unscoped.ms"] == pytest.approx(5)
    parts = sum(got[n] for n in ("model.embed.ms", "model.layers.ms",
                                 "model.head_loss.ms", "sync.flatbuf.ms",
                                 "step.unscoped.ms") if got[n] is not None)
    assert parts == pytest.approx(read("step.xla_ms", ctx))
