"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with jax, so these compile each Pallas
kernel of the training and exchange paths at qwen2-0.5b's flat-buffer
size, the full-width single-chip train step, and the full-width
shard_map driver step on a 2x2 mesh, exactly as the chip would: a block shape Mosaic refuses, or a program that does not fit the
chip's HBM, fails here. Every compiled program must hold a
``tpu_custom_call`` (the kernel ran compiled, not interpreted).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker
imports this file.
"""
import base64
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.core.hierarchy import SyncConfig
from repro.kernels.flash_attention.ops import causal_attention
from repro.kernels.fused_elastic import fused_elastic as fe
from repro.kernels.fused_optim.fused_optim import adagrad_flat, adamw_flat
from repro.kernels.fused_sgd.fused_sgd import sgd_momentum_flat
from repro.kernels.quant_bucket import quant_bucket as qb
from repro.kernels.tensor_reduce.tensor_reduce import group_reduce_flat
from repro.launch import shard_driver as SD
from repro.launch.train import (grad_spec, make_train_state, make_train_step,
                                state_specs)
from repro.models.model import build_model
from repro.optim.sgd import sgd

V5E_HBM = 15.75e9  # bytes the TPU compiler allows one v5e program
F32, BF16, I8 = jnp.float32, jnp.bfloat16, jnp.int8
NI = {"interpret": False}


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compile cache off (a
    described chip's entries cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2-0.5b")
    model = build_model(cfg)
    return model, grad_spec(model).size


def _wire_pad(n: int) -> int:
    """Length of ``quantize_wire``'s codes: whole (64, 128) tiles."""
    tile = qb.WIRE_TILE_ROWS * qb.WIRE_BLOCK
    return -(-n // tile) * tile


def _compile(fn, args, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


# name -> (fn, [(shape as a function of n, dtype)], donated args)
KERNELS = {
    "sgd_momentum_flat f32": (
        lambda p, v, g: sgd_momentum_flat(p, v, g, 0.1, 0.9, **NI),
        [(lambda n: (n,), F32)] * 3, (0, 1)),
    "sgd_momentum_flat bf16 momentum": (
        lambda p, v, g: sgd_momentum_flat(p, v, g, 0.1, 0.9, **NI),
        [(lambda n: (n,), F32), (lambda n: (n,), BF16),
         (lambda n: (n,), F32)], (0, 1)),
    "sgd_momentum_flat ragged tail": (
        lambda p, v, g: sgd_momentum_flat(p, v, g, 0.1, 0.9, **NI),
        [(lambda n: (n - 37,), F32)] * 3, (0, 1)),
    "adagrad_flat": (
        lambda p, s, g: adagrad_flat(p, s, g, 0.1, 1e-10, **NI),
        [(lambda n: (n,), F32)] * 3, (0, 1)),
    "adamw_flat": (
        lambda p, mv, g: adamw_flat(p, mv, g, 3e-3, 0.9, 0.95, 1e-8, 0.0,
                                    0.1, 0.05, **NI),
        [(lambda n: (n,), F32), (lambda n: (2, n), F32),
         (lambda n: (n,), F32)], (0, 1)),
    "adamw_flat bf16 state": (
        lambda p, mv, g: adamw_flat(p, mv, g, 3e-3, 0.9, 0.95, 1e-8, 0.0,
                                    0.1, 0.05, **NI),
        [(lambda n: (n,), F32), (lambda n: (2, n), BF16),
         (lambda n: (n,), F32)], (0, 1)),
    "elastic_center_flat": (
        lambda c, d: fe.elastic_center_flat(c, d, 0.1, **NI),
        [(lambda n: (n,), F32)] * 2, (0,)),
    "elastic_exchange_flat": (
        lambda w, c: fe.elastic_exchange_flat(w, c, 0.1, **NI),
        [(lambda n: (n,), F32)] * 2, (0, 1)),
    "elastic_client_flat": (
        lambda w, c: fe.elastic_client_flat(w, c, 0.1, **NI),
        [(lambda n: (n,), F32)] * 2, (0,)),
    "elastic_server_flat": (
        lambda w, c: fe.elastic_server_flat(w, c, 0.1, **NI),
        [(lambda n: (n,), F32)] * 2, (1,)),
    "elastic_client_diff_flat": (
        lambda w, c: fe.elastic_client_diff_flat(w, c, 0.1, **NI),
        [(lambda n: (n,), F32)] * 2, (0,)),
    "elastic_exchange_flat_mc": (
        lambda w, c: fe.elastic_exchange_flat_mc(w, c, 0.1, **NI),
        [(lambda n: (2, n // 2), F32), (lambda n: (n // 2,), F32)], (0, 1)),
    "quantize_wire": (
        lambda x: qb.quantize_wire(x, **NI), [(lambda n: (n,), F32)], ()),
    "dequantize_wire": (
        lambda c, s: qb.dequantize_wire(c, s, c.shape[0], **NI),
        [(lambda n: (_wire_pad(n),), I8),
         (lambda n: (_wire_pad(n) // qb.WIRE_BLOCK,), F32)], ()),
    "quantize_flat": (
        lambda x: qb.quantize_flat(x, **NI), [(lambda n: (n,), F32)], ()),
    "dequantize_flat": (
        lambda c, s: qb.dequantize_flat(c, s, c.shape[0], **NI),
        [(lambda n: (n,), I8), (lambda n: (-(-n // qb.QBLOCK),), F32)], ()),
    "group_reduce_flat": (
        lambda x: group_reduce_flat(x, **NI),
        [(lambda n: (4, n // 4), F32)], ()),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e_at_qwen2_flat_size(name, one_chip, qwen):
    _, n = qwen
    fn, shapes, donate = KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape(n), dt, sharding=one_chip)
            for shape, dt in shapes]
    compiled = _compile(fn, args, donate)
    assert "tpu_custom_call" in compiled.as_text()


def _kernel_bodies(text: str) -> list[bytes]:
    """The decoded body of every Pallas kernel in compiled HLO text."""
    return [base64.b64decode(b) for b in
            re.findall(r'"body":"([A-Za-z0-9+/=]+)"', text)]


ATTENTION_MARK = b"gqa_causal_"     # every attention kernel's name


@pytest.mark.parametrize("heads, kv_heads, head_dim", [
    (14, 2, 64),        # qwen2-0.5b
    (16, 2, 128),       # qwen2.5-3b
])
def test_attention_kernel_compiles_for_v5e_at_the_cells_shapes(
        heads, kv_heads, head_dim, one_chip, monkeypatch):
    """The causal flash kernel's forward and backward at 8 x 1024 tokens:
    three kernels, each named for attention, none of them the update's."""
    import repro.kernels.common as kc

    monkeypatch.setattr(kc, "use_interpret", lambda: False)
    G = heads // kv_heads
    q = jax.ShapeDtypeStruct((8, 1024, kv_heads, G, head_dim), BF16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 1024, kv_heads, head_dim), BF16,
                              sharding=one_chip)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(causal_attention, q, k, v)
        return o, vjp(do)

    bodies = _kernel_bodies(_compile(fwd_bwd, (q, kv, kv, q)).as_text())
    assert len(bodies) == 3
    assert all(ATTENTION_MARK in b for b in bodies)
    assert not any(b"sgd_momentum_flat" in b for b in bodies)


def test_full_width_train_step_compiles_for_one_v5e(one_chip, qwen,
                                                    monkeypatch):
    """qwen2-0.5b at published width, fused flat mpi_sgd with momentum,
    batch 8 x seq 1024, state donated: fits one chip's HBM."""
    import repro.kernels.common as kc

    # the backend here is the CPU: steer the kernels to their TPU form
    monkeypatch.setattr(kc, "use_interpret", lambda: False)
    model, _ = qwen
    opt = sgd(0.01, momentum=0.9)
    sync = SyncConfig(mode="mpi_sgd", num_clients=1)
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip)
    state = jax.tree.map(place, make_train_state(model, opt, sync,
                                                 abstract=True))
    tok = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one_chip)
    compiled = _compile(make_train_step(model, opt, sync, None),
                        (state, {"tokens": tok, "labels": tok}), (0,))
    bodies = _kernel_bodies(compiled.as_text())
    # the update; attention's forward, its recomputation in the layer
    # scan's backward, and its dK/dV and dQ kernels
    assert sum(b"sgd_momentum_flat" in b for b in bodies) == 1
    assert sum(ATTENTION_MARK in b for b in bodies) == 4
    assert not any(b"sgd_momentum_flat" in b and ATTENTION_MARK in b
                   for b in bodies)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def test_gspmd_step_compiles_for_four_v5e(topo, monkeypatch):
    """The GSPMD step (a mesh given to make_train_step, traced under it)
    on a described 2x2 data axis: XLA cannot partition a Mosaic kernel,
    so its attention keeps the jnp path and the step compiles. Two
    layers of qwen2-0.5b, batch 8 x seq 1024."""
    import dataclasses

    import repro.kernels.common as kc

    monkeypatch.setattr(kc, "use_interpret", lambda: False)
    model = build_model(dataclasses.replace(get_config("qwen2-0.5b"),
                                            num_layers=2))
    opt = sgd(0.01, momentum=0.9)
    sync = SyncConfig(mode="mpi_sgd", num_clients=1)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    abstract = make_train_state(model, opt, sync, abstract=True, mesh=mesh)
    state = jax.tree.map(
        lambda spec, s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        state_specs(abstract, mesh, sync), abstract,
        is_leaf=lambda x: isinstance(x, P))
    tok = jax.ShapeDtypeStruct((8, 1024), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    with jax.set_mesh(mesh):
        compiled = _compile(make_train_step(model, opt, sync, mesh),
                            (state, {"tokens": tok, "labels": tok}))
    assert not any(ATTENTION_MARK in b
                   for b in _kernel_bodies(compiled.as_text()))


@pytest.mark.parametrize("mode", ["mpi_sgd", "mpi_esgd"])
def test_gspmd_step_without_an_ambient_mesh_compiles_for_four_v5e(
        mode, topo, monkeypatch):
    """The GSPMD step jitted with no ambient mesh, as chip_smoke.py's
    mpi_esgd 2x2 reference and ``train_loop(..., mesh)`` jit it:
    make_train_step traces it under its mesh, so attention keeps the jnp
    path and the step compiles. Two layers of qwen2-0.5b, batch 8 x seq
    1024 (mpi_esgd: 2 clients of 4, over pod x data)."""
    import dataclasses

    import repro.kernels.common as kc

    monkeypatch.setattr(kc, "use_interpret", lambda: False)
    model = build_model(dataclasses.replace(get_config("qwen2-0.5b"),
                                            num_layers=2))
    opt = sgd(0.01, momentum=0.9)
    if mode == "mpi_sgd":
        sync = SyncConfig(mode="mpi_sgd", num_clients=1)
        mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
        tok = (8, 1024), P("data")
    else:
        sync = SyncConfig(mode="mpi_esgd", num_clients=2, esgd_interval=2)
        mesh = Mesh(np.array(topo.devices).reshape(2, 2),
                    (SD.POD_AXIS, SD.DATA_AXIS))
        tok = (2, 4, 1024), P(SD.POD_AXIS, SD.DATA_AXIS)
    abstract = make_train_state(model, opt, sync, abstract=True, mesh=mesh)
    state = jax.tree.map(
        lambda spec, s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        state_specs(abstract, mesh, sync), abstract,
        is_leaf=lambda x: isinstance(x, P))
    tok = jax.ShapeDtypeStruct(tok[0], jnp.int32,
                               sharding=NamedSharding(mesh, tok[1]))
    assert jax.sharding.get_abstract_mesh().empty
    compiled = _compile(make_train_step(model, opt, sync, mesh),
                        (state, {"tokens": tok, "labels": tok}), (0,))
    assert not any(ATTENTION_MARK in b
                   for b in _kernel_bodies(compiled.as_text()))


@pytest.mark.parametrize("mode,shape", [
    ("mpi_sgd", (4,)),              # 1-axis ring over p=4
    ("mpi_esgd", (2, 2)),           # 2-axis pod x data
])
def test_full_width_sharded_step_compiles_for_four_v5e(mode, shape, topo,
                                                       qwen, monkeypatch):
    """The shard_map driver step at qwen2-0.5b's published width on a
    described 2x2 mesh, batch 8 x seq 1024 over the 4 devices, state
    donated: the Pallas update is in it, the ring legs lower to
    collective-permutes, and each device's program fits its HBM."""
    import repro.kernels.common as kc

    monkeypatch.setattr(kc, "use_interpret", lambda: False)
    model, _ = qwen
    opt = sgd(0.01, momentum=0.9)
    names = (SD.AXIS,) if len(shape) == 1 else (SD.POD_AXIS, SD.DATA_AXIS)
    sync = (SyncConfig(mode="mpi_sgd", num_clients=1) if mode == "mpi_sgd"
            else SyncConfig(mode="mpi_esgd", num_clients=shape[0],
                            esgd_interval=2))
    mesh = Mesh(np.array(topo.devices).reshape(shape), names)
    sharded = NamedSharding(mesh, P(names))
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharded)
    geom = shape[0] if len(shape) == 1 else shape
    state = jax.tree.map(place, jax.eval_shape(
        lambda: SD.make_driver_state(model, opt, sync, geom)))
    tok = jax.ShapeDtypeStruct((4, 2, 1024), jnp.int32, sharding=sharded)
    compiled = _compile(SD.make_sharded_step(model, opt, sync, mesh),
                        (state, {"tokens": tok, "labels": tok}), (0,))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    mem = compiled.memory_analysis()    # per device
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
