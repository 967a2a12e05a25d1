"""The training step names its layers: every op of the compiled step
carries the ``jax.named_scope`` of the layer it belongs to in its HLO
``op_name`` (``model.*`` for the model, ``sync.*`` for the sync
engine's legs), and after inheritance (``benchmarks/chip/scopes.py``)
almost every compute instruction lies in one of them."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import scopes  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.core.hierarchy import SyncConfig  # noqa: E402
from repro.launch.train import make_train_state, make_train_step  # noqa: E402
from repro.models.model import XENT_CHUNK, build_model  # noqa: E402
from repro.optim.sgd import get_optimizer  # noqa: E402

LOCAL = ("model.embed", "model.layers", "model.attention", "model.mlp",
         "model.head_loss", "sync.pack", "sync.update", "sync.unpack")
UNSCOPED_SHARE = 0.05


def tiny_model():
    cfg = dataclasses.replace(
        get_config("qwen2-0.5b"), num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
        dtype="bfloat16")
    return build_model(cfg)


def compiled(model, sync, rows):
    """The compiled text of the step of ``launch/train.py`` for ``rows``
    sequences of two cross-entropy chunks (the chunked head is the path
    taken)."""
    opt = get_optimizer("sgd", 0.01, momentum=0.9)
    state = make_train_state(model, opt, sync, jax.random.key(0),
                             abstract=True)
    shape = rows + (2 * XENT_CHUNK,)
    batch = {"tokens": jax.ShapeDtypeStruct(shape, jnp.int32),
             "labels": jax.ShapeDtypeStruct(shape, jnp.int32)}
    step = make_train_step(model, opt, sync, None)
    return jax.jit(step, donate_argnums=(0,)).lower(
        state, batch).compile().as_text()


def named(hlo) -> set:
    """The program's scopes that some instruction's own op_name names."""
    parsed = scopes.parse(hlo)
    return {s for _, op, _, _, own in parsed.values() if own
            for s in scopes.signature(op)}


def unscoped_share(hlo) -> tuple[int, int]:
    names = scopes.op_scopes(hlo)
    ops = scopes.compute_ops(hlo)
    return sum(not scopes.signature(names[n]) for n in ops), len(ops)


def test_fused_flat_step_names_every_local_layer():
    hlo = compiled(tiny_model(), SyncConfig(mode="mpi_sgd", num_clients=1),
                   (2,))
    assert set(LOCAL) <= named(hlo)
    # one chip: no exchange legs
    assert not {"sync.reduce_scatter", "sync.allgather",
                "sync.elastic"} & named(hlo)
    bare, total = unscoped_share(hlo)
    assert total > 100
    assert bare < UNSCOPED_SHARE * total, (bare, total)
    # the inheritance does work here: many compute ops carry no op_name
    assert len(scopes.inherited(hlo)) > 0.1 * total


def test_elastic_exchange_is_named():
    model = tiny_model()
    hlo = compiled(model, SyncConfig(mode="mpi_esgd", num_clients=2,
                                     esgd_interval=2), (2, 1))
    assert "sync.elastic" in named(hlo)
    assert set(LOCAL) <= named(hlo)
    bare, total = unscoped_share(hlo)
    assert bare < UNSCOPED_SHARE * total, (bare, total)


SHARDED = textwrap.dedent("""
    import dataclasses, json, sys
    import jax, jax.numpy as jnp
    sys.path[:0] = [{root!r}]
    from benchmarks.chip import scopes
    from repro.configs.base import get_config
    from repro.core.hierarchy import SyncConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.shard_driver import (
        AXIS, make_driver_state, make_sharded_step, shard_batch)
    from repro.models.model import XENT_CHUNK, build_model
    from repro.optim.sgd import get_optimizer

    cfg = dataclasses.replace(
        get_config("qwen2-0.5b"), num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
        dtype="bfloat16")
    model = build_model(cfg)
    opt = get_optimizer("sgd", 0.01, momentum=0.9)
    sync = SyncConfig(mode="mpi_sgd", num_clients=1)
    mesh = make_mesh((4,), (AXIS,))
    state = make_driver_state(model, opt, sync, rng=jax.random.key(0),
                              mesh=mesh)
    toks = jnp.zeros((4, 2 * XENT_CHUNK), jnp.int32)
    batch = shard_batch({{"tokens": toks, "labels": toks}}, 4)
    step = jax.jit(make_sharded_step(model, opt, sync, mesh))
    hlo = step.lower(state, batch).compile().as_text()
    parsed = scopes.parse(hlo)
    names = scopes.op_scopes(hlo)
    ops = scopes.compute_ops(hlo)
    print(json.dumps({{
        "named": sorted({{s for _, op, _, _, own in parsed.values() if own
                         for s in scopes.signature(op)}}),
        "bare": sum(not scopes.signature(names[n]) for n in ops),
        "total": len(ops)}}))
""")


@pytest.mark.multidevice
def test_sharded_step_names_its_ring_legs():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", SHARDED.format(root=ROOT)],
                       env=env, capture_output=True, text=True, timeout=600,
                       cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert {"sync.reduce_scatter", "sync.allgather"} <= set(got["named"])
    assert set(LOCAL) <= set(got["named"])
    assert got["bare"] < UNSCOPED_SHARE * got["total"], got


def host_spans(path) -> dict:
    """``{event name: count}`` on the host plane of the trace under
    ``path``."""
    import glob

    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, f"no trace under {path}"
    names: dict = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    names[e.name] = names.get(e.name, 0) + 1
    return names


def test_train_loop_spans_on_the_host_plane(tmp_path):
    from repro.configs.base import reduced
    from repro.launch.train import train_loop

    model = build_model(reduced(get_config("qwen2-0.5b")))
    toks = jax.random.randint(jax.random.key(0), (4, 32), 0, 1024)
    batches = [{"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}] * 3
    with jax.profiler.trace(str(tmp_path / "trace")):
        _, hist = train_loop(
            model, get_optimizer("sgd", 0.1, momentum=0.9),
            SyncConfig(mode="mpi_sgd", num_clients=1), None, batches,
            log_every=2, checkpoint_every=3,
            checkpoint_dir=str(tmp_path / "ckpt"))
    names = host_spans(tmp_path / "trace")
    assert names.get("train") == 3
    assert names.get("repro.metrics_fetch") == 2      # steps 0 and 2
    assert names.get("repro.checkpoint_save") == 1
    assert [h["step"] for h in hist] == [0, 2]


def test_drive_names_its_membership_changes(tmp_path):
    from repro.configs.base import reduced
    from repro.launch.shard_driver import drive

    model = build_model(reduced(get_config("qwen2-0.5b")))
    toks = jax.random.randint(jax.random.key(0), (4, 32), 0, 1024)
    batches = [{"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}] * 3
    with jax.profiler.trace(str(tmp_path / "trace")):
        _, hist = drive(model, get_optimizer("sgd", 0.1, momentum=0.9),
                        SyncConfig(mode="mpi_sgd", num_clients=1), batches,
                        p=2, log_every=1,
                        faults="kill@1:unit=1;restart@2:unit=1")
    names = host_spans(tmp_path / "trace")
    assert names.get("train") == 3
    assert names.get("repro.metrics_fetch") == 3
    assert names.get("repro.reconfigure") == 1
    assert names.get("repro.rejoin") == 1
    assert [h.get("event") for h in hist if "event" in h] == \
        ["reconfigure", "join"]
