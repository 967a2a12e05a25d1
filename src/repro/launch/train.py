"""Production train step + training-loop driver.

``make_train_step`` builds the jittable step for both lowerable sync
modes (core/hierarchy.py):

  mpi_sgd   C=1: one communicator; grads synced over every data axis per
            step (pure-MPI pushpull == tensor allreduce, #servers=0)
  mpi_esgd  C>1: params carry a leading client dim sharded over 'pod';
            vmap gives each client an independent replica whose gradient
            sync happens only over 'data' (intra-client); every INTERVAL
            steps the elastic exchange (eqs. 2/3) crosses 'pod' — the
            only cross-pod traffic.

HOW each leg syncs is no longer decided here: ``core.sync_engine``
resolves the strategy once (``make_sync_engine``) and the step drives
its interface. On the default no-mesh path BOTH modes ride the
flat-buffer substrate:

  * the gradient/update leg packs into a persistent ``FlatBuffer`` (spec
    built ONCE at trace time — no per-step concatenate), ring
    reduce-scatters, runs the fused momentum-SGD Pallas kernel on the
    local 1/p shard (momentum sharded: p× optimizer-memory reduction),
    and ring-allgathers updated params — (p-1)/p·n gradient-leg bytes
    instead of a full allreduce's 2·(p-1)/p·n;
  * the elastic leg packs params and centers and runs ONE fused Pallas
    kernel for eqs. (2)+(3) (one HBM pass, one launch) instead of
    O(num_leaves) tree.maps.

The paths are collective-explicit: they engage when no mesh is given
(single-process drivers, shard_map worker programs — see
launch/shard_driver.py — and vmap emulation; ``axis_name`` names the
device axis). With a mesh, GSPMD keeps inserting the gradient
collectives and the per-leaf legs are kept so parameter sharding is
undisturbed.

The optimizer is momentum SGD by default (what the paper ships to the PS);
state lives in a TrainState pytree so checkpointing is one call.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import comm as comm_lib, flatbuf
from repro.core.hierarchy import (
    SyncConfig,
    clientize,
    clientize_specs,
    should_elastic_sync,
)
from repro.core.sync_engine import (
    flat_exchange_active,
    flat_update_supported,
    make_sync_engine,
)
from repro.models.model import Model
from repro.optim.sgd import Optimizer
from repro.sharding.rules import batch_pspec, param_specs


def fused_path_active(optimizer: Optimizer, sync: SyncConfig,
                      mesh: Mesh | None = None) -> bool:
    """Whether the flat fused update replaces the per-leaf update.

    Back-compat shim over ``core.sync_engine.flat_update_supported`` —
    since the SyncEngine refactor it covers mpi_esgd (C>1) too, where
    each client's local update is the p=1 fused kernel.
    make_train_state and make_train_step must agree, so both call this
    with the same mesh.
    """
    return flat_update_supported(optimizer, sync, mesh)


def grad_spec(model: Model) -> flatbuf.FlatBuffer:
    """The persistent FlatBuffer spec for this model's gradient pytree —
    built once (static lane-aligned offsets) and reused every step."""
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    return flatbuf.spec_for(abstract)


def _engine_spec(model: Model, optimizer: Optimizer, sync: SyncConfig,
                 mesh: Mesh | None):
    """The FlatBuffer spec, when any flat leg will engage (else None)."""
    if (flat_update_supported(optimizer, sync, mesh)
            or flat_exchange_active(sync, mesh)):
        return grad_spec(model)
    return None


def overlap_schedule(model: Model, sync: SyncConfig, p: int = 1):
    """(OverlapStages, BucketSchedule) for the backward-overlapped path.

    The schedule is built ONCE over the STAGED param spec — the FlatBuffer
    of ``stage(params)``'s stage-subtree tuple, whose leaf order groups
    each backward stage's params contiguously so every schedule bucket is
    a leaf-boundary (lane-aligned) slice. ``p`` is the gradient group's
    shard count (``comm.resolve_size()``; 1 for the local state
    geometry).
    """
    if model.overlap_stages is None:
        raise ValueError(
            f"SyncConfig.overlap=True but model {model.cfg.name!r} does "
            "not publish overlap_stages — the staged-backward hook is "
            "wired for the decoder family (models/model.py "
            "_decoder_overlap_stages); run this architecture without "
            "overlap")
    stages = model.overlap_stages(sync.overlap_buckets)
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    staged = jax.eval_shape(stages.stage, abstract)
    spec = flatbuf.spec_for(staged)
    counts = tuple(len(jax.tree_util.tree_leaves(s)) for s in staged)
    return stages, flatbuf.bucket_schedule(spec, counts, p)


def make_overlap_grad_fn(model: Model, stages, schedule,
                         comm: comm_lib.Communicator) -> Callable:
    """``(params, batch) -> (loss, metrics, g_shard)`` with the wire leg
    issued DURING backward.

    Forward runs stage-by-stage under ``jax.vjp`` (recording one pullback
    per stage); backward then replays the pullbacks in reverse AT TRACE
    TIME, and bucket ``s``'s ring reduce-scatter is emitted immediately
    after stage ``s``'s pullback — so in the traced program every
    bucket's ppermute chain except the first-issued one sits BEFORE
    later (earlier-layer) backward compute, where the scheduler can
    overlap wire and math. ``g_shard`` is the bucket-major
    ``(schedule.shard_size,)`` concat of this device's reduced chunks —
    feed it to ``FlatEngine.update_overlapped`` / ``optim.sgd.
    overlap_update``.
    """
    S = stages.num_stages

    def grad_fn(params, batch):
        parts = stages.stage(params)
        # forward: record one pullback per stage
        vjps = [None] * S
        carry = None
        for s in range(S):
            fn = stages.fns[s]
            if S == 1:  # degenerate single bucket: the whole loss_fn
                loss, vjps[0], metrics = jax.vjp(
                    lambda p, fn=fn: fn(p, batch), parts[0], has_aux=True)
            elif s == 0:
                carry, vjps[0] = jax.vjp(
                    lambda p, fn=fn: fn(p, batch), parts[0])
            elif s < S - 1:
                carry, vjps[s] = jax.vjp(
                    lambda p, c, fn=fn: fn(p, c, batch), parts[s], carry)
            else:
                loss, vjps[s], metrics = jax.vjp(
                    lambda p, c, fn=fn: fn(p, c, batch), parts[s], carry,
                    has_aux=True)
        # backward: reversed stage order (head first, embedding last),
        # each bucket's reduce-scatter issued as soon as its grads exist
        shards = [None] * S
        ct: Any = jnp.ones((), loss.dtype)
        for s in range(S - 1, -1, -1):
            if s > 0:
                gp, ct = vjps[s](ct)
            else:
                (gp,) = vjps[0](ct)
            with jax.named_scope("sync.pack"):
                seg = schedule.pack_bucket(s, gp)
            with jax.named_scope("sync.reduce_scatter"):
                shards[s] = comm.reduce_scatter_bucket(seg, schedule, s)
        with jax.named_scope("sync.reduce_scatter"):
            g_shard = shards[0] if S == 1 else jnp.concatenate(shards)
        return loss, metrics, g_shard

    return grad_fn


def make_train_state(model: Model, optimizer: Optimizer, sync: SyncConfig,
                     rng: jax.Array | None = None, *, abstract: bool = False,
                     mesh: Mesh | None = None):
    """Concrete (or eval_shape'd) initial state.

    On the fused path the optimizer state is the flat state buffer
    (momentum / AdaGrad accumulator / AdamW m+v streams) in local (p=1)
    geometry — one per client when C>1; device-sharded drivers
    (shard_map / vmap emulation) re-init it per device with
    ``optim.sgd.optstate_shard_init``.
    """
    rng = jax.random.key(0) if rng is None else rng
    schedule = None
    if sync.overlap:
        _, schedule = overlap_schedule(model, sync, 1)
    engine = make_sync_engine(optimizer, sync, mesh,
                              spec=_engine_spec(model, optimizer, sync, mesh),
                              schedule=schedule)

    def build(rng):
        params = model.init(rng)
        opt0 = engine.init_opt(params)
        state = {
            "params": clientize(params, sync.num_clients),
            "opt": clientize(opt0, sync.num_clients),
            "step": jnp.zeros((), jnp.int32),
        }
        if sync.mode == "mpi_esgd":
            # center variables w̃ (eq. 2), in buffers of their own so the
            # state can be donated
            state["center"] = jax.tree.map(jnp.copy, params)
        return state

    if abstract:
        return jax.eval_shape(build, rng)
    return build(rng)


def state_specs(state: Any, mesh: Mesh, sync: SyncConfig) -> Any:
    """PartitionSpecs for a TrainState (params rules + client dim).

    Optimizer state that mirrors the param tree (per-leaf momentum)
    shares the param specs; anything else (flat fused buffers, custom
    states) is replicated.
    """
    C = sync.num_clients
    base_params = state["params"]
    if C > 1:
        base_params = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), base_params
        )
    pspecs = param_specs(base_params, mesh, fsdp=sync.fsdp)
    out = {
        "params": clientize_specs(pspecs, C),
        "opt": clientize_specs(pspecs, C)
        if _opt_matches(state["opt"], base_params)
        else jax.tree.map(lambda _: P(), state["opt"]),
        "step": P(),
    }
    if "center" in state:
        out["center"] = pspecs
    return out


def _opt_matches(opt_state: Any, params: Any) -> bool:
    try:
        jax.tree.map(lambda a, b: None, opt_state, params)
        return True
    except ValueError:
        return False


def make_grad_fn(model: Model, microbatch: int = 1,
                 pin: Optional[Callable] = None) -> Callable:
    """Build ``(params, batch) -> (loss, metrics, grads)`` for one client.

    ``microbatch`` > 1 splits the per-step batch into M accumulation
    steps — the paper's distinction between the *batch* (MXNET's
    scheduling unit) and the algorithmic *mini_batch_size* (§5), and the
    standard memory-term lever (only 1/M of the activations live at
    once). ``pin`` optionally constrains the accumulator's sharding.

    Shared by launch/train.py and launch/shard_driver.py so the mapped
    per-device step computes grads with exactly the single-process math.
    """
    pin = pin or (lambda g: g)

    def single_grad(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    if microbatch <= 1:
        return single_grad
    M = microbatch

    def accum_grad(params, batch):
        mb = jax.tree.map(
            lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]), batch
        )
        g0 = pin(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        ))
        m0 = jax.eval_shape(lambda b: single_grad(params, b)[1],
                            jax.tree.map(lambda a: a[0], mb))
        m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m0)

        def body(carry, mbatch):
            loss_acc, met_acc, g_acc = carry
            loss, metrics, grads = single_grad(params, mbatch)
            g_acc = pin(jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, grads
            ))
            met_acc = jax.tree.map(jnp.add, met_acc, metrics)
            return (loss_acc + loss, met_acc, g_acc), None

        (loss, metrics, grads), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), m0, g0), mb
        )
        grads = jax.tree.map(
            lambda g, p: (g / M).astype(p.dtype), grads, params
        )
        metrics = jax.tree.map(lambda m: m / M, metrics)
        return loss / M, metrics, grads

    return accum_grad


def _under_mesh(step: Callable, mesh: Mesh | None) -> Callable:
    """``step``, traced with ``mesh`` as the ambient mesh where its
    caller set none: model code that adapts to the mesh (the attention
    kernel's dispatch, ``sharding/rules``' batch constraints) then sees
    the mesh the step's arrays are laid out on, as under
    ``jax.set_mesh``. Inside shard_map or a caller's mesh it is ``step``."""
    if mesh is None:
        return step

    @functools.wraps(step)
    def traced(*args):
        if not jax.sharding.get_abstract_mesh().empty:
            return step(*args)
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return step(*args)

    return traced


def make_train_step(model: Model, optimizer: Optimizer, sync: SyncConfig,
                    mesh: Mesh, *, microbatch: int = 1,
                    comm: comm_lib.Communicator | None = None,
                    axis_name: str | None = None) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    ``comm`` is the gradient communicator for the fused sync path when
    the step runs inside shard_map (real mesh) or vmap (emulation);
    omitted (and with no deprecated ``axis_name``), the group is trivial
    — single-process: the fused update still runs (one Pallas grid over
    the whole flat buffer) with no collective.
    """
    C = sync.num_clients
    if mesh is not None:
        sync.validate(mesh)
    # C>1 vmaps the update over the client dim, so each client's sync
    # geometry is local (the trivial group inside the vmap)
    if comm is None:
        axes = (axis_name,) if (axis_name is not None and C <= 1) else ()
        comm = comm_lib.from_sync(sync, axes)
    elif C > 1:
        comm = comm.local()
    stages = schedule = None
    if sync.overlap:
        sync.validate(mesh)  # overlap guards apply even with no mesh
        if microbatch > 1:
            raise ValueError(
                "overlap=True with microbatch>1 would re-issue every "
                "schedule bucket's ring leg per accumulation step (M× the "
                "wire bytes — exactly the traffic overlap exists to "
                "hide); accumulate without overlap, or raise the per-step "
                "batch instead")
        stages, schedule = overlap_schedule(model, sync, comm.resolve_size())
    engine = make_sync_engine(
        optimizer, sync, mesh, comm=comm,
        spec=_engine_spec(model, optimizer, sync, mesh),
        schedule=schedule)

    if sync.overlap:
        ograd_fn = make_overlap_grad_fn(model, stages, schedule, comm)

        def step_overlap(state, batch):
            engine.check_opt_layout(state["opt"])
            loss, metrics, g_shard = ograd_fn(state["params"], batch)
            staged = stages.stage(state["params"])
            new_staged, new_o = engine.update_overlapped(
                g_shard, staged, state["opt"])
            return (
                {"params": stages.unstage(new_staged), "opt": new_o,
                 "step": state["step"] + 1},
                {"loss": loss, **metrics},
            )

        # overlap is mpi_sgd / C=1 (validate)
        return _under_mesh(step_overlap, mesh)

    # the gradient accumulator is a while-loop carry: without an explicit
    # constraint GSPMD replicates it (measured: +32 GB/dev on qwen3-4b),
    # so pin it to the params' sharding when a mesh is known
    pin = None
    if mesh is not None and C <= 1 and microbatch > 1:
        abstract = jax.eval_shape(model.init, jax.random.key(0))
        acc_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            param_specs(abstract, mesh, fsdp=sync.fsdp),
        )
        pin = lambda grads: jax.tree.map(
            jax.lax.with_sharding_constraint, grads, acc_shardings
        )

    grad_fn = make_grad_fn(model, microbatch, pin)

    def step_c1(state, batch):
        engine.check_opt_layout(state["opt"])
        loss, metrics, grads = grad_fn(state["params"], batch)
        new_p, new_o = engine.update(grads, state["opt"], state["params"])
        return (
            {"params": new_p, "opt": new_o, "step": state["step"] + 1},
            {"loss": loss, **metrics},
        )

    def step_multiclient(state, batch):
        # batch leaves have a leading client dim C (sharded over 'pod')
        engine.check_opt_layout(state["opt"], C)
        loss, metrics, grads = jax.vmap(grad_fn)(state["params"], batch)
        new_p, new_o = jax.vmap(engine.update)(
            grads, state["opt"], state["params"]
        )
        new_state = dict(state, params=new_p, opt=new_o, step=state["step"] + 1)

        if sync.mode == "mpi_esgd":
            def exchange(s):
                p2, c2 = engine.exchange_multiclient(
                    s["params"], s["center"], sync.esgd_alpha / C
                )
                return dict(s, params=p2, center=c2)

            new_state = jax.lax.cond(
                should_elastic_sync(state["step"], sync.esgd_interval),
                exchange, lambda s: s, new_state,
            )
        return new_state, {"loss": jnp.mean(loss),
                           **jax.tree.map(jnp.mean, metrics)}

    return _under_mesh(step_c1 if C <= 1 else step_multiclient, mesh)


def batch_specs(model: Model, shape, mesh: Mesh, sync: SyncConfig) -> Any:
    """PartitionSpecs for the input batch (client dim first when C>1)."""
    specs = model.input_specs(shape)
    C = sync.num_clients

    def one(name, leaf):
        extra = len(leaf.shape) - 1
        bp = batch_pspec(mesh, leaf.shape[0], extra_dims=extra)
        return bp

    base = {k: one(k, v) for k, v in specs.items()}
    if C > 1:
        # (C, B/C, ...): client dim on 'pod', batch dim on 'data'
        def reclient(name, leaf, spec):
            dims = [None] * len(leaf.shape)
            return P("pod", "data", *dims[2:])

        return {
            k: reclient(k, v, base[k]) for k, v in clientize_batch_specs(specs, C).items()
        }
    return base


def clientize_batch_specs(specs: Any, C: int) -> Any:
    return {
        k: jax.ShapeDtypeStruct((C, v.shape[0] // C) + v.shape[1:], v.dtype)
        for k, v in specs.items()
    }


def train_loop(model: Model, optimizer: Optimizer, sync: SyncConfig,
               mesh: Mesh, batches, *, rng=None, log_every: int = 10,
               callback: Optional[Callable] = None,
               checkpoint_every: int = 0, checkpoint_dir: str = "",
               restore: str = ""):
    """Concrete training driver (examples / smoke scale).

    ``checkpoint_every``/``checkpoint_dir`` write atomic durable
    checkpoints (checkpoint/checkpoint.py) of the full TrainState every
    N completed steps; ``restore`` loads one and fast-forwards past the
    steps it already covers (the data pipeline is deterministic per
    step, so the resumed curve continues the original).
    """
    from repro.checkpoint import checkpoint as ckpt

    state = make_train_state(model, optimizer, sync, rng, mesh=mesh)
    start = 0
    if restore:
        state, meta = ckpt.restore_checkpoint(restore, state)
        start = int(meta.get("step", 0))
    # donated: the step's new state reuses the old state's buffers
    step_fn = jax.jit(make_train_step(model, optimizer, sync, mesh),
                      donate_argnums=(0,))
    history = []
    for i, batch in enumerate(batches):
        if i < start:
            continue            # covered by the restored checkpoint
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            state, metrics = step_fn(state, batch)
            if i % log_every == 0:
                with jax.profiler.TraceAnnotation("repro.metrics_fetch"):
                    entry = {k: float(v) for k, v in metrics.items()}
                entry["step"] = i
                history.append(entry)
                if callback:
                    callback(entry)
            if (checkpoint_every and checkpoint_dir
                    and (i + 1) % checkpoint_every == 0):
                with jax.profiler.TraceAnnotation("repro.checkpoint_save"):
                    ckpt.save_checkpoint(
                        ckpt.checkpoint_path(checkpoint_dir, i + 1), state,
                        step=i + 1)
    return state, history


def main() -> None:  # pragma: no cover (CLI driver; see tests/test_launch.py)
    """The per-client worker entry point the launcher's emitted
    ``mpirun ... python -m repro.launch.train`` commands invoke.

    One process == one MPI client (C=1 inside the process; the PS tier
    glues clients together, so --client/--num-clients/--scheduler are
    recorded for the job spec but the in-process sync mode is mpi_sgd).
    Sync knobs arrive as the flags launcher.JobSpec threads through
    (--optimizer / --fused-update / --no-fused-update / --flat-exchange /
    --no-flat-exchange / --bucket-bytes) and are lowered via
    configs.base.TrainSettings.
    """
    import argparse
    import os

    from repro.configs.base import TrainSettings, get_config, reduced
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.launch.compile_cache import init_compile_cache
    from repro.models.model import build_model

    init_compile_cache()

    ap = argparse.ArgumentParser(description="per-client training worker")
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--shape", default="train_4k",
                    help="job-spec input shape id (recorded)")
    ap.add_argument("--client", type=int, default=0)
    ap.add_argument("--num-clients", type=int, default=1)
    ap.add_argument("--scheduler", default=None,
                    help="scheduler host:port from the job spec (recorded; "
                         "the single-process reproduction runs standalone)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--optimizer", default="sgd",
                    choices=("sgd", "adagrad", "adamw"),
                    help="update rule; every choice rides the fused flat "
                         "path when --fused-update is set")
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--fused-update", dest="fused_update",
                    action="store_true", default=True)
    ap.add_argument("--no-fused-update", dest="fused_update",
                    action="store_false")
    ap.add_argument("--flat-exchange", dest="flat_exchange",
                    action="store_true", default=True)
    ap.add_argument("--no-flat-exchange", dest="flat_exchange",
                    action="store_false")
    ap.add_argument("--bucket-bytes", type=int, default=0)
    ap.add_argument("--wire-dtype", default="f32",
                    choices=("f32", "bf16", "int8"),
                    help="low-precision wire protocol on the ring hops "
                         "(requires a ring-family --allreduce method; "
                         "f32 = full precision)")
    ap.add_argument("--state-dtype", default="f32",
                    choices=("f32", "bf16"),
                    help="flat optimizer-state stream dtype (bf16 halves "
                         "AdaGrad/AdamW state bytes per device)")
    ap.add_argument("--overlap", action="store_true", default=False,
                    help="backward-overlapped bucketed reduce-scatter: "
                         "stage backprop and issue each schedule bucket's "
                         "ring leg while earlier layers still "
                         "differentiate (forces a ring allreduce and "
                         "num_rings=1)")
    ap.add_argument("--overlap-buckets", type=int, default=4,
                    help="schedule buckets == backward stages "
                         "(1 = degenerate non-overlapped schedule)")
    ap.add_argument("--allreduce", default=None,
                    choices=("psum", "ring", "multi_ring", "tree",
                             "scatter_gather"),
                    help="intra-client collective (default: psum, or ring "
                         "when --wire-dtype is low-precision)")
    ap.add_argument("--num-rings", type=int, default=0,
                    help="concurrent rings for ring-family methods "
                         "(0 = default: 2, or 1 under --overlap)")
    ap.add_argument("--policy", default=None, choices=("auto",),
                    help="'auto' ranks the collective-policy space with "
                         "the cost model (launch.autotune) and runs the "
                         "fastest valid policy, overriding the flat "
                         "--allreduce/--num-rings/--wire-dtype/--overlap "
                         "knobs")
    ap.add_argument("--tune-p", type=int, default=8,
                    help="devices per client --policy auto scores the "
                         "candidates at (the job geometry)")
    ap.add_argument("--faults", default="",
                    help="deterministic fault schedule (core/faults.py "
                         "string form, e.g. 'kill@12:unit=1'); validated "
                         "here, injected by the drivers that own a clock "
                         "(core/algorithms.py, shard_driver.drive)")
    ap.add_argument("--barrier-timeout", type=float, default=None,
                    help="seconds before the sync PS barrier releases "
                         "with the survivor group (kill/drop schedules "
                         "need it)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="durable checkpoint cadence in completed steps "
                         "(0 = off); transport workers park PS state at "
                         "this cadence instead")
    ap.add_argument("--checkpoint-dir", default="checkpoints",
                    help="directory the in-process loop checkpoints into")
    ap.add_argument("--restore", default="",
                    help="checkpoint path to restore params/opt-state/"
                         "step from before stepping")
    ap.add_argument("--full-size", action="store_true",
                    help="full architecture (default: reduced smoke config)")
    ap.add_argument("--transport", default="loopback",
                    choices=("loopback", "tcp"),
                    help="'tcp' makes this process a real transport worker: "
                         "it joins the rendezvous, gets its identity, and "
                         "runs net/worker.py's loop against the socket PS "
                         "tier ('loopback' keeps the standalone in-process "
                         "reproduction below)")
    ap.add_argument("--rendezvous",
                    default=os.environ.get("REPRO_RDZV_ADDR"),
                    help="rendezvous host:port for --transport tcp "
                         "(default: $REPRO_RDZV_ADDR from the emitted "
                         "script)")
    ap.add_argument("--mode", default="",
                    help="transport algorithm mode (dist_sgd / dist_esgd); "
                         "the job config from the rendezvous is "
                         "authoritative, this is recorded for the spec")
    ap.add_argument("--problem", default="logreg8",
                    help="transport training problem (net/problem.py)")
    args = ap.parse_args()

    if args.transport == "tcp":
        import json as _json

        from repro.net.worker import _jsonable, run_worker

        if not args.rendezvous:
            ap.error("--transport tcp needs --rendezvous (or "
                     "REPRO_RDZV_ADDR in the environment)")
        rank = int(os.environ.get("REPRO_RANK", args.client))
        attempt = int(os.environ.get("REPRO_ATTEMPT", "0"))
        out = run_worker(rank=rank, rendezvous_addr=args.rendezvous,
                         transport="tcp", attempt=attempt)
        from repro.net.transport import connect_with_retry, transport_for

        conn = connect_with_retry(transport_for("tcp"), args.rendezvous)
        config, _ = conn.request("config")
        conn.close()
        outdir = config.get("outdir")
        if outdir:
            path = os.path.join(outdir, f"metrics_worker_{rank}.json")
            with open(path, "w") as f:
                _json.dump(_jsonable(out), f, indent=2)
        print(f"[train] transport worker {rank} done: "
              f"{len(out.get('losses', []))} steps, "
              f"final loss "
              f"{out['losses'][-1] if out.get('losses') else None}",
              flush=True)
        return

    from repro.core.comm import CollectivePolicy

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = reduced(cfg)
    if args.policy == "auto":
        from repro.configs.base import INPUT_SHAPES
        from repro.launch.autotune import autotune_for_model, format_table

        shape = INPUT_SHAPES.get(args.shape)
        tokens = (shape.seq_len * shape.global_batch if shape is not None
                  else 1 << 20)
        result = autotune_for_model(cfg, p=args.tune_p,
                                    tokens_per_step=tokens)
        pol = result.chosen.policy
        print(f"[train] --policy auto: ranked "
              f"{len(result.ranked)} valid / {len(result.pruned)} pruned "
              f"candidates at p={result.p}, "
              f"payload={result.nbytes:.0f} B", flush=True)
        print(format_table(result), flush=True)
    else:
        method = args.allreduce or (
            "psum" if args.wire_dtype == "f32" and not args.overlap
            else "ring")
        pol = CollectivePolicy(
            method=method,
            num_rings=1 if args.overlap else (args.num_rings or 2),
            bucket_bytes=args.bucket_bytes or None,
            wire_dtype=(None if args.wire_dtype == "f32"
                        else args.wire_dtype),
            overlap=args.overlap, overlap_buckets=args.overlap_buckets)
    settings = TrainSettings(lr=args.lr, momentum=args.momentum,
                             optimizer_name=args.optimizer,
                             weight_decay=args.weight_decay,
                             fused_update=args.fused_update,
                             flat_exchange=args.flat_exchange,
                             policy=pol,
                             state_dtype=args.state_dtype,
                             faults=args.faults,
                             barrier_timeout=args.barrier_timeout,
                             checkpoint_every=args.checkpoint_every,
                             restore=args.restore)
    settings.fault_schedule()  # parse errors surface before any compute
    model = build_model(cfg)
    sync = settings.sync_config()
    optimizer = settings.optimizer()
    pipe = TokenPipeline(DataConfig(
        seed=0, vocab_size=min(cfg.padded_vocab, 256), seq_len=64,
        batch_size=8, steps_per_epoch=args.steps, shard=args.client))
    print(f"[train] client {args.client}/{args.num_clients} arch={cfg.name} "
          f"shape={args.shape} scheduler={args.scheduler} "
          f"optimizer={settings.optimizer_name} "
          f"fused_update={settings.fused_update} "
          f"bucket_bytes={settings.bucket_bytes} "
          f"wire_dtype={settings.wire_dtype} "
          f"state_dtype={settings.state_dtype} "
          f"overlap={settings.overlap} "
          f"overlap_buckets={settings.overlap_buckets} "
          f"faults={settings.faults!r} "
          f"barrier_timeout={settings.barrier_timeout}", flush=True)
    _, hist = train_loop(model, optimizer, sync, None, pipe.epoch(0),
                         log_every=max(args.steps // 10, 1),
                         checkpoint_every=settings.checkpoint_every,
                         checkpoint_dir=args.checkpoint_dir,
                         restore=settings.restore)
    for entry in hist:
        print(f"step {entry['step']:4d} loss {entry['loss']:.4f}", flush=True)
    print(f"[train] done: {len(hist)} log points, "
          f"final loss {hist[-1]['loss']:.4f}", flush=True)


if __name__ == "__main__":  # pragma: no cover
    main()
