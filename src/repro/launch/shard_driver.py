"""shard_map production driver: the whole train step per-device.

launch/train.py's collective-explicit fused path engages when no mesh is
given; under GSPMD (a mesh) XLA owns the gradient collectives. This
driver closes the gap between the two: it runs BOTH lowerable modes with
the step mapped per-device over real mesh axes via ``jax.shard_map``
— gradients computed INSIDE the mapped function on the device's batch
shard, explicit ring collectives carrying every byte of cross-device
traffic (GSPMD inserts nothing), optimizer state sharded with
``optstate_shard_init`` (momentum SGD, AdaGrad, or AdamW — AdamW's two
full-size moment streams both live 1/p per device).

Which collective runs over which devices is decided by **communicator
algebra** (core/comm.py), not axis-name strings: the driver builds a
``world`` communicator over the mesh axes and ``comm.sync_comms`` carves
it into the paper's groups —

  mpi_sgd   the gradient group IS the world (C = 1 pure-MPI mode): pack
            grads into the FlatBuffer -> (hierarchical) ring
            reduce-scatter -> fused optimizer Pallas kernel on the local
            1/p shard (state sharded 1/p) -> ring allgather
  mpi_esgd  the 'pod' axis is the PS tier: the gradient group is
            everything BUT 'pod' (local fused update inside the client),
            and every INTERVAL steps the flat sharded elastic exchange
            crosses the 'pod' group (ONE Pallas pass for eq. (3) + the
            packed differences, ring reduce-scatter of the differences,
            fused eq. (2) on the 1/p center shard, allgather) — the only
            cross-client traffic

Two mesh layouts serve this:

  1-axis    ``p`` is an int, one axis (default "dev"). mpi_sgd: the axis
            is the intra-client MPI communicator. mpi_esgd: each device
            is one CLIENT (the axis plays the pod role).
  2-axis    ``p`` is ``(P, D)`` (or the mesh has 'pod' and 'data' axes):
            the paper's full hierarchy in ONE shard_map program. mpi_sgd
            reduce-scatters hierarchically over pod then data (same
            total bytes and final 1/(P*D) shard as one (P*D)-ring).
            mpi_esgd confines the gradient leg to 'data' INSIDE each
            pod-client (state sharded 1/D) while the elastic leg crosses
            'pod' — provably: the legs' ppermutes name disjoint axes
            (tests/test_shard_driver.py asserts this on the jaxpr).

Both legs run under the world communicator's full collective policy —
including the low-precision wire protocol (``SyncConfig.wire_dtype``:
bf16 casts or int8 codes + per-bucket scales on every ppermute hop,
compounding the (p−1)/p·n gradient-leg saving by another 2–4x) and the
low-precision optimizer-state streams (``hyper["state_dtype"]``: bf16
AdaGrad accumulator / AdamW m+v at half the bytes per device).

Driver state is *stacked*: every leaf carries a leading device dim
p_total (pod-major for 2-axis), sharded over the axes on a real mesh (so
each device holds exactly its replica/shard) and vmapped — one nested
vmap per axis — under single-device emulation; one layout serves
production and tests alike. The elastic INTERVAL condition is applied
OUTSIDE the mapped functions (a scalar ``lax.cond`` choosing whether to
invoke the mapped exchange at all), so the collectives never sit inside
a data-dependent branch.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import comm as comm_lib, flatbuf
from repro.core.comm import Communicator, sync_comms
from repro.core.elastic import elastic_exchange_sharded
from repro.core.hierarchy import SyncConfig, should_elastic_sync
from repro.core.sync_engine import flat_update_supported, make_sync_engine
from repro.launch.train import (
    grad_spec,
    make_grad_fn,
    make_overlap_grad_fn,
    overlap_schedule,
)
from repro.models.model import Model
from repro.optim.sgd import Optimizer, optstate_sched_init, optstate_shard_init

AXIS = "dev"                       # the 1-axis layout's single axis
POD_AXIS, DATA_AXIS = "pod", "data"  # the 2-axis (hierarchy) layout

Geometry = Union[int, Sequence[int]]


def _factorize(p: Geometry, axis_name: str = AXIS
               ) -> tuple[tuple[int, ...], tuple[str, ...]]:
    """Normalize the device geometry: an int is the 1-axis layout over
    ``axis_name``; a (pods, data) pair is the 2-axis pod×data layout."""
    if isinstance(p, (tuple, list)):
        if len(p) != 2:
            raise ValueError(
                f"2-axis geometry is (pods, data), got {tuple(p)}")
        return (int(p[0]), int(p[1])), (POD_AXIS, DATA_AXIS)
    return (int(p),), (axis_name,)


def driver_world(sync: SyncConfig, p: Geometry, *,
                 axis_name: str = AXIS) -> Communicator:
    """The top-level communicator for a driver geometry, carrying the
    SyncConfig's collective policy."""
    shape, axes = _factorize(p, axis_name)
    return comm_lib.from_sync(sync, axes, shape)


def _require_supported(model: Model, optimizer: Optimizer, sync: SyncConfig,
                       world: Communicator) -> flatbuf.FlatBuffer:
    if not flat_update_supported(optimizer, sync, None):
        raise ValueError(
            "the shard driver runs the flat fused substrate only: "
            "momentum-SGD (f32 state), AdaGrad or AdamW with "
            "SyncConfig.fused_update=True")
    sync.validate()
    if sync.mode == "mpi_esgd":
        _, ex = sync_comms(sync, world)
        pods = ex.static_size
        if sync.num_clients != pods:
            what = ("one client per pod" if POD_AXIS in world.axes
                    else "one client per device")
            raise ValueError(
                f"mpi_esgd under the shard driver maps {what}: "
                f"num_clients={sync.num_clients} != {pods} (world "
                f"axes {world.axes}, sizes {world.sizes})")
    return grad_spec(model)


def shard_batch(batch: Any, p: Geometry) -> Any:
    """(B, ...) host batch -> (p_total, B/p_total, ...) stacked
    per-device shards (pod-major for 2-axis geometries).

    For mpi_esgd the leading dim doubles as the client dim (pod ==
    client), matching launch/train.py's clientized batch layout.
    """
    shape, _ = _factorize(p)
    n = math.prod(shape)
    leaves = jax.tree_util.tree_leaves(batch)
    if leaves and leaves[0].shape[0] % n:
        raise ValueError(
            f"batch size {leaves[0].shape[0]} does not divide over "
            f"{n} devices (geometry {p}) — after an elastic membership "
            "change, feed batches sized for the SURVIVOR count (a "
            "multiple of every geometry the fault schedule can reach)")
    return jax.tree.map(
        lambda a: a.reshape((n, a.shape[0] // n) + a.shape[1:]), batch
    )


def make_driver_state(model: Model, optimizer: Optimizer, sync: SyncConfig,
                      p: Geometry | None = None, rng: jax.Array | None = None,
                      *, mesh=None, axis_name: str = AXIS) -> dict:
    """Stacked (leading device dim p_total) initial state.

    mpi_sgd: params replicated, optimizer state (momentum / AdaGrad
    accumulator / AdamW m+v streams) sharded 1/p_total per device.
    mpi_esgd: one replica per client (pod), optimizer state sharded over
    the client's gradient group (1-axis: full local state per device;
    2-axis: 1/D per device), replicated center.

    With a ``mesh`` the geometry comes from its axes and the state is
    built in one program whose outputs are sharded over them: each device
    computes and holds only its own row, so no device ever holds the p
    stacked replicas.
    """
    rng = jax.random.key(0) if rng is None else rng
    out_shardings = None
    if mesh is not None:
        if p is not None:
            raise ValueError("pass p= (emulation) or mesh=, not both")
        p, axes = _mesh_geometry(mesh, axis_name)
        out_shardings = NamedSharding(mesh, P(axes))
    world = driver_world(sync, p, axis_name=axis_name)
    spec = _require_supported(model, optimizer, sync, world)
    grad_comm, _ = sync_comms(sync, world)
    gp = grad_comm.static_size
    nr = grad_comm.rings_for(spec.nbytes)
    n = world.static_size
    if sync.overlap:
        # overlapped layout: bucket-major concat of per-bucket chunks
        # over the STAGED spec, at the gradient group's p
        _, schedule = overlap_schedule(model, sync, gp)

    def build(rng):
        params = model.init(rng)
        if sync.overlap:
            opt0 = optstate_sched_init(optimizer.hyper, schedule)
        else:
            opt0 = optstate_shard_init(optimizer.hyper, spec, gp, nr)

        def stack(tree):
            return jax.tree.map(
                lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), tree)

        state = {
            "params": stack(params),
            "opt": stack(opt0),
            "step": jnp.zeros((n,), jnp.int32),
        }
        if sync.mode == "mpi_esgd":
            state["center"] = stack(params)
        return state

    return jax.jit(build, out_shardings=out_shardings)(rng)


def make_device_step(model: Model, optimizer: Optimizer, sync: SyncConfig,
                     *, world: Optional[Communicator] = None,
                     axis_name: str = AXIS, microbatch: int = 1
                     ) -> tuple[Callable, Optional[Callable]]:
    """The per-device programs: ``(device_step, device_exchange)``.

    ``device_step`` computes grads on the device's batch shard and runs
    the engine's sync+update leg over the gradient communicator;
    ``device_exchange`` (mpi_esgd only) is the flat sharded elastic
    exchange over the exchange (pod) communicator. Both are meant to run
    inside shard_map on a real mesh or under nested
    ``jax.vmap(..., axis_name=...)`` emulation — ``make_sharded_step`` /
    ``make_emulated_step`` wrap them.

    ``world`` is the driver's top-level communicator (see
    ``driver_world``); omitted, a 1-axis world over ``axis_name`` with
    trace-time-resolved size is built (the legacy spelling).
    """
    if world is None:
        world = comm_lib.from_sync(sync, (axis_name,))
    grad_comm, ex_comm = sync_comms(sync, world)
    spec = grad_spec(model)
    stages = schedule = None
    if sync.overlap:
        if microbatch > 1:
            raise ValueError(
                "overlap=True with microbatch>1 would re-issue every "
                "schedule bucket's ring leg per accumulation step (M× "
                "the wire bytes overlap exists to hide); accumulate "
                "without overlap, or raise the per-step batch instead")
        stages, schedule = overlap_schedule(model, sync,
                                            grad_comm.resolve_size())
    engine = make_sync_engine(optimizer, sync, None, comm=grad_comm,
                              spec=spec, schedule=schedule)
    grad_fn = (make_overlap_grad_fn(model, stages, schedule, grad_comm)
               if sync.overlap else make_grad_fn(model, microbatch))

    def device_step(state, batch):
        if sync.overlap:
            loss, metrics, g_shard = grad_fn(state["params"], batch)
            staged = stages.stage(state["params"])
            new_staged, new_o = engine.update_overlapped(
                g_shard, staged, state["opt"])
            new_p = stages.unstage(new_staged)
        else:
            loss, metrics, grads = grad_fn(state["params"], batch)
            new_p, new_o = engine.update(grads, state["opt"],
                                         state["params"])
        metrics = {"loss": loss, **metrics}
        metrics = jax.tree.map(world.pmean, metrics)
        return dict(state, params=new_p, opt=new_o,
                    step=state["step"] + 1), metrics

    if ex_comm is None:
        return device_step, None

    def device_exchange(state):
        alpha = sync.esgd_alpha / ex_comm.resolve_size()
        with jax.named_scope("sync.elastic"):
            new_p, new_c = elastic_exchange_sharded(
                spec, state["params"], state["center"], alpha, comm=ex_comm)
        return dict(state, params=new_p, center=new_c)

    return device_step, device_exchange


def _compose(mapped_step: Callable, mapped_exchange: Optional[Callable],
             sync: SyncConfig) -> Callable:
    """Full driver step over stacked state: mapped update, then — on the
    INTERVAL boundary, decided by a scalar cond outside the map — the
    mapped elastic exchange (launch/train.py's step_multiclient order:
    the pre-increment step count gates the exchange AFTER the update)."""

    def step(state, batch):
        old_step = state["step"][0]
        new_state, metrics = mapped_step(state, batch)
        if mapped_exchange is not None:
            new_state = lax.cond(
                should_elastic_sync(old_step, sync.esgd_interval),
                mapped_exchange, lambda s: s, new_state,
            )
        # pmean'd inside the map: identical on every device — report one
        return new_state, jax.tree.map(lambda m: m.reshape(-1)[0], metrics)

    return step


def _nested_vmap(fn: Callable, shape: tuple[int, ...],
                 axes: tuple[str, ...]) -> Callable:
    """Map a per-device program over stacked (p_total-leading) state with
    one named vmap per mesh axis (outermost axis first) — the emulation
    backend of the same communicator programs shard_map runs."""
    mapped = fn
    for a in reversed(axes):
        mapped = jax.vmap(mapped, axis_name=a)

    def g(*args):
        split = jax.tree.map(
            lambda l: l.reshape(shape + l.shape[1:]), args)
        out = mapped(*split)
        n = math.prod(shape)
        return jax.tree.map(
            lambda l: l.reshape((n,) + l.shape[len(shape):]), out)

    return g


def make_emulated_step(model: Model, optimizer: Optimizer, sync: SyncConfig,
                       p: Geometry, *, axis_name: str = AXIS,
                       microbatch: int = 1) -> Callable:
    """vmap-emulated driver step (tests / single-device hosts): the same
    per-device program, with nested vmaps providing the named axes."""
    shape, axes = _factorize(p, axis_name)
    world = driver_world(sync, p, axis_name=axis_name)
    _require_supported(model, optimizer, sync, world)
    dev_step, dev_ex = make_device_step(model, optimizer, sync, world=world,
                                        microbatch=microbatch)
    vstep = _nested_vmap(dev_step, shape, axes)
    vex = _nested_vmap(dev_ex, shape, axes) if dev_ex else None
    return _compose(vstep, vex, sync)


def _mesh_geometry(mesh, axis_name: str = AXIS
                   ) -> tuple[Geometry, tuple[str, ...]]:
    """Which driver layout a mesh carries: ('pod' and 'data') -> 2-axis,
    else the single ``axis_name`` axis."""
    if POD_AXIS in mesh.shape and DATA_AXIS in mesh.shape:
        return ((mesh.shape[POD_AXIS], mesh.shape[DATA_AXIS]),
                (POD_AXIS, DATA_AXIS))
    if axis_name not in mesh.shape:
        raise ValueError(
            f"mesh axes {dict(mesh.shape)} fit neither driver layout: "
            f"expected a '{axis_name}' axis (1-axis) or both "
            f"'{POD_AXIS}' and '{DATA_AXIS}' axes (2-axis hierarchy)")
    return mesh.shape[axis_name], (axis_name,)


def make_sharded_step(model: Model, optimizer: Optimizer, sync: SyncConfig,
                      mesh, *, axis_name: str = AXIS,
                      microbatch: int = 1) -> Callable:
    """Real-mesh driver step: the per-device program under
    ``jax.shard_map`` with every stacked leaf sharded over the mesh
    axes — each device holds exactly its replica/shard and the ring
    collectives are the only cross-device traffic. A mesh with 'pod'
    and 'data' axes selects the 2-axis hierarchy layout."""
    p, axes = _mesh_geometry(mesh, axis_name)
    world = driver_world(sync, p, axis_name=axis_name)
    _require_supported(model, optimizer, sync, world)
    dev_step, dev_ex = make_device_step(model, optimizer, sync, world=world,
                                        microbatch=microbatch)

    def _blocked(fn):
        # shard_map hands each device a leading-dim-1 block of the
        # stacked leaves; the per-device program wants them squeezed
        def g(*args):
            squeezed = jax.tree.map(lambda l: l.reshape(l.shape[1:]), args)
            out = fn(*squeezed)
            return jax.tree.map(lambda l: jnp.asarray(l)[None], out)

        return g

    sspec = P(axes)
    mstep = jax.shard_map(_blocked(dev_step), mesh=mesh,
                          in_specs=(sspec, sspec), out_specs=(sspec, sspec),
                          check_vma=False)
    mex = (jax.shard_map(_blocked(dev_ex), mesh=mesh,
                         in_specs=(sspec,), out_specs=sspec, check_vma=False)
           if dev_ex else None)
    return _compose(mstep, mex, sync)


def _check_driver_faults(inj, mesh, p) -> None:
    """What the driver's fault path can serve: kill (membership
    reconfiguration) and corrupt (deterministic batch noise), under
    vmap emulation. Timing faults need a clock and a real mesh needs
    real process recovery — both out of scope here."""
    if mesh is not None:
        raise ValueError(
            "drive(faults=...) runs under vmap emulation only: elastic "
            "reconfiguration on a REAL mesh needs the multi-process "
            "transport (see ROADMAP.md 'real multi-process transport') "
            "— pass p= instead of mesh=")
    timed = inj.schedule.kinds & {"drop", "delay", "straggle"}
    if timed:
        raise ValueError(
            f"fault kinds {sorted(timed)} need a clock — the driver's "
            "jitted step has no timing axis; run them through the "
            "event-driven simulation (core/algorithms.py, "
            "AlgoConfig.faults). The driver serves kill/corrupt.")
    shape, _ = _factorize(p)
    if inj.schedule.kinds & {"kill", "restart"} and len(shape) == 2:
        # pod kills/joins need the hierarchical (pod-then-data) shard
        # layout re-derived, which only the 1-axis ring-major geometry
        # shares with membership.reshard_optstate today
        raise ValueError(
            "kill/restart faults under the 2-axis pod×data layout are "
            "not wired — the hierarchical state re-layout is part of "
            "the ROADMAP 'real multi-process transport' item; use the "
            "1-axis layout")


def _reconfigure(model: Model, optimizer: Optimizer, sync: SyncConfig,
                 state: dict, p_old: int, dead: list[int],
                 live: "Membership", *, axis_name: str,
                 microbatch: int) -> tuple[dict, int, Callable, dict]:
    """Evict ``dead`` devices from a 1-axis emulated run: re-split the
    geometry to the survivor count, carry survivor rows of the stacked
    state over, re-shard the FlatBuffer optimizer state
    (membership.reshard_optstate — survivors keep their slices, dead
    slices restart from zero), and rebuild + re-jit the step.

    mpi_sgd: the axis is ONE data-parallel group — params are replicated
    (any survivor row serves) and opt state is 1/p sharded, so it is
    re-laid-out p_old -> p_new. mpi_esgd: each device is one CLIENT with
    full local opt state — the dead client's row is simply dropped and
    the SyncConfig shrinks to the survivor client count."""
    import dataclasses as _dc

    from repro.core.membership import reshard_optstate

    for u in dead:
        live.fail(u)
    survivors = [r for r in range(p_old) if live.is_live(r)]
    p_new = len(survivors)
    rows = jnp.asarray(survivors)
    world = driver_world(sync, p_old, axis_name=axis_name)
    info: dict = {"p_old": p_old, "p_new": p_new, "moved_bytes": 0.0,
                  "survivors": tuple(survivors)}
    if sync.mode == "mpi_esgd":
        sync = _dc.replace(sync, num_clients=p_new)
        state = jax.tree.map(lambda l: l[rows], state)
    else:
        spec = grad_spec(model)
        new_opt, rinfo = reshard_optstate(
            optimizer.hyper, spec, state["opt"], p_old, p_new,
            survivors=survivors, num_rings=world.num_rings,
            bucket_bytes=world.bucket_bytes)
        info.update(rinfo)
        state = {**jax.tree.map(lambda l: l[rows],
                                {k: v for k, v in state.items()
                                 if k != "opt"}),
                 "opt": new_opt}
    step = jax.jit(make_emulated_step(model, optimizer, sync, p_new,
                                      axis_name=axis_name,
                                      microbatch=microbatch),
                   donate_argnums=(0,))
    return state, p_new, step, dict(info, sync=sync)


def _rejoin(model: Model, optimizer: Optimizer, sync: SyncConfig,
            state: dict, p_old: int, joiners: list[int],
            live: "Membership", *, axis_name: str,
            microbatch: int) -> tuple[dict, int, Callable, dict]:
    """Admit ``joiners`` into a 1-axis emulated run mid-stream: a new
    membership epoch per joiner, the geometry re-split to the grown
    count, the FlatBuffer optimizer state re-sharded at p_new
    (membership.reshard_optstate with every old shard surviving —
    reconstruct from p_old slices, re-slice), and the step re-jitted.

    mpi_sgd: params are replicated, so the joiner's row is a broadcast
    of row 0 — the emulated analogue of the respawned worker's
    pull-live-params-from-the-PS. mpi_esgd: the joiner is a NEW client
    admitted at the current center (the PS hands it w̃) with fresh
    local optimizer state, and the SyncConfig grows to the new count."""
    import dataclasses as _dc

    from repro.core.membership import reshard_optstate

    old_ids = list(live.live)
    for u in joiners:
        live.join(u)
    new_ids = list(live.live)
    p_new = len(new_ids)
    pos = {u: r for r, u in enumerate(old_ids)}
    rows = [pos.get(u, -1) for u in new_ids]
    world = driver_world(sync, p_old, axis_name=axis_name)
    info: dict = {"p_old": p_old, "p_new": p_new, "moved_bytes": 0.0,
                  "joined": tuple(joiners),
                  "survivors": tuple(range(p_old))}

    def expand(tree, fill):
        return jax.tree.map(
            lambda l: jnp.stack([l[r] if r >= 0 else fill(l)
                                 for r in rows]), tree)

    if sync.mode == "mpi_esgd":
        sync = _dc.replace(sync, num_clients=p_new)
        state = {
            "params": jax.tree.map(
                lambda pl, cl: jnp.stack(
                    [pl[r] if r >= 0 else cl[0] for r in rows]),
                state["params"], state["center"]),
            "opt": expand(state["opt"], lambda l: jnp.zeros_like(l[0])),
            "step": expand(state["step"], lambda l: l[0]),
            "center": expand(state["center"], lambda l: l[0]),
        }
    else:
        spec = grad_spec(model)
        new_opt, rinfo = reshard_optstate(
            optimizer.hyper, spec, state["opt"], p_old, p_new,
            survivors=list(range(p_old)), num_rings=world.num_rings,
            bucket_bytes=world.bucket_bytes)
        info.update(rinfo)
        rest = {k: v for k, v in state.items() if k != "opt"}
        state = {**{k: expand(v, lambda l: l[0]) for k, v in rest.items()},
                 "opt": new_opt}
    step = jax.jit(make_emulated_step(model, optimizer, sync, p_new,
                                      axis_name=axis_name,
                                      microbatch=microbatch),
                   donate_argnums=(0,))
    return state, p_new, step, dict(info, sync=sync)


def drive(model: Model, optimizer: Optimizer, sync: SyncConfig, batches,
          *, p: Geometry | None = None, mesh=None, axis_name: str = AXIS,
          rng=None, microbatch: int = 1, log_every: int = 10,
          callback: Optional[Callable] = None, faults=None,
          fault_seed: int = 0, net: Optional[Any] = None):
    """Training loop over the shard driver.

    ``mesh=None`` emulates ``p`` devices with nested vmaps — an int, or
    a (pods, data) pair for the 2-axis hierarchy; with a mesh, the
    geometry comes from the mesh axes and the step runs under shard_map.
    ``batches`` yield host-layout (B, ...) arrays; they are split into
    per-device shards here.

    ``faults`` (a core.faults schedule / string) injects deterministic
    failures, emulation only: ``kill@s:unit=d`` evicts device d before
    step s — the run reconfigures to the survivors (state re-laid-out
    via membership.reshard_optstate, step re-jitted) and a
    ``reconfigure`` entry with the recovery byte/time accounting
    (cost_model.reconfig_time over ``net``) lands in the history;
    ``restart@s:unit=d`` ADMITS device d before step s when it is not
    live (a brand-new id grows the run; a previously-killed id
    rejoins) — the geometry grows to the joined count (``_rejoin``:
    reshard_optstate at p_new, joiner params pulled from a live row /
    the center) and a ``join`` entry carries the
    cost_model.join_reshard_bytes / recovery_time accounting. Kills
    are generation-indexed: a rejoined unit dies again only at its
    NEXT kill event. ``corrupt`` adds seeded noise to the device's
    batch shard. The same schedule replayed is bit-identical; feed
    batches sized for every geometry the schedule can reach.
    """
    from repro.core import cost_model
    from repro.core.faults import injector
    from repro.core.membership import Membership

    if mesh is not None:
        p, _ = _mesh_geometry(mesh, axis_name)
    if p is None:
        raise ValueError("pass p= (emulation) or mesh=")
    inj = injector(faults, seed=fault_seed)
    if inj is not None:
        if sync.overlap:
            raise ValueError(
                "drive(faults=...) with SyncConfig.overlap=True is not "
                "wired: the elastic re-layout "
                "(membership.reshard_optstate) assumes the monolithic "
                "ring-major shard geometry, not the bucket-major "
                "overlapped schedule — run faults without overlap, or "
                "overlap without faults")
        _check_driver_faults(inj, mesh, p)
    state = make_driver_state(model, optimizer, sync,
                              None if mesh is not None else p, rng,
                              mesh=mesh, axis_name=axis_name)
    if mesh is None:
        step = make_emulated_step(model, optimizer, sync, p,
                                  axis_name=axis_name, microbatch=microbatch)
    else:
        step = make_sharded_step(model, optimizer, sync, mesh,
                                 axis_name=axis_name, microbatch=microbatch)
    step = jax.jit(step, donate_argnums=(0,))
    live = (Membership(math.prod(_factorize(p)[0]))
            if inj is not None else None)
    attempts: dict[int, int] = {}    # unit -> spawn generation
    history = []

    def membership_events(i):
        """Admit the joiners and evict the dead of step ``i``: each
        re-lays the state out and re-jits the step."""
        nonlocal state, p, step, sync
        joiners = [u for u in inj.restart_units(i) if not live.is_live(u)]
        if joiners:
            delay = max(inj.restart_delay(u, attempts.get(u, 0)) or 0.0
                        for u in joiners)
            for u in joiners:
                attempts[u] = attempts.get(u, 0) + 1
            with jax.profiler.TraceAnnotation("repro.rejoin"):
                state, p, step, info = _rejoin(
                    model, optimizer, sync, state, int(p), joiners, live,
                    axis_name=axis_name, microbatch=microbatch)
            sync = info.pop("sync")
            netp = net or cost_model.testbed()
            state_nbytes = info.get("state_nbytes", 0.0)
            entry = {"step": i, "event": "join", **info,
                     "join_reshard_bytes":
                         cost_model.join_reshard_bytes(
                             state_nbytes, info["p_old"]),
                     "recovery_time": cost_model.recovery_time(
                         0.0, delay, info["p_old"], info["p_new"],
                         netp, state_nbytes=state_nbytes)}
            history.append(entry)
            if callback:
                callback(entry)
        dead = [u for u in live.live
                if inj.is_killed(u, i, attempts.get(u, 0))]
        if dead:
            if len(dead) >= live.live_count:
                raise ValueError(
                    f"fault schedule kills every live device at "
                    f"step {i} — no survivor group to reconfigure to")
            with jax.profiler.TraceAnnotation("repro.reconfigure"):
                state, p, step, info = _reconfigure(
                    model, optimizer, sync, state, int(p), dead, live,
                    axis_name=axis_name, microbatch=microbatch)
            sync = info.pop("sync")
            netp = net or cost_model.testbed()
            entry = {"step": i, "event": "reconfigure",
                     "killed": dead, **info,
                     "reconfig_time": cost_model.reconfig_time(
                         info.get("state_nbytes", 0.0), info["p_old"],
                         info["p_new"], netp,
                         survivors=len(info["survivors"]))}
            history.append(entry)
            if callback:
                callback(entry)

    for i, batch in enumerate(batches):
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            if inj is not None:
                membership_events(i)
            shard = shard_batch(batch, p)
            if inj is not None:
                for r, u in enumerate(live.live):
                    if inj.active(u, i):
                        noisy = inj.corrupt(
                            jax.tree.map(lambda l: l[r], shard), u, i)
                        shard = jax.tree.map(
                            lambda l, x: l.at[r].set(x), shard, noisy)
            state, metrics = step(state, shard)
            if i % log_every == 0:
                with jax.profiler.TraceAnnotation("repro.metrics_fetch"):
                    entry = {k: float(v) for k, v in metrics.items()}
                entry["step"] = i
                history.append(entry)
                if callback:
                    callback(entry)
    return state, history


def _selftest(p: int = 8) -> None:  # pragma: no cover (subprocess helper)
    """REAL-mesh check (needs >= p host devices, set XLA_FLAGS): the
    shard_map driver's losses must match the single-process reference
    step for both modes and every lowerable optimizer family — run by
    tests/test_multidevice.py. Also runs the 2-axis pod×data hierarchy
    layout (both factorizations of p) against the same references."""
    import numpy as np

    from repro.configs.base import get_config, reduced
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_train_state, make_train_step
    from repro.models.model import build_model
    from repro.optim.sgd import adagrad, adamw, sgd

    assert len(jax.devices()) >= p, "set XLA_FLAGS host device count"
    model = build_model(reduced(get_config("qwen2-0.5b")))
    mesh = make_mesh((p,), (AXIS,))
    k = jax.random.key(0)
    toks = jax.random.randint(k, (p, 32), 0, 1024)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    for opt in (sgd(0.1, momentum=0.9), adamw(3e-3), adagrad(0.05)):
        oname = opt.hyper["name"]
        for sync in (SyncConfig(mode="mpi_sgd", num_clients=1),
                     SyncConfig(mode="mpi_esgd", num_clients=p,
                                esgd_interval=2)):
            st = make_driver_state(model, opt, sync, rng=jax.random.key(1),
                                   mesh=mesh)
            # built in place: every device holds its own row and no other
            assert all(l.sharding.shard_shape(l.shape)[0] == 1
                       for l in jax.tree.leaves(st))
            step = jax.jit(make_sharded_step(model, opt, sync, mesh),
                           donate_argnums=(0,))
            ref = make_train_state(model, opt, sync, jax.random.key(1))
            ref_step = jax.jit(make_train_step(model, opt, sync, None),
                               donate_argnums=(0,))
            ref_batch = (batch if sync.num_clients <= 1
                         else shard_batch(batch, p))
            for _ in range(3):
                st, m = step(st, shard_batch(batch, p))
                ref, mr = ref_step(ref, ref_batch)
                np.testing.assert_allclose(float(m["loss"]),
                                           float(mr["loss"]), rtol=1e-4)
            print(f"shard driver selftest OK p={p} mode={sync.mode} "
                  f"opt={oname} (shard_map on {len(jax.devices())} devices)")

    # 2-axis pod×data hierarchy: losses must match the stacked C-client
    # reference (mpi_esgd, C = pods) and the single-process data-parallel
    # reference (mpi_sgd) on a REAL (P, D) mesh
    opt = sgd(0.1, momentum=0.9)
    for P_, D_ in ((2, p // 2), (p // 2, 2)):
        mesh2 = make_mesh((P_, D_), (POD_AXIS, DATA_AXIS))
        for sync in (SyncConfig(mode="mpi_sgd", num_clients=1),
                     SyncConfig(mode="mpi_esgd", num_clients=P_,
                                esgd_interval=2)):
            st = make_driver_state(model, opt, sync, rng=jax.random.key(1),
                                   mesh=mesh2)
            step = jax.jit(make_sharded_step(model, opt, sync, mesh2),
                           donate_argnums=(0,))
            ref = make_train_state(model, opt, sync, jax.random.key(1))
            ref_step = jax.jit(make_train_step(model, opt, sync, None),
                               donate_argnums=(0,))
            ref_batch = (batch if sync.num_clients <= 1
                         else shard_batch(batch, P_))
            for _ in range(3):
                st, m = step(st, shard_batch(batch, (P_, D_)))
                ref, mr = ref_step(ref, ref_batch)
                np.testing.assert_allclose(float(m["loss"]),
                                           float(mr["loss"]), rtol=1e-4)
            print(f"shard driver selftest OK mesh=({P_}x{D_}) "
                  f"mode={sync.mode} (2-axis pod×data hierarchy)")


if __name__ == "__main__":  # pragma: no cover
    import sys

    from repro.launch.compile_cache import init_compile_cache

    init_compile_cache()
    _selftest(int(sys.argv[1]) if len(sys.argv) > 1 else 8)
