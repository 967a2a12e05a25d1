"""SyncEngine: the strategy layer behind every lowerable sync mode.

``launch/train.py`` and ``launch/shard_driver.py`` used to branch inline
on HOW a step syncs and updates (``fused_path_active`` / ``step_c1`` /
``step_multiclient``). That choice is now made ONCE, here, and the step
builders drive a single interface:

  init_opt             optimizer-state layout (flat momentum buffer vs
                       per-leaf pytree)
  update               the sync+update leg (packed reduce-scatter ->
                       fused Pallas kernel -> allgather, vs per-leaf
                       ``Optimizer.update``)
  exchange_multiclient the elastic leg for C stacked replicas (packed
                       single-launch kernel vs per-leaf tree.maps)
  check_opt_layout     loud trace-time guard that the state factory and
                       the step factory agreed on the layout

Selection (``make_sync_engine``):

  flat update    ``fused_update`` and a lowerable optimizer (momentum
                 SGD with f32 state, AdaGrad, or AdamW — the K-stream
                 fused kernels in kernels/fused_sgd + kernels/fused_optim)
                 and NO ambient mesh — both ``mpi_sgd`` (C=1, collectives
                 over the gradient Communicator) and ``mpi_esgd`` (per-client local
                 geometry; the step vmaps ``update`` over the client dim)
  flat exchange  ``flat_exchange`` and no mesh — independent of the
                 update substrate, so e.g. a custom-optimizer run still
                 gets the packed elastic leg

With an ambient mesh GSPMD owns the collectives: both legs stay per-leaf
so parameter sharding is undisturbed.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import comm as comm_lib, flatbuf
from repro.core.elastic import (
    elastic_exchange_multiclient,
    elastic_exchange_multiclient_flat,
)
from repro.core.hierarchy import SyncConfig
from repro.optim.sgd import (
    FLAT_STATE_STREAMS,
    Optimizer,
    optstate_sched_init,
    optstate_shard_init,
    overlap_update,
    scatter_update_gather,
)


def flat_update_supported(optimizer: Optimizer, sync: SyncConfig,
                          mesh=None) -> bool:
    """Whether the packed fused-kernel update can replace per-leaf.

    Requires a lowerable optimizer — momentum SGD, AdaGrad or AdamW
    (``optim.sgd.FLAT_STATE_STREAMS``); for SGD the momentum dtype must
    be the buffer's f32 (an explicit low-precision ``state_dtype`` keeps
    the per-leaf path that honors it) — and no ambient mesh: with a
    mesh, GSPMD owns the gradient collectives and per-leaf updates keep
    parameter sharding undisturbed.
    """
    hyper = optimizer.hyper
    if not (sync.fused_update and sync.mode in ("mpi_sgd", "mpi_esgd")
            and mesh is None):
        return False
    # the flat_* Optimizer wrappers alias their per-leaf family
    name = hyper.get("name", "")
    name = name[5:] if name.startswith("flat_") else name
    if name == "sgd":
        return (hyper.get("momentum", 0.0) > 0.0
                and hyper.get("state_dtype") in (None, jnp.float32))
    return name in FLAT_STATE_STREAMS


def flat_exchange_active(sync: SyncConfig, mesh=None) -> bool:
    """Whether the elastic leg runs packed (FlatBuffer + fused kernel)."""
    return sync.mode == "mpi_esgd" and sync.flat_exchange and mesh is None


@dataclass(frozen=True)
class SyncEngine:
    """Per-leaf strategy (the GSPMD / custom-optimizer path).

    ``comm`` is the gradient group (``core.comm.Communicator``) the
    update leg syncs over — trivial for the local / per-client path.
    """

    optimizer: Optimizer
    sync: SyncConfig
    comm: comm_lib.Communicator = comm_lib.LOCAL
    flat_exchange: bool = False
    spec: Optional[flatbuf.FlatBuffer] = None

    fused = False  # class attr, not a field: FlatEngine overrides

    # -- update leg ---------------------------------------------------------
    def init_opt(self, params: Any) -> Any:
        return self.optimizer.init(params)

    def update(self, grads: Any, opt_state: Any, params: Any):
        with jax.named_scope("sync.update"):
            return self.optimizer.update(grads, opt_state, params)

    def check_opt_layout(self, opt_state: Any, num_clients: int = 1) -> None:
        if isinstance(opt_state, jax.Array) or _is_flat_adamw_state(opt_state):
            raise ValueError(
                "per-leaf update got a flat fused state buffer — pass "
                "the same mesh to make_train_state(..., mesh=...) and "
                "make_train_step(..., mesh), or set "
                "SyncConfig.fused_update=False for both")

    # -- elastic leg --------------------------------------------------------
    def exchange_multiclient(self, client_params: Any, center: Any, alpha):
        """One elastic exchange over C stacked replicas (eqs. 2+3)."""
        with jax.named_scope("sync.elastic"):
            if self.flat_exchange:
                return elastic_exchange_multiclient_flat(client_params,
                                                         center, alpha)
            return elastic_exchange_multiclient(client_params, center,
                                                alpha)


def _is_flat_adamw_state(opt_state: Any) -> bool:
    """The flat AdamW layout ({"mv": (2, n), "t": ()}) — distinct from the
    per-leaf adamw pytree ({"m": tree, "v": tree, "t": ()})."""
    return isinstance(opt_state, dict) and set(opt_state) == {"mv", "t"}


@dataclass(frozen=True)
class FlatEngine(SyncEngine):
    """Flat-buffer strategy: the whole gradient pytree rides one packed
    buffer through ring collectives and ONE fused Pallas kernel, with the
    K optimizer-state streams stored as flat (sharded) buffers — in the
    declared stream dtype (``hyper["state_dtype"]``: bf16 halves the
    state bytes on top of the 1/p sharding), over the gradient
    communicator's full policy (rings, bucketing, and the bf16/int8
    low-precision wire protocol on every hop)."""

    fused = True

    # backward-overlapped path (SyncConfig.overlap): the schedule over
    # the STAGED param spec (flatbuf.BucketSchedule, bucket == backward
    # stage), built at the gradient group's p. None = monolithic leg.
    schedule: Optional[flatbuf.BucketSchedule] = None

    def _num_rings(self) -> int:
        return self.comm.rings_for(self.spec.nbytes)

    def init_opt(self, params: Any) -> Any:
        # local (p=1) geometry; device-sharded drivers re-init per device
        # with optstate_shard_init(hyper, spec, p, ...) — or, overlapped,
        # optstate_sched_init(hyper, schedule) at the device schedule
        if self.schedule is not None:
            return optstate_sched_init(self.optimizer.hyper,
                                       self.schedule.with_p(1))
        return optstate_shard_init(self.optimizer.hyper, self.spec, 1,
                                   self._num_rings())

    def update(self, grads: Any, opt_state: Any, params: Any):
        return scatter_update_gather(
            self.spec, grads, params, opt_state,
            hyper=self.optimizer.hyper, comm=self.comm,
        )

    def update_overlapped(self, g_shard: Any, staged_params: Any,
                          opt_state: Any):
        """The post-backward half of the overlapped step: fused kernel on
        the bucket-major shard + the ONE trailing allgather. ``g_shard``
        comes from the staged grad fn (per-bucket reduce-scatter legs
        already issued mid-backward); returns staged params."""
        return overlap_update(
            self.schedule, g_shard, staged_params, opt_state,
            hyper=self.optimizer.hyper, comm=self.comm,
        )

    def check_opt_layout(self, opt_state: Any, num_clients: int = 1) -> None:
        if self.optimizer.hyper.get("name", "").endswith("adamw"):
            if not _is_flat_adamw_state(opt_state):
                raise ValueError(
                    "fused adamw sync path expects the flat {'mv', 't'} "
                    "state, but the train state carries a per-leaf opt "
                    "state — pass the same mesh to "
                    "make_train_state(..., mesh=...) and "
                    "make_train_step(..., mesh)")
            buf, streams = opt_state["mv"], 2
        else:
            if not isinstance(opt_state, jax.Array):
                raise ValueError(
                    "fused sync path expects the flat state buffer, but the "
                    "train state carries a per-leaf opt state — pass the "
                    "same mesh to make_train_state(..., mesh=...) and "
                    "make_train_step(..., mesh)")
            buf, streams = opt_state, 1
        # C>1 vmaps the update per client, so each client is p=1 geometry
        p = 1 if num_clients > 1 else self.comm.resolve_size()
        if self.schedule is not None:
            # overlapped layout: bucket-major concat of per-bucket chunks
            want = self.schedule.with_p(p).shard_size
        else:
            want = flatbuf.shard_size(self.spec, p, self.sync.num_rings,
                                      self.sync.bucket_bytes)
        per_client = buf.size // (streams * max(num_clients, 1))
        if per_client != want:
            raise ValueError(
                f"fused state shard has {per_client} elements per stream "
                f"but the {p}-way axis geometry needs {want} — per-device "
                "state for sharded drivers comes from "
                "optim.sgd.optstate_shard_init(hyper, spec, p, ...), not "
                "from make_train_state's local (p=1) buffer; state saved "
                "under a DIFFERENT device count (elastic membership "
                "change, restore on new geometry) re-lays-out with "
                "core.membership.reshard_optstate(hyper, spec, state, "
                "p_old, p_new)")


def make_sync_engine(optimizer: Optimizer, sync: SyncConfig, mesh=None, *,
                     comm: Optional[comm_lib.Communicator] = None,
                     axis_name: Optional[str] = None,
                     spec: Optional[flatbuf.FlatBuffer] = None,
                     schedule: Optional[flatbuf.BucketSchedule] = None,
                     ) -> SyncEngine:
    """Resolve the strategy for (optimizer, sync, mesh) once.

    ``comm`` is the gradient group the update leg syncs over; omitted,
    it is built from the SyncConfig recipe (trivial group — the local /
    per-client geometry). The old ``axis_name=`` string spelling was
    removed — build the group with ``Communicator.from_axis_name`` and
    pass ``comm=``. ``spec`` (the param-tree FlatBuffer) is required
    whenever a flat leg engages; callers that might need it build it
    with ``launch.train.grad_spec``.
    """
    if axis_name is not None:
        comm_lib._axis_name_removed("make_sync_engine")
    if comm is None:
        comm = comm_lib.from_sync(sync)
    fused = flat_update_supported(optimizer, sync, mesh)
    flat_ex = flat_exchange_active(sync, mesh)
    if fused and spec is None:
        raise ValueError("flat-update engine needs the FlatBuffer spec")
    if sync.overlap and not fused:
        raise ValueError(
            "SyncConfig.overlap=True but the fused flat update cannot "
            "engage for this (optimizer, sync, mesh) — overlap rides the "
            "fused path only (core.sync_engine.flat_update_supported): "
            "use momentum SGD / AdaGrad / AdamW with fused_update=True "
            "and no ambient mesh")
    if sync.overlap and schedule is None:
        raise ValueError(
            "overlap engine needs the BucketSchedule — build it with "
            "launch.train.overlap_schedule(model, sync, p) from the "
            "model's staged param spec")
    if not fused:
        schedule = None
    if fused:
        return FlatEngine(optimizer, sync, comm=comm, flat_exchange=flat_ex,
                          spec=spec, schedule=schedule)
    return SyncEngine(optimizer, sync, comm=comm, flat_exchange=flat_ex,
                      spec=spec)
