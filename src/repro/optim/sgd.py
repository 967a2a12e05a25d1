"""Optimizers, pure-pytree (init/update), mirroring what the paper ships to
the PS via ``KVStore.set_optimizer``: SGD (+momentum), AdaGrad, AdamW, and
the Elastic server/client updates (eqs. 2/3) live in core/elastic.py.

Beyond the per-leaf tree.map optimizers, this module owns the **sharded
fused step** (``scatter_update_gather``): ring reduce-scatter the packed
flat gradient, run the fused optimizer Pallas kernel — momentum SGD,
AdaGrad or AdamW (``FLAT_STATE_STREAMS``) — on the local 1/p shard
(every full-length state stream lives sharded — a p× optimizer-memory
reduction, 2 streams' worth for AdamW), then ring-allgather the updated
params. The gradient leg waits on (p-1)/p·n bytes instead of the full
allreduce's 2·(p-1)/p·n, and the whole update is ONE Pallas grid instead
of O(num_leaves) kernels.
"""
from __future__ import annotations

import types
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import flatbuf


class Optimizer(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], tuple[Any, Any]]  # (g, state, p) -> (new_p, state)
    # static metadata (name + hyperparams) so drivers can lower an
    # optimizer onto its fused-kernel equivalent; empty for custom rules
    # (read-only default so default-constructed Optimizers can't alias a
    # shared mutable dict)
    hyper: Mapping = types.MappingProxyType({})


def sgd(lr: float, momentum: float = 0.0, weight_decay: float = 0.0,
        state_dtype=None) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return ()
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, state_dtype or p.dtype), params
        )

    def update(grads, state, params):
        if weight_decay:
            grads = jax.tree.map(lambda g, p: g + weight_decay * p, grads, params)
        if momentum == 0.0:
            new_p = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new_p, ()
        # hp momentum arithmetic, rounded back to the declared state
        # dtype only at the store — otherwise a bf16 stream would
        # silently promote to f32 on the first update (and retrace any
        # jitted step when the state aval changed)
        hp_v = jax.tree.map(
            lambda v, g: momentum * v.astype(jnp.float32)
            + g.astype(jnp.float32),
            state, grads,
        )
        new_p = jax.tree.map(
            lambda p, v: (p.astype(jnp.float32) - lr * v).astype(p.dtype),
            params, hp_v,
        )
        new_v = jax.tree.map(lambda v, s: v.astype(s.dtype), hp_v, state)
        return new_p, new_v

    return Optimizer(init, update,
                     {"name": "sgd", "lr": lr, "momentum": momentum,
                      "weight_decay": weight_decay,
                      "state_dtype": state_dtype})


def adagrad(lr: float, eps: float = 1e-10, state_dtype=None) -> Optimizer:
    sd = state_dtype or jnp.float32

    def init(params):
        return jax.tree.map(lambda p: jnp.zeros_like(p, sd), params)

    def update(grads, state, params):
        # hp accumulator arithmetic, rounded to the (possibly bf16) state
        # stream only at the store — mirroring the fused kernel, which
        # computes f32 per tile and casts on write
        hp_s = jax.tree.map(
            lambda s, g: s.astype(jnp.float32)
            + jnp.square(g.astype(jnp.float32)),
            state, grads,
        )
        new_p = jax.tree.map(
            lambda p, g, s: (
                p.astype(jnp.float32)
                - lr * g.astype(jnp.float32) / (jnp.sqrt(s) + eps)
            ).astype(p.dtype),
            params, grads, hp_s,
        )
        return new_p, jax.tree.map(lambda s: s.astype(sd), hp_s)

    return Optimizer(init, update,
                     {"name": "adagrad", "lr": lr, "eps": eps,
                      "state_dtype": state_dtype})


def adamw(lr: float, b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
          weight_decay: float = 0.0, state_dtype=None) -> Optimizer:
    sd = state_dtype or jnp.float32

    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, sd)
        return {
            "m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "t": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params):
        t = state["t"] + 1
        # hp moment arithmetic; the (possibly bf16) streams are rounded
        # only at the store, like the fused kernel's per-tile f32 compute
        m = jax.tree.map(
            lambda m_, g: b1 * m_.astype(jnp.float32)
            + (1 - b1) * g.astype(jnp.float32),
            state["m"], grads,
        )
        v = jax.tree.map(
            lambda v_, g: b2 * v_.astype(jnp.float32)
            + (1 - b2) * jnp.square(g.astype(jnp.float32)),
            state["v"], grads,
        )
        c1 = 1 - b1 ** t.astype(jnp.float32)
        c2 = 1 - b2 ** t.astype(jnp.float32)

        def step(p, m_, v_):
            upd = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
            if weight_decay:
                upd = upd + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * upd).astype(p.dtype)

        new_p = jax.tree.map(step, params, m, v)
        cast = lambda tree: jax.tree.map(lambda l: l.astype(sd), tree)
        return new_p, {"m": cast(m), "v": cast(v), "t": t}

    return Optimizer(init, update,
                     {"name": "adamw", "lr": lr, "b1": b1, "b2": b2,
                      "eps": eps, "weight_decay": weight_decay,
                      "state_dtype": state_dtype})


def get_optimizer(name: str, lr: float, **kw) -> Optimizer:
    return {"sgd": sgd, "adagrad": adagrad, "adamw": adamw}[name](lr, **kw)


# ---------------------------------------------------------------------------
# Sharded fused step: reduce-scatter -> Pallas fused update on 1/p -> allgather
# ---------------------------------------------------------------------------

#: optimizers the flat fused path can lower, with their full-length f32
#: state-stream counts (sharded 1/p alongside the momentum buffer):
#: sgd carries 1 (momentum), adagrad 1 (accumulator), adamw 2 (m, v) plus
#: the scalar step count t.
FLAT_STATE_STREAMS: Mapping[str, int] = types.MappingProxyType(
    {"sgd": 1, "adagrad": 1, "adamw": 2})


def _flat_name(hyper) -> str:
    """Canonical optimizer family of a hyper dict (``flat_*`` aliases of
    the local Optimizer wrappers map onto their per-leaf family)."""
    name = hyper if isinstance(hyper, str) else hyper["name"]
    return name[5:] if name.startswith("flat_") else name


def momentum_shard_init(spec: flatbuf.FlatBuffer, p: int = 1,
                        num_rings: int = 1,
                        bucket_bytes: int | None = None,
                        dtype=jnp.float32) -> jax.Array:
    """Zero momentum for one device's shard of the flat buffer (call under
    vmap/shard_map per device, or with p=1 for the local path)."""
    return jnp.zeros((flatbuf.shard_size(spec, p, num_rings, bucket_bytes)),
                     dtype)


def state_stream_dtype(hyper, state_dtypes=None) -> Any:
    """The dtype the flat state streams are stored in: an explicit
    ``state_dtypes`` wins, else the optimizer's ``hyper["state_dtype"]``,
    else f32. The fused kernels always COMPUTE in f32 per tile and cast
    on store, so a bf16 stream halves the state bytes per device without
    touching the update math's precision."""
    sd = state_dtypes
    if sd is None and not isinstance(hyper, str):
        sd = hyper.get("state_dtype")
    return jnp.dtype(sd) if sd is not None else jnp.float32


def optstate_shard_init(hyper, spec: flatbuf.FlatBuffer, p: int = 1,
                        num_rings: int = 1,
                        bucket_bytes: int | None = None,
                        state_dtypes=None) -> Any:
    """Zero flat optimizer state for one device's 1/p shard of the buffer
    (``momentum_shard_init`` generalized to K state streams).

    Layout per family — every full-length stream is sharded 1/p, stored
    in the declared stream dtype (``state_stream_dtype``: f32 default,
    bf16 for the low-precision streams — another 2x state-bytes cut on
    top of the 1/p sharding):

      sgd      (n,) momentum
      adagrad  (n,) accumulator
      adamw    {"mv": (2, n) first/second moments,
                "t":  ()     i32 shared step count (bias correction)}
    """
    name = _flat_name(hyper)
    sd = state_stream_dtype(hyper, state_dtypes)
    n = flatbuf.shard_size(spec, p, num_rings, bucket_bytes)
    k = FLAT_STATE_STREAMS[name]
    if name == "adamw":
        return {"mv": jnp.zeros((k, n), sd),
                "t": jnp.zeros((), jnp.int32)}
    return jnp.zeros((n,), sd)


def _fused_shard_update(name: str, hyper, p_shard: jax.Array,
                        opt_state: Any, g_shard: jax.Array,
                        interpret: bool | None) -> tuple[jax.Array, Any]:
    """Dispatch the ONE-grid Pallas update on this device's shard: the K
    state streams ride the same tiles as (param, grad)."""
    from repro.kernels.fused_optim.fused_optim import adagrad_flat, adamw_flat
    from repro.kernels.fused_sgd.fused_sgd import sgd_momentum_flat

    lr = jnp.float32(hyper["lr"])
    if name == "sgd":
        return sgd_momentum_flat(p_shard, opt_state, g_shard, lr,
                                 jnp.float32(hyper["momentum"]),
                                 interpret=interpret)
    if name == "adagrad":
        return adagrad_flat(p_shard, opt_state, g_shard, lr,
                            jnp.float32(hyper.get("eps", 1e-10)),
                            interpret=interpret)
    if name == "adamw":
        t = opt_state["t"] + 1
        tf = t.astype(jnp.float32)
        b1 = jnp.float32(hyper.get("b1", 0.9))
        b2 = jnp.float32(hyper.get("b2", 0.95))
        # the (2, n) m/v buffer rides the kernel whole — no per-step
        # slice/re-stack copies of the moment streams
        new_p, new_mv = adamw_flat(
            p_shard, opt_state["mv"], g_shard,
            lr, b1, b2, jnp.float32(hyper.get("eps", 1e-8)),
            jnp.float32(hyper.get("weight_decay", 0.0) or 0.0),
            1.0 - b1 ** tf, 1.0 - b2 ** tf, interpret=interpret)
        return new_p, {"mv": new_mv, "t": t}
    raise ValueError(
        f"flat fused update knows {sorted(FLAT_STATE_STREAMS)}, got {name!r}")


def scatter_update_gather(spec: flatbuf.FlatBuffer, grads: Any, params: Any,
                          opt_state: Any, lr=None, momentum=None, *,
                          hyper: Optional[Mapping] = None,
                          comm=None,
                          axis_name: Optional[str] = None,
                          num_rings: int = 1,
                          bucket_bytes: int | None = None,
                          wire_dtype: Optional[str] = None,
                          weight_decay: float = 0.0,
                          mean: bool = True,
                          interpret: bool | None = None) -> tuple[Any, Any]:
    """One fused sync+update step on this device (the paper-faithful MPI
    worker program; run under shard_map on a mesh or vmap emulation):

      1. pack grads into the persistent flat buffer (static offsets)
      2. ring reduce-scatter over the gradient communicator -> this
         device owns a fully-reduced 1/p shard ((p-1)/p·n gradient-leg
         bytes — half the full allreduce; multi-axis groups nest the
         reduce-scatter level by level at the same total cost)
      3. fused optimizer Pallas kernel on (param shard, K state-stream
         shards, grad shard): one grid, state stays sharded (p× memory
         saving per full-length stream — 2 streams for AdamW)
      4. ring allgather of the UPDATED param shards -> full new params

    The optimizer is selected by ``hyper`` (an ``Optimizer.hyper`` dict:
    sgd / adagrad / adamw — see ``FLAT_STATE_STREAMS``); the positional
    ``lr``/``momentum`` form is the momentum-SGD shorthand. ``opt_state``
    is this device's shard as laid out by ``optstate_shard_init``.

    ``comm`` is the gradient group (``core.comm.Communicator``); its
    policy supplies the ring count, bucketing, AND the wire protocol:
    with ``wire_dtype`` "bf16"/"int8" the reduce-scatter hops carry the
    compressed gradient chunks (hp accumulation per hop) and the
    allgather hops carry the compressed updated-param shards (every
    device roundtrips its own shard through the codec, so replicas stay
    bit-identical). A trivial communicator
    (or one whose axes have size 1) degenerates to the local fused
    update: no collective, one Pallas grid over the whole buffer — still
    a win over O(num_leaves) per-leaf updates. The old ``axis_name=``
    string spelling was removed — build the group with
    ``Communicator.from_axis_name`` and pass ``comm=``.

    Returns ``(new_params_tree, new_opt_state_shard)``.
    """
    from repro.core import comm as _comm

    if hyper is None:
        hyper = {"name": "sgd", "lr": lr, "momentum": momentum,
                 "weight_decay": weight_decay}
    elif lr is not None or momentum is not None or weight_decay:
        raise ValueError(
            "pass hyperparameters either positionally (the momentum-SGD "
            "shorthand) or via hyper=, not both — with hyper= the "
            "optimizer reads lr/momentum/weight_decay from the dict, so "
            "move them there")
    name = _flat_name(hyper)

    if axis_name is not None:
        _comm._axis_name_removed("scatter_update_gather")
    if comm is None:
        comm = _comm.LOCAL.with_policy(
            num_rings=num_rings,
            bucket_bytes=bucket_bytes, wire_dtype=wire_dtype)
    elif num_rings != 1 or bucket_bytes is not None or wire_dtype is not None:
        raise ValueError(
            "with comm= the ring/wire policy lives on the communicator — "
            "set num_rings/bucket_bytes/wire_dtype there "
            "(Communicator.with_policy), not as arguments; mixing the two "
            "would desync the gradient sharding (or the wire form) from "
            "the optimizer-state layout")

    p = comm.resolve_size()
    nr = comm.rings_for(spec.nbytes)
    _, total = flatbuf.shard_geometry(spec.size, p, nr)

    with jax.named_scope("sync.pack"):
        gbuf = flatbuf.pack_padded(spec, grads, total)
        pbuf = flatbuf.pack_padded(spec, params, total)

    if p == 1:
        g_shard, p_shard = gbuf, pbuf
    else:
        with jax.named_scope("sync.reduce_scatter"):
            g_shard = comm.reduce_scatter(gbuf, num_rings=nr)
            p_shard = comm.shard_select(pbuf, num_rings=nr)
    with jax.named_scope("sync.update"):
        if mean:
            g_shard = g_shard / p
        wd = hyper.get("weight_decay", 0.0) or 0.0
        if name == "sgd" and wd:
            # coupled L2, matching per-leaf optim.sgd; adamw decays
            # decoupled inside its kernel
            g_shard = g_shard + wd * p_shard
        new_p_shard, new_state = _fused_shard_update(
            name, hyper, p_shard, opt_state, g_shard, interpret)

    if p == 1:
        new_pbuf = new_p_shard
    else:
        with jax.named_scope("sync.allgather"):
            new_pbuf = comm.allgather(new_p_shard, num_rings=nr)
    with jax.named_scope("sync.unpack"):
        return spec.unpack(new_pbuf[:spec.size]), new_state


def optstate_sched_init(hyper, schedule, state_dtypes=None) -> Any:
    """``optstate_shard_init`` for the overlapped (schedule-bucketed)
    layout: the per-device state length is ``schedule.shard_size`` — the
    bucket-major concat of single-ring per-bucket chunks — instead of
    the monolithic ``flatbuf.shard_size`` geometry."""
    name = _flat_name(hyper)
    sd = state_stream_dtype(hyper, state_dtypes)
    n = schedule.shard_size
    k = FLAT_STATE_STREAMS[name]
    if name == "adamw":
        return {"mv": jnp.zeros((k, n), sd),
                "t": jnp.zeros((), jnp.int32)}
    return jnp.zeros((n,), sd)


def overlap_update(schedule, g_shard: jax.Array, staged_params: Any,
                   opt_state: Any, *,
                   hyper: Mapping,
                   comm=None,
                   num_rings: Optional[int] = None,
                   bucket_bytes: int | None = None,
                   wire_dtype: Optional[str] = None,
                   mean: bool = True,
                   interpret: bool | None = None) -> tuple[Any, Any]:
    """The update half of the backward-overlapped step.

    The grad fn already issued each schedule bucket's reduce-scatter leg
    mid-backward (``Communicator.reduce_scatter_bucket``) and handed us
    ``g_shard``: the bucket-major ``(schedule.shard_size,)`` concat of
    this device's fully-reduced per-bucket chunks. This function runs
    what is left after backward finishes:

      1. select this device's matching param shard from the packed
         staged params (``shard_select_sched`` — static, no comm)
      2. ONE fused optimizer Pallas grid over the whole shard (the
         buckets share the kernel launch; only the WIRE was bucketed)
      3. the ONE trailing allgather of the updated shard
         (``allgather_sched``), re-stitched to the packed layout

    ``staged_params`` is the stage-subtree tuple ``Model.overlap_stages``
    produced — the SAME staging the schedule was built from; the return
    is ``(new_staged_params, new_opt_state)`` (caller ``unstage``s).
    ``comm`` carries the whole policy: explicit ``num_rings`` /
    ``bucket_bytes`` / ``wire_dtype`` arguments are rejected here just
    like in ``scatter_update_gather`` — the schedule already fixed the
    bucket geometry and mixing knobs would desync it from the state
    layout.
    """
    from repro.core import comm as _comm

    if num_rings is not None or bucket_bytes is not None \
            or wire_dtype is not None:
        raise ValueError(
            "overlap_update: the bucket/ring/wire policy lives on the "
            "communicator and the BucketSchedule — set wire_dtype on the "
            "comm (Communicator.with_policy) and the bucket split via "
            "overlap_buckets, not as arguments; explicit knobs here "
            "would desync the wire legs from the schedule layout")
    comm = _comm.LOCAL if comm is None else comm
    name = _flat_name(hyper)
    p = comm.resolve_size()
    if p != schedule.p:
        raise ValueError(
            f"schedule was built for p={schedule.p} shards but the "
            f"communicator spans {p} — rebuild the BucketSchedule with "
            f"the gradient group's size (bucket_schedule(spec, counts, "
            f"p={p}))")

    with jax.named_scope("sync.pack"):
        pbuf = schedule.spec.pack(staged_params)
    with jax.named_scope("sync.reduce_scatter"):
        p_shard = comm.shard_select_sched(pbuf, schedule)
    with jax.named_scope("sync.update"):
        if mean:
            g_shard = g_shard / p
        wd = hyper.get("weight_decay", 0.0) or 0.0
        if name == "sgd" and wd:
            g_shard = g_shard + wd * p_shard
        new_p_shard, new_state = _fused_shard_update(
            name, hyper, p_shard, opt_state, g_shard, interpret)

    with jax.named_scope("sync.allgather"):
        new_pbuf = comm.allgather_sched(new_p_shard, schedule)
    with jax.named_scope("sync.unpack"):
        return schedule.spec.unpack(new_pbuf), new_state


def _flat_optimizer(hyper: dict, spec: flatbuf.FlatBuffer,
                    num_rings: int, bucket_bytes: int | None) -> Optimizer:
    """Drop-in ``Optimizer`` whose update is the fused flat-buffer kernel
    (local p=1 geometry — the single-process drivers' default update).
    State is the flat f32 stream shard(s) instead of a pytree."""
    from repro.core import comm as _comm

    nr = flatbuf.effective_rings(spec.nbytes, num_rings, bucket_bytes)
    local = _comm.Communicator(
        axes=(), sizes=(), policy=_comm.CollectivePolicy(num_rings=nr))

    def init(params):
        return optstate_shard_init(hyper, spec, 1, nr)

    @jax.jit
    def update(grads, state, params):
        return scatter_update_gather(
            spec, grads, params, state, hyper=hyper, comm=local, mean=False)

    return Optimizer(init, update, hyper)


def flat_sgd(lr: float, momentum: float, spec: flatbuf.FlatBuffer, *,
             weight_decay: float = 0.0, num_rings: int = 1,
             bucket_bytes: int | None = None) -> Optimizer:
    """Fused flat momentum SGD: state is ONE flat momentum buffer."""
    return _flat_optimizer(
        {"name": "flat_sgd", "lr": lr, "momentum": momentum,
         "weight_decay": weight_decay}, spec, num_rings, bucket_bytes)


def flat_adagrad(lr: float, spec: flatbuf.FlatBuffer, *,
                 eps: float = 1e-10, num_rings: int = 1,
                 bucket_bytes: int | None = None,
                 state_dtype=None) -> Optimizer:
    """Fused flat AdaGrad: state is ONE flat accumulator buffer
    (optionally bf16 — half the state bytes, f32 compute per tile)."""
    return _flat_optimizer(
        {"name": "flat_adagrad", "lr": lr, "eps": eps,
         "state_dtype": state_dtype},
        spec, num_rings, bucket_bytes)


def flat_adamw(lr: float, spec: flatbuf.FlatBuffer, *,
               b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
               weight_decay: float = 0.0, num_rings: int = 1,
               bucket_bytes: int | None = None,
               state_dtype=None) -> Optimizer:
    """Fused flat AdamW: state is the (2, n) m/v buffer + scalar step
    count — the two full-size adaptive streams ride one flat object
    (optionally bf16: another 2x off the dominant state cost)."""
    return _flat_optimizer(
        {"name": "flat_adamw", "lr": lr, "b1": b1, "b2": b2, "eps": eps,
         "weight_decay": weight_decay, "state_dtype": state_dtype},
        spec, num_rings, bucket_bytes)
