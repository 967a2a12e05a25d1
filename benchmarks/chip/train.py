"""One run of a training cell: set-up, the measured window, the check.

Set-up builds one object, the program's compiled step with its state,
from the seed: the benchmark's weights (the reference module's
``init_params``, one jitted call, in the configuration's dtype) and the
program's own optimizer state. It drives that object through its first
``CHECK_STEPS`` steps with the window's own call and feed, reading what
the check needs, then ``WARM_STEPS`` more, and hands it to the window.

The window mirrors the program's training loop (``launch/train.py``
``train_loop``): one step, then a blocking fetch of its loss, whose
completion time is the step's stamp.

After the window the state is freed and the plain reference reruns the
first steps from the same weights and batches; ``check.py`` compares.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import jax
import jax.numpy as jnp

from . import check, harness, traffic as traffic_lib

CHECK_STEPS = 3     # steps the check compares with the reference
WARM_STEPS = 2      # further steps before the window
TRACE_STEPS = 12    # steps of a traced window

# faults planted under the timed path, for the harness's own tests
FAULTS = ("unchanged", "half_batch")


def program_config(cell: harness.Cell):
    """The program's ModelConfig for the configuration file's sizes."""
    from repro.configs.base import get_config

    m, c = cell.config["model"], cell.config
    cfg = get_config(c["program"])
    return dataclasses.replace(
        cfg, num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim") or m["hidden_size"]
        // m["num_attention_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        qkv_bias=bool(m["qkv_bias"]), dtype=c["dtype"])


def _faulty(fn, fault):
    if fault == "unchanged":
        return lambda state, batch: (state, fn(state, batch)[1])
    if fault == "half_batch":
        def half(batch):
            rows = jax.tree.leaves(batch)[0].shape[-2]
            return jax.tree.map(lambda a: a[..., :rows // 2, :], batch)
        return lambda state, batch: fn(state, half(batch))
    return fn


class Program:
    """The system under test on one chip: the step of ``launch/train.py``
    (``make_train_step``), jitted with its state donated."""

    def __init__(self, cell: harness.Cell, fault=None):
        if fault not in (None,) + FAULTS:
            raise ValueError(f"fault {fault!r} is not one of {FAULTS}")
        from repro.core.comm import CollectivePolicy
        from repro.core.hierarchy import SyncConfig
        from repro.launch.train import grad_spec
        from repro.models.model import build_model
        from repro.optim.sgd import get_optimizer

        spec = cell.spec
        self.cell, self.fault = cell, fault
        self.model = build_model(program_config(cell))
        o = dict(spec["optimizer"])
        self.opt = get_optimizer(o.pop("name"), o.pop("lr"), **o)
        sync = dict(spec["sync"])
        if "policy" in sync:
            sync["policy"] = CollectivePolicy(**sync["policy"])
        self.sync = SyncConfig(**sync)
        self.spec = grad_spec(self.model)

    # -- state and step ----------------------------------------------------
    def state(self, params, key):
        """The program's initial state around the benchmark's weights
        (consumed)."""
        from repro.launch.train import make_train_state

        def build(key, p):
            st = make_train_state(self.model, self.opt, self.sync, key)
            st["params"] = p
            if "center" in st:
                st["center"] = jax.tree.map(jnp.copy, p)
            return st

        return jax.jit(build, donate_argnums=(1,))(key, params)

    def compile(self, state, batch):
        from repro.launch.train import make_train_step

        fn = _faulty(make_train_step(self.model, self.opt, self.sync, None),
                     self.fault)
        return jax.jit(fn, donate_argnums=(0,)).lower(state, batch).compile()

    # -- readings of the program's state -------------------------------------
    def grad_norms(self, state):
        """Per-leaf norms of the first gradient as the optimizer got it:
        the momentum after one step from zero."""
        buf = state["opt"]
        leaves = [self.spec.leaf_view(buf, i)
                  for i in range(self.spec.num_leaves)]
        tree = jax.tree_util.tree_unflatten(self.spec.treedef, leaves)
        return jax.device_get(check.leaf_norms(tree))


def _peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def keys(seed: int):
    """(weights, traffic, program state) keys of a seed."""
    key = traffic_lib.seed_key(seed)
    return tuple(jax.random.fold_in(key, i) for i in range(3))


def pool_for(cell: harness.Cell, seed: int) -> list:
    return traffic_lib.make_pool(cell.traffic,
                                 cell.config["model"]["vocab_size"],
                                 cell.chips, keys(seed)[1])


def first_steps(cell: harness.Cell, prog: Program, seed: int, pool: list,
                marks: dict | None = None):
    """Set-up: the program's state and compiled step from the seed, driven
    through the first ``CHECK_STEPS`` steps. Returns the state, the step
    and the program's readings for the check; ``marks`` gets the clock
    after the state is built and after the step is compiled."""
    marks = {} if marks is None else marks
    config = cell.config
    ref = harness.reference_module(cell)
    dtype = jnp.dtype(config["dtype"])
    wkey, _, skey = keys(seed)
    init = jax.jit(lambda k: ref.init_params(config, k, dtype))
    state = jax.block_until_ready(prog.state(init(wkey), skey))
    marks["state"] = time.perf_counter()
    step = prog.compile(state, pool[0])
    marks["compile"] = time.perf_counter()
    losses, grad = [], None
    for i in range(CHECK_STEPS):
        state, m = step(state, pool[i % len(pool)])
        losses.append(float(m["loss"]))
        if i == 0:
            grad = prog.grad_norms(state)
    start = init(wkey)
    change = jax.device_get(check.change_norms(state["params"], start))
    del start
    return state, step, {"losses": losses, "grad": grad, "change": change}


def reference(cell: harness.Cell, seed: int, pool: list,
              precision: str = "f32") -> dict:
    """The plain reference's readings over the same first steps, from the
    same weights and batches (``precision="fp8"``: the control)."""
    config, o = cell.config, cell.spec["optimizer"]
    ref = harness.reference_module(cell)
    dtype = jnp.dtype(config["dtype"])
    init = jax.jit(lambda k: ref.init_params(config, k, dtype))
    wkey = keys(seed)[0]
    first = [(pool[i % len(pool)]["tokens"], pool[i % len(pool)]["labels"])
             for i in range(CHECK_STEPS)]
    losses, grad, params = ref.train(
        init(wkey), first, config, lr=o["lr"], momentum=o["momentum"],
        first_grad=check.leaf_norms, precision=precision,
        block_rows=cell.traffic["batch_per_chip"])
    change = check.change_norms(params, init(wkey))
    return {"losses": losses, "grad": jax.device_get(grad),
            "change": jax.device_get(change)}


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        t0: float, devices, fault=None) -> dict:
    """One run; returns what the result line and the metric readers need."""
    marks = {"devices": time.perf_counter()}
    prog = Program(cell, fault)
    pool = pool_for(cell, seed)
    state, step, got = first_steps(cell, prog, seed, pool, marks)
    marks["first_steps"] = time.perf_counter()
    k = CHECK_STEPS
    for _ in range(WARM_STEPS):
        state, m = step(state, pool[k % len(pool)])
        float(m["loss"])
        k += 1

    # the window
    profile = trace_dir = None
    if trace:
        from . import trace as trace_lib

        trace_dir = trace_lib.start()
    t_start = time.perf_counter()
    stamps, window_losses = [], []
    while True:
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, m = step(state, pool[k % len(pool)])
        with jax.profiler.TraceAnnotation("bench.loss_fetch"):
            window_losses.append(float(m["loss"]))
        stamps.append(time.perf_counter())
        k += 1
        if stamps[-1] - t_start >= seconds:
            break
        if trace and len(stamps) >= TRACE_STEPS:
            break
    if trace:
        profile = trace_lib.stop(trace_dir)
    peak = _peak_bytes(devices)
    hlo = step.as_text() if trace else ""
    del state, step, m
    gc.collect()

    t_ref = time.perf_counter()
    want = reference(cell, seed, pool)
    t_ref = time.perf_counter() - t_ref
    correct, table = check.verdict(check.readings(got, want),
                                   cell.spec["limits"])
    ref = harness.reference_module(cell)
    names = check.leaf_names(jax.eval_shape(
        lambda: ref.init_params(cell.config, jax.random.key(0), jnp.float32)))
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    return {
        "correct": correct and failed == 0, "checks": table,
        "attempted": len(stamps), "failed": failed,
        "t_start": t_start, "stamps": stamps, "setup_s": t_start - t0,
        "setup_marks": {k: v - t0 for k, v in marks.items()},
        "memory_peak_bytes": peak, "profile": profile, "hlo": hlo,
        "losses": got["losses"], "ref_losses": want["losses"],
        "reference_s": t_ref, "worst": check.worst_leaves(got, want, names),
    }
