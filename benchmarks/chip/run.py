#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics over a window of
``--seconds``; ``--trace 1`` takes a profiler trace of a short window and
reports the per-layer metrics. Either way the run ends by checking the
timed path's first steps against the plain reference. The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` with a trace) and,
last, ``checks``: each number compared beside its limit, which are also
the last lines on standard error.

It runs only on a TPU: it exits non-zero and prints no result when JAX
finds no TPU, fewer chips than the cell asks for, or would interpret the
Pallas kernels.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def fail(msg: str) -> None:
    print(f"bench: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def context(cell, res, devices, root=ROOT) -> dict:
    """What the metric readers read."""
    from benchmarks.chip import harness, trace as trace_lib

    prof = res["profile"]
    calls = trace_lib.custom_calls(res["hlo"])

    def kernels(name):
        mark = harness.cost_module(name, root).MARK
        return {c["name"] for c in calls if mark in c["body"]}

    kernel_ops = {c["name"] for c in calls}
    window = busy = 0.0
    steps = len(res["stamps"]) if prof is not None else 0
    if prof is not None:
        lo, hi = prof.window()
        window, busy = (hi - lo) / 1e9, trace_lib.busy_s(prof)
    rows, seq = cell.traffic["batch_per_chip"] * cell.chips, \
        cell.traffic["seq_len"]
    return {
        "cell": cell, "run": res, "chips": cell.chips,
        "tokens_per_step": rows * seq, "trace": prof, "steps": steps,
        "window_s": window, "busy_s": busy, "calls": calls,
        "kernels": kernels, "kernel_ops": kernel_ops, "lib": trace_lib,
        "peaks": harness.peaks(devices[0].device_kind, root),
        "costs": lambda name: harness.cost_module(name, root),
    }


def result_line(cell, res, devices, trace: bool) -> dict:
    from benchmarks.chip import harness, trace as trace_lib

    ctx = context(cell, res, devices)
    metrics = harness.read_metrics(
        cell, cell.per_layer if trace else cell.end_to_end, ctx)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=ctx["busy_s"], window_s=ctx["window_s"])
        line["breakdown"] = {"device_ops": trace_lib.top_ops(res["profile"]),
                             "idle_gaps": trace_lib.idle_gaps(res["profile"])}
    line["checks"] = res["checks"]
    return line


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program is not here ({ROOT / 'src' / 'repro'})")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness, train

    cell = harness.load_cell(args.workload)
    from repro.launch.compile_cache import init_compile_cache

    init_compile_cache()
    import jax

    # every program of the run goes to the cache, so that only a
    # checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.kernels.common import use_interpret

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        fail(f"cell {cell.name} needs {cell.chips} chips, found "
             f"{len(devices)}")
    if use_interpret():
        fail("the Pallas kernels would run in interpret mode")
    devices = devices[:cell.chips]

    res = train.run(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t0=T0, devices=devices)
    line = result_line(cell, res, devices, bool(args.trace))
    edges = [res["t_start"]] + res["stamps"]
    steps = sorted((b - a) * 1e3 for a, b in zip(edges, edges[1:]))
    print(f"window: {len(steps)} steps of {steps[len(steps) // 2]:.2f} ms "
          f"(median), slowest {[round(x, 2) for x in steps[-5:]]} ms",
          file=sys.stderr)
    print("set-up (s from process start): " + ", ".join(
        f"{k} {v:.2f}" for k, v in res["setup_marks"].items())
        + f", window {res['setup_s']:.2f}", file=sys.stderr)
    print(f"losses {res['losses']} reference {res['ref_losses']} "
          f"({res['reference_s']:.1f} s)", file=sys.stderr)
    print(f"worst leaves: {res['worst']}", file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
