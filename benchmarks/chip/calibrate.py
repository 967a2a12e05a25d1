#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from, on the chip.

    python benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,... [--control-seeds ...] [--fault half_batch:7,8,9]

For every seed of ``--seeds``: the program's first steps against the
reference (the lower readings). For every seed of ``--control-seeds``:
the reference computed in float8 put in the program's place (the
control, whose smallest readings are the upper ones). For every
``--fault`` kind and seed: the program with that fault planted under
its step. One process, so the step compiles once. Each reading is one
JSON line on standard output. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def readings(cell, seed: int, *, fault=None, control=False):
    """The check's numbers for one seed: the program's (or the control's)
    first steps against the reference."""
    from benchmarks.chip import check, train

    pool = train.pool_for(cell, seed)
    want = train.reference(cell, seed, pool)
    if control:
        got = train.reference(cell, seed, pool, precision="fp8")
    else:
        prog = train.Program(cell, fault)
        state, step, got = train.first_steps(cell, prog, seed, pool)
        del state, step
        gc.collect()
    return check.readings(got, want)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault", action="append", default=[],
                    help="kind:seed,seed,...")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    from repro.launch.compile_cache import init_compile_cache

    init_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        sys.exit(f"calibrate: needs {cell.chips} TPU chips, found "
                 f"{len(devices)} {devices[0].platform}")
    jobs = [("program", None, s) for s in args.seeds]
    jobs += [("control", None, s) for s in args.control_seeds]
    for f in args.fault:
        kind, _, ss = f.partition(":")
        jobs += [("fault", kind, s) for s in seeds(ss)]
    for what, fault, seed in jobs:
        t = time.perf_counter()
        nums = readings(cell, seed, fault=fault,
                        control=what == "control")
        print(json.dumps({"cell": cell.name, "what": what, "fault": fault,
                          "seed": seed, **nums,
                          "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
