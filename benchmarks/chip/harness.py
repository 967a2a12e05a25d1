"""Loads the benchmark's data by name: cells, configurations, traffic
mixes, metric readers, cost functions and the table of peaks.

Everything that belongs to one cell, configuration, traffic mix or metric
sits in a file of its own under this directory, found by the name that
``BENCHMARK.json`` gives it:

  workloads/<cell>.json     sync mode, optimizer, limits
  configs/<config>.json     the sizes as run (``reference`` names the
                            plain reference module beside it)
  traffic/<traffic>.json    batch geometry and pool, for traffic.py
  metrics/<metric>.py       ``read(ctx)`` -> a number, or None
  costs/<name>.py           operation and byte counts

so a later cell, configuration or metric adds files and entries and
edits none.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]                 # the checkout holding BENCHMARK.json
REL = HERE.relative_to(ROOT)           # benchmarks/chip


class HarnessError(ValueError):
    """A benchmark file is missing or malformed."""


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise HarnessError(f"{path} not found")
    try:
        data = json.loads(path.read_text())
    except json.JSONDecodeError as e:
        raise HarnessError(f"{path}: {e}") from None
    if not isinstance(data, dict):
        raise HarnessError(f"{path}: a JSON object is expected")
    return data


def _need(data: dict, keys: dict, where: str) -> None:
    for key, kind in keys.items():
        if key not in data:
            raise HarnessError(f"{where}: missing key {key!r}")
        if not isinstance(data[key], kind):
            raise HarnessError(f"{where}: {key!r} should be {kind}, "
                               f"got {type(data[key]).__name__}")


WORKLOAD_KEYS = {"sync": dict, "optimizer": dict, "limits": dict}
CONFIG_KEYS = {"source": str, "program": str, "reference": str,
               "model": dict, "dtype": str}
TRAFFIC_KEYS = {"batch_per_chip": int, "seq_len": int, "pool": int,
                "ids": str}
LIMIT_KEYS = ("loss_gap", "grad_gap", "change_gap")


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    spec: dict          # workloads/<cell>.json
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<traffic>.json
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    root: Path = ROOT

    @property
    def dir(self) -> Path:
        return self.root / REL


def load_benchmark(root: Path = ROOT) -> dict:
    bench = _read_json(Path(root) / "BENCHMARK.json")
    _need(bench, {"workloads": list, "configs": list, "end_to_end": list,
                  "per_layer": list}, "BENCHMARK.json")
    return bench


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with every file it names, checked for shape."""
    root = Path(root)
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w.get("name") == name),
                 None)
    if entry is None:
        known = [w.get("name") for w in bench["workloads"]]
        raise HarnessError(f"no cell {name!r} in BENCHMARK.json ({known})")
    _need(entry, {"config": str, "traffic": str, "chips": int},
          f"BENCHMARK.json cell {name!r}")
    if not any(c.get("name") == entry["config"] for c in bench["configs"]):
        raise HarnessError(f"cell {name!r} names configuration "
                           f"{entry['config']!r}, which BENCHMARK.json "
                           f"does not list")
    d = root / REL
    spec = _read_json(d / "workloads" / f"{name}.json")
    _need(spec, WORKLOAD_KEYS, f"workloads/{name}.json")
    extra = sorted(set(spec) - set(WORKLOAD_KEYS))
    if extra:
        raise HarnessError(f"workloads/{name}.json: unknown keys {extra}")
    for key in LIMIT_KEYS:
        if not isinstance(spec["limits"].get(key), (int, float)):
            raise HarnessError(f"workloads/{name}.json: limits needs a "
                               f"number under {key!r}")
    config = _read_json(d / "configs" / f"{entry['config']}.json")
    _need(config, CONFIG_KEYS, f"configs/{entry['config']}.json")
    traffic = _read_json(d / "traffic" / f"{entry['traffic']}.json")
    _need(traffic, TRAFFIC_KEYS, f"traffic/{entry['traffic']}.json")
    if entry["chips"] != 1:
        raise HarnessError(f"cell {name!r}: the harness drives the step of "
                           f"launch/train.py, on one chip")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if _applies(m, name):
            metric_reader(m["name"], root)      # every reader is there
    return Cell(name, entry["chips"], entry["config"], entry["traffic"],
                spec, config, traffic,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)], root)


@functools.lru_cache(maxsize=None)
def _module(path: Path, what: str):
    if not path.is_file():
        raise HarnessError(f"{what}: {path} not found")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{path.parent.name}_{path.stem}".replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    mod = _module(Path(root) / REL / "metrics" / f"{name}.py",
                  f"metric {name!r}")
    if not callable(getattr(mod, "read", None)):
        raise HarnessError(f"metrics/{name}.py defines no read(ctx)")
    return mod.read


def cost_module(name: str, root: Path = ROOT):
    """``costs/<name>.py``: operation and byte counts."""
    return _module(Path(root) / REL / "costs" / f"{name}.py",
                   f"cost functions {name!r}")


def reference_module(cell: Cell):
    """The plain reference that the configuration names, beside it."""
    return _module(cell.dir / "configs" / f"{cell.config['reference']}.py",
                   f"reference of {cell.config_name!r}")


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = _read_json(Path(root) / REL / "peaks.json")
    if device_kind not in table.get("devices", {}):
        raise HarnessError(f"peaks.json has no entry for {device_kind!r}")
    return table["devices"][device_kind]


def read_metrics(cell: Cell, metrics: list, ctx: dict) -> dict:
    """Each metric's reader on ``ctx``; a reader that finds nothing to
    read returns None and its metric is left out."""
    out = {}
    for m in metrics:
        value = metric_reader(m["name"], cell.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
