"""A copy of the benchmark in a temporary checkout, with a cell small
enough for the CPU: the harness finds it by name, as it finds the real
cells."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

CELL = "tiny.sgd.c1"
CONFIG = {
    "source": "a CPU-sized member of the Qwen2 family, for tests",
    "program": "qwen2-0.5b", "reference": "dense_decoder",
    "dtype": "bfloat16",
    "model": {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "num_hidden_layers": 2, "vocab_size": 512,
              "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
              "tie_word_embeddings": True, "qkv_bias": True},
    "storage": {"embedding_rows": 512},
}
TRAFFIC = {"batch_per_chip": 4, "seq_len": 32, "pool": 8, "ids": "uniform"}
# Limits set as the real cells' are, from readings on the CPU at this
# size (bf16 program, 5 seeds; float8 control and half-batch fault, 3):
#   loss_gap    program at most 4.8e-5; control at least 2.6e-4
#   grad_gap    program at most 1.2e-2; control 2.6e-2 (under 3x: no
#               upper reading); half batch at least 0.45
#   change_gap  program at most 1.8e-2; control 3.0e-2 (under 3x);
#               half batch at least 0.64, a state left unchanged 1.0
WORKLOAD = {
    "sync": {"mode": "mpi_sgd", "num_clients": 1},
    "optimizer": {"name": "sgd", "lr": 0.05, "momentum": 0.9},
    "limits": {"loss_gap": 1.2e-4, "grad_gap": 0.1, "change_gap": 0.15},
}


def checkout(tmp: Path, *, workload=None, name=CELL, chips=1) -> Path:
    """``tmp`` as a checkout: BENCHMARK.json and benchmarks/chip, with the
    tiny cell added beside the real ones."""
    shutil.copytree(REPO / "benchmarks" / "chip", tmp / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": CONFIG["source"],
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": [], "why": "CPU tests"})
    bench["workloads"].append({"name": name, "config": "tiny",
                               "traffic": "tiny", "chips": chips,
                               "why": "CPU tests"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    d = tmp / "benchmarks" / "chip"
    (d / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (d / "traffic" / "tiny.json").write_text(json.dumps(TRAFFIC))
    (d / "workloads" / f"{name}.json").write_text(
        json.dumps(workload or WORKLOAD))
    return tmp
