"""The chip benchmark's check decides ``correct``: a sound run passes, and
a run with the timed path broken underneath, or the float8 control in
the program's place, fails. Drives the whole run except the look for a
chip, at a size the CPU holds (tiny.py)."""
from __future__ import annotations

import math
import time

import jax
import numpy as np
import pytest

import tiny
from benchmarks.chip import check, harness, train


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny.checkout(tmp_path_factory.mktemp("checkout"))
    return harness.load_cell(tiny.CELL, root)


def run(cell, fault=None, seed=2**31 + 101):
    return train.run(cell, seed=seed, seconds=0.5, trace=False,
                     t0=time.perf_counter(), devices=jax.devices()[:1],
                     fault=fault)


def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["checks"]) == set(harness.LIMIT_KEYS)
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(cell, fault):
    res = run(cell, fault)
    assert not res["correct"], res["checks"]


def test_float8_control_is_not_correct(cell):
    seed = 2**31 + 202
    pool = train.pool_for(cell, seed)
    want = train.reference(cell, seed, pool)
    got = train.reference(cell, seed, pool, precision="fp8")
    ok, table = check.verdict(check.readings(got, want),
                              cell.spec["limits"])
    assert not ok, table


def test_gaps_by_the_worst_leaf():
    want = np.array([1.0, 2.0, 4.0, 1e-9])
    # leaf 3 is nought in the reference: its gap is taken against the
    # median leaf (1.5), not against itself
    got = np.array([1.0, 2.2, 4.0, 0.15])
    assert check.norm_gap(got, want) == pytest.approx(0.1)
    assert check.norm_gap(want, want) == 0.0
    keep = np.array([True, False, True, True])
    # the median over the kept leaves (1.0) is the floor
    assert check.norm_gap(got, want, keep) == pytest.approx(0.15)
    assert check.norm_gap(np.array([1, 2, np.nan, 0]), want) == math.inf
    assert check.loss_gap([1.0, 2.0], [1.0, 1.0]) == 1.0
    assert check.loss_gap([float("nan")], [1.0]) == math.inf


def test_worst_leaves_are_named():
    names = ["embedding", "layers/w[0]", "layers/w[1]"]
    ref = {"grad": [1.0, 2.0, 1e-9], "change": [1.0, 1.0, 1e-12]}
    got = {"grad": [1.0, 2.5, 1e-9],
           # leaf 0 is worst; leaf 2 is left out
           "change": [1.3, 1.0, 9.0]}
    assert check.worst_leaves(got, ref, names) == {
        "grad": "layers/w[0]", "change": "embedding"}


def test_tiny_gradients_leave_the_change_check():
    ref = {"losses": [1.0], "grad": [1.0, 1.0, 1e-4],
           "change": [1.0, 1.0, 1e-9]}
    got = {"losses": [1.0], "grad": [1.0, 1.0, 1e-4],
           "change": [1.0, 1.0, 5.0]}
    assert check.readings(got, ref)["change_gap"] == 0.0
    ok, table = check.verdict({"loss_gap": math.nan}, {"loss_gap": 1.0})
    assert not ok and table["loss_gap"]["limit"] == 1.0


def test_leaf_names_split_layers():
    tree = {"embedding": np.zeros((4, 2)),
            "layers": {"w": np.zeros((3, 2, 2))}}
    assert check.leaf_names(tree) == ["embedding", "layers/w[0]",
                                      "layers/w[1]", "layers/w[2]"]
    norms = check.leaf_norms({"embedding": np.ones((4, 2)),
                              "layers": {"w": np.ones((3, 2, 2))}})
    np.testing.assert_allclose(norms, [math.sqrt(8), 2, 2, 2])
