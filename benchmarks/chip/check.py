"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against a limit of its own from the cell's file:

  loss_gap    the largest relative gap, over the first steps, between
              the program's loss and the reference's
  grad_gap    the first step's gradient as the optimizer got it: by the
              worst leaf, |norm(program) - norm(reference)| over the
              larger of the reference's norm of that leaf and of the
              median leaf
  change_gap  the parameters' change over the first steps, by the worst
              leaf, measured the same way;
              leaves whose reference gradient is under a thousandth of
              the median leaf's are left out, since only round-off
              moves them

A leaf is one tensor of one layer: the layer-stacked tensors are split
along their leading axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

TINY_GRAD = 1e-3     # of the median leaf's reference gradient norm


def leaf_names(tree) -> list[str]:
    """Names of the per-layer leaves, in ``leaf_norms`` order."""
    names = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.startswith("layers/"):
            names += [f"{name}[{i}]" for i in range(leaf.shape[0])]
        else:
            names.append(name)
    return names


def _norms(leaves_with_stacked) -> jax.Array:
    out = []
    for stacked, x in leaves_with_stacked:
        x = jnp.square(x.astype(jnp.float32))
        if stacked:
            out.append(jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1), axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(x))[None])
    return jnp.concatenate(out)


def _stacked_flags(tree) -> list[bool]:
    return [str(getattr(path[0], "key", path[0])) == "layers"
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree) -> jax.Array:
    """Per-leaf (per-layer) float32 norms of a parameter-shaped tree."""
    return _norms(zip(_stacked_flags(tree), jax.tree.leaves(tree)))


@jax.jit
def change_norms(new, old) -> jax.Array:
    """Per-leaf norms of ``new - old``, in float32."""
    diff = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                        - b.astype(jnp.float32), new, old)
    return _norms(zip(_stacked_flags(diff), jax.tree.leaves(diff)))


def _gaps(got, want, keep=None):
    """Per leaf: |got - want| / max(want, median(want)), with left-out
    leaves at 0."""
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    if keep is None:
        keep = np.ones(want.shape, bool)
    floor = np.maximum(want, np.median(want[keep]))
    gaps = np.where(keep, np.abs(got - want) / floor, 0.0)
    return np.where(np.isfinite(got), gaps, math.inf)


def norm_gap(got, want, keep=None) -> float:
    """The worst leaf's gap."""
    return float(np.max(_gaps(got, want, keep)))


def loss_gap(got: list, want: list) -> float:
    if len(got) != len(want) or not all(map(math.isfinite, got)):
        return math.inf
    return max(abs(a - b) / abs(b) for a, b in zip(got, want))


def _moved(ref: dict):
    grad = np.asarray(ref["grad"], np.float64)
    return grad >= TINY_GRAD * np.median(grad)


def worst_leaves(prog: dict, ref: dict, names: list) -> dict:
    """The leaf behind ``grad_gap`` and behind ``change_gap``."""
    out = {}
    for key, keep in (("grad", None), ("change", _moved(ref))):
        gaps = _gaps(prog[key], ref[key], keep)
        out[key] = names[int(np.argmax(gaps))]
    return out


def readings(prog: dict, ref: dict) -> dict:
    """The three numbers, from the program's and the reference's
    ``{"losses", "grad", "change"}`` (per-leaf norms)."""
    keep = _moved(ref)
    return {
        "loss_gap": loss_gap(prog["losses"], ref["losses"]),
        "grad_gap": norm_gap(prog["grad"], ref["grad"]),
        "change_gap": norm_gap(prog["change"], ref["change"], keep),
    }


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct``, and each number beside its limit."""
    table = {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
