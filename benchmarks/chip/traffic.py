"""The one generator of training traffic: a pool of distinct token
batches made on the device from the seed.

A traffic file (``traffic/<name>.json``) gives:

  batch_per_chip   rows per chip per step
  seq_len          tokens per row
  pool             distinct batches in the pool; the run cycles them
  ids              how token ids are drawn: "uniform" over the
                   configuration's vocabulary

Every seed gets the same sizes; only the ids differ. Labels are the
tokens shifted by one, so each row is a stretch of ``seq_len + 1`` ids.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

IDS = ("uniform",)


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed: the low 32 bits make the key, the
    rest are folded in, so seeds past 2**32 stay distinct."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def batch_shape(traffic: dict, chips: int) -> tuple[int, int]:
    return traffic["batch_per_chip"] * chips, traffic["seq_len"]


def make_pool(traffic: dict, vocab: int, chips: int,
              key: jax.Array) -> list[dict]:
    """``traffic["pool"]`` batches of ``{"tokens", "labels"}``, each
    ``(batch_per_chip * chips, seq_len)`` int32, made in one call."""
    if traffic["ids"] not in IDS:
        raise ValueError(f"ids {traffic['ids']!r} is not one of {IDS}")
    rows, seq = batch_shape(traffic, chips)

    @partial(jax.jit, static_argnums=(1,))
    def draw(key, shape):
        ids = jax.random.randint(key, shape, 0, vocab, jnp.int32)
        return [{"tokens": ids[i, :, :-1], "labels": ids[i, :, 1:]}
                for i in range(shape[0])]

    return draw(key, (traffic["pool"], rows, seq + 1))
