"""Causal flash attention for grouped-query heads: forward and backward
Pallas kernels behind one ``jax.custom_vjp``.

Layout. One KV head's G query heads share its K and V, so each kernel
works on one (batch, KV head) pair at a time and stacks its G query
heads into the rows of a block: q is ``(B, KV, S * G, D)`` with row
``s * G + g`` for position s of query head g (the model's ``(B, S, KV,
G, D)`` transposed, then merged), k and v are ``(B, KV, S, D)``. A block
of ``bq`` positions is then ``R = bq * G`` contiguous rows, and one
matmul serves all G heads. Row r of a block starting at position q0
holds position ``q0 + r // G``, so key k is visible to it iff
``(k - q0) * G <= r``: the causal mask needs no division.

Forward: per (batch, KV head, q block) the kernel streams KV blocks
through VMEM with a running max, sum and f32 accumulator, and skips
every block wholly above the diagonal (its grid step does nothing, and
the K/V index map repeats the last block it needs, so no DMA is
issued); only blocks that cross the diagonal are masked. The running
max and sum are lane-replicated ``(R, 128)`` tiles, widened to a score
block by whole-tile copies. It writes the output and the per-row
log-sum-exp, nothing else.

Backward: ``dkv`` grids over KV blocks and streams the q blocks at or
below the diagonal, computing the scores transposed (keys on sublanes,
query rows on lanes) so that the log-sum-exp and ``rowsum(dO * O)`` are
lane-dense rows; ``dq`` grids over q blocks and streams KV blocks as the
forward does. Each recomputes its score block in VMEM.

Precision: scores are Q·Kᵀ of the input dtype with f32 accumulation,
scaled by ``1/sqrt(D)`` in f32; the softmax statistics are f32; P·V and
every backward product take their non-f32 operand in the input dtype and
accumulate in f32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

NEG_INF = -1e30        # the finite mask value of models/attention.py
LANES = 128
BLOCKS = (256, 128)        # candidate block lengths, preferred first
VMEM_LIMIT = 64 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_NN = (((1,), (0,)), ((), ()))    # a @ b


def pick_blocks(seq_len: int, groups: int):
    """``(block_q, block_kv)`` in positions for a sequence of
    ``seq_len`` and ``groups`` query heads a KV head, or None when no
    block divides the sequence.

    Blocks of 256 where they divide it, else 128: on a v5e at 8 x 1024
    tokens, head_dim 64 and 128, the training step's kernels took 5-6 %
    less time a step at blocks of 256 (10 of the 16 blocks of the square
    computed) than at 512 (3 of 4), and the step ran 0.1-0.7 % faster
    (PERF.md §6). The q block's ``block_q * groups`` rows of f32 scores
    live in VMEM with their exponent, so it halves while it holds more
    than 4096 rows.
    """
    bk = next((b for b in BLOCKS if seq_len % b == 0), None)
    if bk is None:
        return None
    bq = bk
    while bq > LANES and bq * groups > 4096:
        bq //= 2
    return bq, bk


def _mask(s, offset: jax.Array, groups: int, *, keys_on_rows: bool,
          masked: bool = True):
    """``s`` with the keys that its query rows may not see set to
    NEG_INF (``s`` itself unless ``masked``). ``offset`` is the key
    block's first position less the q block's; query rows run along axis
    0 (or 1 with ``keys_on_rows``)."""
    if not masked:
        return s
    q_axis, k_axis = (1, 0) if keys_on_rows else (0, 1)
    rows = lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    keys = lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
    return jnp.where((keys + offset) * groups <= rows, s, NEG_INF)


def _visit(i, j, bq: int, bk: int, step) -> None:
    """Runs ``step(masked)`` for q block ``i`` and KV block ``j`` unless
    the KV block lies wholly above the diagonal; ``masked`` is whether
    the block crosses it (a block wholly below it needs no mask)."""
    below = j * bk < (i + 1) * bq           # some key visible to some row
    crosses = (j + 1) * bk - 1 > i * bq     # some key hidden from some row
    pl.when(below & crosses)(lambda: step(True))
    pl.when(below & jnp.logical_not(crosses))(lambda: step(False))


def _lanes(x, n: int):
    """An ``(R, LANES)`` block of lane-replicated columns widened (by
    whole copies of its tiles) or cut to ``(R, n)``."""
    return pltpu.repeat(x, n // LANES, axis=1) if n >= LANES else x[:, :n]


def _column(row):
    """A ``(1, R)`` row as an ``(R, LANES)`` block of lane-replicated
    columns."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, scale, groups, bq, bk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(masked):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        s = _mask(s * scale, j * bk - i * bq, groups,
                  keys_on_rows=False, masked=masked)
        m_prev = m_sc[...]                       # (R, LANES), replicated
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = corr * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = _lanes(corr, acc_sc.shape[1]) * acc_sc[...] + \
            lax.dot_general(p.astype(v.dtype), v, _NN,
                            preferred_element_type=jnp.float32)

    _visit(i, j, bq, bk, step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _out():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / _lanes(l, acc_sc.shape[1])).astype(
            o_ref.dtype)
        lse_ref[...] = (m_sc[...] + jnp.log(l)).T[:1]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, groups, bq, bk):
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def step(masked):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        st = lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        st = _mask(st * scale, j * bk - i * bq, groups,
                   keys_on_rows=True, masked=masked)
        pt = jnp.exp(st - lse_ref[...])                # (bk, R)
        dv_sc[...] += lax.dot_general(pt.astype(do.dtype), do, _NN,
                                      preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[...])
        dk_sc[...] += lax.dot_general(dst.astype(q.dtype), q, _NN,
                                      preferred_element_type=jnp.float32)

    _visit(i, j, bq, bk, step)

    @pl.when(i == pl.num_programs(3) - 1)
    def _out():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               lse_sc, di_sc, dq_sc, *, scale, groups, bq, bk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)
        lse_sc[...] = _column(lse_ref[...])
        di_sc[...] = _column(di_ref[...])

    def step(masked):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        s = _mask(s * scale, j * bk - i * bq, groups,
                  keys_on_rows=False, masked=masked)
        p = jnp.exp(s - _lanes(lse_sc[...], bk))
        dp = lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(di_sc[...], bk))
        dq_sc[...] += lax.dot_general(ds.astype(k.dtype), k, _NN,
                                      preferred_element_type=jnp.float32)

    _visit(i, j, bq, bk, step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _out():
        dq_ref[...] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _geometry(q, k):
    B, KV, SG, D = q.shape
    S = k.shape[2]
    groups = SG // S
    bq, bk = pick_blocks(S, groups)
    return B, KV, S, D, groups, bq, bk, 1.0 / math.sqrt(D)


def _params():
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=common.use_interpret())


def _q_outer_specs(bq: int, bk: int, R: int, D: int):
    """Block specs of a grid with q blocks outer and KV blocks inner: the
    q block's rows, its ``(1, R)`` statistics, and K/V, whose index stops
    at the last block the q block sees (so skipped steps fetch nothing).

    Index maps use lax primitives only (their operands are never
    negative): ``//`` and ``jnp.minimum`` trace through jitted functions
    whose cached traces carry the source frames of whichever kernel traced
    them first, and those frames land in every later kernel's body."""
    last_kv = lambda i: lax.div((i + 1) * bq - 1, bk)
    rows = pl.BlockSpec((None, None, R, D), lambda b, h, i, j: (b, h, i, 0))
    stats = pl.BlockSpec((None, None, 1, R), lambda b, h, i, j: (b, h, 0, i))
    kv = pl.BlockSpec((None, None, bk, D), lambda b, h, i, j: (
        b, h, lax.min(j, last_kv(i)), 0))
    return rows, stats, kv


def _forward(q, k, v):
    B, KV, S, D, groups, bq, bk, scale = _geometry(q, k)
    R = bq * groups
    row_spec, stat_spec, kv_spec = _q_outer_specs(bq, bk, R, D)
    kernel = functools.partial(_fwd_kernel, scale=scale, groups=groups,
                               bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=(B, KV, S // bq, S // bk),
        in_specs=[row_spec, kv_spec, kv_spec],
        out_specs=[row_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, KV, 1, S * groups), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((R, LANES), jnp.float32),
                        pltpu.VMEM((R, LANES), jnp.float32),
                        pltpu.VMEM((R, D), jnp.float32)],
        name="gqa_causal_fwd", **_params(),
    )(q, k, v)


def _backward(q, k, v, o, lse, do):
    B, KV, S, D, groups, bq, bk, scale = _geometry(q, k)
    R = bq * groups
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = di.reshape(B, KV, 1, S * groups)

    # dk, dv: KV blocks outer, the q blocks at or below the diagonal inner
    first_q = lambda j: lax.div(j * bk, bq)
    row = lambda b, h, j, i: (b, h, lax.max(i, first_q(j)), 0)
    stat = lambda b, h, j, i: (b, h, 0, lax.max(i, first_q(j)))
    kv_spec = pl.BlockSpec((None, None, bk, D), lambda b, h, j, i: (b, h, j, 0))
    row_spec = pl.BlockSpec((None, None, R, D), row)
    stat_spec = pl.BlockSpec((None, None, 1, R), stat)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, groups=groups, bq=bq,
                          bk=bk),
        grid=(B, KV, S // bk, S // bq),
        in_specs=[row_spec, kv_spec, kv_spec, row_spec, stat_spec, stat_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        name="gqa_causal_dkv", **_params(),
    )(q, k, v, do, lse, di)

    # dq: q blocks outer, the KV blocks at or left of the diagonal inner
    row_spec, stat_spec, kv_spec = _q_outer_specs(bq, bk, R, D)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, groups=groups, bq=bq,
                          bk=bk),
        grid=(B, KV, S // bq, S // bk),
        in_specs=[row_spec, kv_spec, kv_spec, row_spec, stat_spec, stat_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((R, LANES), jnp.float32),
                        pltpu.VMEM((R, LANES), jnp.float32),
                        pltpu.VMEM((R, D), jnp.float32)],
        name="gqa_causal_dq", **_params(),
    )(q, k, v, do, lse, di)
    return dq, dk, dv


@jax.custom_vjp
def causal_flash(q, k, v):
    """Causal attention on the kernel layout: q ``(B, KV, S * G, D)``,
    k and v ``(B, KV, S, D)``; returns ``(B, KV, S * G, D)``, in blocks
    of ``pick_blocks``."""
    return _forward(q, k, v)[0]


def _causal_flash_fwd(q, k, v):
    o, lse = _forward(q, k, v)
    return o, (q, k, v, o, lse)


def _causal_flash_bwd(res, do):
    return _backward(*res, do)


causal_flash.defvjp(_causal_flash_fwd, _causal_flash_bwd)
