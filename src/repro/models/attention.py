"""Attention: GQA with RoPE / qk-norm / QKV-bias / sliding-window / prefix-LM,
memory-efficient chunked softmax for long sequences, and KV-cache decode.

The train/prefill path unrolls query chunks at the Python level so each
chunk attends to a *statically truncated* KV range (triangular skipping —
no FLOPs spent on fully-masked blocks), and scans over KV blocks inside a
chunk with a running (max, sum, acc) — flash-attention structure in pure
jnp, which both bounds memory and lowers on any backend.

On a TPU, plain causal self-attention (no prefix, no window, equal
lengths a multiple of the kernel's block) takes the Pallas kernel of
``kernels/flash_attention`` instead: its score blocks stay in VMEM.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.flash_attention.flash_attention import pick_blocks
from repro.kernels.flash_attention.ops import causal_attention
from repro.models.layers import apply_rope, dense_init, rms_norm

NEG_INF = -1e30


@dataclass(frozen=True)
class AttnSpec:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    qkv_bias: bool = False
    sliding_window: int = 0
    use_rope: bool = True
    rope_theta: float = 10000.0
    causal: bool = True
    prefix_len: int = 0  # prefix-LM: first N positions attend bidirectionally


def init_attention(key, d_model: int, spec: AttnSpec, dtype) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    h, kvh, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    p = {
        "wq": dense_init(kq, d_model, (d_model, h * hd), dtype),
        "wk": dense_init(kk, d_model, (d_model, kvh * hd), dtype),
        "wv": dense_init(kv, d_model, (d_model, kvh * hd), dtype),
        "wo": dense_init(ko, h * hd, (h * hd, d_model), dtype),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kvh * hd,), dtype)
        p["bv"] = jnp.zeros((kvh * hd,), dtype)
    if spec.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(params, x, x_kv, spec: AttnSpec, positions, kv_positions):
    B = x.shape[0]
    h, kvh, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    k = jnp.einsum("bsd,dh->bsh", x_kv, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", x_kv, params["wv"])
    if spec.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, -1, h, hd)
    k = k.reshape(B, -1, kvh, hd)
    v = v.reshape(B, -1, kvh, hd)
    if spec.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if spec.use_rope:
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, kv_positions, spec.rope_theta)
    return q, k, v


def _block_mask(qpos, kpos, spec: AttnSpec):
    """(qc, kc) bool mask of allowed attention."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if spec.causal:
        causal = kpos[None, :] <= qpos[:, None]
        if spec.prefix_len > 0:
            causal = causal | (kpos[None, :] < spec.prefix_len)
        m = m & causal
    if spec.sliding_window > 0:
        m = m & (kpos[None, :] > qpos[:, None] - spec.sliding_window)
    return m


def _chunk_attend(q, k, v, qpos0: int, spec: AttnSpec, kv_chunk: int,
                  kv_valid: Optional[jax.Array] = None):
    """Flash-style scan over KV blocks for one query chunk.

    q: (B, qc, KV, G, D); k/v: (B, Sk, KV, D). Returns (B, qc, KV, G, D).
    """
    B, qc, KV, G, D = q.shape
    Sk = k.shape[1]
    nkv = max(1, math.ceil(Sk / kv_chunk))
    pad = nkv * kv_chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nkv, kv_chunk, KV, D)
    vb = v.reshape(B, nkv, kv_chunk, KV, D)
    kb = jnp.moveaxis(kb, 1, 0)  # (nkv, B, kc, KV, D)
    vb = jnp.moveaxis(vb, 1, 0)
    qpos = qpos0 + jnp.arange(qc)
    scale = 1.0 / math.sqrt(D)

    def body(carry, blk):
        m_run, l_run, acc = carry
        kblk, vblk, bidx = blk
        kpos = bidx * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, kblk).astype(jnp.float32) * scale
        mask = _block_mask(qpos, kpos, spec)
        mask = mask & (kpos[None, :] < Sk)
        if kv_valid is not None:
            mask = mask & (kpos[None, :] < kv_valid)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(q.dtype), vblk
        ).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, KV, G, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, qc), jnp.float32)
    a0 = jnp.zeros((B, KV, G, qc, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nkv))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).astype(q.dtype)  # (B, qc, KV, G, D)


def _partitioned() -> bool:
    """Whether the ambient mesh has an axis that XLA partitions (GSPMD):
    a Mosaic kernel cannot be partitioned automatically, only inside
    shard_map, whose axes are manual."""
    mesh = jax.sharding.get_abstract_mesh()
    return any(mesh.shape[a] > 1
               for a in mesh.auto_axes + mesh.explicit_axes)


def takes_flash_kernel(spec: AttnSpec, q_len: int, kv_len: int, *,
                       cross: bool = False) -> bool:
    """Whether ``multi_head_attention`` runs the causal flash kernel: on a
    TPU outside a GSPMD-partitioned mesh, for causal self-attention with
    no prefix and no window, over equal lengths that the kernel's block
    divides."""
    return (not common.use_interpret() and not _partitioned()
            and not cross and spec.causal
            and spec.prefix_len == 0 and spec.sliding_window == 0
            and q_len == kv_len
            and pick_blocks(q_len, spec.num_heads // spec.num_kv_heads)
            is not None)


def multi_head_attention(
    params: dict,
    x: jax.Array,
    spec: AttnSpec,
    *,
    x_kv: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross)."""
    B, Sq, _ = x.shape
    x_kv = x if x_kv is None else x_kv
    Sk = x_kv.shape[1]
    if positions is None:
        positions = jnp.arange(Sq)[None, :]
    kv_positions = jnp.arange(Sk)[None, :]
    q, k, v = _project_qkv(params, x, x_kv, spec, positions, kv_positions)
    KV, G = spec.num_kv_heads, spec.num_heads // spec.num_kv_heads
    q = q.reshape(B, Sq, KV, G, spec.head_dim)
    if takes_flash_kernel(spec, Sq, Sk, cross=x_kv is not x):
        out = causal_attention(q, k, v).reshape(B, Sq, -1)
        return jnp.einsum("bsh,hd->bsd", out, params["wo"])

    nq = max(1, math.ceil(Sq / q_chunk))
    outs = []
    # checkpoint each q-chunk: the inner scan's per-step (m, l, acc) f32
    # carries are otherwise saved for the backward pass — measured
    # ~4.3 GB/layer on qwen3-4b × train_4k; recomputing them per chunk
    # bounds the residuals to one chunk's worth
    attend = jax.checkpoint(
        lambda qi, ki, vi, off, sp: _chunk_attend(qi, ki, vi, off, sp, kv_chunk),
        static_argnums=(3, 4),
    )
    for i in range(nq):  # python unroll: static triangular KV truncation
        lo, hi = i * q_chunk, min((i + 1) * q_chunk, Sq)
        qi = q[:, lo:hi]
        if spec.causal and spec.prefix_len == 0:
            k_hi = hi  # blocks past the diagonal are statically skipped
            k_lo = 0
            if spec.sliding_window > 0:
                k_lo = max(0, (lo - spec.sliding_window) // kv_chunk * kv_chunk)
        else:
            k_lo, k_hi = 0, Sk
        sub = attend(
            qi, k[:, k_lo:k_hi], v[:, k_lo:k_hi], lo - k_lo,
            _shift_spec(spec, k_lo),
        )
        outs.append(sub)
    out = jnp.concatenate(outs, axis=1).reshape(B, Sq, spec.num_heads * spec.head_dim)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"])


def _shift_spec(spec: AttnSpec, k_lo: int) -> AttnSpec:
    if k_lo == 0 or spec.prefix_len == 0:
        return spec
    import dataclasses

    return dataclasses.replace(spec, prefix_len=max(0, spec.prefix_len - k_lo))


# --------------------------------------------------------------------------
# KV-cache decode
# --------------------------------------------------------------------------

def init_kv_cache(batch: int, max_seq: int, spec: AttnSpec, dtype) -> dict:
    """Sliding-window specs allocate only a window-sized rolling buffer."""
    size = min(max_seq, spec.sliding_window) if spec.sliding_window else max_seq
    shape = (batch, size, spec.num_kv_heads, spec.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "index": jnp.zeros((), jnp.int32),
    }


def decode_attention(
    params: dict, x: jax.Array, cache: dict, spec: AttnSpec
) -> tuple[jax.Array, dict]:
    """One-token decode: x (B, 1, d). Returns (out (B, 1, d), new cache)."""
    B = x.shape[0]
    idx = cache["index"]
    pos = idx[None, None]  # (1,1) broadcast position of the new token
    q, k_new, v_new = _project_qkv(params, x, x, spec, pos, pos)
    size = cache["k"].shape[1]
    slot = jnp.where(spec.sliding_window > 0, idx % size, jnp.minimum(idx, size - 1))
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)

    KV, G, D = spec.num_kv_heads, spec.num_heads // spec.num_kv_heads, spec.head_dim
    q = q.reshape(B, 1, KV, G, D)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k_cache).astype(jnp.float32) * scale
    slots = jnp.arange(size)
    if spec.sliding_window > 0:
        # rolling buffer: a slot is valid if written within the last `size`
        # steps (including the token just inserted at `slot`).
        age = (slot - slots) % size
        valid = age <= jnp.minimum(idx, size - 1)
    else:
        valid = slots <= idx
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(x.dtype), v_cache)
    out = out.reshape(B, 1, spec.num_heads * D)
    out = jnp.einsum("bsh,hd->bsd", out, params["wo"])
    return out, {"k": k_cache, "v": v_cache, "index": idx + 1}


def reference_attention(params, x, spec: AttnSpec, x_kv=None) -> jax.Array:
    """O(S^2) oracle used by tests."""
    B, Sq, _ = x.shape
    x_kv = x if x_kv is None else x_kv
    Sk = x_kv.shape[1]
    q, k, v = _project_qkv(
        params, x, x_kv, spec,
        jnp.arange(Sq)[None, :], jnp.arange(Sk)[None, :],
    )
    KV, G, D = spec.num_kv_heads, spec.num_heads // spec.num_kv_heads, spec.head_dim
    out = reference_attend(q.reshape(B, Sq, KV, G, D), k, v, spec)
    return jnp.einsum(
        "bsh,hd->bsd", out.reshape(B, Sq, spec.num_heads * D), params["wo"]
    )


def reference_attend(q, k, v, spec: AttnSpec) -> jax.Array:
    """The oracle's core, the whole score square: q (B, Sq, KV, G, D),
    k/v (B, Sk, KV, D) -> (B, Sq, KV, G, D)."""
    Sq, Sk, D = q.shape[1], k.shape[1], q.shape[-1]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) / math.sqrt(D)
    mask = _block_mask(jnp.arange(Sq), jnp.arange(Sk), spec)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
