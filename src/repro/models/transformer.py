"""Block composition: pre-norm transformer blocks (dense FFN or MoE),
scan-over-layers stacking, encoder-decoder (whisper), and the zamba2-style
hybrid backbone (Mamba2 layers + one shared attention block with
per-invocation LoRA adapters).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm
from repro.models.attention import (
    AttnSpec,
    decode_attention,
    init_attention,
    init_kv_cache,
    multi_head_attention,
)
from repro.models.layers import dense_init, ffn, gelu_ffn, init_ffn, init_mlp, layer_norm, mlp_ffn, rms_norm
from repro.models.moe import init_moe, moe_block


def attn_spec(cfg: ModelConfig, *, causal: bool = True, prefix_len: int = 0,
              cross: bool = False) -> AttnSpec:
    return AttnSpec(
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim,
        qk_norm=cfg.qk_norm and not cross,
        qkv_bias=cfg.qkv_bias,
        sliding_window=cfg.sliding_window if causal and not cross else 0,
        use_rope=cfg.use_rope and not cross,
        rope_theta=cfg.rope_theta,
        causal=causal and not cross,
        prefix_len=prefix_len,
    )


# --------------------------------------------------------------------------
# Standard decoder block (dense or MoE FFN)
# --------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, dtype) -> dict:
    ka, kf = jax.random.split(key)
    spec = attn_spec(cfg)
    p = {
        "attn_norm": jnp.zeros((cfg.d_model,), dtype),
        "attn": init_attention(ka, cfg.d_model, spec, dtype),
        "ffn_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.arch_type == "moe":
        p["moe"] = init_moe(kf, cfg.d_model, cfg.num_experts,
                            cfg.num_shared_experts, cfg.moe_d_ff, dtype)
    else:
        p["mlp"] = init_ffn(kf, cfg.d_model, cfg.d_ff, dtype)
    return p


def apply_block(params: dict, x: jax.Array, cfg: ModelConfig, *,
                prefix_len: int = 0) -> tuple[jax.Array, jax.Array]:
    spec = attn_spec(cfg, prefix_len=prefix_len)
    with jax.named_scope("model.attention"):
        h = rms_norm(x, params["attn_norm"], cfg.norm_eps)
        x = x + multi_head_attention(params["attn"], h, spec)
    with jax.named_scope("model.mlp"):
        h = rms_norm(x, params["ffn_norm"], cfg.norm_eps)
        if cfg.arch_type == "moe":
            y, aux = moe_block(
                params["moe"], h, num_experts=cfg.num_experts,
                top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                aux_weight=cfg.router_aux_weight,
            )
        else:
            mlp = gelu_ffn if cfg.arch_type == "vlm" else ffn
            y, aux = mlp(params["mlp"], h), jnp.zeros((), jnp.float32)
        return x + y, aux


def decode_block(params: dict, x: jax.Array, cache: dict, cfg: ModelConfig):
    spec = attn_spec(cfg)
    h = rms_norm(x, params["attn_norm"], cfg.norm_eps)
    a, cache = decode_attention(params["attn"], h, cache, spec)
    x = x + a
    h = rms_norm(x, params["ffn_norm"], cfg.norm_eps)
    if cfg.arch_type == "moe":
        y, _ = moe_block(
            params["moe"], h, num_experts=cfg.num_experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
            aux_weight=cfg.router_aux_weight,
            deterministic_capacity=max(
                cfg.top_k,
                (x.shape[0] * cfg.top_k + cfg.num_experts - 1) // cfg.num_experts + 1,
            ),
        )
    else:
        mlp = gelu_ffn if cfg.arch_type == "vlm" else ffn
        y = mlp(params["mlp"], h)
    return x + y, cache


# --------------------------------------------------------------------------
# Scanned decoder stack
# --------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig, dtype) -> dict:
    keys = jax.random.split(key, cfg.num_layers)
    return jax.vmap(lambda k: init_block(k, cfg, dtype))(keys)


def layer_scan(body, carry, xs, cfg: ModelConfig, *, with_out: bool = False):
    """scan-over-layers, or a python unroll of the same (dry-run lowers
    unrolled because XLA cost_analysis ignores while trip counts)."""
    if not cfg.unroll_layers:
        return jax.lax.scan(body, carry, xs)
    length = jax.tree_util.tree_leaves(xs)[0].shape[0]
    outs = []
    for i in range(length):
        sl = jax.tree.map(lambda a: a[i], xs)
        carry, out = body(carry, sl)
        if with_out:
            outs.append(out)
    if with_out:
        stacked = jax.tree.map(lambda *xs_: jnp.stack(xs_), *outs)
        return carry, stacked
    return carry, None


def apply_stack(stacked: dict, x: jax.Array, cfg: ModelConfig, *,
                prefix_len: int = 0) -> tuple[jax.Array, jax.Array]:
    from repro.sharding.rules import maybe_seq_shard

    def body(carry, layer_params):
        h, aux = carry
        h = maybe_seq_shard(h, cfg.seq_shard_activations)
        h, a = apply_block(layer_params, h, cfg, prefix_len=prefix_len)
        return (h, aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    with jax.named_scope("model.layers"):
        (x, aux), _ = layer_scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                 stacked, cfg)
    return x, aux


def decode_stack(stacked: dict, x: jax.Array, caches: dict, cfg: ModelConfig):
    def body(h, inp):
        layer_params, cache = inp
        h, cache = decode_block(layer_params, h, cache, cfg)
        return h, cache

    x, caches = layer_scan(body, x, (stacked, caches), cfg, with_out=True)
    return x, caches


def init_stack_cache(batch: int, max_seq: int, cfg: ModelConfig, dtype) -> dict:
    spec = attn_spec(cfg)
    one = init_kv_cache(batch, max_seq, spec, dtype)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape).copy(), one
    )


# --------------------------------------------------------------------------
# Encoder-decoder (whisper): encoder self-attn + decoder self/cross-attn
# --------------------------------------------------------------------------

def init_enc_layer(key, cfg: ModelConfig, dtype) -> dict:
    ka, kf = jax.random.split(key)
    return {
        "attn_norm": jnp.ones((cfg.d_model,), dtype),
        "attn_norm_b": jnp.zeros((cfg.d_model,), dtype),
        "attn": init_attention(ka, cfg.d_model, attn_spec(cfg, causal=False), dtype),
        "ffn_norm": jnp.ones((cfg.d_model,), dtype),
        "ffn_norm_b": jnp.zeros((cfg.d_model,), dtype),
        "mlp": init_mlp(kf, cfg.d_model, cfg.d_ff, dtype),
    }


def apply_enc_layer(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    spec = attn_spec(cfg, causal=False)
    h = layer_norm(x, p["attn_norm"], p["attn_norm_b"])
    x = x + multi_head_attention(p["attn"], h, spec)
    h = layer_norm(x, p["ffn_norm"], p["ffn_norm_b"])
    return x + mlp_ffn(p["mlp"], h)


def init_dec_layer(key, cfg: ModelConfig, dtype) -> dict:
    ka, kc, kf = jax.random.split(key, 3)
    return {
        "attn_norm": jnp.ones((cfg.d_model,), dtype),
        "attn_norm_b": jnp.zeros((cfg.d_model,), dtype),
        "attn": init_attention(ka, cfg.d_model, attn_spec(cfg), dtype),
        "cross_norm": jnp.ones((cfg.d_model,), dtype),
        "cross_norm_b": jnp.zeros((cfg.d_model,), dtype),
        "cross": init_attention(kc, cfg.d_model, attn_spec(cfg, cross=True), dtype),
        "ffn_norm": jnp.ones((cfg.d_model,), dtype),
        "ffn_norm_b": jnp.zeros((cfg.d_model,), dtype),
        "mlp": init_mlp(kf, cfg.d_model, cfg.d_ff, dtype),
    }


def apply_dec_layer(p: dict, x: jax.Array, enc: jax.Array, cfg: ModelConfig):
    h = layer_norm(x, p["attn_norm"], p["attn_norm_b"])
    x = x + multi_head_attention(p["attn"], h, attn_spec(cfg))
    h = layer_norm(x, p["cross_norm"], p["cross_norm_b"])
    x = x + multi_head_attention(p["cross"], h, attn_spec(cfg, cross=True), x_kv=enc)
    h = layer_norm(x, p["ffn_norm"], p["ffn_norm_b"])
    return x + mlp_ffn(p["mlp"], h)


def decode_dec_layer(p: dict, x: jax.Array, enc: jax.Array, cache: dict,
                     cfg: ModelConfig):
    h = layer_norm(x, p["attn_norm"], p["attn_norm_b"])
    a, cache = decode_attention(p["attn"], h, cache, attn_spec(cfg))
    x = x + a
    h = layer_norm(x, p["cross_norm"], p["cross_norm_b"])
    x = x + multi_head_attention(p["cross"], h, attn_spec(cfg, cross=True), x_kv=enc)
    h = layer_norm(x, p["ffn_norm"], p["ffn_norm_b"])
    return x + mlp_ffn(p["mlp"], h), cache


# --------------------------------------------------------------------------
# Hybrid (zamba2): Mamba2 backbone + ONE shared attention block, invoked
# every ``attn_period`` layers with per-invocation LoRA deltas on qkv.
# --------------------------------------------------------------------------

def n_shared_invocations(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.attn_period if cfg.attn_period else 0


def init_hybrid(key, cfg: ModelConfig, dtype) -> dict:
    km, ks, kl, kf = jax.random.split(key, 4)
    mamba_keys = jax.random.split(km, cfg.num_layers)
    mamba = jax.vmap(
        lambda k: {
            "norm": jnp.zeros((cfg.d_model,), dtype),
            **init_mamba_layer(k, cfg, dtype),
        }
    )(mamba_keys)
    spec = attn_spec(cfg)
    shared = {
        "attn_norm": jnp.zeros((cfg.d_model,), dtype),
        "attn": init_attention(ks, cfg.d_model, spec, dtype),
        "ffn_norm": jnp.zeros((cfg.d_model,), dtype),
        "mlp": init_ffn(kf, cfg.d_model, cfg.d_ff, dtype),
    }
    n_inv = n_shared_invocations(cfg)
    r = cfg.shared_lora_rank
    h = cfg.num_heads * cfg.resolved_head_dim
    lkeys = jax.random.split(kl, max(n_inv, 1))
    lora = jax.vmap(
        lambda k: {
            "lora_a_q": dense_init(jax.random.fold_in(k, 0), cfg.d_model,
                                   (cfg.d_model, r), dtype),
            "lora_b_q": jnp.zeros((r, h), dtype),
        }
    )(lkeys)
    return {"mamba": mamba, "shared": shared, "lora": lora}


def init_mamba_layer(key, cfg: ModelConfig, dtype) -> dict:
    return ssm.init_mamba(
        key, cfg.d_model, expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
        state=cfg.ssm_state, conv_width=cfg.ssm_conv_width, dtype=dtype,
    )


def _shared_attn(shared: dict, lora_i: dict, x: jax.Array, cfg: ModelConfig):
    """Shared block with LoRA delta on the q projection for this invocation."""
    spec = attn_spec(cfg)
    h = rms_norm(x, shared["attn_norm"], cfg.norm_eps)
    params = dict(shared["attn"])
    params["wq"] = params["wq"] + lora_i["lora_a_q"] @ lora_i["lora_b_q"]
    x = x + multi_head_attention(params, h, spec)
    h = rms_norm(x, shared["ffn_norm"], cfg.norm_eps)
    return x + ffn(shared["mlp"], h)


def apply_hybrid(params: dict, x: jax.Array, cfg: ModelConfig):
    """Groups of ``attn_period`` scanned mamba layers + shared attn."""
    from repro.sharding.rules import maybe_seq_shard

    period = cfg.attn_period or cfg.num_layers
    n_inv = n_shared_invocations(cfg)

    def mamba_body(h, layer_params):
        h = maybe_seq_shard(h, cfg.seq_shard_activations)
        norm = layer_params["norm"]
        lp = {k: v for k, v in layer_params.items() if k != "norm"}
        y, _ = ssm.mamba_block(
            lp, rms_norm(h, norm, cfg.norm_eps),
            expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state, chunk=cfg.ssm_chunk,
        )
        return h + y, None

    body = jax.checkpoint(mamba_body) if cfg.remat else mamba_body
    done = 0
    for i in range(n_inv):
        group = jax.tree.map(lambda a: a[done : done + period], params["mamba"])
        x, _ = layer_scan(body, x, group, cfg)
        lora_i = jax.tree.map(lambda a: a[i], params["lora"])
        x = _shared_attn(params["shared"], lora_i, x, cfg)
        done += period
    if done < cfg.num_layers:
        group = jax.tree.map(lambda a: a[done:], params["mamba"])
        x, _ = layer_scan(body, x, group, cfg)
    return x, jnp.zeros((), jnp.float32)


def init_hybrid_cache(batch: int, max_seq: int, cfg: ModelConfig, dtype):
    h, conv = ssm.init_mamba_state(
        batch, cfg.d_model, expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
        state=cfg.ssm_state, conv_width=cfg.ssm_conv_width, dtype=dtype,
    )
    stacked = {
        "h": jnp.broadcast_to(h, (cfg.num_layers,) + h.shape).copy(),
        "conv": jnp.broadcast_to(conv, (cfg.num_layers,) + conv.shape).copy(),
    }
    n_inv = n_shared_invocations(cfg)
    spec = attn_spec(cfg)
    one = init_kv_cache(batch, max_seq, spec, dtype)
    attn_caches = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (max(n_inv, 1),) + a.shape).copy(), one
    )
    return {"mamba": stacked, "attn": attn_caches}


def decode_hybrid(params: dict, x: jax.Array, cache: dict, cfg: ModelConfig):
    period = cfg.attn_period or cfg.num_layers
    n_inv = n_shared_invocations(cfg)
    spec = attn_spec(cfg)

    def mamba_body(h, inp):
        layer_params, st = inp
        norm = layer_params["norm"]
        lp = {k: v for k, v in layer_params.items() if k != "norm"}
        y, (hs, cs) = ssm.mamba_decode(
            lp, rms_norm(h, norm, cfg.norm_eps), st["h"], st["conv"],
            expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
        )
        return h + y, {"h": hs, "conv": cs}

    new_mamba_states = []
    done = 0
    for i in range(n_inv):
        sl = slice(done, done + period)
        group = jax.tree.map(lambda a: a[sl], params["mamba"])
        states = jax.tree.map(lambda a: a[sl], cache["mamba"])
        x, new_states = layer_scan(mamba_body, x, (group, states), cfg,
                                   with_out=True)
        new_mamba_states.append(new_states)
        lora_i = jax.tree.map(lambda a: a[i], params["lora"])
        attn_cache_i = jax.tree.map(lambda a: a[i], cache["attn"])
        h = rms_norm(x, params["shared"]["attn_norm"], cfg.norm_eps)
        ap = dict(params["shared"]["attn"])
        ap["wq"] = ap["wq"] + lora_i["lora_a_q"] @ lora_i["lora_b_q"]
        a, attn_cache_i = decode_attention(ap, h, attn_cache_i, spec)
        x = x + a
        h = rms_norm(x, params["shared"]["ffn_norm"], cfg.norm_eps)
        x = x + ffn(params["shared"]["mlp"], h)
        cache["attn"] = jax.tree.map(
            lambda full, new: full.at[i].set(new), cache["attn"], attn_cache_i
        )
        done += period
    if done < cfg.num_layers:
        sl = slice(done, cfg.num_layers)
        group = jax.tree.map(lambda a: a[sl], params["mamba"])
        states = jax.tree.map(lambda a: a[sl], cache["mamba"])
        x, new_states = layer_scan(mamba_body, x, (group, states), cfg,
                                   with_out=True)
        new_mamba_states.append(new_states)
    new_mamba = jax.tree.map(
        lambda *parts: jnp.concatenate(parts, axis=0), *new_mamba_states
    )
    return x, {"mamba": new_mamba, "attn": cache["attn"]}
