"""Unified model API: ``build_model(cfg)`` returns a ``Model`` whose
functions cover every assigned architecture family:

  init(rng)                 -> params
  loss_fn(params, batch)    -> (loss, metrics)          [train_4k]
  forward(params, batch)    -> logits                    [prefill_32k]
  init_cache(batch, seq)    -> decode cache/state        [decode shapes]
  serve_step(params, cache, tokens) -> (logits, cache)   [one new token]
  input_specs(shape)        -> ShapeDtypeStruct batch stand-ins

Modality frontends (SigLIP patches, mel+conv frames) are stubs per the
brief: ``input_specs`` supplies embeddings of the right shape.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import ssm, transformer as tfm
from repro.models.layers import layer_norm, rms_norm
from repro.models.transformer import (
    apply_dec_layer,
    layer_scan,
    apply_enc_layer,
    apply_hybrid,
    apply_stack,
    decode_dec_layer,
    decode_hybrid,
    decode_stack,
    init_dec_layer,
    init_enc_layer,
    init_hybrid,
    init_hybrid_cache,
    init_mamba_layer,
    init_stack,
    init_stack_cache,
)

MAX_WHISPER_POSITIONS = 32768


@dataclass
class Model:
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable
    forward: Callable
    init_cache: Callable
    serve_step: Callable
    input_specs: Callable
    # backward-overlap staging (optional): overlap_stages(num_buckets) ->
    # OverlapStages splitting loss_fn into a chain of stages whose param
    # subtrees become the reduce-scatter schedule buckets. None = the
    # family has no staged form yet (overlap is rejected with a pointer).
    overlap_stages: "Callable | None" = None


@dataclass(frozen=True)
class OverlapStages:
    """``loss_fn`` as a chain of stages for backward-overlapped sync.

    ``stage(params)`` splits the param tree into per-stage subtrees
    (tuple, forward order); ``fns[0](p0, batch)`` produces the first
    carry and ``fns[s](ps, carry, batch)`` the next, with the LAST stage
    returning ``(loss, metrics)`` — composing all stages reproduces
    ``loss_fn`` exactly (same ops, same order). ``unstage(tuple)``
    inverts ``stage``. A leaf used by several stages (the tied embedding:
    token lookup in stage 0, the logits einsum in the head) is a stage
    param of ONLY its earliest stage and its VALUE rides the carry to
    later stages — so each leaf lives in exactly one schedule bucket,
    and its full gradient is complete exactly when its owning stage's
    vjp runs (the carried value's cotangent flows back through the
    intermediate stages' pass-throughs).
    """

    stage: Callable
    fns: tuple
    unstage: Callable

    @property
    def num_stages(self) -> int:
        return len(self.fns)


def _embed_init(key, cfg: ModelConfig, dtype):
    v, d = cfg.padded_vocab, cfg.d_model
    emb = jax.random.normal(key, (v, d), jnp.float32).astype(dtype) * 0.02
    p = {"embedding": emb, "final_norm": jnp.zeros((d,), dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = (
            jax.random.normal(jax.random.fold_in(key, 1), (d, v), jnp.float32)
            * (d ** -0.5)
        ).astype(dtype)
    return p


def _logits(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["embedding"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["lm_head"])
    # mask padded vocab ids
    pad = cfg.padded_vocab - cfg.vocab_size
    if pad:
        mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(mask, logits, -1e30)
    return logits


def _embed(p: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = p["embedding"][tokens]
    if cfg.arch_type == "vlm":  # gemma-style embedding scale
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    # one-hot contraction instead of take_along_axis: a gather over the
    # vocab dim would force GSPMD to all-gather vocab-sharded logits; the
    # masked-sum keeps every op elementwise/reduction over the sharded dim.
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    vocab = logits.shape[-1]
    onehot = (labels[..., None] == jnp.arange(vocab)).astype(jnp.float32)
    gold = jnp.sum(logits.astype(jnp.float32) * onehot, axis=-1)
    return jnp.mean(lse - gold)


XENT_CHUNK = 512


def _sequence_xent(p: dict, h: jax.Array, labels: jax.Array,
                   cfg: ModelConfig) -> jax.Array:
    """Next-token xent from hidden states WITHOUT materializing the full
    (B, S, V) logits: scan over sequence chunks, rematerializing each
    chunk's logits in fwd and bwd. The vocab-path temps (logits, one-hot,
    dlogits — all f32) dominate train-step memory for big-vocab models
    (~11 GB/dev layer-independent on qwen3-4b × train_4k)."""
    B, S, _ = h.shape
    if S % XENT_CHUNK or S <= XENT_CHUNK:
        return _xent(_logits(p, h, cfg), labels)
    nc = S // XENT_CHUNK
    hs = jnp.moveaxis(h.reshape(B, nc, XENT_CHUNK, -1), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, XENT_CHUNK), 1, 0)

    def body(acc, inp):
        hc, lc = inp
        logits = _logits(p, hc, cfg)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        onehot = (lc[..., None] == jnp.arange(cfg.padded_vocab)
                  ).astype(jnp.float32)
        gold = jnp.sum(logits.astype(jnp.float32) * onehot, axis=-1)
        return acc + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                            (hs, ls))
    return total / (B * S)


def build_model(cfg: ModelConfig) -> Model:
    dtype = jnp.dtype(cfg.dtype)
    builder = {
        "dense": _build_decoder,
        "vlm": _build_decoder,
        "moe": _build_decoder,
        "ssm": _build_ssm,
        "hybrid": _build_hybrid,
        "audio": _build_enc_dec,
    }[cfg.arch_type]
    return builder(cfg, dtype)


# --------------------------------------------------------------------------
# decoder-only (dense / moe / vlm)
# --------------------------------------------------------------------------

def _build_decoder(cfg: ModelConfig, dtype) -> Model:
    n_img = cfg.num_image_tokens

    def init(rng):
        k1, k2 = jax.random.split(rng)
        p = _embed_init(k1, cfg, dtype)
        p["layers"] = init_stack(k2, cfg, dtype)
        return p

    def embed(p, batch):
        with jax.named_scope("model.embed"):
            x = _embed(p, batch["tokens"], cfg)
            if n_img:
                x = jnp.concatenate(
                    [batch["image_embeds"].astype(x.dtype), x], axis=1
                )
            return x

    def forward(p, batch):
        x, _ = apply_stack(p["layers"], embed(p, batch), cfg,
                           prefix_len=n_img)
        with jax.named_scope("model.head_loss"):
            h = rms_norm(x, p["final_norm"], cfg.norm_eps)
            return _logits(p, h, cfg)

    def loss_fn(p, batch):
        x, aux = apply_stack(p["layers"], embed(p, batch), cfg,
                             prefix_len=n_img)
        with jax.named_scope("model.head_loss"):
            h = rms_norm(x, p["final_norm"], cfg.norm_eps)
            if n_img:
                h = h[:, n_img:]
            xent = _sequence_xent(p, h, batch["labels"], cfg)
        loss = xent + aux
        return loss, {"xent": xent, "aux": aux}

    def init_cache(batch, max_seq):
        return init_stack_cache(batch, max_seq, cfg, dtype)

    def serve_step(p, cache, tokens):
        x = _embed(p, tokens, cfg)  # (B, 1, d)
        x, cache = decode_stack(p["layers"], x, cache, cfg)
        x = rms_norm(x, p["final_norm"], cfg.norm_eps)
        return _logits(p, x, cfg), cache

    def input_specs(shape: InputShape):
        return _decoder_specs(cfg, shape, dtype)

    return Model(cfg, init, loss_fn, forward, init_cache, serve_step,
                 input_specs,
                 overlap_stages=_decoder_overlap_stages(cfg, loss_fn))


def _decoder_overlap_stages(cfg: ModelConfig, loss_fn) -> Callable:
    """Stage factory for the decoder family (dense / moe / vlm):
    [embed] + k layer slices + [head], where k = num_buckets - 2 clamped
    to [1, num_layers] (uneven last slice allowed — ceil split). Each
    stage replays exactly the ops ``loss_fn`` runs over that span, so
    the composed chain is bit-identical to the monolithic loss. With
    tied embeddings the embedding is stage 0's param and its VALUE rides
    the carry to the head's logits einsum (see ``OverlapStages``)."""
    n_img = cfg.num_image_tokens

    def factory(num_buckets: int) -> OverlapStages:
        if num_buckets <= 1:
            # degenerate single-bucket schedule: the whole loss is one
            # stage, the one reduce-scatter leg simply trails backward
            return OverlapStages(stage=lambda p: (p,),
                                 fns=(lambda p0, batch: loss_fn(p0, batch),),
                                 unstage=lambda parts: parts[0])
        k = min(cfg.num_layers, max(1, int(num_buckets) - 2))
        base, rem = divmod(cfg.num_layers, k)
        slices, lo = [], 0
        for i in range(k):
            hi = lo + base + (1 if i < rem else 0)
            slices.append((lo, hi))
            lo = hi

        def stage(p):
            head = {"final_norm": p["final_norm"]}
            if not cfg.tie_embeddings:
                head["lm_head"] = p["lm_head"]
            return (({"embedding": p["embedding"]},)
                    + tuple(jax.tree.map(lambda a: a[lo:hi], p["layers"])
                            for lo, hi in slices)
                    + (head,))

        def unstage(parts):
            p = {"embedding": parts[0]["embedding"],
                 "layers": jax.tree.map(
                     lambda *xs: jnp.concatenate(xs, axis=0)
                     if len(xs) > 1 else xs[0], *parts[1:-1]),
                 "final_norm": parts[-1]["final_norm"]}
            if not cfg.tie_embeddings:
                p["lm_head"] = parts[-1]["lm_head"]
            return p

        def embed_fn(p0, batch):
            with jax.named_scope("model.embed"):
                x = _embed(p0, batch["tokens"], cfg)
                if n_img:
                    x = jnp.concatenate(
                        [batch["image_embeds"].astype(x.dtype), x], axis=1)
            carry = {"x": x, "aux": jnp.zeros((), jnp.float32)}
            if cfg.tie_embeddings:
                carry["emb"] = p0["embedding"]
            return carry

        def layer_fn(ps, carry, batch):
            h, a = apply_stack(ps, carry["x"], cfg, prefix_len=n_img)
            out = dict(carry)
            out["x"] = h
            out["aux"] = carry["aux"] + a
            return out

        def head_fn(ph, carry, batch):
            with jax.named_scope("model.head_loss"):
                h = rms_norm(carry["x"], ph["final_norm"], cfg.norm_eps)
                if n_img:
                    h = h[:, n_img:]
                pl = ({"embedding": carry["emb"]} if cfg.tie_embeddings
                      else {"lm_head": ph["lm_head"]})
                xent = _sequence_xent(pl, h, batch["labels"], cfg)
            return xent + carry["aux"], {"xent": xent, "aux": carry["aux"]}

        fns = (embed_fn,) + (layer_fn,) * k + (head_fn,)
        return OverlapStages(stage=stage, fns=fns, unstage=unstage)

    return factory


def _decoder_specs(cfg: ModelConfig, shape: InputShape, dtype):
    B, S = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct
    if shape.kind == "decode":
        return {"tokens": tok((B, 1), jnp.int32)}
    n_img = cfg.num_image_tokens
    text = S - n_img if n_img else S
    batch = {"tokens": tok((B, text), jnp.int32)}
    if n_img:
        batch["image_embeds"] = tok((B, n_img, cfg.d_model), dtype)
    if shape.kind == "train":
        batch["labels"] = tok((B, text), jnp.int32)
    return batch


# --------------------------------------------------------------------------
# pure SSM (mamba2)
# --------------------------------------------------------------------------

def _build_ssm(cfg: ModelConfig, dtype) -> Model:
    def init(rng):
        k1, k2 = jax.random.split(rng)
        p = _embed_init(k1, cfg, dtype)
        keys = jax.random.split(k2, cfg.num_layers)
        p["layers"] = jax.vmap(
            lambda k: {
                "norm": jnp.zeros((cfg.d_model,), dtype),
                **init_mamba_layer(k, cfg, dtype),
            }
        )(keys)
        return p

    def backbone(p, x):
        from repro.sharding.rules import maybe_seq_shard

        def body(h, layer_params):
            h = maybe_seq_shard(h, cfg.seq_shard_activations)
            norm = layer_params["norm"]
            lp = {k: v for k, v in layer_params.items() if k != "norm"}
            y, _ = ssm.mamba_block(
                lp, rms_norm(h, norm, cfg.norm_eps),
                expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
                state=cfg.ssm_state, chunk=cfg.ssm_chunk,
            )
            return h + y, None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        with jax.named_scope("model.layers"):
            x, _ = layer_scan(body_fn, x, p["layers"], cfg)
        return x

    def embed(p, batch):
        with jax.named_scope("model.embed"):
            return _embed(p, batch["tokens"], cfg)

    def forward(p, batch):
        x = backbone(p, embed(p, batch))
        with jax.named_scope("model.head_loss"):
            return _logits(p, rms_norm(x, p["final_norm"], cfg.norm_eps),
                           cfg)

    def loss_fn(p, batch):
        x = backbone(p, embed(p, batch))
        with jax.named_scope("model.head_loss"):
            h = rms_norm(x, p["final_norm"], cfg.norm_eps)
            loss = _sequence_xent(p, h, batch["labels"], cfg)
        return loss, {"xent": loss}

    def init_cache(batch, max_seq):
        h, conv = ssm.init_mamba_state(
            batch, cfg.d_model, expand=cfg.ssm_expand,
            head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
            conv_width=cfg.ssm_conv_width, dtype=dtype,
        )
        return {
            "h": jnp.broadcast_to(h, (cfg.num_layers,) + h.shape).copy(),
            "conv": jnp.broadcast_to(conv, (cfg.num_layers,) + conv.shape).copy(),
        }

    def serve_step(p, cache, tokens):
        x = _embed(p, tokens, cfg)

        def body(h, inp):
            layer_params, st = inp
            norm = layer_params["norm"]
            lp = {k: v for k, v in layer_params.items() if k != "norm"}
            y, (hs, cs) = ssm.mamba_decode(
                lp, rms_norm(h, norm, cfg.norm_eps), st["h"], st["conv"],
                expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
                state=cfg.ssm_state,
            )
            return h + y, {"h": hs, "conv": cs}

        x, cache = layer_scan(body, x, (p["layers"], cache), cfg,
                              with_out=True)
        x = rms_norm(x, p["final_norm"], cfg.norm_eps)
        return _logits(p, x, cfg), cache

    def input_specs(shape: InputShape):
        return _decoder_specs(cfg, shape, dtype)

    return Model(cfg, init, loss_fn, forward, init_cache, serve_step, input_specs)


# --------------------------------------------------------------------------
# hybrid (zamba2)
# --------------------------------------------------------------------------

def _build_hybrid(cfg: ModelConfig, dtype) -> Model:
    def init(rng):
        k1, k2 = jax.random.split(rng)
        p = _embed_init(k1, cfg, dtype)
        p.update(init_hybrid(k2, cfg, dtype))
        return p

    def backbone(p, batch):
        with jax.named_scope("model.embed"):
            x = _embed(p, batch["tokens"], cfg)
        with jax.named_scope("model.layers"):
            x, _ = apply_hybrid(p, x, cfg)
        return x

    def forward(p, batch):
        x = backbone(p, batch)
        with jax.named_scope("model.head_loss"):
            return _logits(p, rms_norm(x, p["final_norm"], cfg.norm_eps),
                           cfg)

    def loss_fn(p, batch):
        x = backbone(p, batch)
        with jax.named_scope("model.head_loss"):
            h = rms_norm(x, p["final_norm"], cfg.norm_eps)
            loss = _sequence_xent(p, h, batch["labels"], cfg)
        return loss, {"xent": loss}

    def init_cache(batch, max_seq):
        return init_hybrid_cache(batch, max_seq, cfg, dtype)

    def serve_step(p, cache, tokens):
        x = _embed(p, tokens, cfg)
        x, cache = decode_hybrid(p, x, cache, cfg)
        x = rms_norm(x, p["final_norm"], cfg.norm_eps)
        return _logits(p, x, cfg), cache

    def input_specs(shape: InputShape):
        return _decoder_specs(cfg, shape, dtype)

    return Model(cfg, init, loss_fn, forward, init_cache, serve_step, input_specs)


# --------------------------------------------------------------------------
# encoder-decoder (whisper): conv/mel frontend stubbed as frame embeddings
# --------------------------------------------------------------------------

def _build_enc_dec(cfg: ModelConfig, dtype) -> Model:
    def init(rng):
        ks = jax.random.split(rng, 4)
        p = _embed_init(ks[0], cfg, dtype)
        p["final_norm_b"] = jnp.zeros((cfg.d_model,), dtype)
        p["final_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["enc_pos"] = {
            "pos_embedding": jax.random.normal(
                ks[1], (cfg.enc_seq_len, cfg.d_model), jnp.float32
            ).astype(dtype) * 0.02
        }
        p["dec_pos"] = {
            "pos_embedding": jax.random.normal(
                ks[2], (MAX_WHISPER_POSITIONS, cfg.d_model), jnp.float32
            ).astype(dtype) * 0.02
        }
        enc_keys = jax.random.split(ks[3], cfg.enc_layers)
        p["encoder"] = jax.vmap(lambda k: init_enc_layer(k, cfg, dtype))(enc_keys)
        dec_keys = jax.random.split(jax.random.fold_in(ks[3], 7), cfg.num_layers)
        p["decoder"] = jax.vmap(lambda k: init_dec_layer(k, cfg, dtype))(dec_keys)
        p["enc_final_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["enc_final_norm_b"] = jnp.zeros((cfg.d_model,), dtype)
        return p

    def encode(p, frames):
        with jax.named_scope("model.embed"):
            x = (frames.astype(dtype)
                 + p["enc_pos"]["pos_embedding"][: frames.shape[1]])

        def body(h, lp):
            return apply_enc_layer(lp, h, cfg), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        with jax.named_scope("model.layers"):
            x, _ = layer_scan(body_fn, x, p["encoder"], cfg)
            return layer_norm(x, p["enc_final_norm"], p["enc_final_norm_b"])

    def decode_full(p, enc, tokens):
        with jax.named_scope("model.embed"):
            x = p["embedding"][tokens]
            x = x + p["dec_pos"]["pos_embedding"][: tokens.shape[1]]

        def body(h, lp):
            return apply_dec_layer(lp, h, enc, cfg), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        with jax.named_scope("model.layers"):
            x, _ = layer_scan(body_fn, x, p["decoder"], cfg)
        with jax.named_scope("model.head_loss"):
            return layer_norm(x, p["final_norm"], p["final_norm_b"])

    def forward(p, batch):
        enc = encode(p, batch["audio_frames"])
        h = decode_full(p, enc, batch["tokens"])
        with jax.named_scope("model.head_loss"):
            return _logits(p, h, cfg)

    def loss_fn(p, batch):
        enc = encode(p, batch["audio_frames"])
        h = decode_full(p, enc, batch["tokens"])
        with jax.named_scope("model.head_loss"):
            loss = _sequence_xent(p, h, batch["labels"], cfg)
        return loss, {"xent": loss}

    def init_cache(batch, max_seq):
        cache = init_stack_cache(batch, max_seq, cfg, dtype)
        cache = jax.tree.map(lambda a: a, cache)
        return {
            "self": cache,
            "enc": jnp.zeros((batch, cfg.enc_seq_len, cfg.d_model), dtype),
        }

    def serve_step(p, cache, tokens):
        idx = cache["self"]["index"][0]
        x = p["embedding"][tokens]
        x = x + jax.lax.dynamic_slice_in_dim(
            p["dec_pos"]["pos_embedding"], idx, 1, axis=0
        )

        def body(h, inp):
            lp, c = inp
            h, c = decode_dec_layer(lp, h, cache["enc"], c, cfg)
            return h, c

        x, new_self = layer_scan(body, x, (p["decoder"], cache["self"]), cfg,
                                 with_out=True)
        x = layer_norm(x, p["final_norm"], p["final_norm_b"])
        return _logits(p, x, cfg), {"self": new_self, "enc": cache["enc"]}

    def input_specs(shape: InputShape):
        B, S = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct
        if shape.kind == "decode":
            return {"tokens": tok((B, 1), jnp.int32)}
        batch = {
            "tokens": tok((B, S), jnp.int32),
            "audio_frames": tok((B, cfg.enc_seq_len, cfg.d_model), dtype),
        }
        if shape.kind == "train":
            batch["labels"] = tok((B, S), jnp.int32)
        return batch

    return Model(cfg, init, loss_fn, forward, init_cache, serve_step, input_specs)
