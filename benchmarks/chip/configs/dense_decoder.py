"""Plain reference of the Qwen2 dense decoder, for training.

The architecture of Qwen2 (arXiv:2407.10671) and Qwen2.5 (the
``Qwen/Qwen2.5-*`` model cards): pre-norm blocks with RMSNorm, grouped
query attention with biases on q, k and v and rotary embeddings
(rotate-half), a SwiGLU MLP, and an embedding tied to the output head;
the loss is the mean next-token cross-entropy. Written in plain
``jax.numpy`` in float32 at the "highest" matmul precision, with no
kernels, no flat buffer and nothing taken from the program under test.

Three departures from the published form, all of storage only, so that
one set of weights serves the program and the reference alike: an
RMSNorm weight ``w`` scales by ``1 + w``; the layers' tensors are
stacked along a leading layer axis; and the embedding holds
``embedding_rows`` rows (the configuration file's ``storage``), of which
only the first ``vocab_size`` are ever read or scored.

``init_params`` makes the benchmark's weights from a key. ``train`` runs
momentum SGD as the configuration states it: the gradient and the
momentum in float32, the parameters stored in ``param_dtype`` after
every update. ``precision="fp8"`` is the control: every matmul, forward
and backward, takes float8 (e4m3) operands scaled per row.
"""
from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0   # largest finite float8_e4m3fn


def dims(cfg: dict) -> dict:
    """The sizes the reference reads, from the configuration file's keys."""
    m = cfg["model"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    return {
        "layers": m["num_hidden_layers"], "d": m["hidden_size"],
        "heads": h, "kv_heads": kv,
        "head_dim": m.get("head_dim") or m["hidden_size"] // h,
        "ff": m["intermediate_size"], "vocab": m["vocab_size"],
        "theta": float(m["rope_theta"]), "eps": float(m["rms_norm_eps"]),
        "rows": cfg.get("storage", {}).get("embedding_rows",
                                           m["vocab_size"]),
    }


def param_shapes(cfg: dict) -> dict:
    """The parameter tree: names, and each leaf's shape."""
    z = dims(cfg)
    L, d, f, v = z["layers"], z["d"], z["ff"], z["rows"]
    q, k = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    return {
        "embedding": (v, d),
        "final_norm": (d,),
        "layers": {
            "attn_norm": (L, d),
            "attn": {"wq": (L, d, q), "wk": (L, d, k), "wv": (L, d, k),
                     "wo": (L, q, d), "bq": (L, q), "bk": (L, k),
                     "bv": (L, k)},
            "ffn_norm": (L, d),
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                    "w_down": (L, f, d)},
        },
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def init_params(cfg: dict, key: jax.Array, dtype) -> dict:
    """Seeded weights: matrices N(0, 1/fan_in), the embedding N(0, 0.02^2),
    norm weights and biases N(0, 0.02^2); each leaf from its own key."""
    shapes = param_shapes(cfg)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=_is_shape)
    out = []
    for i, shape in enumerate(leaves):
        std = 1.0 / math.sqrt(shape[-2]) if len(shape) == 3 else 0.02
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out.append((x * std).astype(dtype))
    return jax.tree.unflatten(tree, out)


# -- matmuls ---------------------------------------------------------------

def _f8(x: jax.Array) -> jax.Array:
    """x rounded through float8 e4m3, with one scale per last-axis row."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_f8(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(eq, _f8(a), _f8(b), precision=HIGHEST)


def _einsum_f8_fwd(eq, a, b):
    return _einsum_f8(eq, a, b), (a, b)


def _einsum_f8_bwd(eq, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y, precision=HIGHEST),
                     _f8(a), _f8(b))
    return vjp(_f8(g))


_einsum_f8.defvjp(_einsum_f8_fwd, _einsum_f8_bwd)


def _mm(precision: str):
    if precision == "f32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "fp8":
        return _einsum_f8
    raise ValueError(f"precision is 'f32' or 'fp8', not {precision!r}")


# -- forward and loss ------------------------------------------------------

def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (B, S, H, D), rotate-half form."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(z, mm, x, p):
    B, S, _ = x.shape
    H, KV, D = z["heads"], z["kv_heads"], z["head_dim"]
    a = p["attn"]
    h = _rms(x, p["attn_norm"], z["eps"])
    q = (mm("bsd,dh->bsh", h, a["wq"]) + a["bq"]).reshape(B, S, H, D)
    k = (mm("bsd,dh->bsh", h, a["wk"]) + a["bk"]).reshape(B, S, KV, D)
    v = (mm("bsd,dh->bsh", h, a["wv"]) + a["bv"]).reshape(B, S, KV, D)
    q, k = _rope(q, z["theta"]), _rope(k, z["theta"])
    k = jnp.repeat(k, H // KV, axis=2)     # query head i reads kv head i // G
    v = jnp.repeat(v, H // KV, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + mm("bsh,hd->bsd", o.reshape(B, S, H * D), a["wo"])
    h = _rms(x, p["ffn_norm"], z["eps"])
    m = p["mlp"]
    u = jax.nn.silu(mm("bsd,df->bsf", h, m["w_gate"])) \
        * mm("bsd,df->bsf", h, m["w_up"])
    return x + mm("bsf,fd->bsd", u, m["w_down"])


def loss_fn(params, tokens, labels, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy of a (B, S) block, in float32."""
    z, mm = dims(cfg), _mm(precision)
    p = jax.tree.map(lambda l: l.astype(jnp.float32), params)
    x = p["embedding"][tokens]

    def body(x, layer):
        return jax.checkpoint(partial(_layer, z, mm))(x, layer), None

    x, _ = lax.scan(body, x, p["layers"])
    x = _rms(x, p["final_norm"], z["eps"])

    def row(total, xy):          # one row at a time: (S, vocab) logits
        h, y = xy
        logits = mm("sd,vd->sv", h, p["embedding"][:z["vocab"]])
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - gold), None

    total, _ = lax.scan(jax.checkpoint(row), jnp.zeros((), jnp.float32),
                        (x, labels))
    return total / labels.size


@lru_cache(maxsize=None)
def _loss_grad(model_json: str, precision: str):
    cfg = json.loads(model_json)
    grad = jax.value_and_grad(lambda p, t, y: loss_fn(p, t, y, cfg,
                                                        precision))
    # differentiate at float32 copies, so that the gradient is float32
    return jax.jit(lambda p, t, y: grad(
        jax.tree.map(lambda l: l.astype(jnp.float32), p), t, y))


def loss_and_grad(params, tokens, labels, cfg: dict, *, precision="f32",
                  block_rows: int = 8):
    """Loss and float32 gradient of the mean over all rows, computed in
    blocks of ``block_rows`` rows so that the activations fit."""
    fn = _loss_grad(json.dumps(cfg, sort_keys=True), precision)
    n = tokens.shape[0]
    block_rows = min(block_rows, n)
    if n % block_rows:
        raise ValueError(f"{n} rows do not split into blocks of {block_rows}")
    loss, grad = 0.0, None
    for i in range(0, n, block_rows):
        l, g = fn(params, tokens[i:i + block_rows],
                  labels[i:i + block_rows])
        loss = loss + l
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    k = n // block_rows
    return loss / k, jax.tree.map(lambda g: g / k, grad)


@partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1))
def _sgd(params, mom, grad, hyper, dtype):
    lr, mu = hyper
    mom = jax.tree.map(lambda v, g: mu * v + g, mom, grad)
    params = jax.tree.map(
        lambda p, v: (p.astype(jnp.float32) - lr * v).astype(dtype),
        params, mom)
    return params, mom


def train(params, batches, cfg: dict, *, lr: float, momentum: float,
          first_grad=lambda g: g, precision: str = "f32",
          block_rows: int = 8):
    """Momentum SGD over ``batches`` of ``(tokens, labels)``; consumes
    ``params``.

    Returns the loss of every step, ``first_grad`` of the first step's
    gradient, and the parameters after the last step (in their own
    dtype)."""
    dtype = jax.tree.leaves(params)[0].dtype
    mom = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    losses, first = [], None
    for tokens, labels in batches:
        loss, grad = loss_and_grad(params, tokens, labels, cfg,
                                   precision=precision,
                                   block_rows=block_rows)
        losses.append(float(loss))
        if first is None:
            first = first_grad(grad)
        params, mom = _sgd(params, mom, grad,
                           (jnp.float32(lr), jnp.float32(momentum)), dtype)
        del grad
    return losses, first, params
