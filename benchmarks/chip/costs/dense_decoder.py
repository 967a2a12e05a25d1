"""Model operations of the dense decoder family, for ``step.mfu``.

Per trained token: 6 N for the forward and backward passes through the
N parameters (the tied embedding counts once, as the output head's
matmul; its lookup is no matmul), plus 12 L d_attn S for the attention
scores and their weighted sum over a sequence of S (forward 4 L d_attn
S, backward twice that), with d_attn = heads x head_dim. Recomputation
is not counted.
"""
from __future__ import annotations


def params(model: dict) -> int:
    """Parameters of the configuration file's ``model`` sizes."""
    d, f, v = model["hidden_size"], model["intermediate_size"], \
        model["vocab_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or d // h
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    if model["qkv_bias"]:
        attn += (h + 2 * kv) * hd
    layer = attn + 3 * d * f + 2 * d          # + two norm weights
    head = 0 if model["tie_word_embeddings"] else v * d
    return v * d + head + model["num_hidden_layers"] * layer + d


def flops_per_token(model: dict, seq_len: int) -> float:
    h = model["num_attention_heads"]
    d_attn = h * (model.get("head_dim") or model["hidden_size"] // h)
    return (6.0 * params(model)
            + 12.0 * model["num_hidden_layers"] * d_attn * seq_len)
