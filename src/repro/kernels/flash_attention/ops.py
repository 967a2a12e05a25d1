"""Causal flash attention on the model's layout: the transposes into the
kernel's row-stacked layout and back."""
from __future__ import annotations

import jax

from repro.kernels.flash_attention.flash_attention import causal_flash


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal self-attention: q ``(B, S, KV, G, D)``, k and v ``(B, S, KV,
    D)``; returns ``(B, S, KV, G, D)`` in q's dtype."""
    B, S, KV, G, D = q.shape
    qt = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, S * G, D)
    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    o = causal_flash(qt, kt, vt)
    return o.reshape(B, KV, S, G, D).transpose(0, 2, 1, 3, 4)
