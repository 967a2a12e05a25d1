"""The causal flash-attention kernel (interpret mode, small sizes) against
the O(S^2) oracle, and which attention calls take it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.common as kc
from repro.kernels.flash_attention import flash_attention as fa
from repro.kernels.flash_attention.flash_attention import pick_blocks
from repro.kernels.flash_attention.ops import causal_attention
from repro.models import attention as A

# (B, S, KV, G, D, blocks): qwen2-0.5b's group of 7 heads of 64 and
# qwen2.5-3b's 8 of 128, at blocks that make the diagonal cross whole
# blocks, and a q block shorter than the KV block (and the reverse)
SHAPES = [
    (2, 256, 2, 7, 64, (128, 128)),
    (1, 256, 2, 8, 128, (128, 128)),
    (1, 512, 1, 7, 64, (128, 256)),
    (1, 512, 1, 2, 64, (256, 128)),
]
CAUSAL = A.AttnSpec(num_heads=1, num_kv_heads=1, head_dim=1)


def _inputs(B, S, KV, G, D, dtype):
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, S, KV, G, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    do = jax.random.normal(ks[3], (B, S, KV, G, D), dtype)
    return q, k, v, do


def _oracle(q, k, v):
    return A.reference_attend(q, k, v, CAUSAL)


def _rel(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("B, S, KV, G, D, blocks", SHAPES)
def test_kernel_and_its_gradients_match_the_oracle(B, S, KV, G, D, blocks,
                                                   monkeypatch):
    """bf16 inputs: the output and dQ, dK, dV against the f32 oracle on
    the same bf16 values, within bf16 rounding of the results."""
    monkeypatch.setattr(fa, "pick_blocks", lambda *a: blocks)
    q, k, v, do = _inputs(B, S, KV, G, D, jnp.bfloat16)
    o, vjp = jax.vjp(causal_attention, q, k, v)
    grads = vjp(do)
    f32 = lambda t: t.astype(jnp.float32)
    o_ref, vjp_ref = jax.vjp(lambda *a: _oracle(*map(f32, a)), q, k, v)
    grads_ref = vjp_ref(f32(do))
    assert o.dtype == jnp.bfloat16 and o.shape == q.shape
    assert _rel(o, o_ref) < 1e-2
    for name, got, want in zip("qkv", grads, grads_ref):
        assert got.dtype == jnp.bfloat16, name
        assert _rel(got, want) < 1e-2, name


def test_kernel_is_exact_to_f32_rounding_in_f32(monkeypatch):
    monkeypatch.setattr(fa, "pick_blocks", lambda *a: (128, 128))
    q, k, v, do = _inputs(2, 256, 2, 7, 64, jnp.float32)
    o, vjp = jax.vjp(causal_attention, q, k, v)
    o_ref, vjp_ref = jax.vjp(_oracle, q, k, v)
    np.testing.assert_allclose(o, o_ref, rtol=1e-5, atol=1e-5)
    for got, want in zip(vjp(do), vjp_ref(do)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("spec_kw", [{}, {"qkv_bias": True, "qk_norm": True}])
def test_attention_through_the_kernel_matches_reference_attention(
        spec_kw, monkeypatch):
    """multi_head_attention with the kernel taken (interpreted here)
    against reference_attention: projections, RoPE, GQA and the output
    projection around it, and the gradient of the parameters."""
    monkeypatch.setattr(A, "takes_flash_kernel", lambda *a, **k: True)
    spec = A.AttnSpec(num_heads=14, num_kv_heads=2, head_dim=64, **spec_kw)
    params = A.init_attention(jax.random.key(1), 128, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(2), (1, 256, 128)) * 0.5
    loss = lambda f: lambda p: jnp.sum(f(p, x, spec) ** 2)
    got, g_got = jax.value_and_grad(loss(A.multi_head_attention))(params)
    want, g_want = jax.value_and_grad(loss(A.reference_attention))(params)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for name in params:
        np.testing.assert_allclose(g_got[name], g_want[name], rtol=2e-4,
                                   atol=2e-4, err_msg=name)


def test_blocks_follow_the_shape():
    # blocks of 256 where they divide the sequence, else 128; the q block
    # halves while it holds more than 4096 rows of scores
    assert pick_blocks(1024, 7) == (256, 256)      # qwen2-0.5b
    assert pick_blocks(1024, 8) == (256, 256)      # qwen2.5-3b
    assert pick_blocks(8192, 7) == (256, 256)
    assert pick_blocks(1024, 32) == (128, 256)
    assert pick_blocks(384, 7) == (128, 128)
    assert pick_blocks(200, 7) is None


@pytest.mark.parametrize("spec_kw, q_len, kv_len, cross, takes", [
    ({}, 1024, 1024, False, True),                  # qwen2 training
    ({"qkv_bias": True, "qk_norm": True}, 256, 256, False, True),
    ({"prefix_len": 16}, 1024, 1024, False, False),         # paligemma
    ({"sliding_window": 256}, 1024, 1024, False, False),    # mixtral
    ({"causal": False}, 1024, 1024, False, False),  # whisper encoder
    ({"causal": False}, 1024, 1024, True, False),   # cross attention
    ({}, 1024, 1024, True, False),                  # causal, but cross
    ({}, 200, 200, False, False),                   # no block divides it
    ({}, 1024, 512, False, False),                  # ragged lengths
])
def test_only_plain_causal_self_attention_on_a_tpu_takes_the_kernel(
        spec_kw, q_len, kv_len, cross, takes, monkeypatch):
    spec = A.AttnSpec(num_heads=14, num_kv_heads=2, head_dim=64, **spec_kw)
    # the CPU keeps the jnp path whatever the spec
    assert not A.takes_flash_kernel(spec, q_len, kv_len, cross=cross)
    monkeypatch.setattr(kc, "use_interpret", lambda: False)   # a TPU
    assert A.takes_flash_kernel(spec, q_len, kv_len, cross=cross) is takes


@pytest.mark.parametrize("axis_type, size, takes", [
    (jax.sharding.AxisType.Auto, 4, False),      # GSPMD partitions it
    (jax.sharding.AxisType.Explicit, 4, False),
    (jax.sharding.AxisType.Manual, 4, True),     # inside shard_map
    (jax.sharding.AxisType.Auto, 1, True),       # nothing to partition
])
def test_a_partitioned_mesh_keeps_the_jnp_path(axis_type, size, takes,
                                               monkeypatch):
    monkeypatch.setattr(kc, "use_interpret", lambda: False)   # a TPU
    spec = A.AttnSpec(num_heads=14, num_kv_heads=2, head_dim=64)
    mesh = jax.sharding.AbstractMesh((size,), ("data",),
                                     axis_types=(axis_type,))
    with jax.sharding.use_abstract_mesh(mesh):
        assert A.takes_flash_kernel(spec, 1024, 1024) is takes


def test_the_jnp_path_is_what_a_cpu_runs(monkeypatch):
    """On the CPU multi_head_attention never reaches the kernel."""
    called = []
    monkeypatch.setattr(A, "causal_attention",
                        lambda *a, **k: called.append(1))
    spec = A.AttnSpec(num_heads=4, num_kv_heads=2, head_dim=16)
    params = A.init_attention(jax.random.key(3), 32, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(4), (1, 256, 32))
    got = A.multi_head_attention(params, x, spec)
    np.testing.assert_allclose(got, A.reference_attention(params, x, spec),
                               rtol=2e-4, atol=2e-4)
    assert not called
