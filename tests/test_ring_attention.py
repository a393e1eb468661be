"""Ring attention (sequence parallelism) must match plain XLA attention —
forward and gradients — since it is the same math rearranged around a
ppermute ring (SURVEY.md §5.7: the long-context capability the reference
lacks entirely)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llm_fine_tune_distributed_tpu.ops.attention import attention, xla_attention
from llm_fine_tune_distributed_tpu.parallel.ring_attention import (
    ring_attention,
    ring_attention_supported,
)


def _mesh(devs, data=1, fsdp=1, tensor=1, seq=8):
    shape = (data, fsdp, tensor, seq)
    n = data * fsdp * tensor * seq
    return Mesh(
        np.array(devs[:n]).reshape(shape), ("data", "fsdp", "tensor", "seq")
    )


def _qkv(b=2, s=64, h=4, kv=2, d=16, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kv, d), jnp.float32)
    return q, k, v


def test_ring_matches_xla_causal(eight_devices):
    mesh = _mesh(eight_devices, seq=8)
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=True)
    out = jax.jit(lambda a, b_, c: ring_attention(a, b_, c, mesh=mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_matches_xla_with_padding(eight_devices):
    mesh = _mesh(eight_devices, seq=4, data=2)
    q, k, v = _qkv(b=2, s=32)
    pad = jnp.concatenate(
        [jnp.ones((2, 24), jnp.int32), jnp.zeros((2, 8), jnp.int32)], axis=1
    )
    ref = xla_attention(q, k, v, padding_mask=pad, causal=True)
    out = jax.jit(
        lambda a, b_, c, p: ring_attention(a, b_, c, mesh=mesh, padding_mask=p)
    )(q, k, v, pad)
    # pad-query rows are garbage in both impls; compare real tokens only
    real = np.asarray(pad, bool)
    np.testing.assert_allclose(
        np.asarray(out)[real], np.asarray(ref)[real], atol=2e-5
    )


def test_ring_with_tensor_axis(eight_devices):
    """Heads sharded over tensor simultaneously with seq over the ring."""
    mesh = _mesh(eight_devices, tensor=2, seq=4)
    q, k, v = _qkv(b=2, s=32, h=4, kv=2)
    ref = xla_attention(q, k, v, causal=True)
    out = jax.jit(lambda a, b_, c: ring_attention(a, b_, c, mesh=mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_gradients_match(eight_devices):
    mesh = _mesh(eight_devices, seq=8)
    q, k, v = _qkv(s=32)

    def loss_ring(q, k, v):
        return (ring_attention(q, k, v, mesh=mesh) ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_attention(q, k, v, causal=True) ** 2).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def _segments(b, s, seed=0, n_seg=3, pad_tail=8):
    """Contiguous per-row segment ids like data/packing.py produces:
    1..n_seg blocks then a 0 pad tail."""
    rng = np.random.RandomState(seed)
    out = np.zeros((b, s), np.int32)
    for r in range(b):
        cuts = np.sort(rng.choice(np.arange(1, s - pad_tail), n_seg - 1, replace=False))
        bounds = [0, *cuts.tolist(), s - pad_tail]
        for i in range(n_seg):
            out[r, bounds[i] : bounds[i + 1]] = i + 1
    return jnp.asarray(out)


def test_ring_matches_xla_with_segments(eight_devices):
    """Packed rows (block-diagonal causal via segment ids) through the ring:
    the rotated key-side id chunk must reproduce xla_attention's segment
    masking exactly (packing x sequence parallelism)."""
    mesh = _mesh(eight_devices, seq=8)
    q, k, v = _qkv(s=64)
    seg = _segments(2, 64)
    ref = xla_attention(q, k, v, segment_ids=seg, causal=True)
    out = jax.jit(
        lambda a, b_, c, s_: ring_attention(a, b_, c, mesh=mesh, segment_ids=s_)
    )(q, k, v, seg)
    real = np.asarray(seg) > 0  # pad-tail rows are garbage in both impls
    np.testing.assert_allclose(
        np.asarray(out)[real], np.asarray(ref)[real], atol=2e-5
    )


def test_ring_segments_via_dispatch(eight_devices):
    """attention(impl='ring', segment_ids=...) keeps the seq axis (no
    fallback) and matches the xla reference."""
    mesh = _mesh(eight_devices, seq=4, data=2)
    q, k, v = _qkv(b=2, s=32)
    seg = _segments(2, 32, pad_tail=4)
    ref = xla_attention(q, k, v, segment_ids=seg, causal=True)
    from llm_fine_tune_distributed_tpu.parallel.diagnostics import assert_seq_parallel

    with assert_seq_parallel("ring"):
        out = jax.jit(
            lambda a, b_, c, s_: attention(
                a, b_, c, impl="ring", mesh=mesh, segment_ids=s_
            )
        )(q, k, v, seg)
    real = np.asarray(seg) > 0
    np.testing.assert_allclose(
        np.asarray(out)[real], np.asarray(ref)[real], atol=2e-5
    )


def test_ring_segment_gradients_match(eight_devices):
    mesh = _mesh(eight_devices, seq=8)
    q, k, v = _qkv(s=32)
    seg = _segments(2, 32, pad_tail=4)
    w = (np.asarray(seg) > 0).astype(np.float32)[..., None, None]

    def loss_ring(q, k, v):
        return ((ring_attention(q, k, v, mesh=mesh, segment_ids=seg) * w) ** 2).sum()

    def loss_ref(q, k, v):
        return ((xla_attention(q, k, v, segment_ids=seg, causal=True) * w) ** 2).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_dispatch_falls_back_without_mesh():
    q, k, v = _qkv(b=1, s=16)
    out = attention(q, k, v, impl="ring", mesh=None)  # no mesh -> xla path
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_supported_predicate(eight_devices):
    mesh = _mesh(eight_devices, seq=8)
    q, k, _ = _qkv(s=64)
    assert ring_attention_supported(q, k, mesh)
    assert not ring_attention_supported(q, k, None)
    assert not ring_attention_supported(q, k, mesh, sliding_window=8)
    q61 = jnp.zeros((2, 61, 4, 16))  # 61 not divisible by 8
    assert not ring_attention_supported(q61, k, mesh)


def test_model_forward_with_ring(eight_devices):
    """Full transformer forward, seq-sharded activations, ring attention ==
    unsharded xla forward."""
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import forward, init_params

    config = get_preset("tiny")
    mesh = _mesh(eight_devices, data=2, seq=4)
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, config.vocab_size, (2, 64)), jnp.int32
    )

    ref, _ = forward(params, ids, config, attention_impl="xla", compute_dtype=jnp.float32)
    act = NamedSharding(mesh, P(("data", "fsdp"), "seq", None))
    from llm_fine_tune_distributed_tpu.parallel.diagnostics import assert_seq_parallel

    with assert_seq_parallel("ring"):
        out, _ = jax.jit(
            lambda p, i: forward(
                p,
                i,
                config,
                attention_impl="ring",
                compute_dtype=jnp.float32,
                activation_sharding=act,
            )
        )(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-4)
