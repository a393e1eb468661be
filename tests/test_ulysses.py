"""Ulysses (all-to-all sequence parallelism) must match plain XLA attention —
forward and gradients — since it is ordinary attention computed on a
head-sharded re-partition (SURVEY.md §5.7: the long-context capability the
reference lacks entirely; companion strategy to tests/test_ring_attention.py)."""

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llm_fine_tune_distributed_tpu.ops.attention import attention, xla_attention
from llm_fine_tune_distributed_tpu.parallel.ulysses import (
    ulysses_attention,
    ulysses_attention_supported,
)


def _mesh(devs, data=1, fsdp=1, tensor=1, seq=4):
    shape = (data, fsdp, tensor, seq)
    n = data * fsdp * tensor * seq
    return Mesh(
        np.array(devs[:n]).reshape(shape), ("data", "fsdp", "tensor", "seq")
    )


def _qkv(b=2, s=64, h=8, kv=4, d=16, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kv, d), jnp.float32)
    return q, k, v


def test_ulysses_matches_xla_causal(eight_devices):
    mesh = _mesh(eight_devices, data=2, seq=4)
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=True)
    out = jax.jit(lambda a, b_, c: ulysses_attention(a, b_, c, mesh=mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_matches_xla_with_segments(eight_devices):
    """Packed rows through Ulysses: segment ids all-gather over the seq axis
    and the inner full-sequence kernel masks natively (packing x sequence
    parallelism)."""
    from tests.test_ring_attention import _segments

    mesh = _mesh(eight_devices, data=2, seq=4)
    q, k, v = _qkv(b=2, s=32)
    seg = _segments(2, 32, pad_tail=4)
    ref = xla_attention(q, k, v, segment_ids=seg, causal=True)
    out = jax.jit(
        lambda a, b_, c, s_: ulysses_attention(a, b_, c, mesh=mesh, segment_ids=s_)
    )(q, k, v, seg)
    real = np.asarray(seg) > 0
    np.testing.assert_allclose(
        np.asarray(out)[real], np.asarray(ref)[real], atol=2e-5
    )


def test_ulysses_segment_gradients_match(eight_devices):
    from tests.test_ring_attention import _segments

    mesh = _mesh(eight_devices, seq=4)
    q, k, v = _qkv(b=2, s=32)
    seg = _segments(2, 32, pad_tail=4)
    w = (np.asarray(seg) > 0).astype(np.float32)[..., None, None]

    def loss_uly(q, k, v):
        return ((ulysses_attention(q, k, v, mesh=mesh, segment_ids=seg) * w) ** 2).sum()

    def loss_ref(q, k, v):
        return ((xla_attention(q, k, v, segment_ids=seg, causal=True) * w) ** 2).sum()

    g_uly = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_uly, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_ulysses_matches_xla_with_padding(eight_devices):
    mesh = _mesh(eight_devices, data=2, seq=4)
    q, k, v = _qkv(b=2, s=32)
    pad = jnp.concatenate(
        [jnp.ones((2, 24), jnp.int32), jnp.zeros((2, 8), jnp.int32)], axis=1
    )
    ref = xla_attention(q, k, v, padding_mask=pad, causal=True)
    out = jax.jit(
        lambda a, b_, c, p: ulysses_attention(a, b_, c, mesh=mesh, padding_mask=p)
    )(q, k, v, pad)
    # pad-query rows are garbage in both impls; compare real tokens only
    real = np.asarray(pad, bool)
    np.testing.assert_allclose(
        np.asarray(out)[real], np.asarray(ref)[real], atol=2e-5
    )


def test_ulysses_with_tensor_axis(eight_devices):
    """Heads sharded over tensor simultaneously with the seq re-partition."""
    mesh = _mesh(eight_devices, tensor=2, seq=2, data=2)
    q, k, v = _qkv(b=2, s=32, h=8, kv=4)
    ref = xla_attention(q, k, v, causal=True)
    out = jax.jit(lambda a, b_, c: ulysses_attention(a, b_, c, mesh=mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_gradients_match(eight_devices):
    mesh = _mesh(eight_devices, data=2, seq=4)
    q, k, v = _qkv(s=32)

    def loss_uly(q, k, v):
        return (ulysses_attention(q, k, v, mesh=mesh) ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_attention(q, k, v, causal=True) ** 2).sum()

    g_uly = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_uly, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_dispatch_falls_back_without_mesh():
    q, k, v = _qkv(b=1, s=16)
    out = attention(q, k, v, impl="ulysses", mesh=None)  # no mesh -> xla path
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_supported_predicate(eight_devices):
    mesh = _mesh(eight_devices, data=2, seq=4)
    q, k, _ = _qkv(s=64)
    assert ulysses_attention_supported(q, k, mesh)
    assert not ulysses_attention_supported(q, k, None)
    assert not ulysses_attention_supported(q, k, mesh, sliding_window=8)
    q61 = jnp.zeros((2, 61, 8, 16))  # 61 not divisible by 4
    assert not ulysses_attention_supported(q61, k, mesh)
    # parallelism degree capped by kv heads: kv=2 local heads not divisible by 4
    k2 = jnp.zeros((2, 64, 2, 16))
    assert not ulysses_attention_supported(q, k2, mesh)


def test_model_forward_with_ulysses(eight_devices):
    """Full transformer forward, seq-sharded activations, ulysses attention ==
    unsharded xla forward. tiny has 4 heads / 2 kv heads -> seq degree 2."""
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import forward, init_params

    config = get_preset("tiny")
    mesh = _mesh(eight_devices, data=2, fsdp=2, seq=2)
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, config.vocab_size, (4, 64)), jnp.int32
    )

    ref, _ = forward(params, ids, config, attention_impl="xla", compute_dtype=jnp.float32)
    act = NamedSharding(mesh, P(("data", "fsdp"), "seq", None))
    from llm_fine_tune_distributed_tpu.parallel.diagnostics import assert_seq_parallel

    with assert_seq_parallel("ulysses"):
        out, _ = jax.jit(
            lambda p, i: forward(
                p,
                i,
                config,
                attention_impl="ulysses",
                compute_dtype=jnp.float32,
                activation_sharding=act,
            )
        )(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-4)


@pytest.mark.slow
def test_train_step_with_ulysses_matches_xla(eight_devices):
    """One full train step (grad-accum scan, freezing, AdamW) with
    seq-sharded activations + ulysses attention must produce the same loss
    and grad_norm as the unsharded XLA-attention step."""
    from llm_fine_tune_distributed_tpu.config import TrainConfig
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
    from llm_fine_tune_distributed_tpu.parallel.optimizer import build_optimizer
    from llm_fine_tune_distributed_tpu.train.state import TrainState
    from llm_fine_tune_distributed_tpu.train.step import build_train_step
    from llm_fine_tune_distributed_tpu.utils.tree import split_by_mask

    model_config = get_preset("tiny")

    def run(attention_impl, mesh, act_spec):
        train_config = TrainConfig(
            model_preset="tiny",
            per_device_batch_size=1,
            gradient_accumulation_steps=2,
            max_seq_length=64,
            gradient_checkpointing=True,
            attention_impl=attention_impl,
        )
        params = init_params(jax.random.PRNGKey(0), model_config, dtype=jnp.float32)
        mask = trainable_mask(params, model_config, train_config)
        trainable, frozen = split_by_mask(params, mask)
        optimizer = build_optimizer(train_config, None, total_steps=4, data_parallel_size=1)
        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            trainable=trainable,
            frozen=frozen,
            opt_state=optimizer.init(trainable),
        )
        act = NamedSharding(mesh, act_spec) if mesh is not None else None
        step = jax.jit(build_train_step(model_config, train_config, optimizer, activation_sharding=act))
        rng = np.random.RandomState(1)
        batch = {
            "input_ids": jnp.asarray(rng.randint(0, model_config.vocab_size, (2, 4, 64)), jnp.int32),
            "loss_mask": jnp.ones((2, 4, 64), jnp.float32),
            "attention_mask": jnp.ones((2, 4, 64), jnp.int32),
        }
        _, metrics = step(state, batch)
        return float(metrics["loss"]), float(metrics["grad_norm"])

    from llm_fine_tune_distributed_tpu.parallel.diagnostics import assert_seq_parallel

    mesh = _mesh(eight_devices, data=2, fsdp=2, seq=2)
    loss_ref, gn_ref = run("xla", None, None)
    with assert_seq_parallel("ulysses"):
        loss_uly, gn_uly = run("ulysses", mesh, P(("data", "fsdp"), "seq", None))
    np.testing.assert_allclose(loss_uly, loss_ref, rtol=1e-4)
    np.testing.assert_allclose(gn_uly, gn_ref, rtol=1e-3)
