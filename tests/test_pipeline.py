"""Pipeline parallelism (GPipe over a ``pipe`` mesh axis): exact parity with
the plain forward, through forward AND backward (jax.grad through the
scan+ppermute schedule)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import forward, init_params
from llm_fine_tune_distributed_tpu.parallel.pipeline import (
    pipeline_forward,
    pipeline_loss_fn,
    stack_stage_params,
    stage_sharding,
)

B, SEQ = 8, 64


@pytest.fixture(scope="module")
def setup(eight_devices):
    config = get_preset("tiny").replace(no_rope_layers=(), num_layers=4)
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, config.vocab_size, (B, SEQ)), jnp.int32
    )
    return config, params, ids


def _mesh(n_stages):
    return Mesh(np.array(jax.devices()[:n_stages]), ("pipe",))


@pytest.mark.parametrize("n_stages,n_micro", [(2, 2), (4, 2), (2, 4), (4, 8)])
def test_pipeline_forward_matches_plain(setup, n_stages, n_micro):
    config, params, ids = setup
    mesh = _mesh(n_stages)
    stacked = jax.device_put(
        stack_stage_params(params, config, n_stages), stage_sharding(mesh)
    )
    logits_pipe, report = pipeline_forward(
        params, stacked, ids, config, mesh, n_micro,
        compute_dtype=jnp.float32, remat_blocks=False,
    )
    assert report == {}  # a dense model: its layers count nothing
    logits_plain, _ = forward(
        params, ids, config, compute_dtype=jnp.float32, logits_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(logits_pipe), np.asarray(logits_plain), atol=2e-4, rtol=2e-4
    )


@pytest.mark.slow
def test_pipeline_grads_match_plain(setup):
    """Gradients through the pipelined schedule == plain-model gradients,
    for both the replicated params and the stacked (stage-sharded) layers."""
    import optax

    config, params, ids = setup
    mesh = _mesh(4)
    stacked = jax.device_put(
        stack_stage_params(params, config, 4), stage_sharding(mesh)
    )
    mask = jnp.ones((B, SEQ), jnp.float32)
    batch = {"input_ids": ids, "loss_mask": mask}

    def loss_pipe(params, stacked):
        return pipeline_loss_fn(
            params, stacked, batch, config, mesh, 4, compute_dtype=jnp.float32
        )

    def loss_plain(params):
        logits, _ = forward(
            params, ids, config, compute_dtype=jnp.float32, logits_dtype=jnp.float32
        )
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], ids[:, 1:]
        )
        return (ce * mask[:, 1:]).sum() / mask[:, 1:].sum()

    (lp, (g_params, g_stacked)) = jax.value_and_grad(loss_pipe, argnums=(0, 1))(
        params, stacked
    )
    lr, g_plain = jax.value_and_grad(loss_plain)(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)

    # embedding grads (replicated side)
    np.testing.assert_allclose(
        np.asarray(g_params["model"]["embed_tokens"]["weight"]),
        np.asarray(g_plain["model"]["embed_tokens"]["weight"]),
        atol=2e-5, rtol=2e-4,
    )
    # per-layer grads: stacked [L, ...] rows must equal the plain per-layer grads
    for i in range(4):
        np.testing.assert_allclose(
            np.asarray(g_stacked["self_attn"]["q_proj"]["kernel"][i]),
            np.asarray(g_plain["model"]["layers"][str(i)]["self_attn"]["q_proj"]["kernel"]),
            atol=2e-5, rtol=2e-4, err_msg=f"layer {i} q_proj grad",
        )
        np.testing.assert_allclose(
            np.asarray(g_stacked["mlp"]["down_proj"]["kernel"][i]),
            np.asarray(g_plain["model"]["layers"][str(i)]["mlp"]["down_proj"]["kernel"]),
            atol=2e-5, rtol=2e-4, err_msg=f"layer {i} down_proj grad",
        )


def test_pipeline_nope_interleaved_matches_plain(setup):
    """SmolLM3-style NoPE interleaving: per-layer RoPE flags ride the layer
    scan as data, so the pipelined model matches the plain one exactly."""
    config, params, ids = setup
    nope = config.replace(no_rope_layers=(1, 0, 1, 0))
    mesh = _mesh(2)
    stacked = jax.device_put(
        stack_stage_params(params, nope, 2), stage_sharding(mesh)
    )
    logits_pipe, _ = pipeline_forward(
        params, stacked, ids, nope, mesh, 2,
        compute_dtype=jnp.float32, remat_blocks=False,
    )
    logits_plain, _ = forward(
        params, ids, nope, compute_dtype=jnp.float32, logits_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(logits_pipe), np.asarray(logits_plain), atol=2e-4, rtol=2e-4
    )


def test_stack_stage_params_layout(setup):
    config, params, _ = setup
    stacked = stack_stage_params(params, config, 2)
    assert stacked["self_attn"]["q_proj"]["kernel"].shape[0] == config.num_layers
    np.testing.assert_array_equal(
        np.asarray(stacked["mlp"]["up_proj"]["kernel"][2]),
        np.asarray(params["model"]["layers"]["2"]["mlp"]["up_proj"]["kernel"]),
    )
    with pytest.raises(ValueError, match="divisible"):
        stack_stage_params(params, config, 3)


def test_pipeline_padded_batch_matches_plain(setup):
    """Right-padded batches: the padding mask must ride the schedule so real
    queries never attend pad keys (same semantics as the plain forward)."""
    config, params, ids = setup
    mesh = _mesh(2)
    stacked = jax.device_put(
        stack_stage_params(params, config, 2), stage_sharding(mesh)
    )
    lengths = np.array([64, 50, 33, 64, 12, 64, 40, 64])
    pm = jnp.asarray((np.arange(SEQ)[None, :] < lengths[:, None]).astype(np.float32))
    logits_pipe, _ = pipeline_forward(
        params, stacked, ids, config, mesh, 2,
        padding_mask=pm, compute_dtype=jnp.float32, remat_blocks=False,
    )
    logits_plain, _ = forward(
        params, ids, config, padding_mask=pm,
        compute_dtype=jnp.float32, logits_dtype=jnp.float32,
    )
    real = np.asarray(pm) > 0
    np.testing.assert_allclose(
        np.asarray(logits_pipe)[real], np.asarray(logits_plain)[real],
        atol=2e-4, rtol=2e-4,
    )


@pytest.mark.slow
def test_pipeline_chunked_loss_matches_full(setup):
    """loss_chunk_size path (large-vocab HBM saver) == full-unembed path."""
    config, params, ids = setup
    mesh = _mesh(2)
    stacked = jax.device_put(
        stack_stage_params(params, config, 2), stage_sharding(mesh)
    )
    batch = {"input_ids": ids, "loss_mask": jnp.ones((B, SEQ), jnp.float32)}
    full = pipeline_loss_fn(params, stacked, batch, config, mesh, 2,
                            compute_dtype=jnp.float32)
    chunked = pipeline_loss_fn(params, stacked, batch, config, mesh, 2,
                               compute_dtype=jnp.float32, loss_chunk_size=16)
    assert float(full) == pytest.approx(float(chunked), rel=1e-5)
