"""The flash forward kernel's output and row statistics across the remat
boundary: where a row is long enough that recomputing them costs more than
holding them, a block's ``jax.checkpoint`` policy keeps them and the gradient
program holds one forward kernel call a layer, not two. The kernels are
counted in the jaxpr (nothing runs) or run under the Pallas interpreter at
tiny widths; what the chip's compiler makes of the step is in
the families' files (``FamilySuite.test_the_cells_step_compiles_for_v5e``). The gated delta rule's forward sweep and its
two outputs (``ops/gated_delta.KEPT_ACROSS_REMAT``) are held the same way at
the end of the file.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_fine_tune_distributed_tpu.models import transformer
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import (
    _remat_policy,
    forward,
    init_params,
    keeps_flash_outputs,
    keeps_scan_output,
)
from llm_fine_tune_distributed_tpu.ops import flash_attention as fa
from llm_fine_tune_distributed_tpu.ops import gated_delta, rope

LAYERS, SEQ = 2, 128

# one head of 128 (the kernel's lanes) over a hidden size on either side of
# the rule at 128 tokens: 128 > 64 keeps, 128 < 256 recomputes
DENSE = dataclasses.replace(
    get_preset("tiny"), num_layers=LAYERS, num_heads=1, num_kv_heads=1, head_dim=128,
    no_rope_layers=None,
)
# latent attention at the published head widths (128 + 64 against 128), dense MLPs only
LATENT = dataclasses.replace(
    get_preset("tiny_mla_moe"), num_layers=LAYERS, num_heads=1, num_kv_heads=1,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    n_routed_experts=0, n_shared_experts=0, held_experts=None, first_k_dense_replace=0,
)


@pytest.fixture
def interpreted_kernel(monkeypatch):
    """``attention_impl="flash"`` takes the kernel on a TPU only, compiled:
    say the backend is one and hand the dispatch the interpreted kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        fa, "pallas_flash_attention", partial(fa.pallas_flash_attention, interpret=True)
    )
    # a layer of heads hands the kernel its operands through the fused IN pass there (ops/rope.heads_in, PR 41)
    monkeypatch.setattr(rope, "heads_in", partial(rope.heads_in, interpret=True))


def _kernel_calls(jaxpr, name: str) -> int:
    """``pallas_call`` equations named ``name`` in ``jaxpr`` and every jaxpr
    inside it (jit, checkpoint, custom_vjp bodies)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == name:
            n += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _kernel_calls(sub, name)
    return n


def _loss(params, config, remat_policy):
    ids = jnp.asarray(np.random.RandomState(0).randint(0, config.vocab_size, (1, SEQ)), jnp.int32)
    logits, _ = forward(
        params, ids, config, attention_impl="flash", compute_dtype=jnp.float32,
        remat=True, remat_policy=remat_policy,
    )
    return jnp.sum(jnp.sin(logits))


def _gradient_program(config, remat_policy="full"):
    params = jax.eval_shape(partial(init_params, config=config), jax.random.PRNGKey(0))
    return jax.make_jaxpr(jax.grad(partial(_loss, config=config, remat_policy=remat_policy)))(params).jaxpr


@pytest.mark.parametrize("hidden_size, forward_calls_a_layer", [(64, 1), (256, 2)])
def test_forward_kernel_runs_once_a_layer_where_the_rule_engages(
    interpreted_kernel, hidden_size, forward_calls_a_layer
):
    config = dataclasses.replace(DENSE, hidden_size=hidden_size)
    assert keeps_flash_outputs(config, SEQ) == (forward_calls_a_layer == 1)
    program = _gradient_program(config)
    assert _kernel_calls(program, "flash_attention_fwd") == LAYERS * forward_calls_a_layer
    # q, k and v are rebuilt either way and each backward kernel runs once
    assert _kernel_calls(program, "flash_attention_dq") == LAYERS
    assert _kernel_calls(program, "flash_attention_dkv") == LAYERS


@pytest.mark.parametrize("remat_policy", ["full", "mlp", "dots", "dots_no_batch"])
def test_every_remat_policy_keeps_the_kernels_outputs_on_long_rows(interpreted_kernel, remat_policy):
    """The rule is joined to whatever the policy saves besides: under each
    the second forward call is gone, and under none does the policy's name
    decide it."""
    program = _gradient_program(DENSE, remat_policy)
    assert _kernel_calls(program, "flash_attention_fwd") == LAYERS
    short = _gradient_program(dataclasses.replace(DENSE, hidden_size=256), remat_policy)
    assert _kernel_calls(short, "flash_attention_fwd") == 2 * LAYERS


def test_forward_kernel_runs_once_a_layer_under_a_shard_map(interpreted_kernel, eight_devices):
    """On a mesh the kernel runs per shard under a ``shard_map`` over batch
    and heads (ops/attention.py): the names inside it reach the block's
    policy all the same."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llm_fine_tune_distributed_tpu.config import MeshConfig
    from llm_fine_tune_distributed_tpu.runtime.mesh import make_mesh

    config = dataclasses.replace(DENSE, num_heads=2, num_kv_heads=2)
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, tensor=2, seq=1), eight_devices[:4])
    params = jax.eval_shape(partial(init_params, config=config), jax.random.PRNGKey(0))

    def loss(params):
        logits, _ = forward(
            params, jnp.zeros((2, SEQ), jnp.int32), config, attention_impl="flash",
            compute_dtype=jnp.float32, remat=True, remat_policy="full",
            activation_sharding=NamedSharding(mesh, P(("data", "fsdp"), None, None)),
        )
        return jnp.sum(jnp.sin(logits))

    with mesh:
        program = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    assert "shard_map" in str(program)
    assert _kernel_calls(program, "flash_attention_fwd") == LAYERS
    assert _kernel_calls(program, "flash_attention_dq") == LAYERS


@pytest.mark.parametrize("config", [DENSE, LATENT], ids=["dense-128", "latent-192-128"])
def test_gradients_with_the_outputs_kept_equal_those_recomputed(interpreted_kernel, monkeypatch, config):
    """Same kernel, same inputs, one run instead of two: the backward kernels
    see the numbers the forward made, bit for bit."""
    assert keeps_flash_outputs(config, SEQ)
    params = init_params(jax.random.PRNGKey(1), config)
    grad = lambda: jax.jit(jax.grad(partial(_loss, config=config, remat_policy="full")))(params)  # noqa: E731
    kept = grad()
    monkeypatch.setattr(fa, "worth_keeping_across_remat", lambda *shapes, **window: False)
    recomputed = grad()
    leaves = jax.tree.leaves(kept)
    assert leaves and all(float(jnp.abs(leaf).max()) > 0 for leaf in leaves)
    for a, b in zip(leaves, jax.tree.leaves(recomputed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "preset, seq, keeps",
    [
        ("smollm3_3b", 1024, False),  # 1024 against 2048: the compiler refuses the step with them kept
        ("mistral_7b", 2048, False),  # 2048 against 4096
        ("moonlight_16b_a3b", 4096, True),  # 4096 x 320 / 256 = 5120 against 2048
        ("smollm3_3b", 4096, True),  # the same model on rows four times as long
        ("moonlight_16b_a3b", 1024, False),
    ],
)
def test_rule_at_the_cells_shapes(preset, seq, keeps):
    assert keeps_flash_outputs(get_preset(preset), seq) is keeps


def test_rule_is_the_cost_of_a_kept_byte():
    """A causal forward costs ``seq * (d_qk + d_v) / (2 * d_v)`` FLOPs a byte
    of ``o``, a projection ``hidden_size``: strictly more keeps."""
    assert not fa.worth_keeping_across_remat(2048, 128, 128, 2048)
    assert fa.worth_keeping_across_remat(2048 + 128, 128, 128, 2048)
    assert fa.worth_keeping_across_remat(2048, 192, 128, 2048)  # wider q/k heads: more work a byte


# -- the gated delta rule's forward sweep ------------------------------------------


@pytest.mark.parametrize("preset, attention, sweep", [
    ("qwen3_next_80b_a3b", "linear", "gdn_rule_fwd"), ("kimi_linear_48b_a3b", "kda", "kda_rule_fwd"),
], ids=["a-decay-a-head", "a-decay-a-channel"])
def test_the_delta_rules_forward_sweep_runs_once_where_the_block_keeps_all_its_outputs(monkeypatch, preset, attention, sweep):
    """The rule as its two Pallas sweeps (under the interpreter, one key head of 128 serving two value heads, a row of
    100 tokens padded to a step) inside ``jax.checkpoint`` with the policy a block of that model gets. Where the
    kernels run (a TPU at these heads) the policy saves the sweep's output AND everything its backward sweep reads of
    it (the states; with a decay a channel each chunk's ``T``, decayed ``k k^T`` and ``P`` too, PR 48): one forward sweep
    in the gradient's program. With one of a sweep's names missing nothing is removed (that one still needs the whole
    sweep), as under the policy of a backend that runs the XLA form, which keeps nothing of the rule at these widths:
    two. Same kernels on the same operands either way: every cotangent equal bit for bit."""
    config, policies = get_preset(preset), jax.checkpoint_policies
    by_channel = attention == "kda"
    names = ("gdn_o", "gdn_states") + ("kda_t_kk", "kda_p") * by_channel
    assert keeps_scan_output(config) == ()  # this CPU: the XLA form's count, 224 against 2048 and 256 against 2304
    monkeypatch.setattr(transformer, "REMAT_KEEPS", {})
    recomputes = _remat_policy("full", config, 8192, None, attention)
    assert f"{attention} (full): moe_*" in transformer.remat_summary()
    with monkeypatch.context() as on_a_tpu:
        on_a_tpu.setattr(jax, "default_backend", lambda: "tpu")
        assert keeps_scan_output(config) == (gated_delta.KEPT_BY_CHANNEL if by_channel else gated_delta.KEPT_ACROSS_REMAT) == names
        keeps = _remat_policy("full", config, 8192, None, attention)
        _remat_policy("mlp", config, 8192, None, "latent" if by_channel else "heads")  # the model's one softmax layer
    kinds = sorted([f"{'latent' if by_channel else 'heads'} (mlp): flash_o, flash_lse, moe_*", f"{attention} (full): {', '.join(names)}, moe_*"])
    # the line a run prints: whether the mechanism engaged
    assert transformer.remat_summary() == f"a rematerialized block keeps, besides what its policy does: {'; '.join(kinds)}"
    keys = jax.random.split(jax.random.PRNGKey(44), 5)
    q, k = (gated_delta.l2_norm(jax.random.normal(key, (1, 100, 1, 128))) for key in keys[:2])
    v = jax.random.normal(keys[2], (1, 100, 2, 128))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (1, 100, 2) + (128,) * by_channel))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, 100, 2)))

    def gradient(policy):
        rule = jax.checkpoint(lambda *a: gated_delta.gated_delta_rule(*a, impl="kernels_interpret"), policy=policy)
        return jax.grad(lambda *a: jnp.sum(jnp.sin(rule(*a))), argnums=(0, 1, 2, 3, 4))

    calls = {name: _kernel_calls(jax.make_jaxpr(gradient(policy))(q, k, v, g, beta).jaxpr, sweep) for name, policy in (
        ("all kept", keeps), ("o alone", policies.save_only_these_names("gdn_o")), ("none", recomputes),
        *((f"all but {name}", policies.save_only_these_names(*(n for n in names if n != name))) for name in names[1:]))}
    assert calls == {"all kept": 1, "o alone": 2, "none": 2, **{f"all but {name}": 2 for name in names[1:]}}
    kept, recomputed = (jax.jit(gradient(policy))(q, k, v, g, beta) for policy in (keeps, recomputes))
    for name, a, b in zip("q k v g beta".split(), kept, recomputed):
        assert float(jnp.abs(a).max()) > 0, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
