"""The routed experts' routing and gathered rows across the remat boundary:
a rematerialized block whose feed-forward is ``grouped_experts`` keeps what
``ops/moe.KEPT_ACROSS_REMAT`` names, under every ``remat_policy``, so that the
gradient program holds the router's product, the selection, the two sorts and
the first chunk's row gather once an expert layer and not twice. Counted in
the jaxpr (nothing runs) after the pattern of ``tests/test_flash_remat.py``;
what the chip's compiler makes of the step is in the families' files (``tests/family_suite.py``,
``test_the_cells_step_compiles_for_v5e``).
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
from llm_fine_tune_distributed_tpu.models.transformer import forward, init_params, keeps_routing
from llm_fine_tune_distributed_tpu.ops import moe

MC = get_preset("tiny_mla_moe")  # layer 0 dense, layers 1 and 2 routed: 3 of 16 a token, 4 held
EXPERT_LAYERS = MC.num_layers - MC.first_k_dense_replace
ROWS, SEQ = 2, 48
POLICIES = ["full", "mlp", "dots", "dots_no_batch"]


def _count(jaxpr, wanted) -> int:
    """Equations for which ``wanted(eqn)`` holds, in ``jaxpr`` and every jaxpr
    inside it (jit, checkpoint, cond and custom_vjp bodies)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(wanted(eqn))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count(sub, wanted)
    return n


def _primitive(name):
    return lambda eqn: eqn.primitive.name == name


def _router_product(eqn):
    """The forward product ``[T, h] x [h, E]``: its transposes are ``[T, h]`` and ``[h, E]``."""
    return eqn.primitive.name == "dot_general" and eqn.outvars[0].aval.shape == (ROWS * SEQ, MC.n_routed_experts)


def _loss(params, config, remat_policy):
    ids = jnp.asarray(np.random.RandomState(0).randint(0, config.vocab_size, (ROWS, SEQ)), jnp.int32)
    logits, _ = forward(params, ids, config, compute_dtype=jnp.float32, remat=True, remat_policy=remat_policy)
    return jnp.sum(jnp.sin(logits))


def _gradient_program(remat_policy):
    params = jax.eval_shape(partial(init_params, config=MC), jax.random.PRNGKey(0))
    return jax.make_jaxpr(jax.grad(partial(_loss, config=MC, remat_policy=remat_policy)))(params).jaxpr


@pytest.mark.parametrize("remat_policy", POLICIES)
def test_router_selection_and_sorts_run_once_an_expert_layer(monkeypatch, remat_policy):
    """Two sorts (``order``, ``rank``), one ``top_k`` and one router product a
    layer under each policy, whose name decides nothing; with nothing named
    kept the backward pass makes each of them a second time."""
    program = _gradient_program(remat_policy)
    assert _count(program, _primitive("sort")) == 2 * EXPERT_LAYERS
    assert _count(program, _primitive("top_k")) == EXPERT_LAYERS
    assert _count(program, _primitive("cumsum")) == EXPERT_LAYERS
    assert _count(program, _router_product) == EXPERT_LAYERS
    monkeypatch.setattr(moe, "KEPT_ACROSS_REMAT", ())
    recomputed = _gradient_program(remat_policy)
    assert _count(recomputed, _primitive("sort")) == 4 * EXPERT_LAYERS
    assert _count(recomputed, _primitive("top_k")) == 2 * EXPERT_LAYERS
    # (the policies that save every product's output had one router product already)
    assert _count(recomputed, _router_product) == (1 if "dots" in remat_policy else 2) * EXPERT_LAYERS
    # the grouped products are recomputed either way: their outputs are not kept
    assert _count(program, _primitive("ragged_dot_general")) == _count(recomputed, _primitive("ragged_dot_general"))


def test_a_block_keeps_the_eight_names_once_each_and_no_overflow_chunks_rows(capsys):
    """What a block keeps of an expert layer besides its arguments is the
    eight names, the small tables flat (a ``[T, k]`` pads to 128 lanes on the
    chip), and ONE ``[T, h]`` of rows, the first chunk's: a name inside the
    k - 1 overflow chunks is reached by the block's policy through ``cond``
    and the loop, and five chunks' rows a layer the chip's compiler refused
    (PERF.md, PR 29)."""
    config = dataclasses.replace(MC, num_layers=2)
    lp = init_params(jax.random.PRNGKey(0), config)["model"]["layers"]["1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(1), (ROWS, SEQ, config.hidden_size), jnp.float32)
    layer = jax.checkpoint(
        lambda lp, x: moe.grouped_moe_mlp(lp, x, config, jnp.float32)[0].sum(),
        policy=transformer._remat_policy("full", config, SEQ),
    )
    jax.ad_checkpoint.print_saved_residuals(layer, lp, x)
    kept = [ln.split()[0] for ln in capsys.readouterr().out.splitlines() if "from the argument" not in ln]
    t, k, held = ROWS * SEQ, config.num_experts_per_tok, len(config.held_expert_ids)
    assert sorted(kept) == sorted([
        f"f32[{t},{config.n_routed_experts}]", f"i32[{t},{k}]",  # scores, selection
        f"i32[{t * k}]", f"i32[{t * k}]", f"f32[{t * k}]",  # tokens, rank, weights of the sorted pairs
        f"i32[{held}]", f"i32[{held}]",  # load, ends
        f"f32[{t},{config.hidden_size}]",  # the first chunk's rows
    ])
    assert len(kept) == len(moe.KEPT_ACROSS_REMAT)


def _pull(params, experts):
    """A selection bias that sends every token to ``experts`` in every layer."""
    bias = np.zeros((MC.n_routed_experts,), np.float32)
    bias[list(experts)] = 10.0
    for i in range(MC.first_k_dense_replace, MC.num_layers):
        params["model"]["layers"][str(i)]["mlp"]["gate"]["e_score_correction_bias"] = jnp.asarray(bias)
    return params


@pytest.mark.parametrize("pulled", [(), (0, 1, 2)], ids=["as-drawn", "all-k-held"])
def test_gradients_with_the_routing_kept_equal_those_recomputed(monkeypatch, pulled):
    """Same values, made once instead of twice: leaf for leaf the same bits,
    with the first chunk alone and with every overflow chunk taken (all k
    choices of every token held here: 3 chunks of pairs). Run operation by
    operation: as one program XLA fuses the two differently and the float32
    sums round apart by 1e-7 of a leaf's norm."""
    params = _pull(init_params(jax.random.PRNGKey(1), MC), pulled)
    grad = lambda: jax.grad(partial(_loss, config=MC, remat_policy="full"))(params)  # noqa: E731
    kept = grad()
    monkeypatch.setattr(moe, "KEPT_ACROSS_REMAT", ())
    recomputed = grad()
    router = kept["model"]["layers"]["1"]["mlp"]["gate"]["kernel"]
    experts = kept["model"]["layers"]["1"]["mlp"]["experts"]["w1"]
    assert float(jnp.abs(router).max()) > 0 and float(jnp.abs(experts).max()) > 0
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(recomputed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "preset, keeps",
    [("tiny", False), ("tiny_moe", False), ("smollm3_3b", False), ("mistral_7b", False),
     ("tiny_mla_moe", True), ("moonlight_16b_a3b", True)],
)
def test_only_a_model_with_a_grouped_experts_layer_keeps_routing(preset, keeps):
    assert keeps_routing(get_preset(preset)) is keeps


@pytest.mark.parametrize(
    "remat_policy, plain",
    [("full", None), (None, None), ("dots", jax.checkpoint_policies.checkpoint_dots),
     ("dots_no_batch", jax.checkpoint_policies.dots_with_no_batch_dims_saveable)],
)
@pytest.mark.parametrize("preset", ["tiny", "tiny_moe"])
def test_a_model_without_such_a_layer_gets_the_plain_policy_object(preset, remat_policy, plain):
    """Dense layers and Mixtral's capacity experts on short rows: the very
    object ``jax.checkpoint_policies`` has, so their programs cannot differ."""
    assert transformer._remat_policy(remat_policy, get_preset(preset), 32) is plain


@pytest.mark.parametrize(
    "preset, checkpointing, pipe, routing_kept",
    [("tiny_mla_moe", True, 1, EXPERT_LAYERS), ("tiny_mla_moe", False, 1, 0), ("tiny_mla_moe", True, 2, 0), ("tiny", True, 1, 0)],
)
def test_the_trainer_counts_the_blocks_that_keep_their_routing(preset, checkpointing, pipe, routing_kept):
    """The static count the trainer's ``REFUSED`` line states beside the flash
    kernel's: none without checkpointing, none under the pipeline schedule
    (its blocks are wrapped without a policy), none in a dense model."""
    from llm_fine_tune_distributed_tpu.config import TrainConfig
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    trainer = SFTTrainer.__new__(SFTTrainer)
    trainer.config = TrainConfig(model_preset=preset, gradient_checkpointing=checkpointing, max_seq_length=SEQ)
    trainer.model_config, trainer._pipe_size, trainer._frozen_boundary = get_preset(preset), pipe, 0
    assert trainer._layers_keeping_routing() == routing_kept
