"""Window and global attention layers side by side, each with its own rope,
and a softmax router over experts with no shared expert (Mellum 2's structure,
``model_type`` mellum) on the normal training path, at the ``tiny_mellum``
preset on a CPU, against the benchmark's plain reference
(``benchmarks/chipbench/reference_swa_moe.py``, which imports nothing of the
program).

Tolerances and their reasons. Program and reference both compute in float32
at ``highest`` matmul precision here (``compute_dtype="float32"``,
``conftest.py``), over the same bfloat16-valued weights, so they differ by
summation order alone: ``RTOL`` 1e-4 relative covers logits, loss and
gradients with room (observed 4e-7 to 5e-7). That is tight enough to see what
must not pass: the router in bfloat16 moves the logits by 5e-3, a window left
off by 0.18 and one that is a key too wide by 4e-2, YaRN's factor left off by
3e-3 and YaRN on the window layers too by 9e-3 (the three tests at the end
hold that each is above ten times the tolerance). Near-ties among the top k could flip on 1e-4; at these
sizes with seeded weights none does, and the program's counted load is held
to the reference's selection exactly.
"""

import dataclasses
import json
import math
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from llm_fine_tune_distributed_tpu.config import TrainConfig
from llm_fine_tune_distributed_tpu.models import hf_io
from llm_fine_tune_distributed_tpu.models.configs import from_hf_config, get_preset, to_hf_dict
from llm_fine_tune_distributed_tpu.models.transformer import (
    forward_with_report, init_params, keeps_flash_outputs, rope_tables,
)
from llm_fine_tune_distributed_tpu.ops import moe
from llm_fine_tune_distributed_tpu.ops.rope import rope_inv_freq, yarn_attention_factor
from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
from llm_fine_tune_distributed_tpu.parallel.pipeline import layer_scan_problems
from llm_fine_tune_distributed_tpu.parallel.sharding import param_spec
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.train.step import build_train_step
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import check, reference_swa_moe as ref  # noqa: E402
from benchmarks.chipbench import weights, weights_swa_moe  # noqa: E402

MC = get_preset("tiny_mellum")
ACCUM, ROWS, SEQ = 2, 2, 64  # rows twice the window of 32
RTOL = 1e-4
RECIPE = {"learning_rate": 1e-3, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8, "max_grad_norm": 1.0,
          "lr_schedule": "constant", "optimizer": "adamw", "weight_decay": 0.0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def bench_cfg(mc=MC) -> dict:
    """The benchmark's configuration dict (the published config's names) of a ModelConfig."""
    return {
        "hidden_size": mc.hidden_size, "head_dim": mc.head_dim, "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "num_hidden_layers": mc.num_layers, "vocab_size": mc.vocab_size,
        "intermediate_size": mc.intermediate_size, "moe_intermediate_size": mc.moe_intermediate_size,
        "num_experts": len(mc.held_expert_ids), "router_experts": mc.n_routed_experts,
        "held_experts": list(mc.held_expert_ids), "num_experts_per_tok": mc.num_experts_per_tok,
        "rms_norm_eps": mc.rms_norm_eps, "sliding_window": mc.sliding_window, "layer_types": list(mc.layer_types),
        "max_position_embeddings": mc.max_position_embeddings, "tie_word_embeddings": False, "init_std": 0.02,
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": mc.rope_theta, "factor": mc.rope_scaling_factor,
                               "original_max_position_embeddings": mc.rope_original_max_position,
                               "beta_fast": mc.rope_beta_fast, "beta_slow": mc.rope_beta_slow},
            "sliding_attention": {"rope_type": "default", "rope_theta": mc.rope_theta},
        },
    }


@pytest.fixture(scope="module")
def flat():
    return weights_swa_moe.make_flat(11, bench_cfg())


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(5).randint(0, MC.vocab_size, (2, ACCUM, ROWS, SEQ)).astype(np.int32)  # two steps


def _params(flat, dtype=jnp.float32):
    return weights.nest({k: v.astype(dtype) for k, v in flat.items()})


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def test_leaves_and_parameter_count_agree_with_the_benchmarks_weights():
    own = flatten_dict(init_params(jax.random.PRNGKey(0), MC))
    assert {k: v.shape for k, v in own.items()} == weights_swa_moe.leaf_shapes(bench_cfg())
    assert MC.num_params == sum(int(np.prod(s)) for s in weights_swa_moe.leaf_shapes(bench_cfg()).values())
    assert not any("e_score_correction_bias" in k or "shared_experts" in k for k in own)


def test_forward_logits_agree_with_the_reference(flat, ids):
    got, _, report = forward_with_report(_params(flat), jnp.asarray(ids[0, 0]), MC, compute_dtype=jnp.float32)
    assert set(report) == {"expert_load"}
    assert _rel(got, ref.logits(flat, bench_cfg(), ids[0, 0])) < RTOL
    # the program's counter against the reference's selection, layer by layer
    chosen = ref.selections(flat, bench_cfg(), ids[0, 0])
    held = list(MC.held_expert_ids)
    want_load = np.stack([np.asarray(chosen[i]).sum((0, 1))[held] for i in sorted(chosen)])
    np.testing.assert_array_equal(np.asarray(report["expert_load"]), want_load)


def _state(flat, tc, dtype):
    params = _params(flat, dtype)
    mask = flatten_dict(trainable_mask(params, MC, tc))
    assert all(mask.values())  # nothing frozen, and no buffer among the leaves
    optimizer = optax.chain(optax.clip_by_global_norm(RECIPE["max_grad_norm"]),
                            optax.adamw(RECIPE["learning_rate"], weight_decay=0.0))
    trainable = flatten_dict(params)
    return optimizer, TrainState(step=jnp.zeros((), jnp.int32), trainable=trainable, frozen={},
                                 opt_state=optimizer.init(trainable))


def _train_config(param_dtype):
    return TrainConfig(model_preset=None, compute_dtype="float32", param_dtype=param_dtype,
                       gradient_checkpointing=True, remat_policy="full", freeze_strategy="none",
                       per_device_batch_size=ROWS, gradient_accumulation_steps=ACCUM, max_seq_length=SEQ)


def _batch(ids):
    return {"input_ids": jnp.asarray(ids), "loss_mask": jnp.ones(ids.shape, jnp.float32),
            "attention_mask": jnp.ones(ids.shape, jnp.int32)}


@pytest.fixture(scope="module")
def two_steps(flat, ids):
    """Two optimizer steps through ``build_train_step`` (the normal path), at
    float32 masters for the gradients and at the cell's bfloat16 masters for
    the parameters' change, and the reference's two steps."""
    tc = _train_config("float32")
    optimizer, state = _state(flat, tc, jnp.float32)
    new_state, metrics = jax.jit(build_train_step(MC, tc, optimizer))(state, _batch(ids[0]))
    mu = new_state.opt_state[1][0].mu
    tc16 = _train_config("bfloat16")
    optimizer16, state16 = _state(flat, tc16, jnp.bfloat16)
    step16 = jax.jit(build_train_step(MC, tc16, optimizer16))
    before = {k: np.asarray(v, np.float32) for k, v in state16.trainable.items()}
    for batch in ids:
        state16, _ = step16(state16, _batch(batch))
    delta = {k: float(np.linalg.norm(np.asarray(v, np.float32) - before[k])) for k, v in state16.trainable.items()}
    want = ref.sft_reference({k: jnp.array(v) for k, v in flat.items()}, bench_cfg(), RECIPE, list(ids),
                             lambda names: weights_swa_moe.make_flat(11, bench_cfg(), only=names),
                             keep_first_grad=True)
    return {"metrics": metrics, "delta": delta, "want": want,
            "first_grad": {k: np.asarray(v) / (1 - RECIPE["adam_b1"]) for k, v in mu.items()}}


def test_loss_and_gradient_norm_agree_with_the_reference(two_steps):
    assert abs(float(two_steps["metrics"]["loss"]) - two_steps["want"]["losses"][0]) < RTOL
    assert abs(float(two_steps["metrics"]["grad_norm"]) / two_steps["want"]["grad_norm"] - 1) < RTOL


def test_every_leafs_gradient_agrees_with_the_reference(two_steps):
    got, want = two_steps["first_grad"], two_steps["want"]["first_grad"]
    assert sorted(got) == sorted(want)
    worst = max((_rel(got[k], want[k]), k) for k in want)
    assert worst[0] < RTOL, worst


def test_two_steps_parameter_change_agrees_with_the_reference(two_steps):
    """The norm by leaf of what two AdamW steps changed, bfloat16 masters on
    both sides (the update computed in float32, the sum rounded once a step):
    the benchmark's own comparison. Where the two float32 sums differ in their
    last bits a rounding to bfloat16 falls the other way, an element here and
    there by 2^-8 of its value: observed 7e-4 of the worst leaf's change (a
    router's kernel), held to 3e-3; a step left out, or a second step from the
    wrong moments, is 0.3 and more."""
    gap, where = check.worst_leaf_gap(two_steps["delta"], two_steps["want"]["delta_norms"])
    assert gap < 3e-3, (gap, where)


def test_the_step_reports_its_expert_counters(two_steps):
    m = two_steps["metrics"]
    assert m["expert_load"].shape == (len(MC.held_expert_ids),)
    # 4 of 16 chosen, 4 held: 1 pair a token expected; the seed's draw is near it
    assert 0.6 < float(m["expert_pairs_per_token"]) < 1.4
    assert 1.0 <= float(m["expert_load_max_over_mean"]) <= len(MC.held_expert_ids)


def test_the_four_shares_add_up_to_the_uncut_layer(flat):
    """The share test. Experts 0-3, 4-7, 8-11 and 12-15 as four programs, each
    told its share (``held_experts``) and handed its rows of the expert leaves
    and the whole router: their routed outputs add up to what the uncut
    reference gives for the whole layer (all 16 experts). There is nothing
    every share computes alike to count once: no shared expert."""
    whole = dict(bench_cfg(), num_experts=16, held_experts=list(range(16)))
    full = weights_swa_moe.make_flat(11, whole)
    lp = {k: v.astype(jnp.float32) for k, v in ref.layer_leaves(full, 1).items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, MC.hidden_size), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(lp, h, dict(ref.cfg_items(whole)))
    total, loads = 0.0, []
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        tree = {"gate": {"kernel": lp["mlp/gate/kernel"]},
                "experts": {w: lp[f"mlp/experts/{w}"][4 * share: 4 * share + 4] for w in ("w1", "w3", "w2")}}
        y, load = moe.grouped_moe_mlp(tree, h, MC.replace(held_experts=held), jnp.float32)
        total, loads = total + y, loads + [int(load.sum())]
    assert _rel(total, want) < RTOL
    assert sum(loads) == 2 * SEQ * MC.num_experts_per_tok  # every pair of every token is some share's


@pytest.mark.parametrize("pulled, chunks", [((0,), 1), ((0, 1, 2, 3), 2), ((9,), 1)],
                         ids=["one-held", "all-k-held", "none-held"])
def test_no_token_is_dropped_whatever_the_routing(flat, pulled, chunks):
    """The router's columns of the ``pulled`` experts raised so that every
    token chooses them: with all 4 chosen experts held here the pairs fill two
    chunks (``pairs_a_chunk`` is 2 of the 4 a token can hold), the second
    behind its ``lax.cond``. Same result as the reference each time."""
    assert moe.pairs_a_chunk(MC) == 2 and moe.pairs_a_chunk(get_preset("tiny_mla_moe")) == 1
    assert moe.pairs_a_chunk(get_preset("mellum2_12b_a2_5b").replace(held_experts=tuple(range(16)))) == 3
    lp = {k: v.astype(jnp.float32) for k, v in ref.layer_leaves(flat, 2).items()}
    gate = np.asarray(lp["mlp/gate/kernel"]).copy()
    h = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, MC.hidden_size), jnp.float32)
    gate[:, list(pulled)] += 5.0 * np.asarray(h).mean((0, 1))[:, None] + 1.0  # (and a push along every token's mean)
    h = h + 3.0
    lp["mlp/gate/kernel"] = jnp.asarray(gate)
    tree = weights.nest({k: v for k, v in lp.items() if k.startswith("mlp/")})["mlp"]
    y, load = moe.grouped_moe_mlp(tree, h, MC, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(lp, h, dict(ref.cfg_items(bench_cfg())))
    assert _rel(y, want) < RTOL if float(jnp.abs(want).max()) > 0 else float(jnp.abs(y).max()) == 0
    held_pulled = [e for e in pulled if e in MC.held_expert_ids]
    assert int(load.sum()) >= len(held_pulled) * 2 * SEQ
    assert -(-int(load.sum()) // (moe.pairs_a_chunk(MC) * 2 * SEQ)) >= chunks - (0 if held_pulled else 1)


def test_yarn_by_hand():
    """``inv_freq`` and the factor on cos and sin against numbers worked by
    hand from HF's formula at the published values (theta 500,000, factor 16,
    original length 8192, beta 32 and 1, heads of 128): c(32) = 128 ln(8192 /
    (64 pi)) / (2 ln 500000) = 18.08 -> low 18; c(1) = 34.98 -> high 35; so
    dimensions 0..18 keep theta^(-2i/128), 35..63 are that over 16, and
    dimension 26 is (26 - 18) / 17 of the way: 500000^(-52/128) x (1 - 8/17 x
    15/16) = 4.83942e-3 x 0.558824 = 2.70438e-3."""
    inv = np.asarray(rope_inv_freq(128, 500_000.0, scaling_type="yarn", factor=16.0, original_max_position=8192,
                                   beta_fast=32.0, beta_slow=1.0), np.float64)
    plain = 500_000.0 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16.0, rtol=1e-6)
    np.testing.assert_allclose(inv[26], 2.70438e-3, rtol=1e-5)
    assert math.isclose(yarn_attention_factor(16.0), 1.2772588722239782, rel_tol=1e-12)  # 0.1 ln 16 + 1
    assert yarn_attention_factor(16.0, 1.5) == 1.5 and yarn_attention_factor(1.0) == 1.0
    # the model's two tables: the window layers' is plain rope, the global layers' carries the factor
    big = get_preset("mellum2_12b_a2_5b")
    tables = rope_tables(big, jnp.arange(4)[None])
    assert sorted(tables) == ["plain", "scaled"]
    np.testing.assert_allclose(np.asarray(tables["plain"][0][0, 0]), 1.0)
    np.testing.assert_allclose(np.asarray(tables["scaled"][0][0, 0]), 1.2772588722239782, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.inv_freq(bench_cfg(big)["rope_parameters"]["full_attention"], 128)[0]), inv,
                               rtol=1e-6)


def test_published_config_builds_and_round_trips():
    if not os.path.exists(CATALOG):
        pytest.skip("the driver's catalog is not installed here")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f if '"Mellum2-12B-A2.5B-Instruct"' in line][0]
    mc = from_hf_config(SimpleNamespace(**row["config"]))  # verbatim
    assert dataclasses.replace(mc, name="mellum2_12b_a2_5b") == get_preset("mellum2_12b_a2_5b")
    assert 12.1e9 < mc.num_params < 12.2e9
    cut = mc.replace(num_layers=4, vocab_size=24576, held_experts=tuple(range(16)))
    assert cut.num_params == 595_153_152  # the cell's 595.2 M
    for preset in ("mellum2_12b_a2_5b", "tiny_mellum"):
        assert from_hf_config(SimpleNamespace(**to_hf_dict(get_preset(preset)))) == get_preset(preset)


@pytest.mark.parametrize("key, value", [
    ("mlp_layer_types", ["dense", "sparse"]), ("norm_topk_prob", False),
    ("rope_parameters", {"full_attention": {"rope_type": "longrope", "rope_theta": 1e4}, "sliding_attention": {"rope_theta": 1e4}}),
    ("rope_parameters", {"full_attention": {"rope_type": "default", "rope_theta": 1e4}, "sliding_attention": {"rope_theta": 1e6}}),
    ("layer_types", ["sliding_attention"]),
])
def test_what_is_not_implemented_is_refused_by_name(key, value):
    base = dict(model_type="mellum", vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                num_attention_heads=2, num_key_value_heads=2, sliding_window=8, num_experts=4, num_experts_per_tok=2,
                moe_intermediate_size=16, layer_types=["sliding_attention", "full_attention"],
                rope_parameters={"full_attention": {"rope_type": "default", "rope_theta": 1e4},
                                 "sliding_attention": {"rope_type": "default", "rope_theta": 1e4}})
    assert from_hf_config(SimpleNamespace(**base)).layer(1).window is None
    with pytest.raises(ValueError, match=key.split("_")[0]):
        from_hf_config(SimpleNamespace(**dict(base, **{key: value})))


def test_checkpoint_names_round_trip(flat):
    """HF per-expert Linears under ``mlp.experts.<global id>`` (the names
    Qwen3-MoE and DeepSeek-V3 store; Mellum's checkpoint is taken to store
    them alike), the router as ``mlp.gate.weight``, no bias, no shared expert."""
    params = _params(flat)
    state = hf_io.pytree_to_hf_state_dict(params, MC)
    layer = "model.layers.3."
    for name in ("self_attn.q_proj.weight", "mlp.gate.weight", "mlp.experts.3.gate_proj.weight", "mlp.experts.0.down_proj.weight"):
        assert layer + name in state
    assert state[layer + "mlp.gate.weight"].shape == (MC.n_routed_experts, MC.hidden_size)
    assert not any("e_score_correction_bias" in k or "shared_experts" in k for k in state)
    back = hf_io.hf_state_dict_to_pytree(state, MC)
    for k, v in flatten_dict(params).items():
        np.testing.assert_array_equal(np.asarray(flatten_dict(back)[k]), np.asarray(v), err_msg=k)


def test_sharding_freeze_and_pipeline_rules():
    assert param_spec("model/layers/0/mlp/experts/w1", 3) == jax.sharding.PartitionSpec("expert", "fsdp", "tensor")
    assert param_spec("model/layers/0/mlp/gate/kernel", 2) == jax.sharding.PartitionSpec("fsdp", None)
    params = init_params(jax.random.PRNGKey(0), MC)
    tail = flatten_dict(trainable_mask(params, MC, TrainConfig(model_preset=None, freeze_strategy="last_n_and_head",
                                                                unfreeze_last_n_layers=1)))
    assert tail["model/layers/3/mlp/gate/kernel"] and not tail["model/layers/2/mlp/experts/w2"]
    # the pipeline's layer scan runs identical layers: a model that mixes kinds is refused, by what differs
    (problem,) = layer_scan_problems(MC, seq_parallel=False)
    assert "layers 0 and 3" in problem and "window" in problem and "rope_kind" in problem
    # over a mesh's expert axis the layer still raises: the exchange is not written
    from llm_fine_tune_distributed_tpu.models import transformer

    mesh = SimpleNamespace(shape={"expert": 2})
    with pytest.raises(NotImplementedError, match="exchange"):
        transformer._grouped_experts({}, None, None, MC, compute_dtype=jnp.float32, mesh=mesh)


def test_a_window_layer_recomputes_its_flash_forward_and_a_global_layer_keeps_it():
    """``worth_keeping_across_remat`` reads the keys a query sees: at 8192
    tokens a window of 1024 is 1024 x (2 - 1/8) = 1920 against the hidden
    2304, the global layer 8192."""
    big = get_preset("mellum2_12b_a2_5b")
    assert not keeps_flash_outputs(big, 8192, 1024) and keeps_flash_outputs(big, 8192, None)
    assert not keeps_flash_outputs(big, 2048, None)  # 2048 against 2304
    assert keeps_flash_outputs(big, 8192, 8192)  # a window as long as the row is none


# -- what the tolerance must not let through ----------------------------------


def _logit_gap(flat, ids, mc):
    got = forward_with_report(_params(flat), jnp.asarray(ids[0, 0]), mc, compute_dtype=jnp.float32)[0]
    return _rel(got, ref.logits(flat, bench_cfg(), ids[0, 0]))


def test_a_bfloat16_router_fails_the_tolerance(flat, ids, monkeypatch):
    monkeypatch.setattr(moe, "ROUTER_DTYPE", jnp.bfloat16)
    assert _logit_gap(flat, ids, MC) > 10 * RTOL


def test_a_dropped_window_fails_the_tolerance(flat, ids):
    assert _logit_gap(flat, ids, MC.replace(layer_types=("full_attention",) * 4)) > 10 * RTOL
    assert _logit_gap(flat, ids, MC.replace(sliding_window=33)) > 10 * RTOL  # one key too many


def test_a_rope_of_the_wrong_kind_fails_the_tolerance(flat, ids):
    assert _logit_gap(flat, ids, MC.replace(rope_attention_factor=1.0)) > 10 * RTOL  # YaRN's factor left off
    assert _logit_gap(flat, ids, MC.replace(rope_scaling_layer_type=None)) > 10 * RTOL  # YaRN on the window layers too
