"""Gated window layers with rope beside a gated global layer without, per-head
q/k norms, four norms a block around a sigmoid router with a selection bias,
a shared expert and a leading dense layer (Trinity-Mini's structure,
``model_type`` afmoe) on the normal training path, at the ``tiny_trinity``
preset on a CPU, against the benchmark's plain reference
(``benchmarks/chipbench/reference_afmoe.py``, which imports nothing of the
program).

Tolerances and their reasons. Program and reference both compute in float32
at ``highest`` matmul precision here (``compute_dtype="float32"``,
``conftest.py``), over the same bfloat16-valued weights, so they differ by
summation order alone: ``RTOL`` 1e-4 relative covers logits, loss and
gradients with room (observed 3e-7 to 2e-6). That is tight enough to see what
must not pass (the tests at the end hold each above ten times the tolerance):
the router in bfloat16, the gate left off, an output norm left off, a rope on
the global layer, a window a key too wide. In bfloat16 (the cell's compute
dtype) the program's logits stand 1e-2 from the float32 reference's, the
rounding of every product's output to 8 bits of mantissa through ten halves,
and are held to ``BF16_RTOL`` 4e-2; the loss, a mean over 256 tokens, to 5e-3.
Near-ties among the top k could flip on 1e-4; at these sizes with seeded
weights none does, and the program's counted load is held to the reference's
selection exactly.
"""

import dataclasses
import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from llm_fine_tune_distributed_tpu.config import TrainConfig
from llm_fine_tune_distributed_tpu.models import hf_io, transformer
from llm_fine_tune_distributed_tpu.models.configs import from_hf_config, get_preset, to_hf_dict
from llm_fine_tune_distributed_tpu.models.transformer import (
    forward, forward_with_report, init_cache, init_params, keeps_flash_outputs,
)
from llm_fine_tune_distributed_tpu.ops import moe
from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
from llm_fine_tune_distributed_tpu.parallel.pipeline import layer_scan_problems
from llm_fine_tune_distributed_tpu.parallel.sharding import param_spec
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.train.step import build_train_step
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import check, reference_afmoe as ref  # noqa: E402
from benchmarks.chipbench import weights, weights_afmoe  # noqa: E402

MC = get_preset("tiny_trinity")
ACCUM, ROWS, SEQ = 2, 2, 64  # rows twice the window of 32
RTOL, BF16_RTOL = 1e-4, 4e-2
RECIPE = {"learning_rate": 1e-3, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8, "max_grad_norm": 1.0,
          "lr_schedule": "constant", "optimizer": "adamw", "weight_decay": 0.0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BIAS = "e_score_correction_bias"


def bench_cfg(mc=MC) -> dict:
    """The benchmark's configuration dict (the published config's names) of a ModelConfig."""
    return {
        "model_type": "afmoe", "hidden_size": mc.hidden_size, "head_dim": mc.head_dim,
        "num_attention_heads": mc.num_heads, "num_key_value_heads": mc.num_kv_heads,
        "num_hidden_layers": mc.num_layers, "vocab_size": mc.vocab_size, "intermediate_size": mc.intermediate_size,
        "moe_intermediate_size": mc.moe_intermediate_size, "num_experts": len(mc.held_expert_ids),
        "router_experts": mc.n_routed_experts, "held_experts": list(mc.held_expert_ids),
        "num_experts_per_tok": mc.num_experts_per_tok, "num_shared_experts": mc.n_shared_experts,
        "num_dense_layers": mc.first_k_dense_replace, "route_scale": mc.routed_scaling_factor, "route_norm": True,
        "score_func": "sigmoid", "mup_enabled": mc.embed_scale, "rope_theta": mc.rope_theta,
        "rms_norm_eps": mc.rms_norm_eps, "sliding_window": mc.sliding_window, "layer_types": list(mc.layer_types),
        "max_position_embeddings": mc.max_position_embeddings, "tie_word_embeddings": False, "init_std": 0.02,
        "embed_std": 0.25,
    }


@pytest.fixture(scope="module")
def flat():
    return weights_afmoe.make_flat(11, bench_cfg())


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
    assert {k: v.shape for k, v in own.items()} == weights_afmoe.leaf_shapes(bench_cfg())
    assert MC.num_params == sum(int(np.prod(s)) for s in weights_afmoe.leaf_shapes(bench_cfg()).values())
    # four norms in every block, around the dense layer and around the expert layers alike
    for layer in (0, 1):
        for norm in ("input", "post_attention", "pre_feedforward", "post_feedforward"):
            assert f"model/layers/{layer}/{norm}_layernorm/weight" in own
    assert "model/layers/0/mlp/gate_proj/kernel" in own and f"model/layers/1/mlp/gate/{BIAS}" in own
    assert own["model/layers/1/self_attn/q_proj/kernel"].shape == (MC.hidden_size, 2 * MC.num_heads * MC.head_dim)


def test_forward_logits_agree_with_the_reference(flat, ids):
    got, _, report = forward_with_report(_params(flat), jnp.asarray(ids[0, 0]), MC, compute_dtype=jnp.float32)
    assert set(report) == {"expert_load"}
    assert _rel(got, ref.logits(flat, bench_cfg(), ids[0, 0])) < RTOL
    # the program's counter against the reference's selection, expert layer by expert layer
    chosen = ref.selections(flat, bench_cfg(), ids[0, 0])
    assert sorted(chosen) == [1, 2, 3, 4]  # layer 0 is dense
    held = list(MC.held_expert_ids)
    want_load = np.stack([np.asarray(chosen[i]).sum((0, 1))[held] for i in sorted(chosen)])
    np.testing.assert_array_equal(np.asarray(report["expert_load"]), want_load)


def test_bfloat16_forward_stands_by_the_float32_reference(flat, ids):
    """The cell's compute dtype. Every product's output is rounded to 8 bits
    of mantissa (2^-9 relative a rounding, through ten halves and a head over
    64 inputs): observed 1.0e-2 on the logits and 6e-4 on the loss."""
    want = ref.logits(flat, bench_cfg(), ids[0, 0])
    got = forward(_params(flat, jnp.bfloat16), jnp.asarray(ids[0, 0]), MC, compute_dtype=jnp.bfloat16)[0]
    assert RTOL < _rel(got, want) < BF16_RTOL

    def loss(logits):
        logp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)[:, :-1], axis=-1)
        return -float(jnp.take_along_axis(logp, jnp.asarray(ids[0, 0])[:, 1:, None], axis=-1).mean())

    assert abs(loss(got) - loss(want)) < 5e-3


def _state(flat, tc, dtype):
    params = _params(flat, dtype)
    mask = flatten_dict(trainable_mask(params, MC, tc))
    assert [k for k, on in mask.items() if not on] == [f"model/layers/{i}/mlp/gate/{BIAS}" for i in (1, 2, 3, 4)]
    optimizer = optax.chain(optax.clip_by_global_norm(RECIPE["max_grad_norm"]),
                            optax.adamw(RECIPE["learning_rate"], weight_decay=0.0))
    every = flatten_dict(params)
    trainable = {k: v for k, v in every.items() if mask[k]}
    return optimizer, TrainState(step=jnp.zeros((), jnp.int32), trainable=trainable,
                                 frozen={k: v for k, v in every.items() if not mask[k]},
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
                             lambda names: weights_afmoe.make_flat(11, bench_cfg(), only=names),
                             keep_first_grad=True)
    return {"metrics": metrics, "delta": delta, "want": want, "frozen": new_state.frozen,
            "first_grad": {k: np.asarray(v) / (1 - RECIPE["adam_b1"]) for k, v in mu.items()}}


def test_loss_and_gradient_norm_agree_with_the_reference(two_steps):
    assert abs(float(two_steps["metrics"]["loss"]) - two_steps["want"]["losses"][0]) < RTOL
    assert abs(float(two_steps["metrics"]["grad_norm"]) / two_steps["want"]["grad_norm"] - 1) < RTOL


def test_every_leafs_gradient_agrees_with_the_reference(two_steps):
    got, want = two_steps["first_grad"], two_steps["want"]["first_grad"]
    assert sorted(got) == sorted(want) and not any(k.endswith(BIAS) for k in want)
    worst = max((_rel(got[k], want[k]), k) for k in want)
    assert worst[0] < RTOL, worst
    # both halves of q_proj's joined leaf take a gradient: the query's columns and the gate's
    dq = got["model/layers/3/self_attn/q_proj/kernel"].reshape(MC.hidden_size, MC.num_heads, 2, MC.head_dim)
    assert np.abs(dq[:, :, 0]).max() > 0 and np.abs(dq[:, :, 1]).max() > 0


def test_two_steps_parameter_change_agrees_with_the_reference(two_steps):
    """The norm by leaf of what two AdamW steps changed, bfloat16 masters on
    both sides (the update computed in float32, the sum rounded once a step):
    the benchmark's own comparison. Where the two float32 sums differ in their
    last bits a rounding to bfloat16 falls the other way, an element here and
    there by 2^-8 of its value: held to 3e-3 as in the other expert models'
    tests; a step left out, or a second step from the wrong moments, is 0.3 and more."""
    gap, where = check.worst_leaf_gap(two_steps["delta"], two_steps["want"]["delta_norms"])
    assert gap < 3e-3, (gap, where)
    # the selection bias is a buffer: the step hands it back as it came
    assert all(float(jnp.abs(v).max()) == 0 for k, v in two_steps["frozen"].items() if k.endswith(BIAS))


def test_the_step_reports_its_expert_counters(two_steps):
    m = two_steps["metrics"]
    assert m["expert_load"].shape == (len(MC.held_expert_ids),)
    # 4 of 16 chosen, 4 held: 1 pair a token expected; the seed's draw is near it
    assert 0.6 < float(m["expert_pairs_per_token"]) < 1.4
    assert 1.0 <= float(m["expert_load_max_over_mean"]) <= len(MC.held_expert_ids)


def test_the_eight_shares_add_up_to_the_uncut_layer(flat):
    """The share test. Experts 0-1, 2-3, ... 14-15 as eight programs, each told
    its share (``held_experts``) and handed its rows of the expert leaves, the
    whole router and its bias: their routed outputs, with the shared expert
    (which every share computes alike) counted ONCE, add up to what the uncut
    reference gives for the whole layer BEFORE ``post_mlp_layernorm`` (all 16
    experts and the shared one). The norm is not linear, so it is what comes
    before it that adds up; each share norms its own partial sum."""
    whole = dict(bench_cfg(), num_experts=16, held_experts=list(range(16)))
    full = weights_afmoe.make_flat(11, whole)
    lp = {k: v.astype(jnp.float32) for k, v in ref.layer_leaves(full, 2).items()}
    lp[f"mlp/gate/{BIAS}"] = 0.05 * jnp.cos(jnp.arange(16.0))  # a bias that moves choices, and no weight
    h = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, MC.hidden_size), jnp.float32)
    items = dict(ref.cfg_items(whole))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(lp, h, items)
        shared_once = ref.experts(lp, h, items, held=())
    total, loads = shared_once, []
    for share in range(8):
        held = (2 * share, 2 * share + 1)
        tree = {"gate": {"kernel": lp["mlp/gate/kernel"], BIAS: lp[f"mlp/gate/{BIAS}"]},
                "experts": {w: lp[f"mlp/experts/{w}"][2 * share: 2 * share + 2] for w in ("w1", "w3", "w2")}}
        y, load = moe.grouped_moe_mlp(tree, h, MC.replace(held_experts=held), jnp.float32)
        total, loads = total + y, loads + [int(load.sum())]
    assert _rel(total, want) < RTOL
    assert sum(loads) == 2 * SEQ * MC.num_experts_per_tok  # every pair of every token is some share's
    # and the program's whole feed-forward half of ONE share is the reference's for that share
    mine = weights.nest({k: v for k, v in lp.items() if k.startswith("mlp/")})["mlp"]
    mine["experts"] = {w: v[:4] for w, v in mine["experts"].items()}
    got, _ = transformer._grouped_experts(mine, h, lambda x, p: x @ p["kernel"], MC, compute_dtype=jnp.float32, mesh=None)
    with jax.default_matmul_precision("highest"):
        assert _rel(got, ref.experts(lp, h, dict(items, held_experts=tuple(range(16))), held=(0, 1, 2, 3))) < RTOL


def _one_layer(kind):
    return MC.replace(num_layers=1, layer_types=(kind,), no_rope_layers=(int(kind == "sliding_attention"),))


def test_a_window_layer_masks_at_its_window():
    """One window layer (window 32): a query sees its own key and the 31
    before it, so another token at position 0 changes the output at positions
    0 to 31 and at no position from 32 on; with a window of 33 position 32
    moves too."""
    mc = _one_layer("sliding_attention")
    params = init_params(jax.random.PRNGKey(1), mc)
    a = np.random.RandomState(2).randint(0, mc.vocab_size, (1, SEQ)).astype(np.int32)
    b = a.copy()
    b[0, 0] = (a[0, 0] + 1) % mc.vocab_size
    run = lambda cfg, x: np.asarray(forward(params, jnp.asarray(x), cfg, compute_dtype=jnp.float32, output_hidden=True)[0])  # noqa: E731
    moved = np.abs(run(mc, a) - run(mc, b)).max(-1)[0]
    assert moved[:32].min() > 0 and moved[32:].max() == 0
    wider = mc.replace(sliding_window=33)
    moved = np.abs(run(wider, a) - run(wider, b)).max(-1)[0]
    assert moved[32] > 0 and moved[33:].max() == 0


def test_a_global_layer_has_no_rope():
    """One global layer: without a rope the keys a query sees are a SET, so
    the first 16 tokens in another order leave every position from 16 on as it
    was (to the order of a float32 sum); the same layer WITH a rope does not."""
    mc = _one_layer("full_attention")
    assert not mc.layer(0).rope and mc.layer(0).window is None
    params = init_params(jax.random.PRNGKey(1), mc)
    a = np.random.RandomState(2).randint(0, mc.vocab_size, (1, SEQ)).astype(np.int32)
    b = a.copy()
    b[0, :16] = a[0, :16][::-1]
    run = lambda cfg, x: np.asarray(forward(params, jnp.asarray(x), cfg, compute_dtype=jnp.float32, output_hidden=True)[0])  # noqa: E731
    assert _rel(run(mc, b)[0, 16:], run(mc, a)[0, 16:]) < 1e-5
    roped = mc.replace(no_rope_layers=(1,))
    assert _rel(run(roped, b)[0, 16:], run(roped, a)[0, 16:]) > 1e-3
    # and the model's plans: the global layer of the period rotates nothing, the window layers do
    assert [(MC.layer(i).rope, MC.layer(i).window) for i in range(5)] == [
        (True, 32), (True, 32), (True, 32), (False, None), (True, 32)]
    assert [MC.layer(i).feed_forward for i in range(5)] == ["dense"] + ["grouped_experts"] * 4


def test_cached_decoding_agrees_with_the_whole_row(flat, ids):
    """The dense cache path takes this block as it is (q/k norms and the rope
    before the write, the window as a mask over the buffer, the gate after the
    kernel): a prefill of 48 tokens and 16 single-token steps give the logits
    of the whole row's forward pass."""
    params, row = _params(flat), jnp.asarray(ids[0, 0, :1])
    want = forward(params, row, MC, compute_dtype=jnp.float32)[0]
    cache = init_cache(MC, 1, SEQ, dtype=jnp.float32)
    got, cache = forward(params, row[:, :48], MC, cache=cache, cache_pos=0, compute_dtype=jnp.float32)
    steps = [got]
    for t in range(48, SEQ):
        out, cache = forward(params, row[:, t:t + 1], MC, cache=cache, cache_pos=t, compute_dtype=jnp.float32)
        steps.append(out)
    assert _rel(jnp.concatenate(steps, axis=1), want) < RTOL


def test_the_generator_serves_the_block_as_the_whole_row_computes_it():
    """``infer/`` refuses no layer of this model: greedy decoding through
    ``Generator`` (bucketed prefill, the device-resident decode loop over the
    dense cache) picks the tokens the whole row's forward pass picks."""
    from llm_fine_tune_distributed_tpu.infer.generate import GenerationConfig, Generator, unserved_layer_kind

    assert unserved_layer_kind(MC) is None
    params = init_params(jax.random.PRNGKey(0), MC)
    tokenizer = SimpleNamespace(eos_token_id=None, pad_token_id=0)
    prompt = [int(t) for t in np.random.RandomState(1).randint(1, MC.vocab_size, 40)]  # past the window of 32
    got = Generator(params, MC, tokenizer, compute_dtype=jnp.float32).generate_ids(
        prompt, GenerationConfig(max_new_tokens=6, do_sample=False, repetition_penalty=1.0))
    row = list(prompt)
    for _ in range(6):
        row.append(int(jnp.argmax(forward(params, jnp.asarray([row]), MC, compute_dtype=jnp.float32)[0][0, -1])))
    assert got == row[len(prompt):]


def test_published_config_builds_and_round_trips():
    if not os.path.exists(CATALOG):
        pytest.skip("the driver's catalog is not installed here")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f if '"Trinity-Mini"' in line][0]
    mc = from_hf_config(SimpleNamespace(**row["config"]))  # verbatim
    assert dataclasses.replace(mc, name="trinity_mini") == get_preset("trinity_mini")
    assert (mc.first_k_dense_replace, mc.embed_scale, mc.sandwich_norms, mc.qk_norm, mc.attention_output_gate) == (
        2, True, True, True, True)
    assert (mc.router_scoring, mc.routed_scaling_factor, mc.n_routed_experts, mc.n_shared_experts) == ("sigmoid", 2.826, 128, 1)
    assert mc.no_rope_layers == (1, 1, 1, 0) * 8 and mc.sliding_window == 2048
    assert 26.1e9 < mc.num_params < 26.2e9  # 26B-A3B
    cut = mc.replace(num_layers=5, first_k_dense_replace=1, vocab_size=25024, held_experts=tuple(range(16)))
    assert cut.num_params == 705_474_304  # the cell's 705.5 M
    for preset in ("trinity_mini", "tiny_trinity"):
        assert from_hf_config(SimpleNamespace(**to_hf_dict(get_preset(preset)))) == get_preset(preset)
    # a framework save of a model loaded under the family's own name comes back too
    assert from_hf_config(SimpleNamespace(**to_hf_dict(mc))) == mc
    # layer_types left out: every fourth layer global
    assert from_hf_config(SimpleNamespace(**{k: v for k, v in row["config"].items() if k != "layer_types"})) == mc


@pytest.mark.parametrize("key, value", [
    ("n_group", 2), ("topk_group", 2), ("num_expert_groups", 4), ("route_norm", False), ("score_func", "softmax"),
    ("layer_types", ["sliding_attention"]), ("layer_types", ["sliding_attention", "linear_attention"]),
])
def test_what_is_not_implemented_is_refused_by_name(key, value):
    base = dict(model_type="afmoe", vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                num_attention_heads=2, num_key_value_heads=2, sliding_window=8, num_experts=4, num_experts_per_tok=2,
                moe_intermediate_size=16, num_dense_layers=1, num_shared_experts=1, route_scale=2.0, mup_enabled=True,
                layer_types=["sliding_attention", "full_attention"])
    mc = from_hf_config(SimpleNamespace(**base))
    assert mc.layer(1).window is None and not mc.layer(1).rope and mc.layer(0).rope and mc.embed_scale
    with pytest.raises(ValueError, match=key):
        from_hf_config(SimpleNamespace(**dict(base, **{key: value})))


def test_checkpoint_names_round_trip(flat):
    """HF afmoe's names: the gate a ``self_attn.gate_proj`` of its own (split
    out of ``q_proj``'s joined leaf by head, and joined again on load), the
    four norms, ``mlp.router.gate``, ``mlp.expert_bias``, ``mlp.shared_experts``,
    per-expert Linears under the experts' global ids."""
    params = _params(flat)
    state = hf_io.pytree_to_hf_state_dict(params, MC)
    layer = "model.layers.3."
    for name in ("self_attn.q_proj.weight", "self_attn.gate_proj.weight", "self_attn.q_norm.weight",
                 "pre_mlp_layernorm.weight", "post_mlp_layernorm.weight", "post_attention_layernorm.weight",
                 "mlp.router.gate.weight", "mlp.expert_bias", "mlp.shared_experts.up_proj.weight",
                 "mlp.experts.3.gate_proj.weight", "mlp.experts.0.down_proj.weight"):
        assert layer + name in state, name
    assert "model.layers.0.mlp.gate_proj.weight" in state  # the dense layer's own gate_proj keeps its name
    assert not any("feedforward" in k or BIAS in k or ".mlp.gate." in k for k in state)
    width = MC.num_heads * MC.head_dim
    assert state[layer + "self_attn.q_proj.weight"].shape == state[layer + "self_attn.gate_proj.weight"].shape == (width, MC.hidden_size)
    # by head: head 1's query columns and gate columns of the joined leaf
    joined = np.asarray(flat["model/layers/3/self_attn/q_proj/kernel"], np.float32)
    d = MC.head_dim
    np.testing.assert_array_equal(state[layer + "self_attn.q_proj.weight"].T[:, d:2 * d], joined[:, 2 * d:3 * d])
    np.testing.assert_array_equal(state[layer + "self_attn.gate_proj.weight"].T[:, d:2 * d], joined[:, 3 * d:4 * d])
    back = flatten_dict(hf_io.hf_state_dict_to_pytree(state, MC))
    for k, v in flatten_dict(params).items():
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(v), err_msg=k)
    # the same tree under another family's rule keeps its own names (Qwen3-Next stores the joined leaf)
    assert "model.layers.3.self_attn.gate_proj.weight" not in hf_io.pytree_to_hf_state_dict(params, MC.replace(sandwich_norms=False))


def test_sharding_freeze_and_pipeline_rules():
    assert param_spec("model/layers/1/mlp/experts/w1", 3) == jax.sharding.PartitionSpec("expert", "fsdp", "tensor")
    assert param_spec("model/layers/1/mlp/shared_experts/down_proj/kernel", 2) == jax.sharding.PartitionSpec("tensor", "fsdp")
    assert param_spec("model/layers/1/self_attn/q_proj/kernel", 2) == jax.sharding.PartitionSpec("fsdp", "tensor")
    for vector in ("self_attn/q_norm/weight", "pre_feedforward_layernorm/weight", f"mlp/gate/{BIAS}"):
        assert param_spec("model/layers/1/" + vector, 1) == jax.sharding.PartitionSpec()
    params = init_params(jax.random.PRNGKey(0), MC)
    tail = flatten_dict(trainable_mask(params, MC, TrainConfig(model_preset=None, freeze_strategy="last_n_and_head",
                                                                unfreeze_last_n_layers=1)))
    assert tail["model/layers/4/post_feedforward_layernorm/weight"] and not tail["model/layers/3/mlp/experts/w2"]
    assert not tail[f"model/layers/4/mlp/gate/{BIAS}"]
    # the pipeline's layer scan runs identical layers: a model that mixes kinds is refused, by what differs
    (problem,) = layer_scan_problems(MC, seq_parallel=False)
    assert "layers 0 and 1" in problem and "feed_forward" in problem
    (problem,) = layer_scan_problems(MC.replace(first_k_dense_replace=0), seq_parallel=False)  # (past the dense layer)
    assert "layers 0 and 3" in problem and "window" in problem  # (a rope that differs alone the scan takes as data)
    # over a mesh's expert axis the layer still raises: the exchange is not written
    mesh = SimpleNamespace(shape={"expert": 2})
    with pytest.raises(NotImplementedError, match="exchange"):
        transformer._grouped_experts({}, None, None, MC, compute_dtype=jnp.float32, mesh=mesh)


def test_every_layer_keeps_its_flash_outputs_at_the_cells_rows():
    """``worth_keeping_across_remat`` reads the keys a query sees: at 8192
    tokens a window of 2048 is 2048 x (2 - 1/4) = 3584 against the hidden 2048
    (Mellum's 1024: 1920 against 2304, recompute), the global layer 8192."""
    big = get_preset("trinity_mini")
    assert keeps_flash_outputs(big, 8192, 2048) and keeps_flash_outputs(big, 8192, None)
    assert not keeps_flash_outputs(big, 8192, 1024) and not keeps_flash_outputs(big, 2048, None)
    assert moe.pairs_a_chunk(big.replace(held_experts=tuple(range(16)))) == 2  # 1.0 pairs a token expected, a quarter of room
    assert moe.pairs_a_chunk(MC) == 2


# -- what the tolerance must not let through ----------------------------------


def _logit_gap(flat, ids, mc, params=None):
    got = forward_with_report(params or _params(flat), jnp.asarray(ids[0, 0]), mc, compute_dtype=jnp.float32)[0]
    return _rel(got, ref.logits(flat, bench_cfg(), ids[0, 0]))


def test_a_bfloat16_router_fails_the_tolerance(flat, ids, monkeypatch):
    monkeypatch.setattr(moe, "ROUTER_DTYPE", jnp.bfloat16)
    assert _logit_gap(flat, ids, MC) > 10 * RTOL


def test_a_gate_or_a_norm_left_off_fails_the_tolerance(flat, ids):
    params = _params(flat)
    ungated = jax.tree.map(lambda x: x, params)
    q = ungated["model"]["layers"]["1"]["self_attn"]["q_proj"]["kernel"]
    q = q.reshape(MC.hidden_size, MC.num_heads, 2, MC.head_dim).at[:, :, 1].set(0.0)  # sigmoid(0): every gate a half
    ungated["model"]["layers"]["1"]["self_attn"]["q_proj"]["kernel"] = q.reshape(MC.hidden_size, -1)
    assert _logit_gap(flat, ids, MC, ungated) > 10 * RTOL
    unnormed = jax.tree.map(lambda x: x, params)
    unnormed["model"]["layers"]["2"]["post_feedforward_layernorm"]["weight"] = jnp.full((MC.hidden_size,), 0.5)
    assert _logit_gap(flat, ids, MC, unnormed) > 10 * RTOL
    assert _logit_gap(flat, ids, MC.replace(embed_scale=False)) > 10 * RTOL  # the sqrt(hidden) multiplier left off
    assert _logit_gap(flat, ids, MC.replace(qk_norm=False)) > 10 * RTOL


def test_a_rope_or_a_window_of_the_wrong_kind_fails_the_tolerance(flat, ids):
    assert _logit_gap(flat, ids, MC.replace(no_rope_layers=(1,) * 5)) > 10 * RTOL  # a rope on the global layer
    assert _logit_gap(flat, ids, MC.replace(no_rope_layers=(0,) * 5)) > 10 * RTOL  # none on the window layers
    assert _logit_gap(flat, ids, MC.replace(layer_types=("full_attention",) * 5)) > 10 * RTOL
    assert _logit_gap(flat, ids, MC.replace(sliding_window=33)) > 10 * RTOL  # one key too many
