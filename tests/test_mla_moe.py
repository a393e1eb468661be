"""Latent attention and routed experts with shared experts (Moonlight-16B-A3B's
structure, ``model_type`` deepseek_v3) on the normal training path, at the
``tiny_mla_moe`` preset on a CPU, against the benchmark's plain reference
(``benchmarks/chipbench/reference_mla_moe.py``, which imports nothing of the
program).

Tolerances and their reasons. Program and reference both compute in float32
at ``highest`` matmul precision here (``compute_dtype="float32"``,
``conftest.py``), over the same bfloat16-valued weights, so they differ by
summation order alone: 1e-4 relative covers logits, loss and gradients with
room (observed 1e-6 to 2e-5). Near-ties among the top k could flip on that
much; at these sizes with seeded weights none does, and the test prints the
share of (token, expert) choices that differ so that a reader of a failure
sees it first. On the chip the program computes in bfloat16 and the share is
not zero: the limits of the cell's ``correct`` are set knowing it (PERF.md).
"""

import dataclasses
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
from llm_fine_tune_distributed_tpu.models.transformer import forward_with_report
from llm_fine_tune_distributed_tpu.ops import moe
from llm_fine_tune_distributed_tpu.ops.attention import xla_attention
from llm_fine_tune_distributed_tpu.ops.flash_attention import pallas_flash_attention
from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
from llm_fine_tune_distributed_tpu.parallel.sharding import param_spec
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.train.step import build_train_step
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import reference_mla_moe as ref  # noqa: E402
from benchmarks.chipbench import weights, weights_mla_moe  # noqa: E402

MC = get_preset("tiny_mla_moe")
ACCUM, ROWS, SEQ = 2, 2, 32
RTOL = 1e-4
RECIPE = {"learning_rate": 1e-3, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8, "max_grad_norm": 1.0,
          "lr_schedule": "constant", "optimizer": "adamw", "weight_decay": 0.0}


def bench_cfg(mc=MC) -> dict:
    """The benchmark's configuration dict (HF names) of a ModelConfig."""
    d = to_hf_dict(mc)
    return dict(d, router_experts=mc.n_routed_experts, n_routed_experts=len(mc.held_expert_ids),
                held_experts=list(mc.held_expert_ids), init_std=0.02, router_bias_std=0.02)


@pytest.fixture(scope="module")
def flat():
    return weights_mla_moe.make_flat(11, bench_cfg())


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(5).randint(0, MC.vocab_size, (ACCUM, ROWS, SEQ)).astype(np.int32)


def _params(flat, dtype=jnp.float32):
    return weights.nest({k: v.astype(dtype) for k, v in flat.items()})


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def test_leaves_and_parameter_count_agree_with_the_benchmarks_weights(flat):
    from llm_fine_tune_distributed_tpu.models.transformer import init_params

    own = flatten_dict(init_params(jax.random.PRNGKey(0), MC))
    assert {k: v.shape for k, v in own.items()} == weights_mla_moe.leaf_shapes(bench_cfg())
    assert MC.num_params == sum(int(np.prod(s)) for s in weights_mla_moe.leaf_shapes(bench_cfg()).values())


def test_forward_logits_agree_with_the_reference(flat, ids):
    got, _, report = forward_with_report(_params(flat), jnp.asarray(ids[0]), MC, compute_dtype=jnp.float32)
    assert set(report) == {"expert_load"}
    load = report["expert_load"]
    want = ref.logits(flat, bench_cfg(), ids[0])
    assert _rel(got, want) < RTOL
    # the program's counter against the reference's selection, layer by layer
    chosen = ref.selections(flat, bench_cfg(), ids[0])
    held = list(MC.held_expert_ids)
    want_load = np.stack([np.asarray(chosen[i]).sum((0, 1))[held] for i in sorted(chosen)])
    differ = float(np.abs(np.asarray(load) - want_load).sum()) / (ids[0].size * MC.num_experts_per_tok * len(chosen))
    print(f"share of (token, expert) choices on which program and reference differ: {differ:.2e}")
    np.testing.assert_array_equal(np.asarray(load), want_load)


def _train_config():
    return TrainConfig(model_preset=None, compute_dtype="float32", param_dtype="float32",
                       gradient_checkpointing=True, remat_policy="full", freeze_strategy="none",
                       per_device_batch_size=ROWS, gradient_accumulation_steps=ACCUM, max_seq_length=SEQ)


@pytest.fixture(scope="module")
def one_step(flat, ids):
    """One optimizer step through ``build_train_step`` (the normal path:
    freeze split, optimizer, scopes), and the reference's."""
    tc = _train_config()
    params = _params(flat)
    mask = flatten_dict(trainable_mask(params, MC, tc))
    leaves = flatten_dict(params)
    trainable = {k: v for k, v in leaves.items() if mask[k]}
    frozen = {k: v for k, v in leaves.items() if not mask[k]}
    optimizer = optax.chain(optax.clip_by_global_norm(RECIPE["max_grad_norm"]),
                            optax.adamw(RECIPE["learning_rate"], weight_decay=0.0))
    state = TrainState(step=jnp.zeros((), jnp.int32), trainable=trainable, frozen=frozen,
                       opt_state=optimizer.init(trainable))
    batch = {"input_ids": jnp.asarray(ids), "loss_mask": jnp.ones(ids.shape, jnp.float32),
             "attention_mask": jnp.ones(ids.shape, jnp.int32)}
    step = jax.jit(build_train_step(MC, tc, optimizer))
    new_state, metrics = step(state, batch)
    mu = new_state.opt_state[1][0].mu
    # the reference rounds its masters to the bfloat16 the recipe of the cell
    # states; this test keeps float32 masters on both sides, so the change is
    # compared through a float32 copy of the reference's update rule below
    # (a copy: the reference's optimizer donates the leaves it replaces)
    want = ref.sft_reference({k: jnp.array(v) for k, v in flat.items()}, bench_cfg(), RECIPE, [ids],
                             lambda names: weights_mla_moe.make_flat(11, bench_cfg(), only=names),
                             keep_first_grad=True)
    return {"state": state, "new": new_state, "metrics": metrics, "frozen": frozen,
            "first_grad": {k: np.asarray(v) / (1 - RECIPE["adam_b1"]) for k, v in mu.items()}, "want": want}


def test_only_the_selection_bias_is_frozen(one_step):
    assert sorted(one_step["frozen"]) == [f"model/layers/{i}/mlp/gate/e_score_correction_bias" for i in (1, 2)]


def test_loss_and_gradient_norm_agree_with_the_reference(one_step):
    assert abs(float(one_step["metrics"]["loss"]) - one_step["want"]["losses"][0]) < RTOL
    assert abs(float(one_step["metrics"]["grad_norm"]) / one_step["want"]["grad_norm"] - 1) < RTOL


def test_every_leafs_gradient_agrees_with_the_reference(one_step):
    got, want = one_step["first_grad"], one_step["want"]["first_grad"]
    assert sorted(got) == sorted(want)
    worst = max((_rel(got[k], want[k]), k) for k in want)
    assert worst[0] < RTOL, worst


def test_one_optimizer_steps_parameter_change(one_step):
    """Adam's first step moves every element by the learning rate times
    g / (|g| + eps): the program's change against that rule applied to the
    reference's own (clipped) gradient."""
    before, after = one_step["state"].trainable, one_step["new"].trainable
    for k, g in one_step["want"]["first_grad"].items():
        update = -RECIPE["learning_rate"] * g / (np.abs(g) + RECIPE["adam_eps"])
        got = np.asarray(after[k]) - np.asarray(before[k])
        assert np.linalg.norm(got - update) <= 1e-3 * np.linalg.norm(update) + 1e-9, k


def test_the_step_reports_its_expert_counters(one_step, ids):
    m = one_step["metrics"]
    held = len(MC.held_expert_ids)
    assert m["expert_load"].shape == (held,)
    # the step's counters are the report's expert_load, microbatch by microbatch
    params = weights.nest({**one_step["state"].trainable, **one_step["frozen"]})
    loads = [forward_with_report(params, jnp.asarray(micro), MC, compute_dtype=jnp.float32)[2]["expert_load"]
             for micro in ids]
    assert loads[0].shape == (MC.num_layers - 1, held)  # [expert layers, held]
    pairs = sum(int(load.sum()) for load in loads)
    np.testing.assert_allclose(float(m["expert_pairs_per_token"]), pairs / (ids.size * (MC.num_layers - 1)), rtol=1e-6)
    np.testing.assert_allclose(float(m["expert_load"].sum()), float(m["expert_pairs_per_token"]), rtol=1e-6)
    # 3 of 16 chosen, 4 held: 0.75 pairs a token expected; the seed's draw is near it
    assert 0.4 < float(m["expert_pairs_per_token"]) < 1.1
    assert 1.0 <= float(m["expert_load_max_over_mean"]) <= held


def _expert_layer(flat, layer=1):
    lp = {k: v.astype(jnp.float32) for k, v in ref.layer_leaves(flat, layer).items()}
    return lp, weights.nest({k: v for k, v in lp.items() if k.startswith("mlp/")})["mlp"]


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
@pytest.mark.parametrize("pulled", [(0,), (0, 1, 2), (5,)], ids=["one-held", "all-k-held", "none-held"])
def test_no_token_is_dropped_whatever_the_routing(flat, pulled, impl):
    """A selection bias sends every token to the ``pulled`` experts: one held
    expert gets all tokens; with k held experts pulled every pair of every
    token is held here (3 chunks of pairs); with an expert held elsewhere the
    routed part is what the other held ones add. Same result as the reference
    each time, and the load counts every pair."""
    lp, tree = _expert_layer(flat)
    bias = np.zeros((MC.n_routed_experts,), np.float32)
    bias[list(pulled)] = 10.0
    tree["gate"]["e_score_correction_bias"] = jnp.asarray(bias)
    lp["mlp/gate/e_score_correction_bias"] = jnp.asarray(bias)
    h = jax.random.normal(jax.random.PRNGKey(2), (ROWS, SEQ, MC.hidden_size), jnp.float32)
    y, load = moe.grouped_moe_mlp(tree, h, MC, jnp.float32, impl=impl)
    cfg = dict(ref.cfg_items(bench_cfg()))
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(h, lp["mlp/shared_experts/gate_proj/kernel"], lp["mlp/shared_experts/up_proj/kernel"],
                            lp["mlp/shared_experts/down_proj/kernel"])
        want = ref.experts(lp, h, cfg) - shared
    assert _rel(y, want) < RTOL
    for e in pulled:
        if e in MC.held_expert_ids:
            assert int(load[MC.held_expert_ids.index(e)]) == ROWS * SEQ
    if pulled == (0, 1, 2):
        assert int(load.sum()) == 3 * ROWS * SEQ
    # and the gradient passes through the chunks past the first
    g = jax.grad(lambda t: moe.grouped_moe_mlp(t, h, MC, jnp.float32, impl=impl)[0].sum())(tree)
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))
    with jax.default_matmul_precision("highest"):
        want_g = jax.grad(lambda w: (ref.experts(w, h, cfg) - ref.swiglu(
            h, w["mlp/shared_experts/gate_proj/kernel"], w["mlp/shared_experts/up_proj/kernel"],
            w["mlp/shared_experts/down_proj/kernel"])).sum())(lp)
    for name in ("w1", "w2", "w3"):
        assert _rel(g["experts"][name], want_g["mlp/experts/" + name]) < RTOL, name
    assert _rel(g["gate"]["kernel"], want_g["mlp/gate/kernel"]) < 10 * RTOL


def test_the_shares_add_up_to_the_uncut_layer(flat):
    """Four processes hold 4 of the 16 experts each, all route over all 16:
    their routed parts plus the shared experts once are the whole layer as
    the reference computes it with every expert held."""
    whole_cfg = dict(bench_cfg(), n_routed_experts=MC.n_routed_experts, held_experts=list(range(MC.n_routed_experts)))
    whole = weights_mla_moe.make_flat(13, whole_cfg)
    lp = {k: v.astype(jnp.float32) for k, v in ref.layer_leaves(whole, 1).items()}
    h = jax.random.normal(jax.random.PRNGKey(4), (ROWS, SEQ, MC.hidden_size), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(lp, h, dict(ref.cfg_items(whole_cfg)))
        total = ref.swiglu(h, lp["mlp/shared_experts/gate_proj/kernel"], lp["mlp/shared_experts/up_proj/kernel"],
                           lp["mlp/shared_experts/down_proj/kernel"])
    tree = weights.nest({k: v for k, v in lp.items() if k.startswith("mlp/")})["mlp"]
    pairs = 0
    for first in range(0, MC.n_routed_experts, 4):
        share = dataclasses.replace(MC, held_experts=tuple(range(first, first + 4)))
        held = {k: tree["experts"][k][first:first + 4] for k in ("w1", "w2", "w3")}
        y, load = moe.grouped_moe_mlp(dict(tree, experts=held), h, share, jnp.float32)
        total = total + y
        pairs += int(load.sum())
    assert pairs == ROWS * SEQ * MC.num_experts_per_tok
    assert _rel(total, want) < RTOL


def test_the_grouped_kernel_agrees_with_ragged_dot():
    """The TPU's grouped product (megablox, here under the Pallas
    interpreter) against the one XLA runs anywhere, rows past the groups'
    total left out."""
    rng = np.random.RandomState(0)
    lhs = jnp.asarray(rng.randn(64, 32), jnp.float32)
    rhs = jnp.asarray(rng.randn(4, 32, 48), jnp.float32)
    sizes = jnp.asarray([10, 0, 27, 9], jnp.int32)
    a = moe.grouped_matmul(lhs, rhs, sizes, impl="gmm_interpret")[:46]
    b = moe.grouped_matmul(lhs, rhs, sizes, impl="ragged_dot")[:46]
    assert _rel(a, b) < 1e-5


def test_flash_kernels_with_wider_query_and_key_heads_in_interpret_mode():
    """q/k heads of 192 (128 + 64 rope dimensions) against v heads of 128:
    forward and the three gradients of the Pallas kernels (interpreted)
    against XLA attention."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (1, 256, 2, 192), jnp.float32)
    k = jax.random.normal(keys[1], (1, 256, 2, 192), jnp.float32)
    v = jax.random.normal(keys[2], (1, 256, 2, 128), jnp.float32)
    do = jax.random.normal(keys[3], (1, 256, 2, 128), jnp.float32)
    got, vjp = jax.vjp(lambda *a: pallas_flash_attention(*a, interpret=True), q, k, v)
    want, vjp_ref = jax.vjp(lambda *a: xla_attention(*a), q, k, v)
    assert got.shape == (1, 256, 2, 128)
    assert _rel(got, want) < 1e-4
    for a, b in zip(vjp(do), vjp_ref(do)):
        assert a.shape == b.shape and _rel(a, b) < 1e-4


def test_published_config_builds_and_round_trips():
    import json

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    published = {"attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 11264, "kv_lora_rank": 512, "max_position_embeddings": 8192,
                 "model_type": "deepseek_v3", "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
                 "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
                 "num_experts_per_tok": 6, "num_hidden_layers": 27, "num_key_value_heads": 16,
                 "num_nextn_predict_layers": 0, "q_lora_rank": None, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000, "routed_scaling_factor": 2.446,
                 "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
                 "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
    if os.path.exists(catalog):  # the driver's catalog, where it is installed
        with open(catalog) as f:
            row = [json.loads(line) for line in f if '"Moonlight-16B-A3B"' in line][0]
        assert row["config"] == dict(published, ep_size=1, seq_aux=True)
    mc = from_hf_config(SimpleNamespace(**published))
    assert dataclasses.replace(mc, name="moonlight_16b_a3b") == get_preset("moonlight_16b_a3b")
    assert 15.9e9 < mc.num_params < 16.0e9
    for preset in ("moonlight_16b_a3b", "tiny_mla_moe"):
        assert from_hf_config(SimpleNamespace(**to_hf_dict(get_preset(preset)))) == get_preset(preset)


@pytest.mark.parametrize("key,value", [("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
                                       ("rope_scaling", {"type": "yarn", "factor": 40}),
                                       ("scoring_func", "tanh"), ("norm_topk_prob", False)])
def test_what_is_not_implemented_is_refused_by_name(key, value):
    cfg = dict(to_hf_dict(get_preset("moonlight_16b_a3b")), model_type="deepseek_v3", **{key: value})
    with pytest.raises(ValueError, match=key):
        from_hf_config(SimpleNamespace(**cfg))


def test_checkpoint_names_round_trip_with_the_rope_columns_in_stored_order(flat):
    params = _params(flat)
    state = hf_io.pytree_to_hf_state_dict(params, MC)
    layer = "model.layers.1."
    for name in ("self_attn.kv_a_proj_with_mqa.weight", "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj.weight",
                 "mlp.gate.weight", "mlp.gate.e_score_correction_bias", "mlp.experts.3.gate_proj.weight",
                 "mlp.experts.0.down_proj.weight", "mlp.shared_experts.up_proj.weight"):
        assert layer + name in state, name
    assert state[layer + "mlp.gate.weight"].shape == (MC.n_routed_experts, MC.hidden_size)
    # DeepSeek stores each rotated pair adjacent; the model rotates halves
    dn, dr = MC.qk_nope_head_dim, MC.qk_rope_head_dim
    stored = state[layer + "self_attn.q_proj.weight"].T.reshape(MC.hidden_size, MC.num_heads, dn + dr)
    ours = np.asarray(params["model"]["layers"]["1"]["self_attn"]["q_proj"]["kernel"]).reshape(stored.shape)
    np.testing.assert_array_equal(stored[..., dn::2], ours[..., dn:dn + dr // 2])
    np.testing.assert_array_equal(stored[..., dn + 1::2], ours[..., dn + dr // 2:])
    np.testing.assert_array_equal(stored[..., :dn], ours[..., :dn])
    back = hf_io.hf_state_dict_to_pytree(state, MC)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))), params, back))
    with pytest.raises(ValueError, match="config"):
        hf_io.pytree_to_hf_state_dict(params)


def test_sharding_rules_cover_the_new_leaves():
    spec = lambda path, n: tuple(param_spec("model/layers/1/" + path, n))  # noqa: E731
    assert spec("self_attn/kv_a_proj_with_mqa/kernel", 2) == ("fsdp", None)
    assert spec("self_attn/kv_b_proj/kernel", 2) == ("fsdp", "tensor")
    assert spec("mlp/shared_experts/down_proj/kernel", 2) == ("tensor", "fsdp")
    assert spec("mlp/experts/w1", 3) == ("expert", "fsdp", "tensor")
    assert spec("mlp/gate/kernel", 2) == ("fsdp", None)
    assert spec("mlp/gate/e_score_correction_bias", 1) == ()


def test_serving_refuses_latent_attention_by_name(flat):
    from llm_fine_tune_distributed_tpu.infer.generate import Generator, LatentAttentionNotServed

    with pytest.raises(LatentAttentionNotServed, match="training path only"):
        Generator(_params(flat), MC, tokenizer=None)
    # ...and the model's own cache function refuses a cache handed to it directly
    from llm_fine_tune_distributed_tpu.models.transformer import init_cache

    with pytest.raises(NotImplementedError, match="training form only"):
        forward_with_report(_params(flat), jnp.zeros((1, 4), jnp.int32), MC, cache=init_cache(MC, 1, 8))


def test_the_new_scopes_reach_the_lowered_step(one_step, ids):
    """``router``, ``experts`` and ``shared_expert`` lie inside ``mlp`` of the
    expert layers and nowhere in the dense one."""
    import re

    tc = _train_config()
    optimizer = optax.adamw(1e-3)
    state = one_step["state"].replace(opt_state=optimizer.init(one_step["state"].trainable))
    batch = {"input_ids": jnp.asarray(ids), "loss_mask": jnp.ones(ids.shape, jnp.float32),
             "attention_mask": jnp.ones(ids.shape, jnp.int32)}
    text = jax.jit(build_train_step(MC, tc, optimizer)).lower(state, batch).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for name in ("router", "experts", "shared_expert"):
        inside = [n for n in names if n.startswith("jit(train_step)") and f"/mlp/{name}/" in n]
        assert inside, name
        assert all(re.search(r"layer[12]\b", n) for n in inside), name
        assert any("transpose(" in n for n in inside), f"{name}: no backward operation carries the scope"
