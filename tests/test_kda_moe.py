"""Kimi Delta Attention layers (a delta rule with a decay a channel behind
low-rank gates) beside a latent-attention layer without rope, a leading dense
layer, then a sigmoid router with a selection bias over routed experts beside
one shared expert (Kimi-Linear-48B-A3B's structure, ``model_type``
kimi_linear) on the normal training path, at the ``tiny_kimi_linear`` preset
on a CPU, against the benchmark's plain reference
(``benchmarks/chipbench/reference_kda_moe.py``, which imports nothing of the
program and walks the rule token by token).

Tolerances and their reasons. Program and reference both compute in float32
at ``highest`` matmul precision here (``compute_dtype="float32"``,
``conftest.py``), over the same bfloat16-valued weights, so they differ by
summation order and by the form of the rule (chunks of 64 in sub-blocks of 16
with a triangular inverse against 64 rank-one steps): ``RTOL`` 1e-4 relative
covers logits, loss and gradients with room (observed 4e-7 to 6e-6). That is
tight enough to see what must not pass (the tests at the end hold each above
ten times the tolerance, the rule's state in bfloat16 above twice: these rows
cross one chunk boundary): the decay as one scalar a head, a rotated latent
key, the rule's state carried in bfloat16, a silu where the gate is a sigmoid.
In bfloat16 (the cell's compute dtype) the program's logits stand 1e-2 from
the float32 reference's, the rounding of every product's output to 8 bits of
mantissa through ten halves, and are held to ``BF16_RTOL`` 4e-2; the loss, a
mean over 256 tokens, to 5e-3. Near-ties among the top k could flip on 1e-4;
at these sizes with seeded weights none does, and the program's counted load
is held to the reference's selection exactly.
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

from llm_fine_tune_distributed_tpu.config import ModelConfig, TrainConfig
from llm_fine_tune_distributed_tpu.models import hf_io, transformer
from llm_fine_tune_distributed_tpu.models.configs import from_hf_config, get_preset, to_hf_dict
from llm_fine_tune_distributed_tpu.models.transformer import (
    forward, forward_with_report, init_cache, init_params, keeps_flash_outputs, keeps_scan_output,
)
from llm_fine_tune_distributed_tpu.ops import gated_delta, moe
from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
from llm_fine_tune_distributed_tpu.parallel.pipeline import layer_scan_problems
from llm_fine_tune_distributed_tpu.parallel.sharding import param_spec
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.train.step import build_train_step
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import check, reference_kda_moe as ref  # noqa: E402
from benchmarks.chipbench import weights, weights_kda_moe  # noqa: E402

MC = get_preset("tiny_kimi_linear")
ACCUM, ROWS, SEQ = 2, 2, 72  # rows of a chunk and a half: the rule pads, and its second chunk starts from a state
RTOL, BF16_RTOL = 1e-4, 4e-2
RECIPE = {"learning_rate": 1e-3, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8, "max_grad_norm": 1.0,
          "lr_schedule": "constant", "optimizer": "adamw", "weight_decay": 0.0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BIAS = "e_score_correction_bias"


def bench_cfg(mc=MC) -> dict:
    """The benchmark's configuration dict (the published config's names) of a ModelConfig."""
    kda = [i + 1 for i in range(mc.num_layers) if mc.layer(i).attention == "kda"]
    return {
        "model_type": "kimi_linear", "hidden_size": mc.hidden_size, "head_dim": mc.head_dim,
        "num_attention_heads": mc.num_heads, "num_key_value_heads": mc.num_kv_heads,
        "num_hidden_layers": mc.num_layers, "vocab_size": mc.vocab_size, "intermediate_size": mc.intermediate_size,
        "kv_lora_rank": mc.kv_lora_rank, "q_lora_rank": None, "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim, "v_head_dim": mc.v_head_dim, "mla_use_nope": mc.mla_use_nope,
        "linear_attn_config": {
            "kda_layers": kda, "full_attn_layers": [i + 1 for i in range(mc.num_layers) if i + 1 not in kda],
            "num_heads": mc.linear_num_value_heads, "head_dim": mc.linear_key_head_dim,
            "short_conv_kernel_size": mc.linear_conv_kernel_dim,
        },
        "moe_intermediate_size": mc.moe_intermediate_size, "num_experts": len(mc.held_expert_ids),
        "router_experts": mc.n_routed_experts, "held_experts": list(mc.held_expert_ids),
        "num_experts_per_token": mc.num_experts_per_tok, "num_shared_experts": mc.n_shared_experts,
        "first_k_dense_replace": mc.first_k_dense_replace, "routed_scaling_factor": mc.routed_scaling_factor,
        "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "moe_layer_freq": 1, "num_expert_group": 1,
        "topk_group": 1, "num_nextn_predict_layers": 0, "rope_theta": mc.rope_theta, "rope_scaling": None,
        "rms_norm_eps": mc.rms_norm_eps, "model_max_length": mc.max_position_embeddings, "tie_word_embeddings": False,
        "init_std": 0.02, "embed_std": 0.02,
    }


@pytest.fixture(scope="module")
def flat():
    return weights_kda_moe.make_flat(11, bench_cfg())


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(5).randint(0, MC.vocab_size, (2, ACCUM, ROWS, SEQ)).astype(np.int32)  # two steps


def _params(flat, dtype=jnp.float32):
    return weights.nest({k: v.astype(dtype) for k, v in flat.items()})


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# -- the model against the reference -------------------------------------------


def test_leaves_plans_and_parameter_count_agree_with_the_benchmarks_weights():
    own = flatten_dict(init_params(jax.random.PRNGKey(0), MC))
    assert {k: v.shape for k, v in own.items()} == weights_kda_moe.leaf_shapes(bench_cfg())
    assert MC.num_params == sum(int(np.prod(s)) for s in weights_kda_moe.leaf_shapes(bench_cfg()).values())
    # two mixers and two feed-forwards in one model
    assert [MC.layer(i).attention for i in range(5)] == ["kda", "kda", "kda", "latent", "kda"]
    assert [MC.layer(i).feed_forward for i in range(5)] == ["dense"] + ["grouped_experts"] * 4
    assert not any(MC.layer(i).rope for i in range(5))  # the latent layer rotates nothing (mla_use_nope)
    assert "model/layers/0/linear_attn/f_a_proj/kernel" in own and "model/layers/3/self_attn/kv_a_proj_with_mqa/kernel" in own
    # the draw: a log decay's scale in [1, 16] a head, softplus(dt_bias) in [0.001, 0.1] a channel
    a, dt = np.exp(np.asarray(own["model/layers/0/linear_attn/A_log"])), np.asarray(jax.nn.softplus(own["model/layers/0/linear_attn/dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and 0.99e-3 < dt.min() and dt.max() < 0.101


def test_forward_logits_agree_with_the_reference(flat, ids):
    got, _, report = forward_with_report(_params(flat), jnp.asarray(ids[0, 0]), MC, compute_dtype=jnp.float32)
    assert set(report) == {"expert_load"}
    assert _rel(got, ref.logits(flat, bench_cfg(), ids[0, 0])) < RTOL
    chosen = ref.selections(flat, bench_cfg(), ids[0, 0])
    assert sorted(chosen) == [1, 2, 3, 4]  # layer 0 is dense
    held = list(MC.held_expert_ids)
    want_load = np.stack([np.asarray(chosen[i]).sum((0, 1))[held] for i in sorted(chosen)])
    np.testing.assert_array_equal(np.asarray(report["expert_load"]), want_load)


def test_bfloat16_forward_stands_by_the_float32_reference(flat, ids):
    """The cell's compute dtype. Every product's output is rounded to 8 bits of mantissa (2^-9 relative a rounding,
    through ten halves and a head over 64 inputs; the decay's pre-activation too, which the running sums then carry):
    observed 1.0e-2 on the logits and 1e-3 on the loss."""
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
    """Two optimizer steps through ``build_train_step`` (the normal path), at float32 masters for the gradients and
    at the cell's bfloat16 masters for the parameters' change, and the reference's two steps."""
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
                             lambda names: weights_kda_moe.make_flat(11, bench_cfg(), only=names),
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
    # what only this mixer has takes a gradient: the decay's pair, its two vectors, the gate's pair, each tap of q, k, v
    for leaf in ("f_a_proj/kernel", "f_b_proj/kernel", "A_log", "dt_bias", "g_a_proj/kernel", "g_b_proj/kernel", "b_proj/kernel"):
        assert np.abs(got[f"model/layers/1/linear_attn/{leaf}"]).max() > 0, leaf
    taps = got["model/layers/1/linear_attn/conv1d/weight"]
    assert (np.abs(taps).reshape(4, 3, -1).max(-1) > 0).all()


def test_two_steps_parameter_change_agrees_with_the_reference(two_steps):
    """The norm by leaf of what two AdamW steps changed, bfloat16 masters on both sides (the update computed in
    float32, the sum rounded once a step): the benchmark's own comparison. Where the two float32 sums differ in their
    last bits a rounding to bfloat16 falls the other way, an element here and there by 2^-8 of its value: held to
    3e-3 as in the other expert models' tests; a step left out, or a second step from the wrong moments, is 0.3 and
    more."""
    gap, where = check.worst_leaf_gap(two_steps["delta"], two_steps["want"]["delta_norms"])
    assert gap < 3e-3, (gap, where)
    assert all(float(jnp.abs(v).max()) == 0 for k, v in two_steps["frozen"].items() if k.endswith(BIAS))


def test_the_step_reports_its_expert_counters(two_steps):
    m = two_steps["metrics"]
    assert m["expert_load"].shape == (len(MC.held_expert_ids),)
    assert 0.6 < float(m["expert_pairs_per_token"]) < 1.4  # 4 of 16 chosen, 4 held: 1 pair a token expected
    assert 1.0 <= float(m["expert_load_max_over_mean"]) <= len(MC.held_expert_ids)


# -- the rule with a decay a channel --------------------------------------------


def _rule_inputs(seed, b, s, h, d, decay, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = ref.l2_norm(jax.random.normal(ks[0], (b, s, h, d))) * d ** -0.5
    k = ref.l2_norm(jax.random.normal(ks[1], (b, s, h, d)))
    v = jax.random.normal(ks[2], (b, s, h, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h)))
    if decay == "drawn":  # what the draw allows: -exp(A_log) softplus(.) between -16 x 0.1 and -1 x 0.001, and beyond both
        g = -jnp.exp(jax.random.uniform(ks[4], (b, s, h, d), minval=np.log(1e-3), maxval=np.log(16.0)))
    else:
        g = jnp.full((b, s, h, d), {"hardest": -16.0, "none": 0.0}[decay])
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


def _outputs_and_cotangents(fn, args):
    weigh = jnp.cos(jnp.arange(np.prod(args[2].shape), dtype=jnp.float32)).reshape(args[2].shape)
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weigh)  # noqa: E731
    return (fn(*args),) + jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("seq", [150, 37], ids=["two-chunks-and-a-part", "less-than-a-chunk"])
@pytest.mark.parametrize("decay", ["drawn", "hardest", "none"])
def test_chunked_rule_by_channel_equals_the_rule_token_by_token(decay, seq):
    """The chunked form (chunks of 64, sub-blocks of 16, a triangular inverse) against the reference's walk, the
    output and EVERY cotangent (``dg`` a channel), at rows that are no multiple of the chunk or of the sub-block; at
    ``g = -16`` a token on every channel, where ``exp(-G)`` passes ``exp(1000)`` inside a chunk (the overflow the
    sub-blocks exist for), everything is finite and right, and at ``g = 0`` (no decay at all) too. Errors against
    each array's own scale, with a floor: at the hardest decay ``dg`` is of the order of 1e-7 itself."""
    args = _rule_inputs(3, 2, seq, 3, 16, decay)
    got = _outputs_and_cotangents(gated_delta.gated_delta_rule, args)
    want = _outputs_and_cotangents(ref.delta_rule, args)
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - b).max()) < 1e-5 * max(float(jnp.abs(b).max()), 1e-2), name


def test_a_decay_averaged_over_the_channels_is_another_rule_and_calls_say_which(monkeypatch):
    monkeypatch.setattr(gated_delta, "CALLS", {})
    args = _rule_inputs(4, 1, 100, 2, 16, "drawn")
    want = ref.delta_rule(*args)
    by_channel = gated_delta.gated_delta_rule(*args)
    by_head = gated_delta.gated_delta_rule(*args[:3], args[3].mean(-1), args[4])
    assert _rel(by_channel, want) < 1e-5 < 1e-1 < _rel(by_head, want)
    forms = {shape: form for shape, (_, form) in gated_delta.CALLS.items()}
    assert forms == {(1, 100, 2, 2, 16, 16): "chunked 64: xla",
                     (1, 100, 2, 2, 16, 16, "by channel"): "chunked 64, a decay a channel in sub-blocks of 16: xla"}
    assert "a decay a channel in sub-blocks of 16" in gated_delta.calls_summary()


def test_bfloat16_operands_and_key_heads_that_serve_several_value_heads():
    """In the cell's dtype the rule stands by the float32 walk to bfloat16's grain (products of bfloat16 operands
    added up in float32, the decayed products and the state float32); a key head's value heads decay apart."""
    args = _rule_inputs(5, 2, 130, 2, 16, "drawn", jnp.bfloat16)
    want = ref.delta_rule(*(x.astype(jnp.float32) for x in args))
    got = gated_delta.gated_delta_rule(*args)
    assert got.dtype == jnp.bfloat16 and _rel(got, want) < 2e-2
    q, k, v, g, beta = _rule_inputs(6, 1, 70, 4, 16, "drawn")
    want = ref.delta_rule(jnp.repeat(q[:, :, :2], 2, axis=2), jnp.repeat(k[:, :, :2], 2, axis=2), v, g, beta)
    assert _rel(gated_delta.gated_delta_rule(q[:, :, :2], k[:, :, :2], v, g, beta), want) < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_out_pass_with_a_sigmoid_gate_as_kernels_equals_its_xla_form(dtype):
    """The gated norm's kernels (Pallas interpreter) with the gate's activation a sigmoid against the XLA form,
    output and every cotangent, on a row that is no whole token block; and it is not the silu's."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    o, z = (jax.random.normal(key, (1, 700, 256)).astype(dtype) for key in ks[:2])
    w = (1 + 0.3 * jax.random.normal(ks[2], (128,))).astype(dtype)
    run = lambda impl, act: (lambda *a: gated_delta.gated_norm(*a, 1e-5, activation=act, impl=impl))  # noqa: E731
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32) + 0.3)))  # noqa: E731
    every = lambda fn, args: jax.tree.leaves((fn(*args), jax.grad(loss(fn), argnums=(0, 1, 2))(*args)))  # noqa: E731
    got, want = every(run("kernels_interpret", "sigmoid"), (o, z, w)), every(run("xla", "sigmoid"), (o, z, w))
    exact = every(run("xla", "sigmoid"), tuple(x.astype(jnp.float32) for x in (o, z, w)))
    for a, b, c in zip(got, want, exact):
        assert a.dtype == b.dtype and bool(jnp.isfinite(a).all())
        assert _rel(a, c) < (1e-5 if dtype == jnp.float32 else max(1.25 * _rel(b, c), 2.0 ** -9))
    assert _rel(got[0], run("kernels_interpret", "silu")(o, z, w)) > 0.1


# -- the latent layer without rope ----------------------------------------------


def test_the_latent_layer_has_no_position_signal(flat):
    """One latent layer under ``mla_use_nope``: the keys a query sees are a SET, so the first 16 tokens in another
    order leave every position from 16 on as it was (to the order of a float32 sum), and positions shifted by 1000
    change nothing at all; the same layer WITH a rope moves on the first. And it is ``_latent_qkv(rope=True)`` under
    tables that rotate nothing (cos 1, sin 0)."""
    mc = MC.replace(num_layers=1, layer_types=("full_attention",), first_k_dense_replace=1)
    assert mc.layer(0).attention == "latent" and not mc.layer(0).rope
    params = init_params(jax.random.PRNGKey(1), mc)
    a = np.random.RandomState(2).randint(0, mc.vocab_size, (1, 48)).astype(np.int32)
    b = a.copy()
    b[0, :16] = a[0, :16][::-1]
    run = lambda cfg, x, **kw: np.asarray(forward(params, jnp.asarray(x), cfg, compute_dtype=jnp.float32, output_hidden=True, **kw)[0])  # noqa: E731
    shifted = dict(positions=jnp.arange(48)[None] + 1000)
    assert _rel(run(mc, b)[0, 16:], run(mc, a)[0, 16:]) < 1e-5
    np.testing.assert_array_equal(run(mc, a, **shifted), run(mc, a))
    roped = mc.replace(mla_use_nope=False)
    assert _rel(run(roped, b)[0, 16:], run(roped, a)[0, 16:]) > 1e-3  # (a shift alone a rope does not see: it is relative)
    attn_p = params["model"]["layers"]["0"]["self_attn"]
    hid = jax.random.normal(jax.random.PRNGKey(3), (2, 24, mc.hidden_size))
    lin = lambda x, p: x @ p["kernel"]  # noqa: E731
    ones, zeros = jnp.ones((1, 24, mc.qk_rope_head_dim)), jnp.zeros((1, 24, mc.qk_rope_head_dim))
    bare = transformer._latent_qkv(attn_p, hid, None, None, mc, lin, rope=False)
    for x, y in zip(bare[:3], transformer._latent_qkv(attn_p, hid, ones, zeros, mc, lin, rope=True)[:3]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert bare[0].shape == (2, 24, mc.num_heads, mc.qk_nope_head_dim + mc.qk_rope_head_dim) and bare[3] is None


# -- the configuration and the checkpoint ---------------------------------------


def test_published_config_builds_counts_and_round_trips():
    if not os.path.exists(CATALOG):
        pytest.skip("the driver's catalog is not installed here")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f if '"Kimi-Linear-48B-A3B-Instruct"' in line][0]
    mc = from_hf_config(SimpleNamespace(**row["config"]))  # verbatim
    assert dataclasses.replace(mc, name="kimi_linear_48b_a3b") == get_preset("kimi_linear_48b_a3b")
    # the lists are 1-based: layers 4, 8, ... 24 and 27 are the latent ones
    latent = [i + 1 for i in range(27) if mc.layer(i).attention == "latent"]
    assert latent == row["config"]["linear_attn_config"]["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert all(mc.layer(i).attention == "kda" for i in range(27) if i + 1 not in latent)
    assert (mc.linear_decay_rank, mc.linear_gate_rank, mc.linear_num_key_heads, mc.linear_key_head_dim) == (128, 128, 32, 128)
    assert (mc.mla_use_nope, mc.kv_lora_rank, mc.first_k_dense_replace, mc.max_position_embeddings) == (True, 512, 1, 1048576)
    assert (mc.router_scoring, mc.routed_scaling_factor, mc.n_routed_experts, mc.num_experts_per_tok, mc.n_shared_experts) == (
        "sigmoid", 2.446, 256, 8, 1)
    assert mc.num_params == 49_122_681_728  # "48B-A3B": 2.3% over 48 B, to the parameter
    cut = mc.replace(num_layers=5, vocab_size=20480, held_experts=tuple(range(8)))
    assert cut.num_params == 602_434_432  # the cell's 602.4 M
    layers = [cut.replace(num_layers=n).num_params for n in range(6)]
    assert [b - a for a, b in zip(layers, layers[1:])] == [103_219_872, 103_809_952, 103_809_952, 93_410_560, 103_809_952]
    for preset in ("kimi_linear_48b_a3b", "tiny_kimi_linear"):
        assert from_hf_config(SimpleNamespace(**to_hf_dict(get_preset(preset)))) == get_preset(preset)
    assert from_hf_config(SimpleNamespace(**to_hf_dict(mc))) == mc
    # a cut in depth keeps the published lists and reads them up to the depth
    assert from_hf_config(SimpleNamespace(**dict(row["config"], num_hidden_layers=5))).layer_types == (
        "linear_attention",) * 3 + ("full_attention", "linear_attention")


@pytest.mark.parametrize("key, value", [
    ("num_expert_group", 2), ("topk_group", 4), ("moe_layer_freq", 2), ("moe_renormalize", False),
    ("moe_router_activation_func", "softmax"), ("q_lora_rank", 1536), ("num_nextn_predict_layers", 1),
    ("linear_attn_config", {"kda_layers": [1, 2], "full_attn_layers": [2], "num_heads": 2, "head_dim": 16}),
    ("linear_attn_config", {"kda_layers": [1], "full_attn_layers": [], "num_heads": 2, "head_dim": 16}),
])
def test_what_is_not_implemented_is_refused_by_name(key, value):
    base = {k: v for k, v in bench_cfg().items() if k not in ("router_experts", "held_experts", "init_std", "embed_std")}
    base["num_experts"] = 16
    mc = from_hf_config(SimpleNamespace(**base))
    assert dataclasses.replace(mc, name=MC.name, held_experts=MC.held_experts) == MC
    with pytest.raises(ValueError, match="kimi_linear config has .*" + key):
        from_hf_config(SimpleNamespace(**dict(base, **{key: value})))


def test_the_mixers_fields_go_together():
    with pytest.raises(ValueError, match="linear_decay_rank and linear_gate_rank go together"):
        MC.replace(linear_gate_rank=0)
    with pytest.raises(ValueError, match="as many key heads as value heads"):
        MC.replace(linear_num_key_heads=2)
    assert ModelConfig().linear_decay_rank == 0 and not ModelConfig().mla_use_nope


def test_checkpoint_names_round_trip(flat):
    """HF kimi_linear's names: both mixers a layer's ``self_attn``, a convolution each for q, k, v in torch's
    ``[channels, 1, taps]`` (the tree's one leaf cut in three, and joined again on load), ``A_log [1, 1, heads, 1]``,
    ``o_norm`` / ``o_proj``, the experts a layer's ``block_sparse_moe`` with ``w1``/``w3``/``w2`` under the experts'
    global ids, the router its ``gate`` with the selection bias; the dense layer keeps ``mlp``."""
    params = _params(flat)
    state = hf_io.pytree_to_hf_state_dict(params, MC)
    kda, mla = "model.layers.1.self_attn.", "model.layers.3.self_attn."
    for name in ("q_proj.weight", "k_conv1d.weight", "A_log", "dt_bias", "f_a_proj.weight", "f_b_proj.weight", "b_proj.weight",
                 "g_a_proj.weight", "g_b_proj.weight", "o_norm.weight", "o_proj.weight"):
        assert kda + name in state, name
    for name in ("q_proj.weight", "kv_a_proj_with_mqa.weight", "kv_a_layernorm.weight", "kv_b_proj.weight", "o_proj.weight"):
        assert mla + name in state, name
    for name in ("gate.weight", f"gate.{BIAS}", "experts.3.w1.weight", "experts.0.w2.weight", "shared_experts.up_proj.weight"):
        assert "model.layers.1.block_sparse_moe." + name in state, name
    assert "model.layers.0.mlp.gate_proj.weight" in state
    assert not any("linear_attn" in k or ".mlp.experts" in k or ".mlp.gate." in k for k in state)
    wide = MC.linear_num_value_heads * MC.linear_key_head_dim
    taps = np.asarray(flat["model/layers/1/linear_attn/conv1d/weight"], np.float32)
    assert state[kda + "A_log"].shape == (1, 1, MC.linear_num_value_heads, 1)
    assert state[kda + "v_conv1d.weight"].shape == (wide, 1, 4) and state[kda + "f_b_proj.weight"].shape == (wide, MC.linear_decay_rank)
    np.testing.assert_array_equal(state[kda + "k_conv1d.weight"][:, 0, :].T, taps[:, wide:2 * wide])
    # without a rope the latent layer's columns keep the stored order (nothing to de-interleave)
    np.testing.assert_array_equal(state[mla + "q_proj.weight"].T, np.asarray(flat["model/layers/3/self_attn/q_proj/kernel"], np.float32))
    back = flatten_dict(hf_io.hf_state_dict_to_pytree(state, MC))
    for k, v in flatten_dict(params).items():
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(v), err_msg=k)


def test_the_32_shares_add_up_to_the_uncut_layer():
    """The share test, at the deployment's count of shares: 32 routed experts as 32 programs of one expert each,
    each told its share (``held_experts``) and handed its row of the expert leaves, the whole router and its bias:
    their routed outputs, with the shared expert (which every share computes alike) counted ONCE, add up to what
    the uncut reference gives for the whole layer (all 32 experts and the shared one)."""
    mc = MC.replace(n_routed_experts=32, num_experts_per_tok=8, held_experts=())
    whole = dict(bench_cfg(mc), num_experts=32, held_experts=list(range(32)))
    full = weights_kda_moe.make_flat(11, whole)
    lp = {k: v.astype(jnp.float32) for k, v in ref.layer_leaves(full, 2).items()}
    lp[f"mlp/gate/{BIAS}"] = 0.05 * jnp.cos(jnp.arange(32.0))  # a bias that moves choices, and no weight
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 40, mc.hidden_size), jnp.float32)
    items = dict(ref.cfg_items(whole))
    want = ref.experts(lp, h, items)
    total, loads = ref.experts(lp, h, items, held=()), []  # the shared expert, once
    for share in range(32):
        tree = {"gate": {"kernel": lp["mlp/gate/kernel"], BIAS: lp[f"mlp/gate/{BIAS}"]},
                "experts": {w: lp[f"mlp/experts/{w}"][share: share + 1] for w in ("w1", "w3", "w2")}}
        y, load = moe.grouped_moe_mlp(tree, h, mc.replace(held_experts=(share,)), jnp.float32)
        total, loads = total + y, loads + [int(load.sum())]
    assert _rel(total, want) < RTOL
    assert sum(loads) == 2 * 40 * 8  # every pair of every token is some share's


def test_sharding_freeze_and_what_a_block_keeps(monkeypatch):
    spec = jax.sharding.PartitionSpec
    assert param_spec("model/layers/1/linear_attn/q_proj/kernel", 2) == spec("fsdp", None)
    assert param_spec("model/layers/1/linear_attn/f_b_proj/kernel", 2) == spec(None, "fsdp")
    assert param_spec("model/layers/1/linear_attn/out_proj/kernel", 2) == spec(None, "fsdp")
    assert param_spec("model/layers/3/self_attn/kv_b_proj/kernel", 2) == spec("fsdp", "tensor")
    for leaf, ndim in (("conv1d/weight", 2), ("A_log", 1), ("dt_bias", 1), ("norm/weight", 1)):
        assert param_spec("model/layers/1/linear_attn/" + leaf, ndim) == spec()
    params = init_params(jax.random.PRNGKey(0), MC)
    tail = flatten_dict(trainable_mask(params, MC, TrainConfig(model_preset=None, freeze_strategy="last_n_and_head",
                                                                unfreeze_last_n_layers=2)))
    assert tail["model/layers/4/linear_attn/dt_bias"] and tail["model/layers/3/self_attn/kv_a_layernorm/weight"]
    assert not tail["model/layers/2/linear_attn/A_log"] and not tail[f"model/layers/4/mlp/gate/{BIAS}"]
    # a KDA block whose rule is XLA's scan (this CPU) recomputes it (128 + 64 x 2 = 256 operations a kept byte against
    # the hidden 2304); where the rule is the Pallas sweeps (a TPU at the model's heads of 128) it keeps both of the
    # forward sweep's outputs; the latent layer at 8192 keeps the flash kernel's o and lse (Moonlight's widths)
    big = get_preset("kimi_linear_48b_a3b")
    assert keeps_scan_output(big) == () and keeps_scan_output(big.replace(hidden_size=128)) == ("gdn_o",)
    with monkeypatch.context() as on_a_tpu:
        on_a_tpu.setattr(jax, "default_backend", lambda: "tpu")
        assert keeps_scan_output(big) == ("gdn_o", "gdn_states")
        assert keeps_scan_output(MC) == ("gdn_o",)  # heads of 16: the XLA form there too, 16 + 64 x 2 = 144 against 64
    assert keeps_flash_outputs(big, 8192, None) and not keeps_flash_outputs(big, 1024, None)
    assert moe.pairs_a_chunk(big.replace(held_experts=tuple(range(8)))) >= 1


def test_packed_rows_serving_and_the_pipeline_refuse_the_model_with_their_sentences(flat):
    from llm_fine_tune_distributed_tpu.infer.generate import Generator, LatentAttentionNotServed, unserved_layer_kind

    params, row = _params(flat), jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="restart at every segment boundary.*ROADMAP.md"):
        forward_with_report(params, row, MC, segment_ids=jnp.ones((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="training form only"):
        forward_with_report(params, row[:, :4], MC, cache=init_cache(MC, 1, 8))
    assert unserved_layer_kind(MC) == "latent"  # the first kind it meets keeps its sentence
    with pytest.raises(LatentAttentionNotServed, match="latent attention.*training path only"):
        Generator(params, MC, tokenizer=None)
    only_kda = MC.replace(layer_types=("linear_attention",) * 5)
    with pytest.raises(LatentAttentionNotServed, match="linear-attention layers.*recurrent state"):
        Generator({}, only_kda, tokenizer=None)
    (problem,) = layer_scan_problems(MC, seq_parallel=False)
    assert "layers 0 and 1" in problem and "feed_forward" in problem
    (problem,) = layer_scan_problems(MC.replace(first_k_dense_replace=0), seq_parallel=False)
    assert "layers 0 and 3" in problem and "attention ('kda' vs 'latent')" in problem


# -- what the tolerance must not let through ------------------------------------


def _logit_gap(flat, ids, mc=MC, params=None):
    got = forward_with_report(params or _params(flat), jnp.asarray(ids[0, 0]), mc, compute_dtype=jnp.float32)[0]
    return _rel(got, ref.logits(flat, bench_cfg(), ids[0, 0]))


def test_a_scalar_decay_fails_the_tolerance(flat, ids, monkeypatch):
    rule = gated_delta.gated_delta_rule
    monkeypatch.setattr(gated_delta, "gated_delta_rule", lambda q, k, v, g, beta, **kw: rule(q, k, v, g.mean(-1), beta, **kw))
    assert _logit_gap(flat, ids) > 10 * RTOL


def test_a_rotated_latent_key_or_a_bfloat16_state_fails_the_tolerance(flat, ids, monkeypatch):
    assert _logit_gap(flat, ids, MC.replace(mla_use_nope=False)) > 10 * RTOL
    monkeypatch.setattr(gated_delta, "STATE_DTYPE", jnp.bfloat16)
    assert _logit_gap(flat, ids) > 2 * RTOL  # (rows of 72 tokens carry ONE state across a boundary: 2.8e-4; a row of 8192, 127)


def test_a_silu_gate_or_a_bfloat16_router_fails_the_tolerance(flat, ids, monkeypatch):
    norm = gated_delta.gated_norm
    with monkeypatch.context() as m:
        m.setattr(gated_delta, "gated_norm", lambda *a, activation="silu", **kw: norm(*a, **kw))
        assert _logit_gap(flat, ids) > 10 * RTOL
    monkeypatch.setattr(moe, "ROUTER_DTYPE", jnp.bfloat16)
    assert _logit_gap(flat, ids) > 10 * RTOL
