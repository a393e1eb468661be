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

import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_suite import (
    BIAS, RECIPE, CellStep, Family, FamilySuite, Published, Refusals, Rules, Shares, _logits, _params, _rel,
    assert_two_sums_an_expert_layer, weights,
)
from llm_fine_tune_distributed_tpu.models import hf_io
from llm_fine_tune_distributed_tpu.models.configs import from_hf_config, get_preset, to_hf_dict
from llm_fine_tune_distributed_tpu.models.transformer import forward_with_report
from llm_fine_tune_distributed_tpu.ops import moe
from llm_fine_tune_distributed_tpu.ops.attention import xla_attention
from llm_fine_tune_distributed_tpu.ops.flash_attention import pallas_flash_attention

from benchmarks.chipbench import reference_mla_moe as ref, weights_mla_moe

MC = get_preset("tiny_mla_moe")
SEQ = 32
RTOL = 1e-4
PUBLISHED = {"attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 2048,
             "intermediate_size": 11264, "kv_lora_rank": 512, "max_position_embeddings": 8192,
             "model_type": "deepseek_v3", "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
             "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
             "num_experts_per_tok": 6, "num_hidden_layers": 27, "num_key_value_heads": 16,
             "num_nextn_predict_layers": 0, "q_lora_rank": None, "qk_nope_head_dim": 128,
             "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000, "routed_scaling_factor": 2.446,
             "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
             "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}


def bench_cfg(mc=MC) -> dict:
    """The benchmark's configuration dict (HF names) of a ModelConfig."""
    d = to_hf_dict(mc)
    return dict(d, router_experts=mc.n_routed_experts, n_routed_experts=len(mc.held_expert_ids),
                held_experts=list(mc.held_expert_ids), init_std=0.02, router_bias_std=0.02)


def _shared_experts(lp, h, items=None):
    return ref.swiglu(h, lp["mlp/shared_experts/gate_proj/kernel"], lp["mlp/shared_experts/up_proj/kernel"],
                      lp["mlp/shared_experts/down_proj/kernel"])


def _expert_layer(flat, layer=1):
    lp = {k: v.astype(jnp.float32) for k, v in ref.layer_leaves(flat, layer).items()}
    return lp, weights.nest({k: v for k, v in lp.items() if k.startswith("mlp/")})["mlp"]


FAMILY = Family(
    mc=MC, bench_cfg=bench_cfg, weights=weights_mla_moe, ref=ref, redraw=None, rows=2, seq=SEQ, accum=2,
    rtol=RTOL, delta_tol=3e-3,  # as the other expert models' (this family's two steps came with the suite, PR 45: observed 4e-4)
    pairs_per_token=(0.4, 1.1),  # 3 of 16 chosen, 4 held: 0.75 pairs a token expected
    buffers=tuple(f"model/layers/{i}/mlp/gate/{BIAS}" for i in (1, 2)),
    checkpoint_names=tuple("model.layers.1." + name for name in (
        "self_attn.kv_a_proj_with_mqa.weight", "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj.weight",
        "mlp.gate.weight", "mlp.gate.e_score_correction_bias", "mlp.experts.3.gate_proj.weight",
        "mlp.experts.0.down_proj.weight", "mlp.shared_experts.up_proj.weight")),
    # four processes hold 4 of the 16 experts each, all route over all 16 (the bias as the benchmark draws it): their
    # routed parts plus the shared experts once are the whole layer
    shares=Shares(count=4, layer=1, tokens=SEQ, experts_key="n_routed_experts", shared_once=_shared_experts, bias=False, mc=None),
    refusals=Refusals(
        base=dict(to_hf_dict(get_preset("moonlight_16b_a3b")), model_type="deepseek_v3"),
        cases=(("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4), ("rope_scaling", {"type": "yarn", "factor": 40}),
               ("scoring_func", "tanh"), ("norm_topk_prob", False)),
        match=lambda key: key),
    published=Published(catalog_name="Moonlight-16B-A3B", preset="moonlight_16b_a3b", tiny="tiny_mla_moe", params=(15.9e9, 16.0e9),
                        cut=dict(num_layers=6, vocab_size=20480, held_experts=tuple(range(8))), cut_params=668_890_432),  # the cell's 668.9 M
    rules=Rules(
        specs={"model/layers/1/" + path: spec for path, spec in {
            "self_attn/kv_a_proj_with_mqa/kernel": (2, ("fsdp", None)), "self_attn/kv_b_proj/kernel": (2, ("fsdp", "tensor")),
            "mlp/shared_experts/down_proj/kernel": (2, ("tensor", "fsdp")), "mlp/experts/w1": (3, ("expert", "fsdp", "tensor")),
            "mlp/gate/kernel": (2, ("fsdp", None)), "mlp/gate/" + BIAS: (1, ())}.items()},
        mc=MC, unfreeze_last_n=1, trained=("model/layers/2/self_attn/kv_b_proj/kernel", "model/layers/2/mlp/gate/kernel"),
        held=("model/layers/1/mlp/experts/w2", f"model/layers/2/mlp/gate/{BIAS}"),
        scan_problems=(({}, ("layers 0 and 1", "feed_forward")),)),
    # the dense layer and one expert layer at the published widths (this chip's share: 8 of 64 experts, an eighth of the
    # vocabulary), every parameter trained, one row of 4096 a microbatch
    cell=CellStep(preset="moonlight_16b_a3b", seq=4096, rows=1, float32_moments=False,
                  overrides=dict(num_layers=2, vocab_size=20480, held_experts=tuple(range(8)))),
)


class TestMoonlight(FamilySuite):
    family = FAMILY

    def check_counters(self, two_steps, ids):
        """The step's counters are the report's expert_load, microbatch by microbatch."""
        m, held = two_steps["metrics"], len(MC.held_expert_ids)
        params = weights.nest({**two_steps["state"].trainable, **two_steps["state"].frozen})
        loads = [_logits(params, micro, MC)[1]["expert_load"] for micro in ids[0]]
        assert loads[0].shape == (MC.num_layers - 1, held)  # [expert layers, held]
        pairs = sum(int(load.sum()) for load in loads)
        np.testing.assert_allclose(float(m["expert_pairs_per_token"]), pairs / (ids[0].size * (MC.num_layers - 1)), rtol=1e-6)
        np.testing.assert_allclose(float(m["expert_load"].sum()), float(m["expert_pairs_per_token"]), rtol=1e-6)

    def check_published(self, mc, config):
        assert config == dict(PUBLISHED, ep_size=1, seq_aux=True)
        assert from_hf_config(SimpleNamespace(**PUBLISHED)) == mc

    def check_checkpoint(self, state, params, flat):
        """...with the rope columns in stored order: DeepSeek stores each rotated pair adjacent; the model rotates halves."""
        layer = "model.layers.1."
        assert state[layer + "mlp.gate.weight"].shape == (MC.n_routed_experts, MC.hidden_size)
        dn, dr = MC.qk_nope_head_dim, MC.qk_rope_head_dim
        stored = state[layer + "self_attn.q_proj.weight"].T.reshape(MC.hidden_size, MC.num_heads, dn + dr)
        ours = np.asarray(params["model"]["layers"]["1"]["self_attn"]["q_proj"]["kernel"]).reshape(stored.shape)
        np.testing.assert_array_equal(stored[..., dn::2], ours[..., dn:dn + dr // 2])
        np.testing.assert_array_equal(stored[..., dn + 1::2], ours[..., dn + dr // 2:])
        np.testing.assert_array_equal(stored[..., :dn], ours[..., :dn])
        with pytest.raises(ValueError, match="config"):
            hf_io.pytree_to_hf_state_dict(params)

    def check_the_cells_step(self, step):
        """The flash kernels at 192/128 are in the step. (With the held experts'
        load counted by ``bincount`` this program aborted the compiler:
        ops/moe.py.) Rows of 4096 at heads of 192/128 over a hidden size of 2048:
        each block keeps the forward kernel's o and lse across its remat boundary,
        so the forward kernel is in the step once a layer and not a second time in
        the backward pass (under ``full`` too; tests/test_flash_remat.py)."""
        assert step.calls("flash_attention_fwd") == step.layers
        assert step.calls("flash_attention_dq") == step.calls("flash_attention_dkv") == step.layers
        assert_two_sums_an_expert_layer(step.text, step.layers - 1)

    def test_only_the_selection_bias_is_frozen(self, two_steps):
        assert tuple(sorted(two_steps["state"].frozen)) == FAMILY.buffers

    def test_one_optimizer_steps_parameter_change(self, two_steps):
        """Adam's first step moves every element by the learning rate times
        g / (|g| + eps): the program's change at float32 masters against that rule
        applied to the reference's own (clipped) gradient."""
        before, after = two_steps["state"].trainable, two_steps["new"].trainable
        for k, g in two_steps["want"]["first_grad"].items():
            update = -RECIPE["learning_rate"] * g / (np.abs(g) + RECIPE["adam_eps"])
            got = np.asarray(after[k]) - np.asarray(before[k])
            assert np.linalg.norm(got - update) <= 1e-3 * np.linalg.norm(update) + 1e-9, k

    @pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
    @pytest.mark.parametrize("pulled", [(0,), (0, 1, 2), (5,)], ids=["one-held", "all-k-held", "none-held"])
    def test_no_token_is_dropped_whatever_the_routing(self, flat, pulled, impl):
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
        h = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, MC.hidden_size), jnp.float32)
        y, load = moe.grouped_moe_mlp(tree, h, MC, jnp.float32, impl=impl)
        cfg = dict(ref.cfg_items(bench_cfg()))
        with jax.default_matmul_precision("highest"):
            want = ref.experts(lp, h, cfg) - _shared_experts(lp, h)
        assert _rel(y, want) < RTOL
        for e in pulled:
            if e in MC.held_expert_ids:
                assert int(load[MC.held_expert_ids.index(e)]) == 2 * SEQ
        if pulled == (0, 1, 2):
            assert int(load.sum()) == 3 * 2 * SEQ
        # and the gradient passes through the chunks past the first
        g = jax.grad(lambda t: moe.grouped_moe_mlp(t, h, MC, jnp.float32, impl=impl)[0].sum())(tree)
        assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))
        with jax.default_matmul_precision("highest"):
            want_g = jax.grad(lambda w: (ref.experts(w, h, cfg) - _shared_experts(w, h)).sum())(lp)
        for name in ("w1", "w2", "w3"):
            assert _rel(g["experts"][name], want_g["mlp/experts/" + name]) < RTOL, name
        assert _rel(g["gate"]["kernel"], want_g["mlp/gate/kernel"]) < 10 * RTOL

    def test_serving_refuses_latent_attention_by_name(self, flat):
        from llm_fine_tune_distributed_tpu.infer.generate import Generator, LatentAttentionNotServed
        from llm_fine_tune_distributed_tpu.models.transformer import init_cache

        with pytest.raises(LatentAttentionNotServed, match="training path only"):
            Generator(_params(flat), MC, tokenizer=None)
        # ...and the model's own cache function refuses a cache handed to it directly
        with pytest.raises(NotImplementedError, match="training form only"):
            forward_with_report(_params(flat), jnp.zeros((1, 4), jnp.int32), MC, cache=init_cache(MC, 1, 8))

    def test_the_new_scopes_reach_the_lowered_step(self, two_steps):
        """``router``, ``experts`` and ``shared_expert`` lie inside ``mlp`` of the
        expert layers and nowhere in the dense one."""
        names = set(re.findall(r'op_name="([^"]+)"', two_steps["text"]))
        for name in ("router", "experts", "shared_expert"):
            inside = [n for n in names if n.startswith("jit(train_step)") and f"/mlp/{name}/" in n]
            assert inside, name
            assert all(re.search(r"layer[12]\b", n) for n in inside), name
            assert any("transpose(" in n for n in inside), f"{name}: no backward operation carries the scope"


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
