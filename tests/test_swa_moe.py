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

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_suite import (
    CellStep, Family, FamilySuite, Published, Refusals, Rules, Shares, _logit_gap, _rel, assert_two_sums_an_expert_layer, weights,
)
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import keeps_flash_outputs, rope_tables
from llm_fine_tune_distributed_tpu.ops import moe
from llm_fine_tune_distributed_tpu.ops.rope import rope_inv_freq, yarn_attention_factor

from benchmarks.chipbench import reference_swa_moe as ref, weights_swa_moe

MC = get_preset("tiny_mellum")
SEQ = 64  # rows twice the window of 32
RTOL = 1e-4


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


_ROPES = {"full_attention": {"rope_type": "default", "rope_theta": 1e4}, "sliding_attention": {"rope_type": "default", "rope_theta": 1e4}}

FAMILY = Family(
    mc=MC, bench_cfg=bench_cfg, weights=weights_swa_moe, ref=ref, redraw=None, rows=2, seq=SEQ, accum=2,
    rtol=RTOL, delta_tol=3e-3,  # observed 7e-4 of the worst leaf's change (a router's kernel)
    pairs_per_token=(0.6, 1.4),  # 4 of 16 chosen, 4 held: 1 pair a token expected
    buffers=(),  # nothing frozen, and no buffer among the leaves
    # HF per-expert Linears under ``mlp.experts.<global id>`` (the names Qwen3-MoE and DeepSeek-V3 store; Mellum's
    # checkpoint is taken to store them alike), the router as ``mlp.gate.weight``, no bias, no shared expert
    checkpoint_names=tuple("model.layers.3." + name for name in (
        "self_attn.q_proj.weight", "mlp.gate.weight", "mlp.experts.3.gate_proj.weight", "mlp.experts.0.down_proj.weight")),
    # experts 0-3, 4-7, 8-11 and 12-15 as four programs; nothing every share computes alike: no shared expert
    shares=Shares(count=4, layer=1, tokens=SEQ, experts_key="num_experts", shared_once=None, bias=False, mc=None),
    refusals=Refusals(
        base=dict(model_type="mellum", vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                  num_attention_heads=2, num_key_value_heads=2, sliding_window=8, num_experts=4, num_experts_per_tok=2,
                  moe_intermediate_size=16, layer_types=["sliding_attention", "full_attention"], rope_parameters=_ROPES),
        cases=(("mlp_layer_types", ["dense", "sparse"]), ("norm_topk_prob", False),
               ("rope_parameters", {"full_attention": {"rope_type": "longrope", "rope_theta": 1e4}, "sliding_attention": {"rope_theta": 1e4}}),
               ("rope_parameters", {"full_attention": {"rope_type": "default", "rope_theta": 1e4}, "sliding_attention": {"rope_theta": 1e6}}),
               ("layer_types", ["sliding_attention"])),
        match=lambda key: key.split("_")[0]),
    published=Published(catalog_name="Mellum2-12B-A2.5B-Instruct", preset="mellum2_12b_a2_5b", tiny="tiny_mellum", params=(12.1e9, 12.2e9),
                        cut=dict(num_layers=4, vocab_size=24576, held_experts=tuple(range(16))), cut_params=595_153_152),  # the cell's 595.2 M
    rules=Rules(
        specs={"model/layers/0/mlp/experts/w1": (3, ("expert", "fsdp", "tensor")), "model/layers/0/mlp/gate/kernel": (2, ("fsdp", None))},
        mc=MC, unfreeze_last_n=1, trained=("model/layers/3/mlp/gate/kernel",), held=("model/layers/2/mlp/experts/w2",),
        scan_problems=(({}, ("layers 0 and 3", "window", "rope_kind")),)),
    # one window layer and one global layer at the published widths (this chip's share: 16 of 64 experts, a quarter of
    # the vocabulary), every parameter trained, one row of 8192 a microbatch
    cell=CellStep(preset="mellum2_12b_a2_5b", seq=8192, rows=1, float32_moments=False,
                  overrides=dict(num_layers=2, vocab_size=24576, held_experts=tuple(range(16)), layer_types=("sliding_attention", "full_attention"))),
)


class TestMellum(FamilySuite):
    family = FAMILY

    def check_leaves(self, own):
        assert not any("e_score_correction_bias" in k or "shared_experts" in k for k in own)

    def check_refusal_base(self, mc):
        assert mc.layer(1).window is None

    def check_checkpoint(self, state, params, flat):
        assert state["model.layers.3.mlp.gate.weight"].shape == (MC.n_routed_experts, MC.hidden_size)
        assert not any("e_score_correction_bias" in k or "shared_experts" in k for k in state)

    def check_the_cells_step(self, step):
        """Both kinds of layer run the streamed flash kernels (no ``[8192, 8192]``
        scores in the program), the window layer's forward kernel twice
        (recomputed: 1920 against the hidden 2304) and the global layer's once
        (kept), and the sums of rows into tokens are in the step."""
        assert step.calls("flash_attention_window_fwd") == 2 and step.calls("flash_attention_causal_fwd") == 1
        for kernel in ("window_dq", "window_dkv", "causal_dq", "causal_dkv"):
            assert step.calls(f"flash_attention_{kernel}") == 1
        assert step.calls("flash_attention_fwd") == 0  # no resident kernel at 8 queries a kv head and 8192
        assert not re.search(r"\[[0-9,]*8192,8192\]", step.text), "a [seq, seq] buffer in the step"
        assert_two_sums_an_expert_layer(step.text, step.layers)

    # -- what the tolerance must not let through (the router in bfloat16: the suite's) ------

    def test_a_dropped_window_fails_the_tolerance(self, flat, ids):
        assert _logit_gap(FAMILY, flat, ids, MC.replace(layer_types=("full_attention",) * 4)) > 10 * RTOL
        assert _logit_gap(FAMILY, flat, ids, MC.replace(sliding_window=33)) > 10 * RTOL  # one key too many

    def test_a_rope_of_the_wrong_kind_fails_the_tolerance(self, flat, ids):
        assert _logit_gap(FAMILY, flat, ids, MC.replace(rope_attention_factor=1.0)) > 10 * RTOL  # YaRN's factor left off
        assert _logit_gap(FAMILY, flat, ids, MC.replace(rope_scaling_layer_type=None)) > 10 * RTOL  # YaRN on the window layers too

    @pytest.mark.parametrize("pulled, chunks", [((0,), 1), ((0, 1, 2, 3), 2), ((9,), 1)],
                             ids=["one-held", "all-k-held", "none-held"])
    def test_no_token_is_dropped_whatever_the_routing(self, flat, pulled, chunks):
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


def test_a_window_layer_recomputes_its_flash_forward_and_a_global_layer_keeps_it():
    """``worth_keeping_across_remat`` reads the keys a query sees: at 8192
    tokens a window of 1024 is 1024 x (2 - 1/8) = 1920 against the hidden
    2304, the global layer 8192."""
    big = get_preset("mellum2_12b_a2_5b")
    assert not keeps_flash_outputs(big, 8192, 1024) and keeps_flash_outputs(big, 8192, None)
    assert not keeps_flash_outputs(big, 2048, None)  # 2048 against 2304
    assert keeps_flash_outputs(big, 8192, 8192)  # a window as long as the row is none
