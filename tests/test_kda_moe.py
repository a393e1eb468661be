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
is held to the reference's selection exactly. The rule alone (its XLA form and
its kernels against the walk): ``tests/test_kda_kernels.py``.
"""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_suite import (
    BIAS, CellStep, Family, FamilySuite, Published, Refusals, Rules, Shares, _bfloat16_gaps, _logit_gap, _params, _rel,
    compiled_cells_step, kernel_passes, mixer_passes, xla_remats,
)
from llm_fine_tune_distributed_tpu.config import ModelConfig
from llm_fine_tune_distributed_tpu.models import transformer
from llm_fine_tune_distributed_tpu.models.configs import from_hf_config, get_preset, to_hf_dict
from llm_fine_tune_distributed_tpu.models.transformer import (
    forward, forward_with_report, init_cache, init_params, keeps_flash_outputs, keeps_scan_output,
)
from llm_fine_tune_distributed_tpu.observe.xla import mosaic_programs
from llm_fine_tune_distributed_tpu.ops import gated_delta, moe
from llm_fine_tune_distributed_tpu.ops.attention import dispatch_summary

from benchmarks.chipbench import reference_kda_moe as ref, weights_kda_moe

MC = get_preset("tiny_kimi_linear")
SEQ = 72  # rows of a chunk and a half: the rule pads, and its second chunk starts from a state
RTOL, BF16_RTOL = 1e-4, 4e-2
# The rule with a decay a CHANNEL in the Kimi cell's step as its two sweeps landed (PR 43): distinct Mosaic programs by
# kernel and their serialized modules' bytes together: a warm start of that cell pays for this text (and no longer for
# the XLA form's Python loops over sub-blocks). Since PR 48 the backward sweep reads each chunk's ``T`` and decayed
# products and holds neither ``_kda_products`` nor the inverse: 78,092 -> 60,412 bytes, the forward sweep's 35,620 ->
# 37,420 for the two writes (113,712 -> 97,832 together).
KDA_RULE_PROGRAMS = {"kda_rule_fwd": 1, "kda_rule_bwd": 1}
KDA_RULE_MODULE_BYTES = 97_832


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



_KDA, _MLA, _MOE = "model.layers.1.self_attn.", "model.layers.3.self_attn.", "model.layers.1.block_sparse_moe."

FAMILY = Family(
    mc=MC, bench_cfg=bench_cfg, weights=weights_kda_moe, ref=ref, redraw=None, rows=2, seq=SEQ, accum=2,
    rtol=RTOL, delta_tol=3e-3,  # as in the other expert models' tests
    pairs_per_token=(0.6, 1.4),  # 4 of 16 chosen, 4 held: 1 pair a token expected
    buffers=tuple(f"model/layers/{i}/mlp/gate/{BIAS}" for i in (1, 2, 3, 4)),  # layer 0 is dense
    # HF kimi_linear's names: both mixers a layer's ``self_attn``, a convolution each for q, k, v in torch's ``[channels,
    # 1, taps]`` (the tree's one leaf cut in three, and joined again on load), ``A_log [1, 1, heads, 1]``, ``o_norm`` /
    # ``o_proj``, the experts a layer's ``block_sparse_moe`` with ``w1``/``w3``/``w2`` under the experts' global ids, the
    # router its ``gate`` with the selection bias; the dense layer keeps ``mlp``
    checkpoint_names=tuple(_KDA + name for name in (
        "q_proj.weight", "k_conv1d.weight", "A_log", "dt_bias", "f_a_proj.weight", "f_b_proj.weight", "b_proj.weight",
        "g_a_proj.weight", "g_b_proj.weight", "o_norm.weight", "o_proj.weight",
    )) + tuple(_MLA + name for name in (
        "q_proj.weight", "kv_a_proj_with_mqa.weight", "kv_a_layernorm.weight", "kv_b_proj.weight", "o_proj.weight",
    )) + tuple(_MOE + name for name in (
        "gate.weight", f"gate.{BIAS}", "experts.3.w1.weight", "experts.0.w2.weight", "shared_experts.up_proj.weight",
    )) + ("model.layers.0.mlp.gate_proj.weight",),
    # at the deployment's count of shares: 32 routed experts as 32 programs of one expert each, the shared expert once
    shares=Shares(count=32, layer=2, tokens=40, experts_key="num_experts", bias=True,
                  mc=MC.replace(n_routed_experts=32, num_experts_per_tok=8, held_experts=()),
                  shared_once=lambda lp, h, items: ref.experts(lp, h, items, held=())),
    refusals=Refusals(
        base=dict({k: v for k, v in bench_cfg().items() if k not in ("router_experts", "held_experts", "init_std", "embed_std")},
                  num_experts=16),
        cases=(("num_expert_group", 2), ("topk_group", 4), ("moe_layer_freq", 2), ("moe_renormalize", False),
               ("moe_router_activation_func", "softmax"), ("q_lora_rank", 1536), ("num_nextn_predict_layers", 1),
               ("linear_attn_config", {"kda_layers": [1, 2], "full_attn_layers": [2], "num_heads": 2, "head_dim": 16}),
               ("linear_attn_config", {"kda_layers": [1], "full_attn_layers": [], "num_heads": 2, "head_dim": 16})),
        match=lambda key: "kimi_linear config has .*" + key),
    published=Published(catalog_name="Kimi-Linear-48B-A3B-Instruct", preset="kimi_linear_48b_a3b", tiny="tiny_kimi_linear",
                        params=(49.1e9, 49.2e9),  # "48B-A3B": 2.3% over 48 B
                        cut=dict(num_layers=5, vocab_size=20480, held_experts=tuple(range(8))), cut_params=602_434_432),  # the cell's 602.4 M
    rules=Rules(
        specs={"model/layers/" + path: spec for path, spec in {
            "1/linear_attn/q_proj/kernel": (2, ("fsdp", None)), "1/linear_attn/f_b_proj/kernel": (2, (None, "fsdp")),
            "1/linear_attn/out_proj/kernel": (2, (None, "fsdp")), "3/self_attn/kv_b_proj/kernel": (2, ("fsdp", "tensor")),
            "1/linear_attn/conv1d/weight": (2, ()), "1/linear_attn/A_log": (1, ()), "1/linear_attn/dt_bias": (1, ()),
            "1/linear_attn/norm/weight": (1, ())}.items()},
        mc=MC, unfreeze_last_n=2, trained=("model/layers/4/linear_attn/dt_bias", "model/layers/3/self_attn/kv_a_layernorm/weight"),
        held=("model/layers/2/linear_attn/A_log", f"model/layers/4/mlp/gate/{BIAS}"),
        scan_problems=(({}, ("layers 0 and 1", "feed_forward")),
                       (dict(first_k_dense_replace=0), ("layers 0 and 3", "attention ('kda' vs 'latent')")))),
    # one period at the published widths (KDA, KDA, latent attention without rope, KDA; this chip's share: 8 of 256
    # experts, an eighth of the vocabulary), every parameter trained but the selection bias, the cell's 2 rows of 8192 a
    # microbatch, two microbatches, Adam's moments float32 as the cell holds them
    cell=CellStep(preset="kimi_linear_48b_a3b", seq=8192, rows=2, float32_moments=True,
                  overrides=dict(num_layers=4, first_k_dense_replace=0, vocab_size=20480, held_experts=tuple(range(8)),
                                 layer_types=("linear_attention", "linear_attention", "full_attention", "linear_attention"))),
)


class TestKimiLinear(FamilySuite):
    family = FAMILY

    def check_leaves(self, own):
        # two mixers and two feed-forwards in one model
        assert [MC.layer(i).attention for i in range(5)] == ["kda", "kda", "kda", "latent", "kda"]
        assert [MC.layer(i).feed_forward for i in range(5)] == ["dense"] + ["grouped_experts"] * 4
        assert not any(MC.layer(i).rope for i in range(5))  # the latent layer rotates nothing (mla_use_nope)
        assert "model/layers/0/linear_attn/f_a_proj/kernel" in own and "model/layers/3/self_attn/kv_a_proj_with_mqa/kernel" in own
        # the draw: a log decay's scale in [1, 16] a head, softplus(dt_bias) in [0.001, 0.1] a channel
        a, dt = np.exp(np.asarray(own["model/layers/0/linear_attn/A_log"])), np.asarray(jax.nn.softplus(own["model/layers/0/linear_attn/dt_bias"]))
        assert 1.0 <= a.min() and a.max() <= 16.0 and 0.99e-3 < dt.min() and dt.max() < 0.101

    def check_gradients(self, got):
        # what only this mixer has takes a gradient: the decay's pair, its two vectors, the gate's pair, each tap of q, k, v
        for leaf in ("f_a_proj/kernel", "f_b_proj/kernel", "A_log", "dt_bias", "g_a_proj/kernel", "g_b_proj/kernel", "b_proj/kernel"):
            assert np.abs(got[f"model/layers/1/linear_attn/{leaf}"]).max() > 0, leaf
        taps = got["model/layers/1/linear_attn/conv1d/weight"]
        assert (np.abs(taps).reshape(4, 3, -1).max(-1) > 0).all()

    def check_published(self, mc, config):
        # the lists are 1-based: layers 4, 8, ... 24 and 27 are the latent ones
        latent = [i + 1 for i in range(27) if mc.layer(i).attention == "latent"]
        assert latent == config["linear_attn_config"]["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
        assert all(mc.layer(i).attention == "kda" for i in range(27) if i + 1 not in latent)
        assert (mc.linear_decay_rank, mc.linear_gate_rank, mc.linear_num_key_heads, mc.linear_key_head_dim) == (128, 128, 32, 128)
        assert (mc.mla_use_nope, mc.kv_lora_rank, mc.first_k_dense_replace, mc.max_position_embeddings) == (True, 512, 1, 1048576)
        assert (mc.router_scoring, mc.routed_scaling_factor, mc.n_routed_experts, mc.num_experts_per_tok, mc.n_shared_experts) == (
            "sigmoid", 2.446, 256, 8, 1)
        assert mc.num_params == 49_122_681_728  # to the parameter
        cut = mc.replace(**FAMILY.published.cut)
        layers = [cut.replace(num_layers=n).num_params for n in range(6)]
        assert [b - a for a, b in zip(layers, layers[1:])] == [103_219_872, 103_809_952, 103_809_952, 93_410_560, 103_809_952]
        assert from_hf_config(SimpleNamespace(**to_hf_dict(mc))) == mc
        # a cut in depth keeps the published lists and reads them up to the depth
        assert from_hf_config(SimpleNamespace(**dict(config, num_hidden_layers=5))).layer_types == (
            "linear_attention",) * 3 + ("full_attention", "linear_attention")

    def check_refusal_base(self, mc):
        assert dataclasses.replace(mc, name=MC.name, held_experts=MC.held_experts) == MC

    def check_checkpoint(self, state, params, flat):
        assert not any("linear_attn" in k or ".mlp.experts" in k or ".mlp.gate." in k for k in state)
        wide = MC.linear_num_value_heads * MC.linear_key_head_dim
        taps = np.asarray(flat["model/layers/1/linear_attn/conv1d/weight"], np.float32)
        assert state[_KDA + "A_log"].shape == (1, 1, MC.linear_num_value_heads, 1)
        assert state[_KDA + "v_conv1d.weight"].shape == (wide, 1, 4) and state[_KDA + "f_b_proj.weight"].shape == (wide, MC.linear_decay_rank)
        np.testing.assert_array_equal(state[_KDA + "k_conv1d.weight"][:, 0, :].T, taps[:, wide:2 * wide])
        # without a rope the latent layer's columns keep the stored order (nothing to de-interleave)
        np.testing.assert_array_equal(state[_MLA + "q_proj.weight"].T, np.asarray(flat["model/layers/3/self_attn/q_proj/kernel"], np.float32))

    def check_rules(self, monkeypatch):
        """...and what a block keeps: a KDA block whose rule is XLA's scan (this CPU) recomputes it (128 + 64 x 2 = 256
        operations a kept byte against the hidden 2304); where the rule is the Pallas sweeps (a TPU at the model's
        heads of 128) it keeps ALL FOUR of the forward sweep's outputs (``o``, the states and, since PR 48, what the
        backward sweep made again before: ``T`` beside the decayed ``k k^T``, and ``P``); the latent layer at 8192 keeps
        the flash kernel's o and lse (Moonlight's widths)."""
        big = get_preset("kimi_linear_48b_a3b")
        assert keeps_scan_output(big) == () and keeps_scan_output(big.replace(hidden_size=128)) == ("gdn_o",)
        with monkeypatch.context() as on_a_tpu:
            on_a_tpu.setattr(jax, "default_backend", lambda: "tpu")
            assert keeps_scan_output(big) == ("gdn_o", "gdn_states", "kda_t_kk", "kda_p")
            assert keeps_scan_output(MC) == ("gdn_o",)  # heads of 16: the XLA form there too, 16 + 64 x 2 = 144 against 64
        assert keeps_flash_outputs(big, 8192, None) and not keeps_flash_outputs(big, 1024, None)
        assert moe.pairs_a_chunk(big.replace(held_experts=tuple(range(8)))) >= 1

    def before_the_cells_step(self, monkeypatch):
        monkeypatch.setattr(gated_delta, "CALLS", {})

    def check_the_cells_step(self, step):
        """The compiler's own count stays under the cell's memory line (15.0 GiB for the five layers: these four hold
        0.1 G of state less) and at its landed value (11.91 GiB since PR 48 keeps each chunk's ``T`` and decayed
        products, 320 MiB a KDA layer and microbatch; 11.79 GiB with the rule's kernels, PR 43; 14.576 while the XLA
        form held a row's ``U``, ``W``, ``P`` and decayed operands of all chunks); the latent layer takes the RESIDENT
        flash kernels at q/k 192 against v 128, one query a kv head, on a row of 8192 (``dispatch_summary()`` says
        which set), its forward kernel once (``o`` and ``lse`` kept); each KDA layer's rule is the two Pallas sweeps
        for a decay a channel (``kda_rule_fwd`` ONCE and never under a ``rematted_computation`` path, every one of
        its four outputs kept across the block's remat (PRs 44 and 48; the case below shows the count can fail),
        ``kda_rule_bwd`` once; XLA's triangular solve is out of the step and it rematerializes nothing of
        its own) between the two fused passes' kernels, the out pass with its sigmoid gate; ``kda_gates`` is on the
        step's operations; ``CALLS`` names the kernel form; and the sweeps' Mosaic programs and serialized bytes are
        held where they landed (``KDA_RULE_PROGRAMS``, ``KDA_RULE_MODULE_BYTES``), as the scalar rule's are in the
        Qwen3-Next step."""
        text = step.text
        assert step.compiled.memory_analysis().peak_memory_in_bytes <= 12.0 * 2**30 < 15.0 * 2**30
        for kernel in ("fwd", "dq", "dkv"):
            assert step.calls(f"flash_attention_{kernel}") == 1, kernel  # the resident set, the forward kernel kept
            assert step.calls(f"flash_attention_causal_{kernel}") == 0, kernel
        assert "resident causal" in dispatch_summary()
        sweeps = kernel_passes(text, "linear_attn/gdn_scan", r"\w+_rule_\w+")
        assert sweeps == sorted(
            sweep for i in (0, 1, 3) for sweep in ((f"jvp(layer{i})", "", "kda_rule_fwd"), (f"transpose(jvp(layer{i}))", "", "kda_rule_bwd"))), sweeps
        assert "triangular" not in text.lower() and not xla_remats(text)
        passes = kernel_passes(text, "linear_attn/(gdn_conv|gdn_gate_norm)", r"gdn_(?:in|out)_\w+")
        assert passes == mixer_passes((0, 1, 3)), passes
        for inside in ("linear_attn/kda_gates", "linear_attn/gdn_scan", "attn/"):
            assert any(f"/{inside}" in name for name in step.names), inside
        assert {form for _, form in gated_delta.CALLS.values()} == {"chunked 64, a decay a channel in sub-blocks of 16: kernels"}
        assert set(gated_delta.CALLS) == {(2, 8192, 32, 32, 128, 128, "by channel")}
        # what a start of the process pays for the sweeps, warm cache or not (the Qwen3-Next step's test: why): their text
        found = {name: x for name, x in mosaic_programs(step.lowered.as_text()).items() if name.endswith(("_rule_fwd", "_rule_bwd"))}
        assert {name: x["programs"] for name, x in found.items()} == KDA_RULE_PROGRAMS, found
        assert sum(x["bytes"] for x in found.values()) <= 1.2 * KDA_RULE_MODULE_BYTES, found

    def test_a_block_that_keeps_none_of_the_rules_names_runs_the_forward_sweep_twice(self, topo, monkeypatch):
        """What ``check_the_cells_step``'s count of sweeps can see: ONE KDA layer of the cell's step under a policy that
        saves none of the rule's names (``keeps_scan_output`` made to answer as it does for the XLA form at these
        widths) holds ``kda_rule_fwd`` a second time, under a ``rematted_computation`` path; with the names kept
        (above) that call is not in the program."""
        monkeypatch.setattr(transformer, "keeps_scan_output", lambda config: ())
        cell = dataclasses.replace(FAMILY.cell, overrides=dict(FAMILY.cell.overrides, num_layers=1, layer_types=("linear_attention",)))
        sweeps = kernel_passes(compiled_cells_step(cell, topo, monkeypatch).text, "linear_attn/gdn_scan", r"\w+_rule_\w+")
        assert sweeps == [("jvp(layer0)", "", "kda_rule_fwd"), ("transpose(jvp(layer0))", "", "kda_rule_bwd"),
                          ("transpose(jvp(layer0))", "rematted_computation/", "kda_rule_fwd")], sweeps

    def test_bfloat16_forward_stands_by_the_float32_reference(self, flat, ids):
        """The cell's compute dtype. Every product's output is rounded to 8 bits of mantissa (2^-9 relative a rounding,
        through ten halves and a head over 64 inputs; the decay's pre-activation too, which the running sums then
        carry): observed 1.0e-2 on the logits and 1e-3 on the loss."""
        logits, loss = _bfloat16_gaps(FAMILY, flat, ids)
        assert RTOL < logits < BF16_RTOL and loss < 5e-3

    def test_packed_rows_and_serving_refuse_the_model_with_their_sentences(self, flat):
        """(The pipeline's refusal, by what differs: ``FAMILY.rules.scan_problems``.)"""
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

    # -- what the tolerance must not let through ------------------------------

    def test_a_scalar_decay_fails_the_tolerance(self, flat, ids, monkeypatch):
        rule = gated_delta.gated_delta_rule
        monkeypatch.setattr(gated_delta, "gated_delta_rule", lambda q, k, v, g, beta, **kw: rule(q, k, v, g.mean(-1), beta, **kw))
        assert _logit_gap(FAMILY, flat, ids) > 10 * RTOL

    def test_a_rotated_latent_key_or_a_bfloat16_state_fails_the_tolerance(self, flat, ids, monkeypatch):
        assert _logit_gap(FAMILY, flat, ids, MC.replace(mla_use_nope=False)) > 10 * RTOL
        monkeypatch.setattr(gated_delta, "STATE_DTYPE", jnp.bfloat16)
        assert _logit_gap(FAMILY, flat, ids) > 2 * RTOL  # (rows of 72 tokens carry ONE state across a boundary: 2.8e-4; a row of 8192, 127)

    def test_a_silu_gate_fails_the_tolerance(self, flat, ids, monkeypatch):  # (the router in bfloat16: the suite's)
        norm = gated_delta.gated_norm
        monkeypatch.setattr(gated_delta, "gated_norm", lambda *a, activation="silu", **kw: norm(*a, **kw))
        assert _logit_gap(FAMILY, flat, ids) > 10 * RTOL


# -- the latent layer without rope ----------------------------------------------


def test_the_latent_layer_has_no_position_signal():
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


def test_the_mixers_fields_go_together():
    with pytest.raises(ValueError, match="linear_decay_rank and linear_gate_rank go together"):
        MC.replace(linear_gate_rank=0)
    with pytest.raises(ValueError, match="as many key heads as value heads"):
        MC.replace(linear_num_key_heads=2)
    assert ModelConfig().linear_decay_rank == 0 and not ModelConfig().mla_use_nope
