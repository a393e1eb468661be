"""Gated DeltaNet layers and gated softmax-attention layers side by side, a
softmax router over experts beside one gated shared expert (Qwen3-Next's
structure, ``model_type`` qwen3_next) on the normal training path, at the
``tiny_qwen3_next`` preset over two periods on a CPU, against the benchmark's
plain reference (``benchmarks/chipbench/reference_gdn_moe.py``, which imports
nothing of the program and runs the gated delta rule token by token).

Tolerances and their reasons. Program and reference both compute in float32
at ``highest`` matmul precision here (``compute_dtype="float32"``,
``conftest.py``), over the same bfloat16-valued weights, so they differ by
summation order and by the form of the rule alone (chunked against token by
token): ``RTOL`` 1e-4 relative covers logits, loss and gradients with room
(observed 1e-6 to 6e-6). That is tight enough to see what must not pass: the
router in bfloat16, the rule's carried state in bfloat16, the output gate or
the quarter rope left off, a plain norm where the zero-centred one stands (the
tests at the end hold that each is above ten times the tolerance). The linear
layers' ``A_log`` is redrawn as ``log U(0.02, 2)`` here: as HF draws it (``log
U(0, 16)``) nearly every head forgets its state within a token, and a fault in
what the chunks carry would hide below any tolerance. Rows are 160 tokens:
two chunk boundaries inside a row (chunks of 64) and a last chunk that is not
whole. The rule alone, its kernels and the mixer's passes: ``tests/test_gdn_kernels.py``.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_suite import (
    IN_PASS_PROGRAMS, RECIPE, REPO, CellStep, Family, FamilySuite, Published, Refusals, Rules, Shares, _batch, _logit_gap,
    _logits, _params, _rel, assert_two_sums_an_expert_layer, kernel_passes, mixer_passes, xla_remats,
)
from llm_fine_tune_distributed_tpu.config import ModelConfig
from llm_fine_tune_distributed_tpu.models import transformer
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import (
    forward_with_report, init_cache, init_params, keeps_flash_outputs, keeps_scan_output, rope_tables,
)
from llm_fine_tune_distributed_tpu.observe.xla import mosaic_programs
from llm_fine_tune_distributed_tpu.ops import gated_delta
from llm_fine_tune_distributed_tpu.ops.rope import apply_rope, rope_cos_sin
from llm_fine_tune_distributed_tpu.parallel.lora import add_lora_params
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

from benchmarks.chipbench import reference_gdn_moe as ref, weights_gdn_moe

TINY = get_preset("tiny_qwen3_next")
MC = TINY.replace(num_layers=8, layer_types=TINY.layer_types * 2)  # two periods
ROWS, SEQ = 2, 160
RTOL = 1e-4
# The gated delta rule's kernels in the Qwen3-Next cell's step as they landed (PR 37): distinct Mosaic programs by
# kernel, and their serialized modules' bytes together (PR 36's tree read 125,980 in the same step, my chip run, PR 37).
RULE_PROGRAMS = {"gdn_rule_fwd": 1, "gdn_rule_bwd": 1}
RULE_MODULE_BYTES = 77_064
# The mixer's two elementwise passes around the rule, the same way (PR 39; budget: 40 KB together).
MIXER_PROGRAMS = {"gdn_in_fwd": 1, "gdn_in_bwd": 1, "gdn_out_fwd": 1, "gdn_out_bwd": 1}
MIXER_MODULE_BYTES = 35_816


def bench_cfg(mc=MC) -> dict:
    """The benchmark's configuration dict (the published config's names) of a ModelConfig."""
    return {
        "hidden_size": mc.hidden_size, "head_dim": mc.head_dim, "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "num_hidden_layers": mc.num_layers, "vocab_size": mc.vocab_size,
        "intermediate_size": mc.intermediate_size, "moe_intermediate_size": mc.moe_intermediate_size,
        "shared_expert_intermediate_size": mc.n_shared_experts * mc.moe_intermediate_size,
        "num_experts": len(mc.held_expert_ids), "router_experts": mc.n_routed_experts,
        "held_experts": list(mc.held_expert_ids), "num_experts_per_tok": mc.num_experts_per_tok,
        "rms_norm_eps": mc.rms_norm_eps, "layer_types": list(mc.layer_types), "rope_theta": mc.rope_theta,
        "partial_rotary_factor": mc.partial_rotary_factor, "linear_num_key_heads": mc.linear_num_key_heads,
        "linear_num_value_heads": mc.linear_num_value_heads, "linear_key_head_dim": mc.linear_key_head_dim,
        "linear_value_head_dim": mc.linear_value_head_dim, "linear_conv_kernel_dim": mc.linear_conv_kernel_dim,
        "max_position_embeddings": mc.max_position_embeddings, "tie_word_embeddings": False, "init_std": 0.02,
    }


def _slow_decays(flat: dict) -> dict:
    """``A_log`` redrawn as ``log U(0.02, 2)`` (module docstring), bfloat16-valued like every leaf."""
    out = dict(flat)
    for i, k in enumerate(sorted(k for k in flat if k.endswith("A_log"))):
        a = jax.random.uniform(jax.random.PRNGKey(100 + i), flat[k].shape, jnp.float32, 0.02, 2.0)
        out[k] = jnp.log(a).astype(jnp.bfloat16)
    return out



def _slow_decays(flat: dict) -> dict:
    """``A_log`` redrawn as ``log U(0.02, 2)`` (module docstring), bfloat16-valued like every leaf."""
    out = dict(flat)
    for i, k in enumerate(sorted(k for k in flat if k.endswith("A_log"))):
        a = jax.random.uniform(jax.random.PRNGKey(100 + i), flat[k].shape, jnp.float32, 0.02, 2.0)
        out[k] = jnp.log(a).astype(jnp.bfloat16)
    return out


_LIN, _FULL = "model.layers.0.linear_attn.", "model.layers.3."

FAMILY = Family(
    mc=MC, bench_cfg=bench_cfg, weights=weights_gdn_moe, ref=ref, redraw=_slow_decays, rows=ROWS, seq=SEQ, accum=2,
    rtol=RTOL, delta_tol=5e-3,  # as in ``test_swa_moe.py``; observed 2e-3 of the worst leaf's change
    pairs_per_token=(0.6, 1.4),  # 4 of 16 chosen, 4 held: 1 pair a token expected
    buffers=(),  # nothing frozen, no buffer among the leaves
    # HF's names: ``linear_attn.{in_proj_qkvz, in_proj_ba, conv1d, A_log, dt_bias, norm, out_proj}``,
    # ``mlp.shared_expert`` and ``mlp.shared_expert_gate``
    checkpoint_names=(_LIN + "in_proj_qkvz.weight", _LIN + "in_proj_ba.weight", _LIN + "conv1d.weight", _LIN + "A_log",
                      _LIN + "dt_bias", _LIN + "norm.weight", _LIN + "out_proj.weight", _FULL + "self_attn.q_norm.weight",
                      _FULL + "mlp.shared_expert.up_proj.weight", _FULL + "mlp.shared_expert_gate.weight",
                      _FULL + "mlp.experts.3.gate_proj.weight"),
    # experts 0-3, 4-7, 8-11 and 12-15 as four programs; the gated shared expert (which every share computes alike) once
    shares=Shares(count=4, layer=1, tokens=64, experts_key="num_experts", bias=False, mc=None,
                  shared_once=lambda lp, h, items: ref.experts(lp, h, items, held=())),
    refusals=Refusals(
        base=dict(model_type="qwen3_next", vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=4,
                  num_attention_heads=2, num_key_value_heads=1, head_dim=16, partial_rotary_factor=0.25,
                  num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16, shared_expert_intermediate_size=16,
                  linear_num_key_heads=1, linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=8,
                  linear_conv_kernel_dim=4, full_attention_interval=4),
        cases=(("decoder_sparse_step", 2), ("mlp_only_layers", [0]), ("norm_topk_prob", False),
               ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}), ("shared_expert_intermediate_size", 24),
               ("layer_types", ["linear_attention"])),
        match=lambda key: "qwen3_next config has|layer_types must name each"),
    published=Published(catalog_name="Qwen3-Next-80B-A3B-Instruct", preset="qwen3_next_80b_a3b", tiny="tiny_qwen3_next",
                        params=(0.995 * 79.7e9, 1.005 * 79.7e9),
                        cut=dict(num_layers=4, vocab_size=18992, held_experts=tuple(range(32))), cut_params=625_667_136),  # the cell's 625.7 M
    rules=Rules(
        specs={"model/layers/0/" + path: spec for path, spec in {
            "linear_attn/in_proj_qkvz/kernel": (2, ("fsdp", None)), "linear_attn/out_proj/kernel": (2, (None, "fsdp")),
            "mlp/shared_expert_gate/kernel": (2, ("fsdp", None)), "linear_attn/conv1d/weight": (2, ()), "linear_attn/A_log": (1, ())}.items()},
        mc=TINY, unfreeze_last_n=2,
        trained=("model/layers/2/linear_attn/A_log", "model/layers/2/linear_attn/conv1d/weight", "model/layers/3/self_attn/q_norm/weight"),
        held=("model/layers/1/linear_attn/dt_bias",),
        scan_problems=(({}, ("layers 0 and 3", "attention ('linear' vs 'heads')")),)),  # a model that mixes mixers
    # ``qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams``: one period at the published widths (three Gated DeltaNet
    # layers, one gated full-attention layer; this chip's share: 32 of 512 experts, an eighth of the vocabulary), every
    # parameter trained, 2 rows of 8192 a microbatch, two microbatches, Adam's moments float32 as the cell holds them
    cell=CellStep(preset="qwen3_next_80b_a3b", seq=8192, rows=2, float32_moments=True,
                  overrides=dict(num_layers=4, vocab_size=18992, held_experts=tuple(range(32)))),
)


class TestQwen3Next(FamilySuite):
    family = FAMILY

    def check_leaves(self, own):
        assert [MC.layer(i).attention for i in range(4)] == ["linear"] * 3 + ["heads"]
        # zero-centred norms start at 0, the gated norm at 1, dt_bias at 1, A_log inside log U(0, 16)
        assert float(jnp.abs(own["model/layers/3/self_attn/q_norm/weight"]).max()) == 0.0
        assert float(own["model/layers/0/linear_attn/norm/weight"].min()) == 1.0
        assert float(own["model/layers/0/linear_attn/A_log"].max()) <= np.log(16.0)

    def check_counters(self, two_steps, ids):
        # every linear layer's rule was traced in the chunked form, whole rows of the microbatch
        calls, form = gated_delta.CALLS[ROWS, SEQ, 2, 4, 16, 16]
        assert form == f"chunked {gated_delta.CHUNK}: xla" and calls >= 6 and "chunked" in gated_delta.calls_summary()

    def check_shares(self, parts, lp, h, items, shared_once):
        for tree, mc, routed in parts:  # what every share computes alike: the layer's whole feed-forward half less its routed part
            y, _ = transformer._grouped_experts(tree, h, lambda x, p: x @ p["kernel"], mc, compute_dtype=jnp.float32, mesh=None)
            assert _rel(y - routed, shared_once) < RTOL

    def check_published(self, mc, config):  # (verbatim: layer_types from full_attention_interval)
        assert [mc.layer(i).attention for i in range(8)] == (["linear"] * 3 + ["heads"]) * 2
        assert mc.num_params == 79_674_391_296
        with open(os.path.join(REPO, "benchmarks/chipbench/configs/qwen3-next-80b-a3b-ep16-d4.json")) as f:
            cell = json.load(f)
        assert FAMILY.published.cut_params == sum(int(np.prod(s)) for s in weights_gdn_moe.leaf_shapes(cell).values())

    def check_refusal_base(self, mc):
        assert mc.layer(3).attention == "heads" and mc.layer(0).attention == "linear" and mc.shared_expert_gate

    def check_checkpoint(self, state, params, flat):
        """HF interleaves the two input projections by key head: key head g's rows of ``in_proj_qkvz.weight`` are
        ``[q_g | k_g | v of its two value heads | z of them]``; the tree keeps ``[q | k | v | z]``."""
        assert state[_LIN + "conv1d.weight"].shape == (2 * 2 * 16 + 4 * 16, 1, 4)
        assert state[_FULL + "mlp.shared_expert_gate.weight"].shape == (1, MC.hidden_size)
        assert state[_FULL + "self_attn.q_proj.weight"].shape == (2 * MC.num_heads * MC.head_dim, MC.hidden_size)
        # the layout: HF's row block of key head 1 starts with q of key head 1
        kernel = np.asarray(flatten_dict(params)["model/layers/0/linear_attn/in_proj_qkvz/kernel"])  # [h, q | k | v | z]
        stored = state[_LIN + "in_proj_qkvz.weight"]                                                # [2 x (16 + 16 + 32 + 32), h]
        np.testing.assert_array_equal(stored[96:112], kernel[:, 16:32].T)           # q of key head 1
        np.testing.assert_array_equal(stored[112:128], kernel[:, 32 + 16:32 + 32].T)  # k of key head 1
        np.testing.assert_array_equal(stored[32:64], kernel[:, 64:96].T)            # v of key head 0's two value heads
        np.testing.assert_array_equal(stored[160:192], kernel[:, 128 + 32:128 + 64].T)  # z of key head 1's value heads
        ba = np.asarray(flatten_dict(params)["model/layers/0/linear_attn/in_proj_ba/kernel"])       # [h, b | a]
        np.testing.assert_array_equal(state[_LIN + "in_proj_ba.weight"][4:6], ba[:, 2:4].T)          # b of key head 1
        np.testing.assert_array_equal(state[_LIN + "in_proj_ba.weight"][2:4], ba[:, 4:6].T)          # a of key head 0

    def check_rules(self, monkeypatch):
        # LoRA takes the mixer's projections by their names, like any other
        adapted = flatten_dict(add_lora_params(init_params(jax.random.PRNGKey(0), TINY), jax.random.PRNGKey(1),
                                               target_modules=("in_proj_qkvz", "out_proj")))
        assert "model/layers/0/linear_attn/in_proj_qkvz/lora_a" in adapted and "model/layers/0/linear_attn/out_proj/lora_b" in adapted

    def check_the_cells_step(self, step):
        """The compiler's own count has to fit beside the state (15.49 GiB a program
        may use; at 4 rows a microbatch it refused the step, 17.89 G of 15.75 G,
        until PR 39's passes took the float32 copies out: 14.13 GiB now, PERF.md);
        the full layer runs the streamed flash kernels at heads of 256, 8 queries
        a kv head (no resident kernel: its dk/dv would ask 300 MiB), its forward
        kernel once (``o`` and ``lse`` kept: 8192 against the hidden 2048); each
        linear layer's rule is the Pallas kernels of ``ops/gated_delta.py``, the
        forward sweep ONCE (the block keeps its ``o`` and its per-step states, PR
        44: none in the recomputed pass) and the backward sweep once, and XLA adds
        no rematerialization of its own (no ``.remat`` instruction); the sums of
        rows into tokens are in the step. What the rule's kernels cost every start
        of a process is held too (``RULE_PROGRAMS``, ``RULE_MODULE_BYTES``): PR 36's
        kernels, 126 KB of modules here, added 10.9 s to every warm ``setup_s`` and
        were refused. Around the rule the mixer's elementwise work is two fused
        passes (PR 39: ``gdn_in_*`` under ``gdn_conv``, ``gdn_out_*`` under
        ``gdn_gate_norm``, counted like the sweeps and their text held like the
        rule's), and between the projections and ``out_proj`` nothing else touches
        a whole activation."""
        text, peak = step.text, step.compiled.memory_analysis().peak_memory_in_bytes
        assert peak < 15.49 * 2**30
        for kernel in ("causal_fwd", "causal_dq", "causal_dkv"):
            assert step.calls(f"flash_attention_{kernel}") == 1, kernel
        assert step.calls("flash_attention_fwd") == 0 and step.calls("flash_attention_window_fwd") == 0
        assert_two_sums_an_expert_layer(text, step.layers)
        # each linear layer's rule is the kernels (PR 37): the forward sweep in the forward pass and NOT recomputed (its o
        # and states are kept, PR 44), the backward sweep once; the full layer has none, XLA's triangular solve is out of
        # the step and XLA rematerializes nothing of its own
        sweeps = kernel_passes(text, "linear_attn/gdn_scan", r"gdn_rule_\w+")
        assert sweeps == sorted(
            sweep for i in range(3) for sweep in ((f"jvp(layer{i})", "", "gdn_rule_fwd"), (f"transpose(jvp(layer{i}))", "", "gdn_rule_bwd"))), sweeps
        assert "triangular" not in text.lower() and not xla_remats(text)
        # the two passes around it (PR 39), the same: forward kernels in the forward and the recomputed pass, backward
        # kernels once, none in the full layer
        passes = kernel_passes(text, "linear_attn/(gdn_conv|gdn_gate_norm)", r"gdn_(?:in|out)_\w+")
        assert passes == mixer_passes(range(3)), passes
        # what the passes removed: of the instructions under linear_attn that yield a whole [2, 8192, >= 2048] activation
        # (fused computations' insides apart) none is a pad, a slice, a concatenation, a copy, a transpose or a conversion:
        # each is a kernel, or a fusion that is a projection's product or the sum of the products' input cotangents, or (PR
        # 44) the ONE pass ``jax.checkpoint`` puts on the producer of a float residual it saves (``reduce_precision``, a
        # layer's kept ``o`` of the rule; the flash kernel's kept ``o`` passes the same under ``attn``)
        whole, computation = [], ""
        for line in text.splitlines():
            head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
            if head:
                computation = head.group(1)
            found = re.match(r'\s*(?:ROOT )?%[\w.\-]+ = \w+\[2,8192,(\d+)\]\S* ([\w\-]+)\(.*op_name="([^"]*/linear_attn[^"]*)"', line)
            if found and not computation.startswith("fused_computation") and int(found.group(1)) >= 2048:
                whole.append((found.group(2), found.group(3).rsplit("/", 1)[1]))
        assert whole and {opcode for opcode, _ in whole} <= {"custom-call", "get-tuple-element", "fusion", "bitcast", "reduce-precision"}, set(whole)
        assert {last for opcode, last in whole if opcode == "fusion"} <= {"dot_general", "add_any"}, set(whole)
        assert [last for opcode, last in whole if opcode == "reduce-precision"] == ["reduce_precision"] * 3, set(whole)
        # at the landed count (13.47 GiB at PR 39; the parent's 14.15 held the XLA form's float32 copies and padded
        # cotangents; PR 41's IN pass leaves it where it was: the full layer's gate is a product of its own; 13.56 since
        # the three linear blocks keep 192 MiB each of the rule's o and states, PR 44)
        assert peak <= 13.57 * 2**30
        # the full layer's IN pass (PR 41): forward kernel in the forward and the recomputed pass, backward kernel once
        programs = mosaic_programs(step.lowered.as_text())
        assert {name: programs[name]["programs"] for name in IN_PASS_PROGRAMS} == IN_PASS_PROGRAMS
        assert (step.calls("attn_in_fwd"), step.calls("attn_in_bwd")) == (2, 1)
        # what a start of the process pays for the rule again, warm cache or not: the text of its kernels, traced and
        # lowered before the cache is even asked (PERF.md, PR 37, step 0: the step's lower() follows the serialized
        # modules' bytes, .compile() on a hit does not move). Held at the landed value plus a fifth.
        for prefixes, count, landed in ((("gdn_rule",), RULE_PROGRAMS, RULE_MODULE_BYTES), (("gdn_in", "gdn_out"), MIXER_PROGRAMS, MIXER_MODULE_BYTES)):
            found = {name: x for name, x in programs.items() if name.startswith(prefixes)}
            assert {name: x["programs"] for name, x in found.items()} == count, found
            assert sum(x["bytes"] for x in found.values()) <= 1.2 * landed, found

    def test_right_padded_rows_train_and_match_the_reference_on_the_unpadded_part(self, flat, ids, two_steps):
        """Rows of 100 real tokens padded on the right to 160: nothing is needed
        for them. The rule and the convolution are causal, so no pad reaches a
        real token; the loss masks the pads. Logits at the real positions, and a
        step's loss and gradient norm (the fixture's compiled step, called on the
        padded batch), equal the reference's on the rows cut to their 100 tokens."""
        real = 100
        got = _logits(_params(flat), ids[0, 0], MC, padding_mask=_batch(ids[0, 0], real)["attention_mask"])[0]
        assert _rel(got[:, :real], ref.logits(flat, bench_cfg(), ids[0, 0][:, :real])) < RTOL
        _, metrics = two_steps["step"](two_steps["state"], _batch(ids[0], real))
        want = ref.sft_reference({k: jnp.array(v) for k, v in flat.items()}, bench_cfg(), RECIPE, [ids[0][..., :real]],
                                 lambda names: {k: flat[k] for k in names})
        assert abs(float(metrics["loss"]) - want["losses"][0]) < RTOL
        assert abs(float(metrics["grad_norm"]) / want["grad_norm"] - 1) < RTOL

    def test_packed_rows_and_serving_are_refused_with_a_reason(self, flat):
        from llm_fine_tune_distributed_tpu.infer.generate import Generator, LatentAttentionNotServed

        segments = jnp.ones((1, 8), jnp.int32)
        with pytest.raises(NotImplementedError, match="restart at every segment boundary.*ROADMAP.md"):
            forward_with_report(_params(flat), jnp.zeros((1, 8), jnp.int32), MC, segment_ids=segments)
        with pytest.raises(LatentAttentionNotServed, match="linear-attention layers.*recurrent state"):
            Generator(_params(flat), MC, tokenizer=None)
        with pytest.raises(LatentAttentionNotServed, match="latent attention"):  # the first kind keeps its sentence
            Generator({}, get_preset("tiny_mla_moe"), tokenizer=None)
        with pytest.raises(NotImplementedError, match="training form only"):
            forward_with_report(_params(flat), jnp.zeros((1, 4), jnp.int32), MC, cache=init_cache(MC, 1, 8))

    def test_the_new_scopes_reach_the_lowered_step(self, two_steps):
        names = {re.sub(r"\b(?:jvp|transpose|vmap)\(([^()]*)\)", r"\1", re.sub(r"transpose\(jvp\(([^()]*)\)\)", r"\1", n))
                 for n in re.findall(r'op_name="([^"]+)"', two_steps["text"])}
        for path in ("layer0/linear_attn/gdn_conv/", "layer0/linear_attn/gdn_scan/", "layer0/linear_attn/gdn_gate_norm/",
                     "layer3/attn/attn_gate/", "layer3/mlp/shared_expert/", "layer7/attn/"):
            assert any(path in n for n in names), path
        assert not any("layer3/linear_attn" in n or "layer0/attn/" in n for n in names)
        # the rule's scan is a while of its own right under gdn_scan: what readers/gdn.py counts a call by
        assert any(re.search(r"layer0/linear_attn/gdn_scan/(closed_call/)?while$", n) for n in names)

    # -- what the tolerance must not let through (the router in bfloat16: the suite's; the rule's state: tests/test_gdn_kernels.py)

    @pytest.mark.parametrize("field, value", [
        ("attention_output_gate", False), ("partial_rotary_factor", 1.0), ("zero_centered_norm", False),
        ("shared_expert_gate", False),
    ], ids=lambda v: str(v))
    def test_a_part_left_off_fails_the_tolerance(self, flat, ids, field, value):
        """The output gate, the quarter rope, the zero-centred form of every norm
        (q_norm and k_norm among them: ``_heads_qkv`` passes the model's flag on)
        and the shared expert's gate each move the logits far above the tolerance."""
        tree = _params(flat)
        if field == "attention_output_gate":  # the ungated model's q_proj holds the queries alone
            d = MC.head_dim
            for i in (3, 7):
                q = tree["model"]["layers"][str(i)]["self_attn"]["q_proj"]
                q["kernel"] = q["kernel"].reshape(MC.hidden_size, MC.num_heads, 2 * d)[..., :d].reshape(MC.hidden_size, -1)
        if field == "shared_expert_gate":
            for layer in tree["model"]["layers"].values():
                del layer["mlp"]["shared_expert_gate"]
        assert _logit_gap(FAMILY, flat, ids, MC.replace(**{field: value}), tree) > 10 * RTOL


def test_a_quarter_of_a_head_is_rotated():
    """Tables of 8 on heads of 32: dimensions 0..7 rotate (halves of the 8), 8..31 pass."""
    assert TINY.rotary_dim == 8 and get_preset("qwen3_next_80b_a3b").rotary_dim == 64
    cos, sin = rope_tables(TINY, jnp.arange(5)[None])["plain"]
    assert cos.shape == (1, 5, 8)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 4, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 2, 32))
    q_rot, k_rot = apply_rope(q, k, cos, sin)
    np.testing.assert_array_equal(np.asarray(q_rot[..., 8:]), np.asarray(q[..., 8:]))
    want, _ = apply_rope(q[..., :8], k[..., :8], *rope_cos_sin(jnp.arange(5)[None], 8, TINY.rope_theta))
    np.testing.assert_allclose(np.asarray(q_rot[..., :8]), np.asarray(want), rtol=1e-6)
    assert float(jnp.abs(k_rot[:, 1:, :, :8] - k[:, 1:, :, :8]).max()) > 1e-3
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        ModelConfig(num_heads=4, hidden_size=64, head_dim=16, partial_rotary_factor=0.3)


def test_layer_types_message_names_three_kinds():
    with pytest.raises(ValueError, match="'sliding_attention', 'full_attention' or 'linear_attention'"):
        ModelConfig(num_layers=1, layer_types=("chunked_attention",))
    with pytest.raises(ValueError, match="linear_\\* fields"):
        ModelConfig(num_layers=1, layer_types=("linear_attention",))


def test_what_a_rematerialized_block_keeps_is_read_from_the_shapes(monkeypatch):
    """The full layer at 8192 x 256-wide heads keeps the flash kernel's ``o``
    and ``lse`` (8192 x 512 / 512 = 8192 against the hidden 2048). A linear
    layer keeps what the program its rule runs as makes worth keeping. As
    XLA's scan (this CPU; heads that are no whole lanes on a TPU) the count
    from shapes: 128 + 64 x 1.5 = 224 operations a kept byte of ``o`` against
    2048, recomputed; at a hidden size under that ``gdn_o`` alone, the scan
    carries its own state. As the Pallas sweeps (a TPU at the model's heads
    of 128) both of the forward sweep's outputs, whatever the hidden size:
    ``o`` alone would free no sweep (``tests/test_flash_remat.py`` counts)."""
    big = get_preset("qwen3_next_80b_a3b")
    assert keeps_flash_outputs(big, 8192, None) and not keeps_flash_outputs(big, 1024, None)
    assert keeps_scan_output(big) == () and keeps_scan_output(big.replace(hidden_size=128)) == ("gdn_o",)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert keeps_scan_output(big) == keeps_scan_output(big.replace(hidden_size=128)) == ("gdn_o", "gdn_states")
    assert keeps_scan_output(get_preset("tiny_qwen3_next")) == ("gdn_o",)  # heads of 16: the XLA form there too, 16 + 64 x 1.5 = 112 against 64
