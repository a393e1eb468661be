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

import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from family_suite import (
    BIAS, IN_PASS_PROGRAMS, CellStep, Family, FamilySuite, Published, Refusals, Rules, Shares, _bfloat16_gaps, _logit_gap,
    _logits, _params, _rel, kernel_passes, sum_kernel_calls, weights,
)
from llm_fine_tune_distributed_tpu.models import hf_io, transformer
from llm_fine_tune_distributed_tpu.models.configs import from_hf_config, get_preset, to_hf_dict
from llm_fine_tune_distributed_tpu.models.transformer import forward, init_cache, init_params, keeps_flash_outputs
from llm_fine_tune_distributed_tpu.observe.xla import mosaic_programs
from llm_fine_tune_distributed_tpu.ops import flash_attention as fa, moe

from benchmarks.chipbench import reference_afmoe as ref, weights_afmoe

MC = get_preset("tiny_trinity")
SEQ = 64  # rows twice the window of 32
RTOL, BF16_RTOL = 1e-4, 4e-2


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



FAMILY = Family(
    mc=MC, bench_cfg=bench_cfg, weights=weights_afmoe, ref=ref, redraw=None, rows=2, seq=SEQ, accum=2,
    rtol=RTOL, delta_tol=3e-3,  # as in the other expert models' tests
    pairs_per_token=(0.6, 1.4),  # 4 of 16 chosen, 4 held: 1 pair a token expected
    buffers=tuple(f"model/layers/{i}/mlp/gate/{BIAS}" for i in (1, 2, 3, 4)),  # the selection bias is a buffer; layer 0 is dense
    # HF afmoe's names: the gate a ``self_attn.gate_proj`` of its own (split out of ``q_proj``'s joined leaf by head, and
    # joined again on load), the four norms, ``mlp.router.gate``, ``mlp.expert_bias``, ``mlp.shared_experts``, per-expert
    # Linears under the experts' global ids; the dense layer's own gate_proj keeps its name
    checkpoint_names=tuple("model.layers.3." + name for name in (
        "self_attn.q_proj.weight", "self_attn.gate_proj.weight", "self_attn.q_norm.weight", "pre_mlp_layernorm.weight",
        "post_mlp_layernorm.weight", "post_attention_layernorm.weight", "mlp.router.gate.weight", "mlp.expert_bias",
        "mlp.shared_experts.up_proj.weight", "mlp.experts.3.gate_proj.weight", "mlp.experts.0.down_proj.weight",
    )) + ("model.layers.0.mlp.gate_proj.weight",),
    # experts 0-1, 2-3, ... 14-15 as eight programs; the shared expert counted once; what adds up is the layer BEFORE
    # ``post_mlp_layernorm`` (the norm is not linear: each share norms its own partial sum)
    shares=Shares(count=8, layer=2, tokens=SEQ, experts_key="num_experts", bias=True, mc=None,
                  shared_once=lambda lp, h, items: ref.experts(lp, h, items, held=())),
    refusals=Refusals(
        base=dict(model_type="afmoe", vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                  num_attention_heads=2, num_key_value_heads=2, sliding_window=8, num_experts=4, num_experts_per_tok=2,
                  moe_intermediate_size=16, num_dense_layers=1, num_shared_experts=1, route_scale=2.0, mup_enabled=True,
                  layer_types=["sliding_attention", "full_attention"]),
        cases=(("n_group", 2), ("topk_group", 2), ("num_expert_groups", 4), ("route_norm", False), ("score_func", "softmax"),
               ("layer_types", ["sliding_attention"]), ("layer_types", ["sliding_attention", "linear_attention"])),
        match=lambda key: key),
    published=Published(catalog_name="Trinity-Mini", preset="trinity_mini", tiny="tiny_trinity", params=(26.1e9, 26.2e9),  # 26B-A3B
                        cut=dict(num_layers=5, first_k_dense_replace=1, vocab_size=25024, held_experts=tuple(range(16))),
                        cut_params=705_474_304),  # the cell's 705.5 M
    rules=Rules(
        specs={"model/layers/1/" + path: spec for path, spec in {
            "mlp/experts/w1": (3, ("expert", "fsdp", "tensor")), "mlp/shared_experts/down_proj/kernel": (2, ("tensor", "fsdp")),
            "self_attn/q_proj/kernel": (2, ("fsdp", "tensor")), "self_attn/q_norm/weight": (1, ()),
            "pre_feedforward_layernorm/weight": (1, ()), f"mlp/gate/{BIAS}": (1, ())}.items()},
        mc=MC, unfreeze_last_n=1, trained=("model/layers/4/post_feedforward_layernorm/weight",),
        held=("model/layers/3/mlp/experts/w2", f"model/layers/4/mlp/gate/{BIAS}"),
        # (past the dense layer: a rope that differs alone the scan takes as data)
        scan_problems=(({}, ("layers 0 and 1", "feed_forward")), (dict(first_k_dense_replace=0), ("layers 0 and 3", "window")))),
    # the leading dense layer, one window layer and the global layer at the published widths (this chip's share: 16 of
    # 128 experts, an eighth of the vocabulary), every parameter trained but the selection bias, one row of 8192
    cell=CellStep(preset="trinity_mini", seq=8192, rows=1, float32_moments=False,
                  overrides=dict(num_layers=3, first_k_dense_replace=1, vocab_size=25024, held_experts=tuple(range(16)),
                                 layer_types=("sliding_attention", "sliding_attention", "full_attention"), no_rope_layers=(1, 1, 0))),
)


def _one_layer(kind):
    return MC.replace(num_layers=1, layer_types=(kind,), no_rope_layers=(int(kind == "sliding_attention"),))


class TestTrinity(FamilySuite):
    family = FAMILY

    def check_leaves(self, own):
        # four norms in every block, around the dense layer and around the expert layers alike
        for layer in (0, 1):
            for norm in ("input", "post_attention", "pre_feedforward", "post_feedforward"):
                assert f"model/layers/{layer}/{norm}_layernorm/weight" in own
        assert "model/layers/0/mlp/gate_proj/kernel" in own
        assert own["model/layers/1/self_attn/q_proj/kernel"].shape == (MC.hidden_size, 2 * MC.num_heads * MC.head_dim)

    def check_gradients(self, got):
        # both halves of q_proj's joined leaf take a gradient: the query's columns and the gate's
        dq = got["model/layers/3/self_attn/q_proj/kernel"].reshape(MC.hidden_size, MC.num_heads, 2, MC.head_dim)
        assert np.abs(dq[:, :, 0]).max() > 0 and np.abs(dq[:, :, 1]).max() > 0

    def check_shares(self, parts, lp, h, items, shared_once):
        # and the program's whole feed-forward half of ONE share is the reference's for that share
        mine = weights.nest({k: v for k, v in lp.items() if k.startswith("mlp/")})["mlp"]
        mine["experts"] = {w: v[:4] for w, v in mine["experts"].items()}
        got, _ = transformer._grouped_experts(mine, h, lambda x, p: x @ p["kernel"], MC, compute_dtype=jnp.float32, mesh=None)
        assert _rel(got, ref.experts(lp, h, dict(items, held_experts=tuple(range(16))), held=(0, 1, 2, 3))) < RTOL

    def check_published(self, mc, config):
        assert (mc.first_k_dense_replace, mc.embed_scale, mc.sandwich_norms, mc.qk_norm, mc.attention_output_gate) == (
            2, True, True, True, True)
        assert (mc.router_scoring, mc.routed_scaling_factor, mc.n_routed_experts, mc.n_shared_experts) == ("sigmoid", 2.826, 128, 1)
        assert mc.no_rope_layers == (1, 1, 1, 0) * 8 and mc.sliding_window == 2048
        # a framework save of a model loaded under the family's own name comes back too
        assert from_hf_config(SimpleNamespace(**to_hf_dict(mc))) == mc
        # layer_types left out: every fourth layer global
        assert from_hf_config(SimpleNamespace(**{k: v for k, v in config.items() if k != "layer_types"})) == mc

    def check_refusal_base(self, mc):
        assert mc.layer(1).window is None and not mc.layer(1).rope and mc.layer(0).rope and mc.embed_scale

    def check_checkpoint(self, state, params, flat):
        layer = "model.layers.3."
        assert not any("feedforward" in k or BIAS in k or ".mlp.gate." in k for k in state)
        width = MC.num_heads * MC.head_dim
        assert state[layer + "self_attn.q_proj.weight"].shape == state[layer + "self_attn.gate_proj.weight"].shape == (width, MC.hidden_size)
        # by head: head 1's query columns and gate columns of the joined leaf
        joined = np.asarray(flat["model/layers/3/self_attn/q_proj/kernel"], np.float32)
        d = MC.head_dim
        np.testing.assert_array_equal(state[layer + "self_attn.q_proj.weight"].T[:, d:2 * d], joined[:, 2 * d:3 * d])
        np.testing.assert_array_equal(state[layer + "self_attn.gate_proj.weight"].T[:, d:2 * d], joined[:, 3 * d:4 * d])
        # the same tree under another family's rule keeps its own names (Qwen3-Next stores the joined leaf)
        assert "model.layers.3.self_attn.gate_proj.weight" not in hf_io.pytree_to_hf_state_dict(params, MC.replace(sandwich_norms=False))

    def check_the_cells_step(self, step):
        """The window of 2048 is a band three blocks of 1024 wide (Mellum's 1024:
        two): both kinds of layer run the streamed flash kernels behind the gate
        and the q/k norms, each forward kernel ONCE (``o`` and ``lse`` kept on the
        window layers too: 2048 x 1.75 = 3584 keys' worth against the hidden 2048);
        the post-norm sits on the expert layers' output; the sums of rows into
        tokens are in the step; and the block's three scopes are on its
        operations. Between the projections and the flash kernels stands the IN
        pass (PR 41): ``attn_in_fwd`` in each layer's forward and recomputed pass,
        ``attn_in_bwd`` once, ONE program each for the window layers with rope and
        the global layer without, and the flash kernels read q, k and v as the
        pass wrote them: no transpose, no copy, no fusion between."""
        text = step.text
        for kernel in ("fwd", "dq", "dkv"):
            assert step.calls(f"flash_attention_window_{kernel}") == 2, kernel  # layers 0 and 1, the forward kernel kept
            assert step.calls(f"flash_attention_causal_{kernel}") == 1, kernel
        assert step.calls("flash_attention_fwd") == 0  # no resident kernel at 8 queries a kv head and 8192
        band = fa._band(8192, 1024, 2048)
        assert band.steps == 3 and fa.GRID_TILES["flash_attention_window_fwd", band] == (21, 36)
        # the sums of rows into tokens: forward and backward an expert layer as everywhere, and here a THIRD, recomputed:
        # the expert layer's output is no longer the block's last operation, the output norm's backward reads it
        # (64 MiB a layer and microbatch to keep instead: not kept), and the same three again behind the overflow's cond
        sums = sum_kernel_calls(text)
        first_chunk = [c for c in sums if "/cond/" not in c]
        assert len(sums) == 6 * 2 and len(first_chunk) == 3 * 2, sums
        assert sum("transpose(" in c and "rematted_computation" not in c for c in first_chunk) == 2
        assert sum("rematted_computation" in c for c in first_chunk) == 2
        for inside in ("attn/attn_gate", "attn/attn_in", "attn/out_norm", "mlp/out_norm"):
            assert any(f"/{inside}/" in name for name in step.names), inside
        assert not any("/qk_norm/" in name for name in step.names)  # the norms are inside the pass: the scope is the XLA form's
        assert any("layer2" in name and "mlp/out_norm" in name for name in step.names)  # the post-norm of an EXPERT layer
        # the IN pass: forward kernel in the forward and the recomputed pass, backward kernel once, every layer
        passes = kernel_passes(text, "attn/attn_in", r"attn_in_\w+")
        assert passes == sorted(found for i in range(3) for found in (
            (f"jvp(layer{i})", "", "attn_in_fwd"), (f"transpose(jvp(layer{i}))", "rematted_computation/", "attn_in_fwd"),
            (f"transpose(jvp(layer{i}))", "", "attn_in_bwd"))), passes
        programs = {name: x for name, x in mosaic_programs(step.lowered.as_text()).items() if name.startswith("attn_in")}
        assert {name: x["programs"] for name, x in programs.items()} == IN_PASS_PROGRAMS, programs  # (rope or none: data)
        # (30,048 B landed, plus a fifth; a whole step stands deeper than the ten frames a location keeps: the same anywhere)
        assert sum(x["bytes"] for x in programs.values()) <= 36_000, programs
        # what the forward flash kernels read as q, k, v IS what the pass wrote: get-tuple-elements of its call
        defined = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = \S+ ([\w\-]+)\(", text, flags=re.M))
        reads = re.findall(r"= \S+ \S+ custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\".*?"
                           r'op_name="[^"]*/flash_attention_(?:window|causal)_fwd/pallas_call"', text)
        assert len(reads) == 3
        for operands in reads:
            q_k_v = [name.split("*/")[-1].strip() for name in operands.split(",")][-3:]
            assert all(name.startswith("%jit_attn_in_fwd_") and defined[name] == "get-tuple-element" for name in q_k_v), q_k_v

    def test_bfloat16_forward_stands_by_the_float32_reference(self, flat, ids):
        """The cell's compute dtype. Every product's output is rounded to 8 bits
        of mantissa (2^-9 relative a rounding, through ten halves and a head over
        64 inputs): observed 1.0e-2 on the logits and 6e-4 on the loss."""
        logits, loss = _bfloat16_gaps(FAMILY, flat, ids)
        assert RTOL < logits < BF16_RTOL and loss < 5e-3

    def test_cached_decoding_agrees_with_the_whole_row(self, flat, ids):
        """The dense cache path takes this block as it is (q/k norms and the rope
        before the write, the window as a mask over the buffer, the gate after the
        kernel): a prefill of 48 tokens and 16 single-token steps (ONE compiled
        program, the position an argument) give the logits of the whole row's
        forward pass."""
        params, row = _params(flat), jnp.asarray(ids[0, 0, :1])
        cached = jax.jit(lambda p, x, cache, pos: forward(p, x, MC, cache=cache, cache_pos=pos, compute_dtype=jnp.float32))
        got, cache = cached(params, row[:, :48], init_cache(MC, 1, SEQ, dtype=jnp.float32), 0)
        steps = [got]
        for t in range(48, SEQ):
            out, cache = cached(params, row[:, t:t + 1], cache, t)
            steps.append(out)
        assert _rel(jnp.concatenate(steps, axis=1), _logits(params, row, MC)[0]) < RTOL

    def test_a_gate_or_a_norm_left_off_fails_the_tolerance(self, flat, ids):
        params = _params(flat)
        ungated = jax.tree.map(lambda x: x, params)
        q = ungated["model"]["layers"]["1"]["self_attn"]["q_proj"]["kernel"]
        q = q.reshape(MC.hidden_size, MC.num_heads, 2, MC.head_dim).at[:, :, 1].set(0.0)  # sigmoid(0): every gate a half
        ungated["model"]["layers"]["1"]["self_attn"]["q_proj"]["kernel"] = q.reshape(MC.hidden_size, -1)
        assert _logit_gap(FAMILY, flat, ids, params=ungated) > 10 * RTOL
        unnormed = jax.tree.map(lambda x: x, params)
        unnormed["model"]["layers"]["2"]["post_feedforward_layernorm"]["weight"] = jnp.full((MC.hidden_size,), 0.5)
        assert _logit_gap(FAMILY, flat, ids, params=unnormed) > 10 * RTOL
        assert _logit_gap(FAMILY, flat, ids, MC.replace(embed_scale=False)) > 10 * RTOL  # the sqrt(hidden) multiplier left off
        assert _logit_gap(FAMILY, flat, ids, MC.replace(qk_norm=False)) > 10 * RTOL

    def test_a_rope_or_a_window_of_the_wrong_kind_fails_the_tolerance(self, flat, ids):
        assert _logit_gap(FAMILY, flat, ids, MC.replace(no_rope_layers=(1,) * 5)) > 10 * RTOL  # a rope on the global layer
        assert _logit_gap(FAMILY, flat, ids, MC.replace(no_rope_layers=(0,) * 5)) > 10 * RTOL  # none on the window layers
        assert _logit_gap(FAMILY, flat, ids, MC.replace(layer_types=("full_attention",) * 5)) > 10 * RTOL
        assert _logit_gap(FAMILY, flat, ids, MC.replace(sliding_window=33)) > 10 * RTOL  # one key too many


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


def test_the_generator_serves_the_block_as_the_whole_row_computes_it():
    """``infer/`` refuses no layer of this model: greedy decoding through
    ``Generator`` (bucketed prefill, the device-resident decode loop over the
    dense cache) picks the tokens the whole row's forward pass picks: ONE pass
    over the prompt and what was generated, each generated token the largest
    logit at the position before it."""
    from llm_fine_tune_distributed_tpu.infer.generate import GenerationConfig, Generator, unserved_layer_kind

    assert unserved_layer_kind(MC) is None
    params = init_params(jax.random.PRNGKey(0), MC)
    tokenizer = SimpleNamespace(eos_token_id=None, pad_token_id=0)
    prompt = [int(t) for t in np.random.RandomState(1).randint(1, MC.vocab_size, 40)]  # past the window of 32
    got = Generator(params, MC, tokenizer, compute_dtype=jnp.float32).generate_ids(
        prompt, GenerationConfig(max_new_tokens=6, do_sample=False, repetition_penalty=1.0))
    whole = _logits(params, [prompt + got[:-1]], MC)[0][0]
    assert len(got) == 6 and got == [int(t) for t in jnp.argmax(whole[len(prompt) - 1:], axis=-1)]


def test_every_layer_keeps_its_flash_outputs_at_the_cells_rows():
    """``worth_keeping_across_remat`` reads the keys a query sees: at 8192
    tokens a window of 2048 is 2048 x (2 - 1/4) = 3584 against the hidden 2048
    (Mellum's 1024: 1920 against 2304, recompute), the global layer 8192."""
    big = get_preset("trinity_mini")
    assert keeps_flash_outputs(big, 8192, 2048) and keeps_flash_outputs(big, 8192, None)
    assert not keeps_flash_outputs(big, 8192, 1024) and not keeps_flash_outputs(big, 2048, None)
    assert moe.pairs_a_chunk(big.replace(held_experts=tuple(range(16)))) == 2  # 1.0 pairs a token expected, a quarter of room
    assert moe.pairs_a_chunk(MC) == 2
