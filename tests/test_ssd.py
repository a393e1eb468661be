"""Granite 4.0-H (``granitemoehybrid``: Mamba-2 state-space layers beside GQA layers without rope, the family's four
multipliers, a tied table) against its plain reference (``benchmarks/chipbench/reference_ssd.py``, which imports
nothing of the program), and the scan (``ops/ssd.py``) against the recurrence written out here token by token.

The shared tests are ``family_suite.ModelSuite``'s at ``tiny_granite_h`` (ten layers in the published pattern, rows of
160: no multiple of the scan's chunk): logits, loss, every gradient leaf (``A_log``, ``D``, ``dt_bias``, the
convolution's bias among them) in float32, two steps' change at bfloat16 masters, the published configuration, the
refusals, the checkpoint's names.

**Tolerances.** ``RTOL`` 5e-5: program and reference are float32 under ``highest`` and compute the same sums in another
order (the program's scan in chunks of 128 with a carried state, the reference's dual form over the whole row a head at
a time; the worst leaf observed is 6e-6, at a ``dt_bias`` whose gradient's norm is 7e-6). The scan against the walk
token by token: 2e-4 absolute on outputs of order 10 and 5e-4 of each cotangent's norm (a chunk adds up 128 terms
where the walk adds one). The kernels under the Pallas interpreter against the XLA form: 1e-5 of the output's norm,
1e-4 of each cotangent's (dt's and ``a``'s too, which the backward sweep writes as dt lies and a step at a time). The flash kernels at heads of 64 under the interpreter against XLA attention: 2e-5. bfloat16
against the float32 reference: the logits within 2e-2 of their norm, the loss within 2e-3 (the other dense models')."""

from __future__ import annotations

import dataclasses
import os
import re
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_suite import Model, ModelSuite, Published, Refusals, _bfloat16_gaps, _logit_gap, _logits, _params, _rel
from llm_fine_tune_distributed_tpu.config import TrainConfig
from llm_fine_tune_distributed_tpu.models import transformer
from llm_fine_tune_distributed_tpu.models.configs import from_hf_config, get_preset
from llm_fine_tune_distributed_tpu.ops import flash_attention, rope as rope_ops, ssd
from llm_fine_tune_distributed_tpu.ops.attention import attention
from llm_fine_tune_distributed_tpu.parallel.freeze import quantize_trunk_int8, trainable_mask
from llm_fine_tune_distributed_tpu.parallel.sharding import param_spec
from llm_fine_tune_distributed_tpu.train import step as step_mod
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import reference_ssd as ref, weights_ssd  # noqa: E402

MC = get_preset("tiny_granite_h")
RTOL = 5e-5
SEQ = 160


def bench_cfg(mc=MC) -> dict:
    """The benchmark's configuration dict (the published names) of a ModelConfig."""
    return dict(
        model_type="granitemoehybrid", vocab_size=mc.vocab_size, hidden_size=mc.hidden_size, intermediate_size=mc.intermediate_size,
        shared_intermediate_size=mc.intermediate_size, num_hidden_layers=mc.num_layers, num_attention_heads=mc.num_heads,
        num_key_value_heads=mc.num_kv_heads, head_dim=mc.resolved_head_dim, rope_theta=mc.rope_theta,
        max_position_embeddings=mc.max_position_embeddings, rms_norm_eps=mc.rms_norm_eps, tie_word_embeddings=True,
        layer_types=["mamba" if kind == "mamba" else "attention" for kind in mc.layer_types],
        mamba_n_heads=mc.mamba_n_heads, mamba_d_head=mc.mamba_d_head, mamba_d_state=mc.mamba_d_state,
        mamba_n_groups=mc.mamba_n_groups, mamba_d_conv=mc.mamba_d_conv, mamba_expand=mc.mamba_n_heads * mc.mamba_d_head // mc.hidden_size,
        mamba_conv_bias=True, mamba_proj_bias=False, attention_multiplier=mc.attention_multiplier,
        embedding_multiplier=mc.embedding_multiplier, residual_multiplier=mc.residual_multiplier, logits_scaling=mc.logits_scaling,
        position_embedding_type="nope", normalization_function="rmsnorm", num_local_experts=0, rope_scaling=None,
        initializer_range=0.05,
    )


def _redraw(flat):
    """Norms, ``D`` and the gated norm off 1 (the benchmark draws 1), so that no weight is a no-op here."""
    off_one = lambda v: (1.0 + 0.1 * jnp.cos(jnp.arange(v.shape[0], dtype=jnp.float32))).astype(v.dtype)  # noqa: E731
    return {k: (off_one(v) if k.endswith(("norm/weight", "layernorm/weight", "mamba/D")) else v) for k, v in flat.items()}


FAMILY = Model(
    mc=MC, bench_cfg=bench_cfg, weights=weights_ssd, ref=ref, redraw=_redraw, rows=2, seq=SEQ, accum=1,
    rtol=RTOL, delta_tol=3e-3,  # as in the other models' tests
    buffers=(),
    # HF granitemoehybrid's names (as remembered): the mixer a layer's ``mamba``, the MLP ``shared_mlp`` with one
    # ``input_linear`` of the gate's rows then the up projection's
    checkpoint_names=("model.layers.0.mamba.in_proj.weight", "model.layers.0.mamba.conv1d.weight", "model.layers.0.mamba.conv1d.bias",
                      "model.layers.9.mamba.A_log", "model.layers.9.mamba.D", "model.layers.9.mamba.dt_bias",
                      "model.layers.1.mamba.norm.weight", "model.layers.1.mamba.out_proj.weight", "model.layers.5.self_attn.q_proj.weight",
                      "model.layers.2.shared_mlp.input_linear.weight", "model.layers.2.shared_mlp.output_linear.weight",
                      "model.layers.2.post_attention_layernorm.weight", "model.norm.weight", "model.embed_tokens.weight"),
    refusals=Refusals(
        base={k: v for k, v in bench_cfg().items() if k != "initializer_range"},
        cases=(("num_local_experts", 8), ("mamba_proj_bias", True), ("mamba_conv_bias", False), ("mamba_n_groups", 3),
               ("mamba_expand", 4), ("normalization_function", "layernorm"), ("position_embedding_type", "alibi"),
               ("rope_scaling", {"rope_type": "linear", "factor": 2.0}), ("layer_types", ["mamba"] * 9 + ["moe"])),
        match=lambda key: "granitemoehybrid config has .*" + key),
    published=Published(catalog_name="granite-4.0-h-micro", preset="granite_4_0_h_micro", tiny="tiny_granite_h",
                        params=(3.19e9, 3.2e9),  # 3,191,396,096
                        cut={}, cut_params=3_191_396_096),  # nothing is cut
)


class TestGraniteHybrid(ModelSuite):
    family = FAMILY

    def check_leaves(self, own):
        mamba = {k.rsplit("mamba/", 1)[1]: v for k, v in own.items() if k.startswith("model/layers/0/mamba/")}
        assert set(mamba) == {"in_proj/kernel", "conv1d/weight", "conv1d/bias", "A_log", "D", "dt_bias", "norm/weight", "out_proj/kernel"}
        np.testing.assert_allclose(np.exp(np.asarray(mamba["A_log"], np.float32)), np.arange(1, 9), rtol=1e-6)  # A_log_h = log(h + 1)
        dt = np.log1p(np.exp(np.asarray(mamba["dt_bias"], np.float32)))
        assert 1e-3 * 0.99 < dt.min() and dt.max() < 0.1 * 1.01 and np.all(np.asarray(mamba["D"]) == 1)
        assert mamba["in_proj/kernel"].shape == (64, 2 * 128 + 2 * 32 + 8) and mamba["conv1d/weight"].shape == (4, 128 + 64)
        assert "self_attn/q_proj/kernel" in {k.split("layers/5/", 1)[-1] for k in own} and not [k for k in own if "lm_head" in k]
        # the count ISSUE 49 states, part by part
        mixer = 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
        assert mixer == 25_847_232 and mixer + 3 * 2048 * 8192 + 2 * 2048 == 76_182_976
        assert get_preset("granite_4_0_h_micro").num_params == 36 * 76_182_976 + 4 * 60_821_504 + 205_520_896 + 2048 == 3_191_396_096

    def check_gradients(self, got):
        """The scan's own leaves take a gradient in every Mamba-2 layer, the trunk's through the layers above them."""
        for i in (0, 4, 9):
            for leaf in ("A_log", "D", "dt_bias", "conv1d/bias", "conv1d/weight", "norm/weight"):
                assert np.linalg.norm(got[f"model/layers/{i}/mamba/{leaf}"]) > 1e-7, (i, leaf)

    def check_published(self, mc, config):
        kinds = [mc.layer(i).attention for i in range(mc.num_layers)]
        assert kinds.count("ssd") == 36 and [i for i, k in enumerate(kinds) if k == "heads"] == [5, 15, 25, 35]
        assert not any(mc.layer(i).rope for i in range(mc.num_layers)) and mc.resolved_head_dim == 64
        assert (mc.mamba_n_heads, mc.mamba_d_head, mc.mamba_d_state, mc.mamba_n_groups, mc.mamba_d_conv) == (64, 64, 128, 1, 4)
        assert (mc.embedding_multiplier, mc.residual_multiplier, mc.logits_scaling, mc.attention_multiplier) == (12.0, 0.22, 8.0, 0.015625)
        assert mc.intermediate_size == 8192 and mc.num_experts == 0 and mc.tie_word_embeddings

    def check_checkpoint(self, state, params, flat):
        """``shared_mlp.input_linear`` is the gate's rows then the up projection's, cut into the two leaves ``_dense_mlp`` reads."""
        joined = state["model.layers.2.shared_mlp.input_linear.weight"]
        assert joined.shape == (2 * MC.intermediate_size, MC.hidden_size) and not [k for k in state if ".mlp." in k]
        np.testing.assert_array_equal(joined[:MC.intermediate_size].T, np.asarray(params["model"]["layers"]["2"]["mlp"]["gate_proj"]["kernel"]))
        np.testing.assert_array_equal(joined[MC.intermediate_size:].T, np.asarray(params["model"]["layers"]["2"]["mlp"]["up_proj"]["kernel"]))
        assert state["model.layers.0.mamba.conv1d.weight"].shape == (128 + 64, 1, 4)  # torch's Conv1d

    def test_bfloat16_stands_as_far_from_the_reference_as_the_other_dense_models(self, flat, ids):
        logits_gap, loss_gap = _bfloat16_gaps(FAMILY, flat, ids)
        assert logits_gap < 2e-2 and loss_gap < 2e-3, (logits_gap, loss_gap)

    @pytest.mark.parametrize("fault", ["no_decay", "norm_before_gate", "sqrt_scale", "unit_residual"])
    def test_the_comparison_sees_each_planted_fault(self, flat, ids, fault, monkeypatch):
        """What ``benchmarks/chipbench/tools/fault_ssd.py`` plants, on the program's side only: each moves the logits
        by far more than the sound program stands from the reference (``RTOL``)."""
        scan = ssd.ssd_scan
        if fault == "no_decay":
            monkeypatch.setattr(ssd, "ssd_scan", lambda x, dt, a, *rest, **kw: scan(x, dt, a * 0.0, *rest, **kw))
        elif fault == "norm_before_gate":
            def norm_then_gate(y, z, weight, eps, *, groups=1):
                normed = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
                return normed * weight * jax.nn.silu(z)
            monkeypatch.setattr(ssd, "gated_norm", norm_then_gate)
        elif fault == "sqrt_scale":
            monkeypatch.setattr(transformer, "_scaled_queries", lambda xq, config: xq)
        else:
            monkeypatch.setattr(transformer, "_residual", lambda y, config: y)
        assert _logit_gap(FAMILY, flat, ids) > 20 * RTOL  # (the scale, in one layer of ten at scores near 0, reads 1.6e-3; the others 0.1 and more)

    def test_a_token_moves_no_logit_before_it(self, flat, ids):
        at = 77
        moved = np.array(ids[0, 0])
        moved[:, at] = (moved[:, at] + 1) % MC.vocab_size
        base, other = (np.asarray(_logits(_params(flat), x, MC)[0]) for x in (ids[0, 0], moved))
        change = np.abs(other - base).max(axis=(0, 2))
        assert change[:at].max() == 0.0 and change[at:].min() > 0

    def test_under_last_n_and_head_the_tails_mamba_leaves_train_and_the_trunks_stay_out_of_int8(self, flat):
        tc = TrainConfig(model_preset=None, freeze_strategy="last_n_and_head", unfreeze_last_n_layers=2)
        params = _params(flat)
        mask = flatten_dict(trainable_mask(params, MC, tc))
        for leaf in ("A_log", "D", "dt_bias", "conv1d/weight", "conv1d/bias", "norm/weight", "in_proj/kernel", "out_proj/kernel"):
            assert [mask[f"model/layers/{i}/mamba/{leaf}"] for i in (0, 7, 8, 9)] == [False, False, True, True], leaf
        assert mask["model/embed_tokens/weight"] and not mask["model/norm/weight"]  # the head is the tied table
        frozen, n = quantize_trunk_int8({k: v for k, v in flatten_dict(params).items() if not mask[k]}, 8)
        assert n == 7 * 5 + 7  # in_proj, out_proj, gate, up, down of seven Mamba-2 layers; q, k, v, o and the MLP of layer 5
        small = [k for k in frozen if "/mamba/" in k and not k.split("/mamba/")[1].startswith(("in_proj", "out_proj"))]
        assert len(small) == 7 * 6 and not [k for k in small if "int8" in k]

    def test_the_tied_tables_gradient_has_its_lookup_part_through_the_frozen_trunk(self, flat, ids):
        """Under ``last_n_and_head`` the table is trained and sits below all ten layers: its gradient is the head's
        part plus the lookup's, which crosses every frozen layer (and every scan's backward). Against the reference
        with the same split, and against the head's part alone."""
        cfg = bench_cfg()
        recipe = {"unfreeze_last_n_layers": 2}
        train = set(ref.trainable_paths(cfg, recipe, flat))
        _, want = ref.microbatch_grads(flat, cfg, ids[0, 0], train)
        tc = TrainConfig(model_preset=None, compute_dtype="float32", param_dtype="float32", freeze_strategy="last_n_and_head",
                         unfreeze_last_n_layers=2, loss_chunk_size=64, gradient_checkpointing=True, max_seq_length=SEQ)
        every = flatten_dict(_params(flat))
        mask = flatten_dict(trainable_mask(_params(flat), MC, tc))
        loss_fn = step_mod.make_loss_fn(MC, tc)
        batch = {"input_ids": jnp.asarray(ids[0, 0]), "loss_mask": jnp.ones(ids[0, 0].shape, jnp.float32),
                 "attention_mask": jnp.ones(ids[0, 0].shape, jnp.int32)}
        got = jax.jit(jax.grad(lambda t: loss_fn(t, {k: v for k, v in every.items() if not mask[k]}, batch)[0]))(
            {k: v for k, v in every.items() if mask[k]})
        assert sorted(got) == sorted(want)
        assert max(_rel(got[k], want[k]) for k in want) < RTOL
        _, head_only = ref.microbatch_grads(flat, cfg, ids[0, 0], train - {ref.EMBED})
        assert ref.EMBED not in head_only  # (the head's part alone is what a table trained above a stop would get)
        lookup = np.asarray(got[ref.EMBED])[np.unique(ids[0, 0])]
        assert np.linalg.norm(lookup) > 0

    def test_packed_rows_and_a_cache_are_refused_with_their_sentences(self, flat, ids):
        params = _params(flat)
        with pytest.raises(NotImplementedError, match="state-space layers and the batch is packed .* ops/ssd.py"):
            transformer.forward(params, jnp.asarray(ids[0, 0]), MC, segment_ids=jnp.ones(ids[0, 0].shape, jnp.int32))
        cache = {"layers": {str(i): {"k": jnp.zeros((2, 8, 2, 16)), "v": jnp.zeros((2, 8, 2, 16))} for i in range(MC.num_layers)}}
        with pytest.raises(NotImplementedError, match="a state-space layer has the training form only"):
            transformer.forward(params, jnp.asarray(ids[0, 0][:, :4]), MC, cache=cache)

    def test_infer_refuses_the_model_by_name(self, flat):
        from llm_fine_tune_distributed_tpu.infer.generate import Generator, LatentAttentionNotServed

        with pytest.raises(LatentAttentionNotServed, match="'tiny_granite_h' has state-space"):
            Generator(_params(flat), MC, tokenizer=None)


# -- the multipliers, the rules ----------------------------------------------------------------------


def test_multipliers_of_one_emit_no_operation():
    """A model whose multipliers are 1 (None) lowers to the program it lowered to before they existed: the text of
    ``tiny``'s forward (SmolLM3's structure) with the three hooks taken out is the text with them in."""
    mc = get_preset("tiny")
    assert (mc.embedding_multiplier, mc.residual_multiplier, mc.logits_scaling, mc.attention_multiplier) == (1.0, 1.0, 1.0, None)
    params = jax.eval_shape(lambda: transformer.init_params(jax.random.PRNGKey(0), mc))
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = lambda: jax.jit(lambda p, x: transformer.forward(p, x, mc, remat=True)[0]).lower(params, ids).as_text()  # noqa: E731
    with_hooks = text()
    was = transformer._residual, transformer._scaled_queries
    try:
        transformer._residual, transformer._scaled_queries = (lambda y, config: y), (lambda xq, config: xq)
        assert text() == with_hooks
    finally:
        transformer._residual, transformer._scaled_queries = was
    scaled = jax.jit(lambda p, x: transformer.forward(p, x, mc.replace(residual_multiplier=0.5), remat=True)[0]).lower(params, ids).as_text()
    assert scaled != with_hooks  # (the comparison can tell)


def test_mamba_layers_ask_for_their_sizes():
    from llm_fine_tune_distributed_tpu.config import ModelConfig

    with pytest.raises(ValueError, match="mamba_\\* fields"):
        ModelConfig(num_layers=1, layer_types=("mamba",))
    with pytest.raises(ValueError, match="mamba_n_groups=3 must divide mamba_n_heads=8"):
        ModelConfig(num_layers=1, layer_types=("mamba",), mamba_n_heads=8, mamba_d_head=16, mamba_d_state=32, mamba_n_groups=3)
    with pytest.raises(ValueError, match="or 'linear_attention' \\(or 'mamba'"):
        ModelConfig(num_layers=1, layer_types=("mamba2",))


def test_the_new_leaves_have_their_sharding_rules():
    assert param_spec("model/layers/0/mamba/in_proj/kernel", 2) == jax.sharding.PartitionSpec("fsdp", None)
    assert param_spec("model/layers/0/mamba/out_proj/kernel", 2) == jax.sharding.PartitionSpec(None, "fsdp")
    for leaf, ndim in (("conv1d/weight", 2), ("conv1d/bias", 1), ("A_log", 1), ("D", 1), ("dt_bias", 1), ("norm/weight", 1)):
        assert param_spec(f"model/layers/0/mamba/{leaf}", ndim) == jax.sharding.PartitionSpec()


def test_a_rematerialized_block_keeps_nothing_of_the_scan():
    """The scan names its output and the sweeps' states for a later rule (``ops/ssd.KEPT_ACROSS_REMAT``); today a block
    with a Mamba-2 mixer keeps nothing of them, whatever the row (Granite 4.0-H Micro at 8192: 36 x 80 MiB against 1.8
    GiB of room), and says so in ``remat_summary()``'s table."""
    mc = get_preset("granite_4_0_h_micro")
    for seq in (2048, 8192):
        assert transformer._remat_policy("full", mc, seq, None, "ssd") is None
        assert transformer.REMAT_KEEPS["ssd"] == ("full", ())
    assert 36 * 8192 * 64 * 64 * (2 + 4 * 128 / 1024) == 36 * 80 * 2**20


# -- the scan against the recurrence -------------------------------------------------------------------


def walk(x, dt, a, b, c, d):
    """The recurrence token by token: ``S = exp(dt A) S + dt x B^T;  y = S C + D x`` (group ``i`` serves heads ``i r ..``)."""
    rows, _, heads, p = x.shape
    r = heads // b.shape[2]

    def step(s, t):
        x_t, dt_t, b_t, c_t = t
        b_t, c_t = jnp.repeat(b_t, r, axis=1), jnp.repeat(c_t, r, axis=1)
        s = jnp.exp(dt_t * a)[..., None, None] * s + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t) + d[:, None] * x_t

    by_token = lambda z: jnp.moveaxis(z, 1, 0)  # noqa: E731
    return jnp.moveaxis(jax.lax.scan(step, jnp.zeros((rows, heads, p, b.shape[3])), tuple(map(by_token, (x, dt, b, c))))[1], 0, 1)


def operands(rows, seq, heads, p, n, groups=1, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x, w = (jax.random.normal(k[i], (rows, seq, heads, p), jnp.float32) for i in (0, 6))
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, seq, heads), jnp.float32) - 2.0)
    a = -jnp.arange(1, heads + 1, dtype=jnp.float32)  # the family's draw: a head that forgets in a token beside one that hardly does
    b, c = (0.3 * jax.random.normal(k[i], (rows, seq, groups, n), jnp.float32) for i in (3, 4))
    return (x, dt, a, b, c, 1.0 + 0.1 * jax.random.normal(k[5], (heads,), jnp.float32)), w


NAMES = ("x", "dt", "a", "b", "c", "d")


def _grads(fn, args, w):
    return jax.grad(lambda *z: jnp.sum(fn(*z) * w), argnums=range(6))(*args)


@pytest.mark.parametrize("groups", [1, 2])
def test_the_chunked_scan_is_the_recurrence_forward_and_in_every_cotangent(groups):
    args, w = operands(2, 160, 4, 16, 32, groups=groups)
    np.testing.assert_allclose(ssd.ssd_scan(*args), walk(*args), atol=2e-4)
    for name, g, want in zip(NAMES, _grads(ssd.ssd_scan, args, w), _grads(walk, args, w)):
        assert _rel(g, want) < 5e-4, name
    assert ssd.CALLS[2, 160, 4, 16, 32, groups][1] == "chunked 128: xla"


def test_the_references_dual_form_is_the_recurrence():
    """The reference is tied to the same equations as the program: its form over the whole row against the walk."""
    args, w = operands(2, 96, 4, 16, 32, seed=1)
    np.testing.assert_allclose(ref.scan_dual(*args), walk(*args), atol=2e-4)
    for name, g, want in zip(NAMES, _grads(ref.scan_dual, args, w), _grads(walk, args, w)):
        assert _rel(g, want) < 5e-4, name


@pytest.mark.parametrize("chunk", [16, 64, 160, 256])
def test_any_chunk_gives_one_result(chunk):
    args, _ = operands(1, 160, 4, 16, 32, seed=2)
    np.testing.assert_allclose(ssd.ssd_scan(*args, chunk=chunk), ssd.ssd_scan(*args), atol=2e-4)


def test_a_token_reaches_later_outputs_across_a_chunks_boundary_and_none_before_it():
    args, _ = operands(1, 300, 2, 16, 32, seed=3)
    x = args[0]
    at = 120  # near the first chunk's end: the second chunk sees it through the carried state alone
    base, moved = ssd.ssd_scan(*args), ssd.ssd_scan(x.at[:, at, 0].add(1.0), *args[1:])  # head 0 decays slowest
    change = np.abs(np.asarray(moved - base))[0, :, 0].max(axis=-1)
    assert change[:at].max() == 0.0 and change[at:144].min() > 0  # (further on the decay has taken it below float32's last bit)
    assert np.abs(np.asarray(moved - base))[0, :, 1].max() == 0.0  # the other head's state is its own


@pytest.mark.parametrize("seq", [1, 127, 129, 300])
def test_a_row_of_any_length_is_padded_with_tokens_that_change_nothing(seq):
    """Rows that are no multiple of the chunk (a single token, one short of a chunk, one past it, two chunks and a
    part) against the walk at the Granite heads' width: the pads' ``dt = 0`` is a decay of 1 and no update, and
    their outputs are dropped."""
    args, w = operands(1, seq, 2, 64, 128, seed=5)
    np.testing.assert_allclose(ssd.ssd_scan(*args), walk(*args), atol=1e-3)  # (a state of 128: outputs of order 30)
    for name, g, want in zip(NAMES, _grads(ssd.ssd_scan, args, w), _grads(walk, args, w)):
        assert g.shape == want.shape and _rel(g, want) < 5e-4, name


def test_a_lowered_state_dtype_is_seen(monkeypatch):
    """The carried state in bfloat16 moves the output by far more than the chunked form stands from the walk."""
    args, _ = operands(2, 160, 4, 16, 32, seed=7)
    want = walk(*args)
    sound = _rel(ssd.ssd_scan(*args, chunk=16), want)
    monkeypatch.setattr(ssd, "STATE_DTYPE", jnp.bfloat16)
    assert _rel(ssd.ssd_scan(*args, chunk=16), want) > 5e-5 > 100 * sound  # (9e-5 against 4e-8: most of an output is the skip D x)


def test_the_passes_are_the_equations():
    k = jax.random.split(jax.random.PRNGKey(4), 8)
    x, bc, dt = jax.random.normal(k[0], (2, 50, 24)), jax.random.normal(k[1], (2, 50, 8)), jax.random.normal(k[2], (2, 50, 3))
    weight, bias, dt_bias = jax.random.normal(k[3], (4, 32)), jax.random.normal(k[4], (32,)), jax.random.normal(k[5], (3,))
    got = ssd.mixer_in(x, bc, dt, weight, bias, dt_bias)
    both = jnp.concatenate([x, bc], axis=-1)
    padded = jnp.pad(both, ((0, 0), (3, 0), (0, 0)))
    conv = sum(weight[j] * padded[:, j:j + 50] for j in range(4)) + bias  # three zeros left of a row, then the bias
    np.testing.assert_allclose(jnp.concatenate(got[:2], axis=-1), jax.nn.silu(conv), atol=1e-5)
    np.testing.assert_allclose(got[2], jnp.log1p(jnp.exp(dt + dt_bias)), atol=1e-6)
    y, z, w = jax.random.normal(k[6], (2, 50, 24)), jax.random.normal(k[7], (2, 50, 24)), 1.0 + 0.1 * jax.random.normal(k[0], (24,))
    for groups in (1, 3):
        gated = (y * jax.nn.silu(z)).reshape(2, 50, groups, 24 // groups)  # the gate FIRST, then the norm a group
        want = (gated / jnp.sqrt(jnp.mean(gated ** 2, axis=-1, keepdims=True) + 1e-5)).reshape(2, 50, 24) * w
        np.testing.assert_allclose(ssd.gated_norm(y, z, w, 1e-5, groups=groups), want, atol=1e-5)


# -- the kernels under the interpreter ----------------------------------------------------------------


def _interpreted(*z):
    return ssd.ssd_scan(*z, impl="kernels_interpret")


@pytest.mark.parametrize("rows, seq, heads, p", [(1, 300, 4, 64), (2, 1100, 2, 64), (1, 1024, 2, 128), (1, 1025, 4, 64)])
def test_the_sweeps_under_the_interpreter_are_the_xla_form(rows, seq, heads, p):
    """Heads of 64 two a lane block (and of 128 one), a row shorter than a step, one of two steps (the carried state
    and its cotangent cross the step's boundary) and one a single token over a step (the scratch that holds a step's
    running sums and row forms is made again at the boundary, and the second step's is all padding but a token):
    output and every cotangent, dt's and ``a``'s as the sweeps now write them, against the XLA form."""
    args, w = operands(rows, seq, heads, p, 128, seed=5)
    assert _rel(_interpreted(*args), ssd.ssd_scan(*args)) < 1e-5
    for name, g, want in zip(NAMES, _grads(_interpreted, args, w), _grads(ssd.ssd_scan, args, w)):
        assert g.shape == want.shape and _rel(g, want) < 1e-4, name


def _fast_beside_slow():
    """``a = -64`` and ``-1`` in ONE 128-lane block at dt near 1: ``|G|`` reaches 8,000 inside a chunk."""
    (x, _, _, b, c, d), w = operands(1, 300, 2, 64, 128, seed=8)
    dt = 1.0 + 0.1 * jax.random.uniform(jax.random.PRNGKey(9), (1, 300, 2), jnp.float32, -1.0, 1.0)
    return (x, dt, jnp.asarray([-64.0, -1.0]), b, c, d), w


def test_a_fast_head_beside_a_slow_one_in_one_lane_block():
    """A decay taken before the mask (``exp(G_i - G_j)`` for ``i < j``) would overflow at such a ``G`` and a running
    sum rounded to bfloat16 on its way through the MXU would be off by tens. Finite, and the XLA form's, output and
    every cotangent."""
    args, w = _fast_beside_slow()
    got, want = _interpreted(*args), ssd.ssd_scan(*args)
    assert np.isfinite(np.asarray(got)).all() and _rel(got, want) < 1e-5
    grads = _grads(_interpreted, args, w)
    for name, g, wanted in zip(NAMES, grads, _grads(ssd.ssd_scan, args, w)):
        assert np.isfinite(np.asarray(g)).all() and _rel(g, wanted) < 1e-4, name
    assert np.abs(np.asarray(grads[1])[0, :, 1]).min() > 0  # (the slow head's dt reaches later tokens through G: its cotangent is the folded one)


def test_the_sweeps_running_sum_is_float32_and_the_tests_see_one_that_is_not(monkeypatch):
    """The products that sum and turn the per-token scalars say ``Precision.HIGHEST`` (the interpreter on a CPU would
    not show a bfloat16 pass, the chip would); and with ``dt A`` rounded to bfloat16 before the sum, as the MXU's
    default pass would leave it, the comparison above fails by far."""
    for product in (ssd._turned, ssd._turned_back):
        (eqn,) = jax.make_jaxpr(product)(jnp.zeros((128, 128)), jnp.zeros((128, 128))).eqns
        assert eqn.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2 and eqn.params["preferred_element_type"] == jnp.float32
    args, _ = _fast_beside_slow()
    want = ssd.ssd_scan(*args)
    turned = ssd._turned
    monkeypatch.setattr(ssd, "_turned", lambda u, v: turned(u.astype(jnp.bfloat16).astype(jnp.float32), v))
    ssd._flat_scan.cache_clear(), ssd.ssd_scan_fwd.clear_cache()
    try:
        assert _rel(_interpreted(*args), want) > 1e-3
    finally:
        monkeypatch.undo()
        ssd._flat_scan.cache_clear(), ssd.ssd_scan_fwd.clear_cache()


def test_which_program_runs_the_scan_is_read_from_the_call(monkeypatch):
    sizes = dict(p=64, n=128, groups=1)
    assert ssd._program(**sizes) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd._program(**sizes) == "kernels" and ssd._program(p=128, n=256, groups=1) == "kernels"
    assert ssd._program(p=16, n=32, groups=1) == "xla (a state of 32 is no multiple of 128)"
    assert ssd._program(p=48, n=128, groups=1) == "xla (heads of 48 do not divide the 128 lanes)"
    assert ssd._program(p=64, n=128, groups=2) == "xla (2 groups of B and C: the kernels share one)"
    assert ssd._program(**sizes, chunk=64) == "xla (chunk 64 is not 128)"
    mesh = SimpleNamespace(size=4)
    assert ssd._program(**sizes, mesh=mesh) == "xla (a mesh of 4 devices: the sweeps are one device's program)"


@pytest.mark.parametrize("seq, streamed", [(256, False), (512, True)])
def test_the_flash_kernels_at_heads_of_64_are_xla_attention(seq, streamed, monkeypatch):
    """Granite's attention layers: 4 queries a kv head at heads of 64, causal, no rope, through the resident kernels and
    (the cap lowered, as at a row of 8192) the streamed ones under the interpreter, forward and gradients."""
    if streamed:
        monkeypatch.setattr(flash_attention, "_VMEM_CAP_BYTES", 1 << 20)
    k = jax.random.split(jax.random.PRNGKey(6), 4)
    q, kk, v, w = (jax.random.normal(k[i], (1, seq, h, 64), jnp.float32) for i, h in ((0, 8), (1, 2), (2, 2), (3, 8)))
    assert flash_attention._streamed(q.dtype, seq, 64, 64, flash_attention._pick_block(seq), 4, None) == streamed
    kernels = lambda *z: flash_attention.pallas_flash_attention(*z, interpret=True)  # noqa: E731
    plain = lambda *z: attention(*z, impl="xla", causal=True)  # noqa: E731
    assert _rel(kernels(q, kk, v), plain(q, kk, v)) < 2e-5
    grads, wants = (jax.grad(lambda *z: jnp.sum(fn(*z) * w), argnums=(0, 1, 2))(q, kk, v) for fn in (kernels, plain))
    assert max(_rel(g, want) for g, want in zip(grads, wants)) < 2e-5


def test_heads_of_64_take_the_flash_kernels_and_the_xla_hand_over(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k = (jax.ShapeDtypeStruct((1, 8192, h, 64), jnp.bfloat16) for h in (32, 8))
    assert flash_attention.flash_unsupported_reason(q, k, k) is None
    assert flash_attention.program_label(q, k, k) == "streamed causal"
    odd = jax.ShapeDtypeStruct((1, 8192, 8, 96), jnp.bfloat16)
    assert "head dim 96" in flash_attention.flash_unsupported_reason(odd, odd, odd)
    cos = jnp.zeros((1, 8192, 64))
    assert "head dim 64 is not whole 128-lane registers" in rope_ops.why_not_fused(1, 8192, 64, cos)
    assert rope_ops.why_not_fused(1, 8192, 128, cos) is None


def test_the_attention_scale_is_the_configs():
    """``attention_multiplier`` in place of ``d ** -0.5``: the layer's output against plain attention at that scale."""
    mc = dataclasses.replace(MC, layer_types=("full_attention",) * MC.num_layers, num_layers=1, no_rope_layers=(0,))
    params = transformer.init_params(jax.random.PRNGKey(1), mc)
    attn_p = params["model"]["layers"]["0"]["self_attn"]
    hid = jax.random.normal(jax.random.PRNGKey(2), (1, 32, mc.hidden_size))
    lin = lambda x, p: x @ p["kernel"]  # noqa: E731
    q, k, v, _ = transformer._heads_qkv(attn_p, hid, None, None, mc, lin, False)
    got = attention(q, k, v, impl="xla", causal=True)
    d = mc.resolved_head_dim
    raw = (hid @ attn_p["q_proj"]["kernel"]).reshape(1, 32, mc.num_heads, d)
    np.testing.assert_allclose(got, attention(raw, k, v, impl="xla", causal=True, scale=mc.attention_multiplier), atol=1e-6)
    assert from_hf_config(SimpleNamespace(**bench_cfg())).attention_multiplier == 0.015625


# -- the cell's whole step, for a described v5e (a family's whole step compiles in the family's own file) ------------------


def test_the_cells_step_compiles_for_v5e(topo, monkeypatch):
    """The Granite 4.0-H Micro cell's step as its traffic file states it (``benchmarks/step_memory.STEPS``: all 40
    layers, 38 frozen, one row of 8192 x 2, remat full, loss in chunks of 1024): what the chip's compiler accepts, under
    the 15.0 GiB line the cells are sized by; the scan's forward sweep twice a Mamba-2 layer (nothing of it is kept
    across remat) and its backward sweep in ALL 36 (the tied table's lookup lies below them), the streamed
    flash kernels once each in the four attention layers (``o`` and ``lse`` kept), and no ``[T, T]`` buffer."""
    import dataclasses

    with jax.default_matmul_precision("default"):  # (conftest.py sets ``highest`` for CPU numerics; the entry points run at the default)
        from benchmarks.step_memory import STEPS
        from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        preset, overrides, rows, accum, seq, recipe = STEPS["granite-4.0-h-micro.sft-8k-ssd-tied-last2"]
        setup = abstract_train_setup(
            {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, preset, devices=topo.devices[:1], accum=accum, seq=seq,
            per_dp_batch=rows, param_dtype="bfloat16", train_kwargs=recipe, model_overrides=overrides)
        float32 = lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=x.sharding) if jnp.issubdtype(x.dtype, jnp.floating) else x  # noqa: E731
        compiled = dataclasses.replace(setup, state=setup.state.replace(opt_state=jax.tree.map(float32, setup.state.opt_state))).compile()
        assert 4 * 2**30 < compiled.memory_analysis().peak_memory_in_bytes < 15.0 * 2**30
        lines = [ln for ln in compiled.as_text().splitlines()]
        calls = lambda kernel: sum("tpu_custom_call" in ln and f"/{kernel}/" in ln for ln in lines)  # noqa: E731
        assert {k: calls(k) for k in ("ssd_scan_fwd", "ssd_scan_bwd")} == {"ssd_scan_fwd": 72, "ssd_scan_bwd": 36}
        assert [calls(f"flash_attention_causal_{k}") for k in ("fwd", "dq", "dkv")] == [4, 4, 4]
        assert not calls("attn_in_fwd")  # heads of 64: the XLA hand-over (ops/rope.why_not_fused)
        produced = [ln.split(" = ", 1)[-1].split("(", 1)[0] for ln in lines]
        assert not [x for x in produced if f",{seq},{seq}]" in x]
        # between the IN pass and the sweeps nothing but free reshapes: no XLA operation under the scan's scope turns (the
        # compiler writes a transpose as a ``copy`` between layouts: the parent's step had 360), gathers or sums over a
        # window (108 there): the running sum of dt A and the row forms are made inside the sweeps
        under_scan = [ln.split(" = ", 1)[-1].split(", metadata", 1)[0] for ln in lines if "/ssd_scan/" in ln and "tpu_custom_call" not in ln]
        assert under_scan and not [ln for ln in under_scan if re.search(r"\b(transpose|copy|reduce-window|gather)\(", ln)]
