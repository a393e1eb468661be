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
whole.
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
from llm_fine_tune_distributed_tpu.models import hf_io
from llm_fine_tune_distributed_tpu.models.configs import from_hf_config, get_preset, to_hf_dict
from llm_fine_tune_distributed_tpu.models.transformer import (
    forward_with_report, init_params, keeps_flash_outputs, keeps_scan_output, rope_tables,
)
from llm_fine_tune_distributed_tpu.ops import gated_delta, moe
from llm_fine_tune_distributed_tpu.ops.rope import apply_rope, rope_cos_sin
from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
from llm_fine_tune_distributed_tpu.parallel.lora import add_lora_params
from llm_fine_tune_distributed_tpu.parallel.pipeline import layer_scan_problems
from llm_fine_tune_distributed_tpu.parallel.sharding import param_spec
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.train.step import build_train_step
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import check, reference_gdn_moe as ref  # noqa: E402
from benchmarks.chipbench import weights, weights_gdn_moe  # noqa: E402

TINY = get_preset("tiny_qwen3_next")
MC = TINY.replace(num_layers=8, layer_types=TINY.layer_types * 2)  # two periods
ACCUM, ROWS, SEQ = 2, 2, 160
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


@pytest.fixture(scope="module")
def flat():
    return _slow_decays(weights_gdn_moe.make_flat(11, bench_cfg()))


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(5).randint(0, MC.vocab_size, (2, ACCUM, ROWS, SEQ)).astype(np.int32)  # two steps


def _params(flat, dtype=jnp.float32):
    return weights.nest({k: v.astype(dtype) for k, v in flat.items()})


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# -- the rule alone -----------------------------------------------------------


def _rule_inputs(seed, rows, seq, hk, hv, dk, dv, a_max):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gated_delta.l2_norm(jax.random.normal(ks[0], (rows, seq, hk, dk))) * dk ** -0.5
    k = gated_delta.l2_norm(jax.random.normal(ks[1], (rows, seq, hk, dk)))
    v = jax.random.normal(ks[2], (rows, seq, hv, dv))
    a = jnp.full((hv,), a_max) if a_max == 16.0 else jax.random.uniform(ks[3], (hv,), minval=0.0, maxval=a_max)
    g = -a * jax.nn.softplus(jax.random.normal(ks[4], (rows, seq, hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (rows, seq, hv)))
    return q, k, v, g, beta


def _token_by_token(q, k, v, g, beta):
    r = v.shape[2] // q.shape[2]
    return ref.delta_rule(jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v, g, beta, segment=16)


def _at_a_kernel_head(seed, seq, r, a_max):
    """The rule's inputs at heads of 128 (whole lanes: what the kernels take), one key head serving r value heads."""
    return _rule_inputs(seed, 2, seq, 1, r, 128, 128, a_max)


_LOSS = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a))))  # noqa: E731
_KERNELS = lambda *a: gated_delta.gated_delta_rule(*a, impl="kernels_interpret")  # noqa: E731
_XLA = lambda *a: gated_delta.gated_delta_rule(*a, impl="xla")  # noqa: E731


@pytest.mark.parametrize("seq", [64, 128, 100, 37, 200], ids=lambda s: f"seq{s}")
@pytest.mark.parametrize("a_max", [1.0, 16.0], ids=["slow-decays", "strongest-decay"])
@pytest.mark.parametrize("program", ["xla", "kernels-r1", "kernels-r2"])
def test_chunked_rule_equals_token_by_token(seq, a_max, program):
    """Rows that are and are not whole chunks (of 32 for the XLA form here; the
    kernels pad a row to whole steps of 8 chunks of 64), output and every
    input's gradient. ``a_max`` 16 with every head AT 16 is the strongest
    decay ``A_log`` can give (``exp(-16 softplus(.))`` a token: ``exp(-G)``
    alone would overflow float32 within a chunk; the chunked form never forms
    it) and must stay finite. The XLA form is held to the recurrence in both;
    the kernels (under the Pallas interpreter, heads of 128, one and two value
    heads a key head) to the recurrence in the output and to ``jax.vjp`` of
    the XLA form in the gradients."""
    if program == "xla":
        args = _rule_inputs(seq, 2, seq, 2, 4, 16, 8, a_max)
        chunked, held_to = (lambda *a: gated_delta.gated_delta_rule(*a, chunk=32)), _token_by_token
    else:
        args = _at_a_kernel_head(seq, seq, int(program[-1]), a_max)
        chunked, held_to = _KERNELS, _XLA
    got, want = chunked(*args), _token_by_token(*args)
    assert bool(jnp.isfinite(got).all()) and _rel(got, want) < 1e-5
    g_got = jax.grad(_LOSS(chunked), argnums=(0, 1, 2, 3, 4))(*args)
    g_want = jax.grad(_LOSS(held_to), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        assert bool(jnp.isfinite(a).all()) and _rel(a, b) < 1e-4, name


BY_CHANNEL = "chunked 64, a decay a channel in sub-blocks of 16"


@pytest.mark.parametrize("backend, shape, chunk, form, passes", [
    ("tpu", (2, 128, 16, 32, 128, 128), 64, "chunked 64: kernels", ("kernels", "kernels")),
    ("tpu", (2, 100, 2, 2, 256, 128), 64, "chunked 64: kernels", ("kernels", "kernels")),
    ("tpu", (2, 128, 2, 4, 16, 16), 64, "chunked 64: xla (d_k 16 is no multiple of 128)",
     ("xla (d_k 16 is no multiple of 128)", "xla (d_v 16 is no multiple of 128)")),
    ("tpu", (2, 128, 2, 4, 128, 64), 64, "chunked 64: xla (d_v 64 is no multiple of 128)",
     ("kernels", "xla (d_v 64 is no multiple of 128)")),  # in: a key head's two value heads fill 128 lanes
    ("tpu", (2, 128, 2, 4, 128, 128), 32, "chunked 32: xla (chunk 32 is not 64)", ("kernels", "kernels")),
    ("cpu", (2, 128, 2, 4, 128, 128), 64, "chunked 64: xla", ("xla", "xla")),
    # g of rank 4, a decay a CHANNEL (Kimi Delta Attention; PR 43): the same questions, its own two sweeps
    ("tpu", (2, 8192, 32, 32, 128, 128, "by channel"), 64, f"{BY_CHANNEL}: kernels", ("kernels", "kernels")),
    ("tpu", (2, 100, 2, 4, 128, 128, "by channel"), 64, f"{BY_CHANNEL}: kernels", ("kernels", "kernels")),
    ("tpu", (2, 128, 2, 2, 16, 16, "by channel"), 64, f"{BY_CHANNEL}: xla (d_k 16 is no multiple of 128)",
     ("xla (d_k 16 is no multiple of 128)", "xla (d_v 16 is no multiple of 128)")),
    ("tpu", (2, 128, 2, 2, 128, 128, "by channel"), 32, "chunked 32, a decay a channel in sub-blocks of 16: xla (chunk 32 is not 64)",
     ("kernels", "kernels")),
    ("cpu", (2, 128, 2, 2, 128, 128, "by channel"), 64, f"{BY_CHANNEL}: xla", ("xla", "xla")),
], ids=["cell", "wide-keys", "narrow-keys", "narrow-values", "other-chunk", "cpu",
        "by-channel-cell", "by-channel-shared-keys", "by-channel-narrow", "by-channel-other-chunk", "by-channel-cpu"])
def test_which_program_takes_the_rule_is_read_from_the_input(monkeypatch, backend, shape, chunk, form, passes):
    """No knob: the kernels on a TPU where a head is whole lanes and the chunk is
    64, the XLA form elsewhere, and ``CALLS`` says which and, on a TPU, why not
    (every form starts ``chunked``, what ``gdn_chunked_calls_pct`` reads). The
    mixer's two passes around the rule read the same (the chunk is not theirs)
    and say it in a dict of their own, ``PASSES``: ``CALLS`` is the rule's.
    Which RULE it is is read from ``g``'s rank: a decay a channel is counted
    under a key of its own and takes its own kernels under the same conditions."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(gated_delta, "CALLS", {})
    monkeypatch.setattr(gated_delta, "PASSES", {})
    rows, seq, hk, hv, dk, dv = shape[:6]
    like = lambda *x: jax.ShapeDtypeStruct(x, jnp.bfloat16)  # noqa: E731
    g = jax.ShapeDtypeStruct((rows, seq, hv) + (dk,) * (len(shape) == 7), jnp.float32)
    out = jax.eval_shape(lambda *a: gated_delta.gated_delta_rule(*a, chunk=chunk), like(rows, seq, hk, dk), like(rows, seq, hk, dk),
                         like(rows, seq, hv, dv), g, like(rows, seq, hv))
    assert out.shape == (rows, seq, hv, dv) and out.dtype == jnp.bfloat16
    assert gated_delta.CALLS == {shape: [1, form]} and form in gated_delta.calls_summary()
    q, k, v = jax.eval_shape(lambda *a: gated_delta.mixer_in(*a, hk), like(rows, seq, hk * dk), like(rows, seq, hk * dk),
                             like(rows, seq, hv * dv), like(4, 2 * hk * dk + hv * dv))
    y = jax.eval_shape(lambda *a: gated_delta.gated_norm(*a, 1e-6), like(rows, seq, hv * dv), like(rows, seq, hv * dv), like(dv))
    assert (q.shape, k.shape, v.shape, y.shape) == ((rows, seq, hk * dk),) * 2 + ((rows, seq, hv * dv),) * 2 and y.dtype == jnp.bfloat16
    assert gated_delta.PASSES == {("in", rows, seq, 2 * hk * dk + hv * dv): [1, passes[0]], ("out", rows, seq, hv * dv): [1, passes[1]]}
    assert gated_delta.CALLS == {shape: [1, form]}  # the passes count nothing there
    assert f"mixer passes: in {[rows, seq, 2 * hk * dk + hv * dv]}: {passes[0]} x 1; out {[rows, seq, hv * dv]}: {passes[1]} x 1" \
        in gated_delta.calls_summary()


# -- the mixer's two elementwise passes ---------------------------------------


def _pass_inputs(which, rows, seq, hk, r, dtype, seed=0):
    """A pass's arguments at heads of 128 (what the kernels take): ``hk`` key heads, ``r`` value heads each."""
    ks = jax.random.split(jax.random.PRNGKey(seed + seq), 4)
    act = lambda key, width: jax.random.normal(key, (rows, seq, width)).astype(dtype)  # noqa: E731
    if which == "in":
        return (act(ks[0], hk * 128), act(ks[1], hk * 128), act(ks[2], hk * r * 128),
                (0.5 * jax.random.normal(ks[3], (4, (2 + r) * hk * 128))).astype(dtype))
    return act(ks[0], hk * r * 128), act(ks[1], hk * r * 128), (1 + 0.3 * jax.random.normal(ks[2], (128,))).astype(dtype)


def _pass(which, hk, impl):
    if which == "in":
        return lambda *a: gated_delta.mixer_in(*a, hk, impl=impl)
    return lambda *a: gated_delta.gated_norm(*a, 1e-6, impl=impl)


def _output_and_cotangents(fn, args):
    """``fn``'s outputs and the cotangent of every argument (the taps' and the norm's weight among them) under a loss
    that weighs every output element differently."""
    loss = lambda *a: sum(jnp.sum(jnp.sin(y.astype(jnp.float32) + 0.3)) for y in jax.tree.leaves(fn(*a)))  # noqa: E731
    return jax.tree.leaves((fn(*args), jax.grad(loss, argnums=tuple(range(len(args))))(*args)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rows, seq, hk, r", [(1, 512, 1, 2), (1, 1536, 2, 1), (1, 700, 1, 2), (2, 1024, 1, 1)],
                         ids=["one-block", "three-blocks", "no-whole-block", "two-rows"])
@pytest.mark.parametrize("which", ["in", "out"])
def test_a_pass_as_kernels_equals_its_xla_form(which, rows, seq, hk, r, dtype):
    """Each fused pass under the Pallas interpreter against the XLA form (``causal_conv``, ``l2_norm``, ``rms_norm``),
    the outputs and every argument's cotangent: a row of one token block, of three (blocks of 512: the taps' 3 tokens
    cross a block's edge forward and, in the backward pass, against time), a row padded to a whole block, two rows
    (nothing leaks from the end of one into the start of the next). Float32: the same mathematics, to 1e-5. Bfloat16:
    the kernels round once where the XLA form rounds at every step, so against the XLA form IN FLOAT32 on the same
    bfloat16 values they stand no further off than the XLA form in bfloat16 does (and both within bfloat16's grain)."""
    args = _pass_inputs(which, rows, seq, hk, r, dtype)
    got = _output_and_cotangents(_pass(which, hk, "kernels_interpret"), args)
    want = _output_and_cotangents(_pass(which, hk, "xla"), args)
    assert [a.shape for a in got] == [a.shape for a in want] and [a.dtype for a in got] == [a.dtype for a in want]
    if dtype == jnp.float32:
        for a, b in zip(got, want):
            assert bool(jnp.isfinite(a).all()) and _rel(a, b) < 1e-5
        return
    exact = _output_and_cotangents(_pass(which, hk, "xla"), [x.astype(jnp.float32) for x in args])
    for a, b, c in zip(got, want, exact):
        assert bool(jnp.isfinite(a).all()) and _rel(a, c) < max(1.25 * _rel(b, c), 2.0 ** -9), (_rel(a, c), _rel(b, c))


def test_the_first_three_tokens_of_a_row_see_zeros_left_of_it():
    """The causal zero: token 0 of EVERY row sees its own tap alone, whatever ends the row before it (two rows of one
    block each: the block before row 1's first is row 0's last in memory order, and is not read)."""
    xq, xk, xv, w = _pass_inputs("in", 2, 512, 1, 1, jnp.float32)
    xv = xv.at[0, -3:].set(1e3)                              # what must not leak into row 1
    _, _, v = gated_delta.mixer_in(xq, xk, xv, w, 1, impl="kernels_interpret")
    taps = w[:, 256:]
    for row in range(2):
        want = [jax.nn.silu(sum(taps[3 - j] * xv[row, t - j] for j in range(t + 1))) for t in range(3)]
        np.testing.assert_allclose(np.asarray(v[row, :3]), np.asarray(jnp.stack(want)), rtol=1e-5, atol=1e-6)


def test_the_projection_is_cut_by_column_where_its_leaf_can_be():
    """``in_proj_qkvz`` stays ONE leaf; the mixer cuts it by output column and makes a product a run (with LoRA beside
    the kernel: ``lora_b`` is cut, ``lora_a`` is not), so that no activation is sliced. A leaf it cannot cut makes one
    product whose output is cut. Both equal ``lin(hid, p)`` cut."""
    from llm_fine_tune_distributed_tpu.models import transformer

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    hid = jax.random.normal(ks[0], (2, 8, 16))
    p = {"kernel": jax.random.normal(ks[1], (16, 24)), "lora_a": jax.random.normal(ks[2], (16, 4)),
         "lora_b": jax.random.normal(ks[3], (4, 24)), "lora_scale": jnp.asarray(0.5)}
    products = []
    lin = lambda x, q: products.append(q) or transformer._linear(x, q, jnp.float32)  # noqa: E731
    whole = transformer._linear(hid, p, jnp.float32)
    runs = transformer._by_columns(hid, p, (0, 8, 20, 24), lin)
    assert len(products) == 3 and [q["kernel"].shape[1] for q in products] == [8, 12, 4]
    for y, (lo, hi) in zip(runs, ((0, 8), (8, 20), (20, 24))):
        assert _rel(y, whole[..., lo:hi]) < 1e-6
    del products[:]
    other = {"kernel": p["kernel"], "lora_a_pool": jnp.zeros((2, 16, 4)), "lora_b_pool": jnp.zeros((2, 4, 24)), "lora_scale_pool": jnp.ones((2,))}
    runs = transformer._by_columns(hid, other, (0, 8, 24), lin)
    assert len(products) == 1 and [y.shape[-1] for y in runs] == [8, 16]


def test_unit_lower_inverse_and_its_derivative():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)) * 0.3, -1)
    eye = jnp.eye(64)
    np.testing.assert_allclose(np.asarray(gated_delta.unit_lower_inverse(a) @ (eye + a)), np.broadcast_to(eye, a.shape),
                               atol=2e-5)
    f = lambda inv: (lambda x: jnp.sum(jnp.cos(inv(jnp.tril(x, -1)))))  # noqa: E731
    got = jax.grad(f(gated_delta.unit_lower_inverse))(a)
    want = jax.grad(f(lambda x: jnp.linalg.inv(eye + x)))(a)
    assert _rel(got, want) < 1e-4


def test_causal_conv_is_torchs_padded_depthwise_convolution():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 6))
    got = np.asarray(gated_delta.causal_conv(x, w))
    want = np.zeros_like(got)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(w)[j] * np.asarray(x)[:, t - 3 + j]
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.causal_conv(x, w)), want, atol=1e-5)


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


# -- the model against the reference ------------------------------------------


def test_leaves_and_parameter_count_agree_with_the_benchmarks_weights():
    own = flatten_dict(init_params(jax.random.PRNGKey(0), MC))
    assert {k: v.shape for k, v in own.items()} == weights_gdn_moe.leaf_shapes(bench_cfg())
    assert MC.num_params == sum(int(np.prod(s)) for s in weights_gdn_moe.leaf_shapes(bench_cfg()).values())
    assert [MC.layer(i).attention for i in range(4)] == ["linear"] * 3 + ["heads"]
    # zero-centred norms start at 0, the gated norm at 1, dt_bias at 1, A_log inside log U(0, 16)
    assert float(jnp.abs(own["model/layers/3/self_attn/q_norm/weight"]).max()) == 0.0
    assert float(own["model/layers/0/linear_attn/norm/weight"].min()) == 1.0
    assert float(own["model/layers/0/linear_attn/A_log"].max()) <= np.log(16.0)


def test_forward_logits_agree_with_the_reference(flat, ids):
    got, _, report = forward_with_report(_params(flat), jnp.asarray(ids[0, 0]), MC, compute_dtype=jnp.float32)
    assert set(report) == {"expert_load"}
    assert _rel(got, ref.logits(flat, bench_cfg(), ids[0, 0])) < RTOL
    chosen = ref.selections(flat, bench_cfg(), ids[0, 0])
    held = list(MC.held_expert_ids)
    want_load = np.stack([np.asarray(chosen[i]).sum((0, 1))[held] for i in sorted(chosen)])
    np.testing.assert_array_equal(np.asarray(report["expert_load"]), want_load)


def _state(flat, tc, dtype):
    params = _params(flat, dtype)
    assert all(flatten_dict(trainable_mask(params, MC, tc)).values())  # nothing frozen, no buffer among the leaves
    optimizer = optax.chain(optax.clip_by_global_norm(RECIPE["max_grad_norm"]),
                            optax.adamw(RECIPE["learning_rate"], weight_decay=0.0))
    trainable = flatten_dict(params)
    return optimizer, TrainState(step=jnp.zeros((), jnp.int32), trainable=trainable, frozen={},
                                 opt_state=optimizer.init(trainable))


def _train_config(param_dtype, seq=SEQ):
    return TrainConfig(model_preset=None, compute_dtype="float32", param_dtype=param_dtype,
                       gradient_checkpointing=True, remat_policy="full", freeze_strategy="none",
                       per_device_batch_size=ROWS, gradient_accumulation_steps=ACCUM, max_seq_length=seq)


def _batch(ids, real=None):
    mask = np.ones(ids.shape, np.float32) if real is None else (np.arange(ids.shape[-1]) < real).astype(np.float32) * np.ones(ids.shape, np.float32)
    return {"input_ids": jnp.asarray(ids), "loss_mask": jnp.asarray(mask), "attention_mask": jnp.asarray(mask, jnp.int32)}


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
                             lambda names: {k: flat[k] for k in names}, keep_first_grad=True)
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
    """As in ``test_swa_moe.py``: bfloat16 masters on both sides, a rounding
    here and there falls the other way (observed 2e-3 of the worst leaf's
    change); a step left out is 0.3 and more."""
    gap, where = check.worst_leaf_gap(two_steps["delta"], two_steps["want"]["delta_norms"])
    assert gap < 5e-3, (gap, where)


def test_the_step_reports_its_expert_counters(two_steps):
    m = two_steps["metrics"]
    assert m["expert_load"].shape == (len(MC.held_expert_ids),)
    assert 0.6 < float(m["expert_pairs_per_token"]) < 1.4  # 4 of 16 chosen, 4 held: 1 pair a token expected
    # every linear layer's rule was traced in the chunked form, whole rows of the microbatch
    calls, form = gated_delta.CALLS[ROWS, SEQ, 2, 4, 16, 16]
    assert form == f"chunked {gated_delta.CHUNK}: xla" and calls >= 6 and "chunked" in gated_delta.calls_summary()


def test_right_padded_rows_train_and_match_the_reference_on_the_unpadded_part(flat, ids):
    """Rows of 100 real tokens padded on the right to 160: nothing is needed
    for them. The rule and the convolution are causal, so no pad reaches a
    real token; the loss masks the pads. Logits at the real positions, and a
    step's loss and gradient norm, equal the reference's on the rows cut to
    their 100 tokens."""
    real = 100
    got = forward_with_report(_params(flat), jnp.asarray(ids[0, 0]), MC, compute_dtype=jnp.float32,
                              padding_mask=_batch(ids[0, 0], real)["attention_mask"])[0]
    assert _rel(got[:, :real], ref.logits(flat, bench_cfg(), ids[0, 0][:, :real])) < RTOL
    tc = _train_config("float32")
    optimizer, state = _state(flat, tc, jnp.float32)
    _, metrics = jax.jit(build_train_step(MC, tc, optimizer))(state, _batch(ids[0], real))
    want = ref.sft_reference({k: jnp.array(v) for k, v in flat.items()}, bench_cfg(), RECIPE, [ids[0][..., :real]],
                             lambda names: {k: flat[k] for k in names})
    assert abs(float(metrics["loss"]) - want["losses"][0]) < RTOL
    assert abs(float(metrics["grad_norm"]) / want["grad_norm"] - 1) < RTOL


def test_the_four_shares_add_up_to_the_uncut_layer(flat):
    """The share test. Experts 0-3, 4-7, 8-11 and 12-15 as four programs, each
    told its share (``held_experts``) and handed its rows of the expert leaves
    and the whole router: their routed outputs, plus the gated shared expert
    (which every share computes alike) counted ONCE, add up to what the uncut
    reference gives for the whole layer (all 16 experts and the shared one)."""
    from llm_fine_tune_distributed_tpu.models import transformer

    whole = dict(bench_cfg(), num_experts=16, held_experts=list(range(16)))
    full = weights_gdn_moe.make_flat(11, whole)
    lp = {k: v.astype(jnp.float32) for k, v in ref.layer_leaves(full, 1).items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 64, MC.hidden_size), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(lp, h, dict(ref.cfg_items(whole)))
        shared_once = ref.experts(lp, h, dict(ref.cfg_items(whole)), held=())
    lin = lambda x, p: x @ p["kernel"]  # noqa: E731
    total, loads = 0.0, []
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        tree = weights.nest({k: (v[4 * share: 4 * share + 4] if "/experts/" in k else v)
                             for k, v in lp.items() if k.startswith("mlp/")})["mlp"]
        mc = MC.replace(held_experts=held)
        y, counted = transformer._grouped_experts(tree, h, lin, mc, compute_dtype=jnp.float32, mesh=None)
        routed, _ = moe.grouped_moe_mlp(tree, h, mc, jnp.float32)
        assert _rel(y - routed, shared_once) < RTOL  # what every share computes alike
        total, loads = total + routed, loads + [int(counted["expert_load"].sum())]
    assert _rel(total + shared_once, want) < RTOL
    assert sum(loads) == 2 * 64 * MC.num_experts_per_tok  # every pair of every token is some share's


# -- configuration, names, rules, refusals -------------------------------------


def test_published_config_builds_and_round_trips():
    if not os.path.exists(CATALOG):
        pytest.skip("the driver's catalog is not installed here")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f if '"Qwen3-Next-80B-A3B-Instruct"' in line][0]
    mc = from_hf_config(SimpleNamespace(**row["config"]))  # verbatim: layer_types from full_attention_interval
    assert dataclasses.replace(mc, name="qwen3_next_80b_a3b") == get_preset("qwen3_next_80b_a3b")
    assert [mc.layer(i).attention for i in range(8)] == (["linear"] * 3 + ["heads"]) * 2
    assert abs(mc.num_params / 79.7e9 - 1) < 0.005 and mc.num_params == 79_674_391_296
    cut = mc.replace(num_layers=4, vocab_size=18992, held_experts=tuple(range(32)))
    assert cut.num_params == 625_667_136  # the cell's 625.7 M
    with open(os.path.join(REPO, "benchmarks/chipbench/configs/qwen3-next-80b-a3b-ep16-d4.json")) as f:
        cell = json.load(f)
    assert cut.num_params == sum(int(np.prod(s)) for s in weights_gdn_moe.leaf_shapes(cell).values())
    for preset in ("qwen3_next_80b_a3b", "tiny_qwen3_next"):
        assert from_hf_config(SimpleNamespace(**to_hf_dict(get_preset(preset)))) == get_preset(preset)


@pytest.mark.parametrize("key, value", [
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]), ("norm_topk_prob", False),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}), ("shared_expert_intermediate_size", 24),
    ("layer_types", ["linear_attention"]),
])
def test_what_is_not_implemented_is_refused_by_name(key, value):
    base = dict(model_type="qwen3_next", vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=4,
                num_attention_heads=2, num_key_value_heads=1, head_dim=16, partial_rotary_factor=0.25,
                num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16, shared_expert_intermediate_size=16,
                linear_num_key_heads=1, linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=8,
                linear_conv_kernel_dim=4, full_attention_interval=4)
    mc = from_hf_config(SimpleNamespace(**base))
    assert mc.layer(3).attention == "heads" and mc.layer(0).attention == "linear" and mc.shared_expert_gate
    with pytest.raises(ValueError, match="qwen3_next config has|layer_types must name each"):
        from_hf_config(SimpleNamespace(**dict(base, **{key: value})))


def test_layer_types_message_names_three_kinds():
    with pytest.raises(ValueError, match="'sliding_attention', 'full_attention' or 'linear_attention'"):
        ModelConfig(num_layers=1, layer_types=("chunked_attention",))
    with pytest.raises(ValueError, match="linear_\\* fields"):
        ModelConfig(num_layers=1, layer_types=("linear_attention",))


def test_checkpoint_names_round_trip(flat):
    """HF's names: ``linear_attn.{in_proj_qkvz, in_proj_ba, conv1d, A_log,
    dt_bias, norm, out_proj}``, ``mlp.shared_expert`` and
    ``mlp.shared_expert_gate``. HF interleaves the two input projections by
    key head: key head g's rows of ``in_proj_qkvz.weight`` are ``[q_g | k_g |
    v of its two value heads | z of them]``; the tree keeps ``[q | k | v | z]``."""
    params = _params(flat)
    state = hf_io.pytree_to_hf_state_dict(params, MC)
    lin, full = "model.layers.0.linear_attn.", "model.layers.3."
    for name in (lin + "in_proj_qkvz.weight", lin + "in_proj_ba.weight", lin + "conv1d.weight", lin + "A_log",
                 lin + "dt_bias", lin + "norm.weight", lin + "out_proj.weight", full + "self_attn.q_norm.weight",
                 full + "mlp.shared_expert.up_proj.weight", full + "mlp.shared_expert_gate.weight",
                 full + "mlp.experts.3.gate_proj.weight"):
        assert name in state, name
    assert state[lin + "conv1d.weight"].shape == (2 * 2 * 16 + 4 * 16, 1, 4)
    assert state[full + "mlp.shared_expert_gate.weight"].shape == (1, MC.hidden_size)
    assert state[full + "self_attn.q_proj.weight"].shape == (2 * MC.num_heads * MC.head_dim, MC.hidden_size)
    # the layout: HF's row block of key head 1 starts with q of key head 1
    kernel = np.asarray(flatten_dict(params)["model/layers/0/linear_attn/in_proj_qkvz/kernel"])  # [h, q | k | v | z]
    stored = state[lin + "in_proj_qkvz.weight"]                                                # [2 x (16 + 16 + 32 + 32), h]
    np.testing.assert_array_equal(stored[96:112], kernel[:, 16:32].T)           # q of key head 1
    np.testing.assert_array_equal(stored[112:128], kernel[:, 32 + 16:32 + 32].T)  # k of key head 1
    np.testing.assert_array_equal(stored[32:64], kernel[:, 64:96].T)            # v of key head 0's two value heads
    np.testing.assert_array_equal(stored[160:192], kernel[:, 128 + 32:128 + 64].T)  # z of key head 1's value heads
    ba = np.asarray(flatten_dict(params)["model/layers/0/linear_attn/in_proj_ba/kernel"])       # [h, b | a]
    np.testing.assert_array_equal(state[lin + "in_proj_ba.weight"][4:6], ba[:, 2:4].T)          # b of key head 1
    np.testing.assert_array_equal(state[lin + "in_proj_ba.weight"][2:4], ba[:, 4:6].T)          # a of key head 0
    back = hf_io.hf_state_dict_to_pytree(state, MC)
    for k, v in flatten_dict(params).items():
        np.testing.assert_array_equal(np.asarray(flatten_dict(back)[k]), np.asarray(v), err_msg=k)


def test_sharding_freeze_lora_and_pipeline_rules():
    spec = jax.sharding.PartitionSpec
    assert param_spec("model/layers/0/linear_attn/in_proj_qkvz/kernel", 2) == spec("fsdp", None)
    assert param_spec("model/layers/0/linear_attn/out_proj/kernel", 2) == spec(None, "fsdp")
    assert param_spec("model/layers/0/mlp/shared_expert_gate/kernel", 2) == spec("fsdp", None)
    assert param_spec("model/layers/0/linear_attn/conv1d/weight", 2) == spec()
    assert param_spec("model/layers/0/linear_attn/A_log", 1) == spec()
    params = init_params(jax.random.PRNGKey(0), TINY)
    tail = flatten_dict(trainable_mask(params, TINY, TrainConfig(model_preset=None, freeze_strategy="last_n_and_head",
                                                                  unfreeze_last_n_layers=2)))
    assert tail["model/layers/2/linear_attn/A_log"] and tail["model/layers/2/linear_attn/conv1d/weight"]
    assert not tail["model/layers/1/linear_attn/dt_bias"] and tail["model/layers/3/self_attn/q_norm/weight"]
    # LoRA takes the mixer's projections by their names, like any other
    adapted = flatten_dict(add_lora_params(params, jax.random.PRNGKey(1), target_modules=("in_proj_qkvz", "out_proj")))
    assert "model/layers/0/linear_attn/in_proj_qkvz/lora_a" in adapted and "model/layers/0/linear_attn/out_proj/lora_b" in adapted
    # the pipeline's layer scan runs identical layers: a model that mixes mixers is refused, by what differs
    (problem,) = layer_scan_problems(TINY, seq_parallel=False)
    assert "layers 0 and 3" in problem and "attention ('linear' vs 'heads')" in problem


def test_packed_rows_and_serving_are_refused_with_a_reason(flat):
    from llm_fine_tune_distributed_tpu.infer.generate import Generator, LatentAttentionNotServed
    from llm_fine_tune_distributed_tpu.models.transformer import init_cache

    segments = jnp.ones((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="restart at every segment boundary.*ROADMAP.md"):
        forward_with_report(_params(flat), jnp.zeros((1, 8), jnp.int32), MC, segment_ids=segments)
    with pytest.raises(LatentAttentionNotServed, match="linear-attention layers.*recurrent state"):
        Generator(_params(flat), MC, tokenizer=None)
    with pytest.raises(LatentAttentionNotServed, match="latent attention"):  # the first kind keeps its sentence
        Generator({}, get_preset("tiny_mla_moe"), tokenizer=None)
    with pytest.raises(NotImplementedError, match="training form only"):
        forward_with_report(_params(flat), jnp.zeros((1, 4), jnp.int32), MC, cache=init_cache(MC, 1, 8))


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


def test_the_new_scopes_reach_the_lowered_step(flat, ids):
    tc = _train_config("float32")
    optimizer, state = _state(flat, tc, jnp.float32)
    import re

    text = jax.jit(build_train_step(MC, tc, optimizer)).lower(state, _batch(ids[0])).compile().as_text()
    names = {re.sub(r"\b(?:jvp|transpose|vmap)\(([^()]*)\)", r"\1", re.sub(r"transpose\(jvp\(([^()]*)\)\)", r"\1", n))
             for n in re.findall(r'op_name="([^"]+)"', text)}
    for path in ("layer0/linear_attn/gdn_conv/", "layer0/linear_attn/gdn_scan/", "layer0/linear_attn/gdn_gate_norm/",
                 "layer3/attn/attn_gate/", "layer3/mlp/shared_expert/", "layer7/attn/"):
        assert any(path in n for n in names), path
    assert not any("layer3/linear_attn" in n or "layer0/attn/" in n for n in names)
    # the rule's scan is a while of its own right under gdn_scan: what readers/gdn.py counts a call by
    assert any(re.search(r"layer0/linear_attn/gdn_scan/(closed_call/)?while$", n) for n in names)


# -- what the tolerance must not let through ----------------------------------


def _logit_gap(flat, ids, mc=MC):
    got = forward_with_report(_params(flat), jnp.asarray(ids[0, 0]), mc, compute_dtype=jnp.float32)[0]
    return _rel(got, ref.logits(flat, bench_cfg(), ids[0, 0]))


def test_a_bfloat16_router_fails_the_tolerance(flat, ids, monkeypatch):
    monkeypatch.setattr(moe, "ROUTER_DTYPE", jnp.bfloat16)
    assert _logit_gap(flat, ids) > 10 * RTOL


@pytest.mark.parametrize("program", ["xla", "kernels"])
def test_a_bfloat16_state_in_the_scan_fails_the_tolerance(monkeypatch, program):
    """Held at the rule itself, where chunked is held to token by token at
    1e-5: the state carried in bfloat16 reads 2e-3 there, in the XLA form's
    scan and in the kernels' VMEM scratch alike (``STATE_DTYPE`` is read when
    the rule is traced). (On this tiny model's logits it reads 4e-5: three
    mixers' outputs through ``out_proj`` at 0.02 move a logit little.)"""
    if program == "xla":
        args, rule = _rule_inputs(3, 2, 128, 2, 4, 16, 8, 1.0), lambda *a: gated_delta.gated_delta_rule(*a, chunk=32)
    else:
        args, rule = _at_a_kernel_head(3, 128, 2, 1.0), _KERNELS
    assert _rel(rule(*args), _token_by_token(*args)) < 1e-5
    monkeypatch.setattr(gated_delta, "STATE_DTYPE", jnp.bfloat16)
    assert _rel(rule(*args), _token_by_token(*args)) > 10 * 1e-5


@pytest.mark.parametrize("field, value", [
    ("attention_output_gate", False), ("partial_rotary_factor", 1.0), ("zero_centered_norm", False),
    ("shared_expert_gate", False),
], ids=lambda v: str(v))
def test_a_part_left_off_fails_the_tolerance(flat, ids, field, value):
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
    got = forward_with_report(tree, jnp.asarray(ids[0, 0]), MC.replace(**{field: value}), compute_dtype=jnp.float32)[0]
    assert _rel(got, ref.logits(flat, bench_cfg(), ids[0, 0])) > 10 * RTOL
