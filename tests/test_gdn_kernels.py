"""The gated delta rule alone (``ops/gated_delta.py``): the chunked XLA form
against the benchmark reference's walk token by token
(``benchmarks/chipbench/reference_gdn_moe.delta_rule``, which imports nothing of
the program), the rule's two Pallas sweeps and the mixer's two fused passes under
the Pallas interpreter on a CPU at heads of 128 (whole lanes: what the kernels
take) against their XLA forms, which program takes a call, and the small parts
(the triangular inverse, the causal convolution, a projection cut by column).
The model around the rule is held in ``tests/test_gdn_moe.py``; the rule with a
decay a channel in ``tests/test_kda_kernels.py``.

Every comparison runs under ``jax.jit``, output and gradients in ONE program a
side, and a program is compiled once a shape: the cases of a shape (the decays,
the dtypes' float32 side) call the same executable. Run eagerly each case
compiled every primitive by itself, two to five times the cost (PR 45).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_suite import _rel  # (and the repo's root on ``sys.path``, for the benchmark's reference)
from llm_fine_tune_distributed_tpu.ops import gated_delta

from benchmarks.chipbench import reference_gdn_moe as ref


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


_KERNELS = lambda *a: gated_delta.gated_delta_rule(*a, impl="kernels_interpret")  # noqa: E731
_XLA = lambda *a: gated_delta.gated_delta_rule(*a, impl="xla")  # noqa: E731
_CHUNKS_OF_32 = lambda *a: gated_delta.gated_delta_rule(*a, chunk=32)  # noqa: E731


@functools.cache
def _output_and_gradients(fn):
    """``fn``'s output and every input's gradient as ONE jitted program (compiled once a shape, whatever the case)."""
    def both(*args):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(jnp.sin(out)), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        return out, grads
    return jax.jit(both)


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
        (got, g_got), (want, g_want) = _output_and_gradients(_CHUNKS_OF_32)(*args), _output_and_gradients(_token_by_token)(*args)
    else:
        args = _at_a_kernel_head(seq, seq, int(program[-1]), a_max)
        (got, g_got), want = _output_and_gradients(_KERNELS)(*args), jax.jit(_token_by_token)(*args)
        g_want = _output_and_gradients(_XLA)(*args)[1]
    assert bool(jnp.isfinite(got).all()) and _rel(got, want) < 1e-5
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        assert bool(jnp.isfinite(a).all()) and _rel(a, b) < 1e-4, name


@pytest.mark.parametrize("program", ["xla", "kernels"])
def test_a_bfloat16_state_in_the_scan_fails_the_tolerance(monkeypatch, program):
    """Held at the rule itself, where chunked is held to token by token at
    1e-5: the state carried in bfloat16 reads 2e-3 there, in the XLA form's
    scan and in the kernels' VMEM scratch alike (``STATE_DTYPE`` is read when
    the rule is traced: each side of the patch is a program of its own). (On
    the tiny model's logits it reads 4e-5: three mixers' outputs through
    ``out_proj`` at 0.02 move a logit little.)"""
    if program == "xla":
        args, rule = _rule_inputs(3, 2, 128, 2, 4, 16, 8, 1.0), _CHUNKS_OF_32
    else:
        args, rule = _at_a_kernel_head(3, 128, 2, 1.0), _KERNELS
    want = jax.jit(_token_by_token)(*args)
    assert _rel(jax.jit(lambda *a: rule(*a))(*args), want) < 1e-5
    monkeypatch.setattr(gated_delta, "STATE_DTYPE", jnp.bfloat16)
    assert _rel(jax.jit(lambda *a: rule(*a))(*args), want) > 10 * 1e-5


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


@functools.cache
def _output_and_cotangents(which, hk, impl):
    """A pass's outputs and the cotangent of every argument (the taps' and the norm's weight among them) under a loss
    that weighs every output element differently, as ONE jitted program a (pass, implementation)."""
    fn = _pass(which, hk, impl)
    loss = lambda *a: sum(jnp.sum(jnp.sin(y.astype(jnp.float32) + 0.3)) for y in jax.tree.leaves(fn(*a)))  # noqa: E731
    return jax.jit(lambda *args: jax.tree.leaves((fn(*args), jax.grad(loss, argnums=tuple(range(len(args))))(*args))))


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
    bfloat16 values (the float32 case's own program) they stand no further off than the XLA form in bfloat16 does (and
    both within bfloat16's grain)."""
    args = _pass_inputs(which, rows, seq, hk, r, dtype)
    got = _output_and_cotangents(which, hk, "kernels_interpret")(*args)
    want = _output_and_cotangents(which, hk, "xla")(*args)
    assert [a.shape for a in got] == [a.shape for a in want] and [a.dtype for a in got] == [a.dtype for a in want]
    if dtype == jnp.float32:
        for a, b in zip(got, want):
            assert bool(jnp.isfinite(a).all()) and _rel(a, b) < 1e-5
        return
    exact = _output_and_cotangents(which, hk, "xla")(*(x.astype(jnp.float32) for x in args))
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
