"""The delta rule with a decay a CHANNEL (Kimi Delta Attention) alone (``ops/gated_delta.py``): the program's XLA form
(``_rule_xla_by_channel``: chunks of 64 in sub-blocks of 16 with a triangular inverse; what a CPU, heads of 16 or
another chunk run) against the benchmark reference's walk token by token
(``benchmarks/chipbench/reference_kda_moe.delta_rule``, which imports nothing of the program), and its two Pallas sweeps
(``kda_rule_fwd``, ``kda_rule_bwd``) under the Pallas interpreter on a CPU at heads of 128 (whole lanes: what the
kernels take) against both. The model around the rule is held in ``tests/test_kda_moe.py``.

Both forms compute in float32 at ``highest`` matmul precision here (``conftest.py``), so they differ by summation
order alone: the sub-block products in one product a sub-block against four of XLA's, the triangular inverse by
levels and Newton steps against a triangular solve, the sums from each sub-block's start by shifted adds against a
``cumsum``. Errors are read against each array's own scale with a floor (at the hardest decay ``dg`` is of the order
of 1e-9 itself). Every comparison runs under ``jax.jit``, output and cotangents in ONE program a side, compiled once
a shape: the decays of a shape call the same executable (eagerly each compiled every primitive by itself, PR 45)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_suite import _rel  # (and the repo's root on ``sys.path``, for the benchmark's reference)
from llm_fine_tune_distributed_tpu.ops import gated_delta

from benchmarks.chipbench import reference_kda_moe as ref

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
_KERNELS = lambda *a: gated_delta.gated_delta_rule(*a, impl="kernels_interpret")  # noqa: E731
_XLA = lambda *a: gated_delta.gated_delta_rule(*a, impl="xla")  # noqa: E731
_RULE = lambda *a: gated_delta.gated_delta_rule(*a)  # noqa: E731 (as the model calls it: here the XLA form)


def _rule_inputs(seed, b, s, hk, hv, decay, dtype=jnp.float32, d=128):
    """The rule's inputs: ``hk`` key heads serving ``hv`` value heads of ``d`` (128: a kernel's head)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = ref.l2_norm(jax.random.normal(ks[0], (b, s, hk, d))) * d ** -0.5
    k = ref.l2_norm(jax.random.normal(ks[1], (b, s, hk, d)))
    v = jax.random.normal(ks[2], (b, s, hv, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, hv)))
    if decay == "drawn":  # what the draw allows: -exp(A_log) softplus(.) between -16 x 0.1 and -1 x 0.001, and beyond both
        g = -jnp.exp(jax.random.uniform(ks[4], (b, s, hv, d), minval=np.log(1e-3), maxval=np.log(16.0)))
    else:
        g = jnp.full((b, s, hv, d), {"hardest": -16.0, "none": 0.0}[decay])
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


@functools.cache
def _outputs_and_cotangents(fn):
    """``fn``'s output and all five cotangents, under a loss that weighs every output element differently, as ONE jitted
    program (compiled once a shape, whatever the case)."""
    def both(*args):
        def loss(*a):
            out = fn(*a)
            weigh = jnp.cos(jnp.arange(np.prod(out.shape), dtype=jnp.float32)).reshape(out.shape)
            return jnp.sum(out.astype(jnp.float32) * weigh), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        return (out,) + grads
    return jax.jit(both)


def _gap(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) / max(float(jnp.abs(b.astype(jnp.float32)).max()), 1e-2)


# -- the XLA form against the walk ------------------------------------------------


@pytest.mark.parametrize("seq", [150, 37], ids=["two-chunks-and-a-part", "less-than-a-chunk"])
@pytest.mark.parametrize("decay", ["drawn", "hardest", "none"])
def test_chunked_rule_by_channel_equals_the_rule_token_by_token(decay, seq):
    """The chunked form (chunks of 64, sub-blocks of 16, a triangular inverse) against the reference's walk, the
    output and EVERY cotangent (``dg`` a channel), at rows that are no multiple of the chunk or of the sub-block; at
    ``g = -16`` a token on every channel, where ``exp(-G)`` passes ``exp(1000)`` inside a chunk (the overflow the
    sub-blocks exist for), everything is finite and right, and at ``g = 0`` (no decay at all) too. Errors against
    each array's own scale, with a floor: at the hardest decay ``dg`` is of the order of 1e-7 itself."""
    args = _rule_inputs(3, 2, seq, 3, 3, decay, d=16)
    got = _outputs_and_cotangents(_RULE)(*args)
    want = _outputs_and_cotangents(ref.delta_rule)(*args)
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - b).max()) < 1e-5 * max(float(jnp.abs(b).max()), 1e-2), name


def test_a_decay_averaged_over_the_channels_is_another_rule_and_calls_say_which(monkeypatch):
    monkeypatch.setattr(gated_delta, "CALLS", {})
    args = _rule_inputs(4, 1, 100, 2, 2, "drawn", d=16)
    want = jax.jit(ref.delta_rule)(*args)
    by_channel = jax.jit(lambda *a: gated_delta.gated_delta_rule(*a))(*args)  # (a program of its own: ``CALLS`` counts traces)
    by_head = jax.jit(lambda *a: gated_delta.gated_delta_rule(*a))(*args[:3], args[3].mean(-1), args[4])
    assert _rel(by_channel, want) < 1e-5 < 1e-1 < _rel(by_head, want)
    forms = {shape: form for shape, (_, form) in gated_delta.CALLS.items()}
    assert forms == {(1, 100, 2, 2, 16, 16): "chunked 64: xla",
                     (1, 100, 2, 2, 16, 16, "by channel"): "chunked 64, a decay a channel in sub-blocks of 16: xla"}
    assert "a decay a channel in sub-blocks of 16" in gated_delta.calls_summary()


def test_bfloat16_operands_and_key_heads_that_serve_several_value_heads():
    """In the cell's dtype the rule stands by the float32 walk to bfloat16's grain (products of bfloat16 operands
    added up in float32, the decayed products and the state float32); a key head's value heads decay apart."""
    args = _rule_inputs(5, 2, 130, 2, 2, "drawn", jnp.bfloat16, d=16)
    want = jax.jit(ref.delta_rule)(*(x.astype(jnp.float32) for x in args))
    got = jax.jit(_RULE)(*args)
    assert got.dtype == jnp.bfloat16 and _rel(got, want) < 2e-2
    q, k, v, g, beta = _rule_inputs(6, 1, 70, 4, 4, "drawn", d=16)
    want = jax.jit(ref.delta_rule)(jnp.repeat(q[:, :, :2], 2, axis=2), jnp.repeat(k[:, :, :2], 2, axis=2), v, g, beta)
    assert _rel(jax.jit(_RULE)(q[:, :, :2], k[:, :, :2], v, g, beta), want) < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_out_pass_with_a_sigmoid_gate_as_kernels_equals_its_xla_form(dtype):
    """The gated norm's kernels (Pallas interpreter) with the gate's activation a sigmoid against the XLA form,
    output and every cotangent, on a row that is no whole token block; and it is not the silu's."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    o, z = (jax.random.normal(key, (1, 700, 256)).astype(dtype) for key in ks[:2])
    w = (1 + 0.3 * jax.random.normal(ks[2], (128,))).astype(dtype)
    run = lambda impl, act: (lambda *a: gated_delta.gated_norm(*a, 1e-5, activation=act, impl=impl))  # noqa: E731
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32) + 0.3)))  # noqa: E731
    every = lambda fn, args: jax.jit(lambda *a: jax.tree.leaves((fn(*a), jax.grad(loss(fn), argnums=(0, 1, 2))(*a))))(*args)  # noqa: E731
    got, want = every(run("kernels_interpret", "sigmoid"), (o, z, w)), every(run("xla", "sigmoid"), (o, z, w))
    exact = every(run("xla", "sigmoid"), tuple(x.astype(jnp.float32) for x in (o, z, w)))
    for a, b, c in zip(got, want, exact):
        assert a.dtype == b.dtype and bool(jnp.isfinite(a).all())
        assert _rel(a, c) < (1e-5 if dtype == jnp.float32 else max(1.25 * _rel(b, c), 2.0 ** -9))
    assert _rel(got[0], run("kernels_interpret", "silu")(o, z, w)) > 0.1


# -- the two sweeps under the interpreter -----------------------------------------


@pytest.mark.parametrize("rows, seq", [(2, 1100), (1, 37)], ids=["two-steps-and-a-part", "less-than-a-chunk"])
@pytest.mark.parametrize("decay", ["drawn", "hardest", "none"])
def test_by_channel_kernels_equal_the_xla_form_and_the_rule_token_by_token(decay, rows, seq):
    """The two sweeps under the interpreter, output and ALL FIVE cotangents (``dg`` a channel): two rows of two grid
    steps and a part (the state crosses a step's edge forward, its cotangent against time, and nothing leaks from a
    row into the next), and a row shorter than one chunk (padded with tokens that change nothing). At ``g = -16`` a
    token on every channel ``exp(-G)`` passes ``exp(1000)`` inside a chunk: everything is finite and right, and at
    ``g = 0`` too. Held to the XLA form in everything and to the reference's walk in everything as well."""
    args = _rule_inputs(3, rows, seq, 1, 1, decay)
    got = _outputs_and_cotangents(_KERNELS)(*args)
    for held_to, limit in ((_XLA, 2e-5), (ref.delta_rule, 2e-5)):
        want = _outputs_and_cotangents(held_to)(*args)
        for name, a, b in zip(NAMES, got, want):
            assert a.shape == b.shape and a.dtype == b.dtype and bool(jnp.isfinite(a).all()), name
            assert _gap(a, b) < limit, (name, held_to, _gap(a, b))


def test_by_channel_kernels_serve_a_key_heads_value_heads_each_with_its_own_decay():
    """One key head serving THREE value heads (each decays apart: nothing of a chunk is shared but the q and k
    loads), beside a second key head: the cotangents of q and k add up over a key head's value heads."""
    args = _rule_inputs(6, 1, 200, 2, 6, "drawn")
    got = _outputs_and_cotangents(_KERNELS)(*args)
    want = _outputs_and_cotangents(_XLA)(*args)
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and _gap(a, b) < 2e-5, (name, _gap(a, b))
    q, k, v, g, beta = args
    walked = jax.jit(ref.delta_rule)(jnp.repeat(q, 3, axis=2), jnp.repeat(k, 3, axis=2), v, g, beta)
    assert _rel(got[0], walked) < 1e-5


def test_by_channel_kernels_on_bfloat16_operands_stand_where_the_xla_form_stands():
    """In the cell's dtype: products of bfloat16 operands added up in float32, decays, decayed products, inverse and
    state float32, ``U``, ``W``, the decayed operands and ``D`` rounded where the XLA form rounds them. Against the XLA
    form IN FLOAT32 on the same bfloat16 values the kernels stand no further off than the XLA form in bfloat16 does
    (and both within bfloat16's grain); g and beta and their cotangents are float32 in both."""
    args = _rule_inputs(5, 1, 600, 1, 2, "drawn", jnp.bfloat16)
    got = _outputs_and_cotangents(_KERNELS)(*args)
    want = _outputs_and_cotangents(_XLA)(*args)
    exact = _outputs_and_cotangents(_XLA)(*tuple(x.astype(jnp.float32) for x in args))
    assert [a.dtype for a in got] == [a.dtype for a in want] == [jnp.bfloat16] * 4 + [jnp.float32] * 2
    for name, a, b, c in zip(NAMES, got, want, exact):
        assert bool(jnp.isfinite(a).all()) and _rel(a, c) < max(1.25 * _rel(b, c), 2.0 ** -7), (name, _rel(a, c), _rel(b, c))


def _kept_by_the_forward_sweep(q, k, v, g, beta):
    """``(T, the decayed k k^T, P)`` ``[b, value heads, chunks, C, C]`` as ``kda_rule_fwd`` (interpreted) writes them for
    its backward sweep, taken out of their lane-dense layouts (``T`` beside ``k k^T``; two chunks' ``P`` side by side),
    from the layouts ``_rule_kernels`` hands the sweep."""
    (b, _, hk, _), hv, c = q.shape, v.shape[2], gated_delta.CHUNK
    (fwd, _), _, operands, _ = gated_delta._laid_out(q, k, v, g, beta)
    n = operands[0].shape[1] // c
    o, states, t_kk, p = fwd(*operands, hk=hk, state_dtype=jnp.float32, interpret=True)
    assert t_kk.shape == (b, hv, n * c, 2 * c) and t_kk.dtype == jnp.float32 and p.shape == (b, hv, n * c // 2, 2 * c) and p.dtype == k.dtype
    t_kk = t_kk.reshape(b, hv, n, c, 2 * c)
    return t_kk[..., :c], t_kk[..., c:], jnp.swapaxes(p.reshape(b, hv, n // 2, c, 2, c), 3, 4).reshape(b, hv, n, c, c)


def _chunk_matrices_of_the_xla_form(q, k, v, g, beta):
    """The same three from ``_rule_xla_by_channel``'s own ``_chunk_matrices``, float32, on chunks laid out as it lays
    them (a key head's value heads each get a copy of its q and k), rows padded as the kernels pad them."""
    (b, _, hk, d), hv, c, sub = q.shape, v.shape[2], gated_delta.CHUNK, gated_delta.SUB
    (q, k, g, beta), _ = gated_delta._padded_rows((q, k, g, beta), gated_delta.STEP_CHUNKS * c)
    by_head = lambda x: jnp.moveaxis(x.reshape(b, -1, c, hv, x.shape[-1]), 3, 1)  # noqa: E731  [b, h, n, C, .]
    blocks = lambda x: x.reshape(x.shape[:3] + (c // sub, sub, d))  # noqa: E731
    qc, kc = (by_head(jnp.repeat(x, hv // hk, axis=2)) for x in (q, k))
    local = jnp.cumsum(blocks(by_head(g.astype(jnp.float32))), axis=-2)
    return gated_delta._chunk_matrices(blocks(qc), blocks(kc), by_head(beta.astype(jnp.float32)[..., None]), local)


@pytest.mark.parametrize("seq, hk, hv, dtype", [
    (1024, 1, 1, jnp.float32), (100, 1, 1, jnp.float32), (600, 1, 2, jnp.bfloat16), (200, 2, 6, jnp.float32),
], ids=["rows-of-whole-steps", "a-row-padded-to-a-step", "bfloat16-operands", "key-heads-that-serve-three-value-heads"])
def test_the_forward_sweep_keeps_each_chunks_inverse_and_decayed_products_for_the_backward_sweep(seq, hk, hv, dtype):
    """What the backward sweep READS since PR 48 and made again before: each chunk's ``T = (I + A)^-1`` and decayed ``k
    k^T`` in float32 and its ``P`` (the decayed ``q k^T`` from the diagonal down) in the operands' dtype, written by the
    forward kernel, equal ``_rule_xla_by_channel``'s own for the same chunk: the float32 ones to summation order
    (the inverse by levels and Newton steps against a triangular solve), ``P`` to one rounding of its dtype; the
    padding's chunks hold the identity and nothing (k = 0, beta = 0 there)."""
    args = _rule_inputs(11, 2, seq, hk, hv, "drawn", dtype)
    got = jax.jit(_kept_by_the_forward_sweep)(*args)
    want = jax.jit(_chunk_matrices_of_the_xla_form)(*args)
    for name, a, b in zip(("T", "kk", "P"), got, want):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
        assert _gap(a, b) < (2e-5 if a.dtype == jnp.float32 else 2.0 ** -8), (name, _gap(a, b))
        assert float(jnp.abs(jnp.triu(a, 1)).max()) == 0.0, name                       # nothing above the diagonal
    t, kk, p = got
    padding = -(-seq // gated_delta.CHUNK)                                             # the first chunk that is padding alone
    assert (padding == t.shape[2]) == (seq == 1024)
    np.testing.assert_array_equal(t[:, :, padding:], jnp.broadcast_to(jnp.eye(gated_delta.CHUNK), t[:, :, padding:].shape))
    assert not bool(kk[:, :, padding:].any()) and not bool(p[:, :, padding:].any())
    assert float(jnp.abs(kk[:, :, 0]).max()) > 0.1 and float(jnp.abs(p[:, :, 0].astype(jnp.float32)).max()) > 1e-3


def test_the_sums_from_each_sub_blocks_start_and_their_cotangent():
    """``_from_sub_block_start``: four shifted adds give g's running sum inside each sub-block of 16 (and nothing of
    the sub-block before), and against time the sum's cotangent."""
    from jax.experimental import pallas as pl

    g = jax.random.normal(jax.random.PRNGKey(0), (gated_delta.CHUNK, 128))

    def run(against_time):
        def body(g_ref, o_ref):
            o_ref[...] = gated_delta._from_sub_block_start(g_ref[...], against_time)
        return pl.pallas_call(body, out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype), interpret=True)(g)

    blocks = g.reshape(-1, gated_delta.SUB, 128)
    assert _rel(run(False), jnp.cumsum(blocks, axis=1).reshape(g.shape)) < 1e-6
    assert _rel(run(True), jnp.flip(jnp.cumsum(jnp.flip(blocks, 1), axis=1), 1).reshape(g.shape)) < 1e-6
