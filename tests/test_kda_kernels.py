"""The delta rule with a decay a CHANNEL (Kimi Delta Attention) as its two Pallas sweeps (``ops/gated_delta.py``
``kda_rule_fwd``, ``kda_rule_bwd``), under the Pallas interpreter on a CPU at heads of 128 (whole lanes: what the
kernels take), against the program's XLA form (``_rule_xla_by_channel``, what a CPU, heads of 16 or another chunk
run) and the benchmark reference's walk token by token (``benchmarks/chipbench/reference_kda_moe.delta_rule``, which
imports nothing of the program).

Both forms compute in float32 at ``highest`` matmul precision here (``conftest.py``), so they differ by summation
order alone: the sub-block products in one product a sub-block against four of XLA's, the triangular inverse by
levels and Newton steps against a triangular solve, the sums from each sub-block's start by shifted adds against a
``cumsum``. Errors are read against each array's own scale with a floor (at the hardest decay ``dg`` is of the order
of 1e-9 itself)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_fine_tune_distributed_tpu.ops import gated_delta

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import reference_kda_moe as ref  # noqa: E402

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
_KERNELS = lambda *a: gated_delta.gated_delta_rule(*a, impl="kernels_interpret")  # noqa: E731
_XLA = lambda *a: gated_delta.gated_delta_rule(*a, impl="xla")  # noqa: E731


def _rule_inputs(seed, b, s, hk, hv, decay, dtype=jnp.float32, d=128):
    """``tests/test_kda_moe._rule_inputs`` at a kernel's head: ``hk`` key heads serving ``hv`` value heads."""
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


def _outputs_and_cotangents(fn, args):
    weigh = jnp.cos(jnp.arange(np.prod(args[2].shape), dtype=jnp.float32)).reshape(args[2].shape)
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weigh)  # noqa: E731
    return (fn(*args),) + jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


def _gap(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) / max(float(jnp.abs(b.astype(jnp.float32)).max()), 1e-2)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


@pytest.mark.parametrize("rows, seq", [(2, 1100), (1, 37)], ids=["two-steps-and-a-part", "less-than-a-chunk"])
@pytest.mark.parametrize("decay", ["drawn", "hardest", "none"])
def test_by_channel_kernels_equal_the_xla_form_and_the_rule_token_by_token(decay, rows, seq):
    """The two sweeps under the interpreter, output and ALL FIVE cotangents (``dg`` a channel): two rows of two grid
    steps and a part (the state crosses a step's edge forward, its cotangent against time, and nothing leaks from a
    row into the next), and a row shorter than one chunk (padded with tokens that change nothing). At ``g = -16`` a
    token on every channel ``exp(-G)`` passes ``exp(1000)`` inside a chunk: everything is finite and right, and at
    ``g = 0`` too. Held to the XLA form in everything and to the reference's walk in everything as well."""
    args = _rule_inputs(3, rows, seq, 1, 1, decay)
    got = _outputs_and_cotangents(_KERNELS, args)
    for held_to, limit in ((_XLA, 2e-5), (ref.delta_rule, 2e-5)):
        want = _outputs_and_cotangents(held_to, args)
        for name, a, b in zip(NAMES, got, want):
            assert a.shape == b.shape and a.dtype == b.dtype and bool(jnp.isfinite(a).all()), name
            assert _gap(a, b) < limit, (name, held_to, _gap(a, b))


def test_by_channel_kernels_serve_a_key_heads_value_heads_each_with_its_own_decay():
    """One key head serving THREE value heads (each decays apart: nothing of a chunk is shared but the q and k
    loads), beside a second key head: the cotangents of q and k add up over a key head's value heads."""
    args = _rule_inputs(6, 1, 200, 2, 6, "drawn")
    got = _outputs_and_cotangents(_KERNELS, args)
    want = _outputs_and_cotangents(_XLA, args)
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and _gap(a, b) < 2e-5, (name, _gap(a, b))
    q, k, v, g, beta = args
    walked = ref.delta_rule(jnp.repeat(q, 3, axis=2), jnp.repeat(k, 3, axis=2), v, g, beta)
    assert _rel(got[0], walked) < 1e-5


def test_by_channel_kernels_on_bfloat16_operands_stand_where_the_xla_form_stands():
    """In the cell's dtype: products of bfloat16 operands added up in float32, decays, decayed products, inverse and
    state float32, ``U``, ``W``, the decayed operands and ``D`` rounded where the XLA form rounds them. Against the XLA
    form IN FLOAT32 on the same bfloat16 values the kernels stand no further off than the XLA form in bfloat16 does
    (and both within bfloat16's grain); g and beta and their cotangents are float32 in both."""
    args = _rule_inputs(5, 1, 600, 1, 2, "drawn", jnp.bfloat16)
    got = _outputs_and_cotangents(_KERNELS, args)
    want = _outputs_and_cotangents(_XLA, args)
    exact = _outputs_and_cotangents(_XLA, tuple(x.astype(jnp.float32) for x in args))
    assert [a.dtype for a in got] == [a.dtype for a in want] == [jnp.bfloat16] * 4 + [jnp.float32] * 2
    for name, a, b, c in zip(NAMES, got, want, exact):
        assert bool(jnp.isfinite(a).all()) and _rel(a, c) < max(1.25 * _rel(b, c), 2.0 ** -7), (name, _rel(a, c), _rel(b, c))


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
