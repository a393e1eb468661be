"""Pallas flash attention vs the reference XLA attention — forward and
backward, with GQA and right-padding. Runs the kernels in interpret mode on
the CPU test backend (compiled-mode coverage comes from bench.py on TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_fine_tune_distributed_tpu.ops.attention import xla_attention
from llm_fine_tune_distributed_tpu.ops.flash_attention import pallas_flash_attention


def make_qkv(rng, b, s, hq, hkv, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, s, hq, d), dtype)
    k = jax.random.normal(kk, (b, s, hkv, d), dtype)
    v = jax.random.normal(kv, (b, s, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 2)])
def test_forward_matches_xla(hq, hkv):
    rng = jax.random.PRNGKey(0)
    q, k, v = make_qkv(rng, 2, 256, hq, hkv, 32)
    out_flash = pallas_flash_attention(q, k, v, interpret=True)
    out_xla = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_xla), atol=2e-5, rtol=2e-5)


def test_forward_with_padding_lengths():
    rng = jax.random.PRNGKey(1)
    b, s = 3, 256
    q, k, v = make_qkv(rng, b, s, 4, 2, 32)
    lengths = np.asarray([256, 100, 17], np.int32)
    padding_mask = (np.arange(s)[None, :] < lengths[:, None]).astype(np.float32)
    out_flash = pallas_flash_attention(q, k, v, padding_mask=jnp.asarray(padding_mask), interpret=True)
    out_xla = xla_attention(q, k, v, padding_mask=jnp.asarray(padding_mask), causal=True)
    # only positions < length matter (padded query rows are dropped by the
    # loss mask downstream)
    for i, L in enumerate(lengths):
        np.testing.assert_allclose(
            np.asarray(out_flash)[i, :L], np.asarray(out_xla)[i, :L], atol=2e-5, rtol=2e-5
        )


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_gradients_match_xla(hq, hkv):
    rng = jax.random.PRNGKey(2)
    b, s, d = 2, 256, 32
    q, k, v = make_qkv(rng, b, s, hq, hkv, d)
    lengths = np.asarray([256, 192], np.int32)
    padding_mask = jnp.asarray(
        (np.arange(s)[None, :] < lengths[:, None]).astype(np.float32)
    )
    cot = jax.random.normal(jax.random.PRNGKey(3), (b, s, hq, d), jnp.float32)
    # zero the cotangent on padded query rows: those outputs are undefined
    # garbage in both impls and masked by the loss downstream
    row_ok = (np.arange(s)[None, :] < lengths[:, None]).astype(np.float32)
    cot = cot * jnp.asarray(row_ok)[:, :, None, None]

    def loss_flash(q, k, v):
        return jnp.sum(pallas_flash_attention(q, k, v, padding_mask=padding_mask, interpret=True) * cot)

    def loss_xla(q, k, v):
        return jnp.sum(xla_attention(q, k, v, padding_mask=padding_mask, causal=True) * cot)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5,
            err_msg=f"grad mismatch for {name}",
        )


def test_train_step_with_flash_impl_runs():
    """attention(impl='flash') on CPU falls back to xla (backend check) —
    the config default attention_impl='flash' must be safe everywhere."""
    from llm_fine_tune_distributed_tpu.ops.attention import attention

    rng = jax.random.PRNGKey(0)
    q, k, v = make_qkv(rng, 1, 64, 4, 2, 16)
    out = attention(q, k, v, impl="flash", causal=True)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def _segments(b, s, rng):
    """Random packed layout: 2-4 segments per row + a pad tail (seg 0)."""
    out = np.zeros((b, s), np.int32)
    for r in range(b):
        n_seg = rng.randint(2, 5)
        cuts = np.sort(rng.choice(np.arange(16, s - 16), n_seg - 1, replace=False))
        bounds = [0, *cuts.tolist(), s - rng.randint(0, 32)]
        for sid in range(n_seg):
            out[r, bounds[sid] : bounds[sid + 1]] = sid + 1
    return out


def test_forward_segments_match_xla():
    """Packed (segment-masked) flash == segment-masked XLA at real positions."""
    rng = jax.random.PRNGKey(1)
    q, k, v = make_qkv(rng, 3, 256, 4, 2, 32)
    seg = jnp.asarray(_segments(3, 256, np.random.RandomState(0)))
    out_flash = pallas_flash_attention(q, k, v, segment_ids=seg, interpret=True)
    out_xla = xla_attention(q, k, v, segment_ids=seg, causal=True)
    real = np.asarray(seg) > 0
    np.testing.assert_allclose(
        np.asarray(out_flash)[real], np.asarray(out_xla)[real], atol=2e-5, rtol=2e-5
    )


def test_backward_segments_match_xla():
    rng = jax.random.PRNGKey(2)
    q, k, v = make_qkv(rng, 2, 256, 4, 2, 32)
    seg_np = _segments(2, 256, np.random.RandomState(1))
    seg = jnp.asarray(seg_np)
    cot = jax.random.normal(jax.random.PRNGKey(3), q.shape, q.dtype)
    # zero cotangent at pad rows, like a loss mask would
    cot = cot * jnp.asarray((seg_np > 0)[:, :, None, None].astype(np.float32))

    def f_flash(q, k, v):
        return jnp.sum(pallas_flash_attention(q, k, v, segment_ids=seg, interpret=True) * cot)

    def f_xla(q, k, v):
        return jnp.sum(xla_attention(q, k, v, segment_ids=seg, causal=True) * cot)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(f_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g_flash, g_xla, "qkv"):
        real = (seg_np > 0)[:, :, None, None]
        np.testing.assert_allclose(
            np.asarray(a) * real, np.asarray(b_) * real, atol=5e-5, rtol=5e-5,
            err_msg=f"d{name} mismatch",
        )


# ---------------------------------------------------------------------------
# bfloat16 inputs, as every caller on the chip has them
# ---------------------------------------------------------------------------

# Largest relative error (norm of the difference over the reference's norm)
# measured over the cases below, kernels under the interpreter against
# xla_attention in float32 on the same bfloat16 values: forward 1.59e-3, dq
# 2.54e-3, dk 2.58e-3, dv 2.39e-3 (the kernels compute in float32; what is
# left is the rounding of o, dq, dk, dv to bfloat16 as they are stored). The
# tolerance is three times the largest; the chip smoke's bound for the same
# comparison on the chip is 2e-2.
BF16_REL_TOL = 7.8e-3


def _bf16_case(name):
    """(q, k, v, mask kwargs, [b, s] bool of the rows that count)."""
    b, s, d = 2, 256, 32
    hq, hkv = {"gqa4x4": (4, 4), "gqa4x2": (4, 2), "gqa8x2": (8, 2)}.get(name, (4, 2))
    q, k, v = make_qkv(jax.random.PRNGKey(4), b, s, hq, hkv, d, jnp.bfloat16)
    if name == "padding":
        real = np.arange(s)[None, :] < np.asarray([[s], [100]])
        return q, k, v, {"padding_mask": jnp.asarray(real.astype(np.int32))}, real
    if name == "segments":
        seg = _segments(b, s, np.random.RandomState(2))
        return q, k, v, {"segment_ids": jnp.asarray(seg)}, seg > 0
    return q, k, v, {}, np.ones((b, s), bool)


def _rel(a, r):
    a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
    return float(np.linalg.norm(a - r) / np.linalg.norm(r))


BF16_CASES = ["gqa4x4", "gqa4x2", "gqa8x2", "padding", "segments"]


@pytest.mark.parametrize("case", BF16_CASES)
def test_bfloat16_forward_matches_float32_xla(case):
    q, k, v, kw, real = _bf16_case(case)
    out = pallas_flash_attention(q, k, v, interpret=True, **kw)
    assert out.dtype == jnp.bfloat16
    ref = xla_attention(*(x.astype(jnp.float32) for x in (q, k, v)), causal=True, **kw)
    assert _rel(np.asarray(out, np.float32)[real], np.asarray(ref)[real]) < BF16_REL_TOL


@pytest.mark.parametrize("case", BF16_CASES)
def test_bfloat16_gradients_match_float32_xla(case):
    q, k, v, kw, real = _bf16_case(case)
    cot = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32)
    cot = cot * jnp.asarray(real[:, :, None, None].astype(np.float32))  # like a loss mask

    def f_flash(q, k, v):
        out = pallas_flash_attention(q, k, v, interpret=True, **kw)
        return jnp.sum(out.astype(jnp.float32) * cot)

    def f_xla(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True, **kw) * cot)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(f_xla, argnums=(0, 1, 2))(*(x.astype(jnp.float32) for x in (q, k, v)))
    for a, r, name in zip(g_flash, g_xla, "qkv"):
        assert a.dtype == jnp.bfloat16
        assert _rel(a, r) < BF16_REL_TOL, f"d{name}"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_first_tile_holding_only_another_segment(monkeypatch, dtype):
    """A packed row whose first K tile holds none of its own segment's keys:
    its running max is still -1e30 after that tile, so exp(s - m) is 1 for
    every masked key, and the second ``where`` (on p) is what keeps them out
    of the normalizer and the accumulator at that point. Two 128-wide tiles,
    the first all segment 1, the second all segment 2; the second segment's
    outputs and all three gradients must not feel the first tile."""
    monkeypatch.setenv("FLASH_BLOCK", "128")
    q, k, v = make_qkv(jax.random.PRNGKey(6), 1, 256, 4, 2, 32, dtype)
    seg = jnp.asarray(np.repeat([[1, 2]], 128, axis=1).astype(np.int32))
    cot = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def f_flash(q, k, v):
        out = pallas_flash_attention(q, k, v, segment_ids=seg, interpret=True)
        return jnp.sum(out.astype(jnp.float32) * cot), out

    def f_xla(q, k, v):
        out = xla_attention(q, k, v, segment_ids=seg, causal=True)
        return jnp.sum(out * cot), out

    (_, o_f), g_f = jax.value_and_grad(f_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    (_, o_x), g_x = jax.value_and_grad(f_xla, argnums=(0, 1, 2), has_aux=True)(*f32)
    tol = 5e-5 if dtype == jnp.float32 else BF16_REL_TOL
    # the second segment's rows are the ones whose first tile is all foreign
    assert _rel(np.asarray(o_f, np.float32)[:, 128:], np.asarray(o_x)[:, 128:]) < tol
    for a, r, name in zip(g_f, g_x, "qkv"):
        assert _rel(a, r) < tol, f"d{name}"


def _kernel_dots(jaxpr, kernel=None):
    """(kernel name, operand dtypes, accumulator dtype) of every dot_general
    inside the pallas_call bodies of ``jaxpr``, loops included."""
    for eqn in jaxpr.eqns:
        name = eqn.params["name"] if eqn.primitive.name == "pallas_call" else kernel
        if eqn.primitive.name == "dot_general" and kernel is not None:
            yield kernel, [x.aval.dtype for x in eqn.invars], eqn.outvars[0].aval.dtype
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernel_dots(sub, name)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kernels_hand_the_mxu_one_operand_dtype_and_accumulate_in_float32(dtype):
    """Whatever dtype q, k, v arrive in, every dot_general of the three kernel
    bodies gets both operands in ``MXU_OPERAND_DTYPE`` (none is mixed, none is
    left in the input dtype while its partner is cast) and accumulates in
    float32. PR 25 measured on the chip that operands left in bfloat16 make
    all three kernels slower (``ops/flash_attention.py``, ``PERF.md``), so
    the dtype is float32; this pins that a change of it is made in one place
    and reaches all ten products."""
    from llm_fine_tune_distributed_tpu.ops.flash_attention import (
        MXU_OPERAND_DTYPE,
        _diagonal_pieces,
    )

    q, k, v = make_qkv(jax.random.PRNGKey(8), 1, 256, 4, 2, 32, dtype)

    def loss(q, k, v):
        return pallas_flash_attention(q, k, v, interpret=True).astype(jnp.float32).sum()

    dots = list(_kernel_dots(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr))
    per_kernel = {}
    for kernel, operands, acc in dots:
        per_kernel[kernel] = per_kernel.get(kernel, 0) + 1
        assert operands == [MXU_OPERAND_DTYPE, MXU_OPERAND_DTYPE], (kernel, operands)
        assert acc == jnp.float32, (kernel, acc)
    # QK^T, PV | QK^T, dO V^T, dS K | K Q^T, P^T dO, V dO^T, dS^T q: a body is
    # traced for each strip of the block on the diagonal (seq 256 is one block:
    # there is none below it and no code for one), with and without the segment
    # test
    bodies = 2 * len(_diagonal_pieces(256, own="queries"))
    assert per_kernel == {
        "flash_attention_fwd": 2 * bodies,
        "flash_attention_dq": 3 * bodies,
        "flash_attention_dkv": 4 * bodies,
    }


@pytest.mark.parametrize("own", ["queries", "keys"])
@pytest.mark.parametrize("block", [128, 256, 512, 1024])
def test_diagonal_pieces_cover_what_the_causal_mask_lets_through(block, own):
    """The strips the diagonal tile is cut into hold every (key <= query)
    pair of the tile, each position of the kernel's own axis in exactly one
    strip, and leave out only pairs the causal mask removes anyway."""
    from llm_fine_tune_distributed_tpu.ops.flash_attention import _diagonal_pieces

    covered = np.zeros((block, block), int)  # [key, query]
    own_rows = np.zeros(block, int)
    for own_at, own_n, other_at, other_n in _diagonal_pieces(block, own=own):
        assert own_n % 128 == 0 and other_n % 128 == 0  # whole lanes
        own_rows[own_at : own_at + own_n] += 1
        keys, queries = (other_at, other_n), (own_at, own_n)
        if own == "keys":
            keys, queries = queries, keys
        covered[keys[0] : keys[0] + keys[1], queries[0] : queries[0] + queries[1]] += 1
    assert (own_rows == 1).all()
    causal = np.arange(block)[:, None] <= np.arange(block)[None, :]
    assert (covered[causal] == 1).all() and covered.max() == 1
    if block >= 512:
        assert covered.sum() <= 0.75 * block * block  # the cut saves a quarter or more


@pytest.mark.parametrize("block", ["", "512"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_diagonal_in_strips(monkeypatch, dtype, block):
    """seq 1024 as the kernel takes it, one block of four strips, and as two
    512-wide blocks (one block below the diagonal taken whole, the two on it
    in two strips each); a packed row (three segments and a pad tail, so both
    the one-segment programs and the masked ones run), forward and all three
    gradients."""
    monkeypatch.setenv("FLASH_BLOCK", block)
    b, s, hq, hkv, d = 2, 1024, 2, 1, 32
    q, k, v = make_qkv(jax.random.PRNGKey(9), b, s, hq, hkv, d, dtype)
    seg_np = np.ones((b, s), np.int32)
    seg_np[1, 300:700], seg_np[1, 700:990], seg_np[1, 990:] = 2, 3, 0
    seg = jnp.asarray(seg_np)
    real = seg_np > 0
    cot = jax.random.normal(jax.random.PRNGKey(10), q.shape, jnp.float32)
    cot = cot * jnp.asarray(real[:, :, None, None].astype(np.float32))

    def f_flash(q, k, v):
        out = pallas_flash_attention(q, k, v, segment_ids=seg, interpret=True)
        return jnp.sum(out.astype(jnp.float32) * cot), out

    def f_xla(q, k, v):
        out = xla_attention(q, k, v, segment_ids=seg, causal=True)
        return jnp.sum(out * cot), out

    (_, o_f), g_f = jax.value_and_grad(f_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    (_, o_x), g_x = jax.value_and_grad(f_xla, argnums=(0, 1, 2), has_aux=True)(*f32)
    tol = 5e-5 if dtype == jnp.float32 else BF16_REL_TOL
    assert _rel(np.asarray(o_f, np.float32)[real], np.asarray(o_x)[real]) < tol
    for a, r, name in zip(g_f, g_x, "qkv"):
        assert _rel(a, r) < tol, f"d{name}"


# ---------------------------------------------------------------------------
# streamed kernels: a sliding window, and rows whose query group is not held
# ---------------------------------------------------------------------------

from llm_fine_tune_distributed_tpu.ops import flash_attention as fa  # noqa: E402


def _forward_and_grads(fn, q, k, v, cot):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(cot)


# (window, q heads, kv heads, q/k head, v head): a window that is and is not a
# multiple of the block (128 here), 8 and 1 queries a kv head, heads of 128 and
# of 192 against 128; None = the causal row through the streamed kernels
STREAMED_CASES = [
    (128, 8, 1, 128, 128), (200, 8, 1, 128, 128), (128, 2, 2, 128, 128), (200, 2, 2, 192, 128),
    (384, 2, 1, 128, 128), (None, 8, 1, 128, 128),
]


@pytest.mark.parametrize("window, hq, hkv, d, d_v", STREAMED_CASES)
def test_streamed_kernels_match_masked_xla_attention(monkeypatch, window, hq, hkv, d, d_v):
    """Forward and all three gradients of the streamed kernels (interpret
    mode, blocks of 128 on rows of 512: four blocks, band of two to four)
    against ``xla_attention``'s masked scores. float32 inputs: what differs is
    the order of the sums (observed 6e-7 forward, 6e-6 gradients); a window
    off by one key, or a tile left out, is 1e-2 and more."""
    monkeypatch.setenv("FLASH_BLOCK", "128")
    monkeypatch.setattr(fa, "_VMEM_CAP_BYTES", 0 if window is None else fa._VMEM_CAP_BYTES)  # None: force streaming
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    b, s = 2, 512
    q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d_v), jnp.float32)
    cot = jax.random.normal(ks[3], (b, s, hq, d_v), jnp.float32)
    assert fa.program_label(q, k, v, sliding_window=window).startswith("streamed")
    got = _forward_and_grads(lambda *a: pallas_flash_attention(*a, sliding_window=window, interpret=True), q, k, v, cot)
    want = _forward_and_grads(lambda *a: xla_attention(*a, causal=True, sliding_window=window), q, k, v, cot)
    for a, b_, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5, err_msg=name)
    names = {"fwd", "dq", "dkv"}
    kind = "causal" if window is None else "window"
    assert {f"flash_attention_{kind}_{n}" for n in names} <= {name for name, _ in fa.GRID_TILES}


def test_streamed_window_with_padding_and_a_row_as_one_block(monkeypatch):
    """Right-padded rows through the window's kernels (the padding rides the
    segment test: one flag a row says whether any tile makes it), on a row
    that is one block of 256 with a window of 100 inside it."""
    b, s, window = 3, 256, 100
    q, k, v = make_qkv(jax.random.PRNGKey(4), b, s, 4, 2, 128)
    lengths = np.asarray([256, 190, 40], np.int32)
    row_ok = (np.arange(s)[None, :] < lengths[:, None]).astype(np.float32)
    padding_mask = jnp.asarray(row_ok)
    cot = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32) * padding_mask[:, :, None, None]
    got = _forward_and_grads(
        lambda *a: pallas_flash_attention(*a, padding_mask=padding_mask, sliding_window=window, interpret=True), q, k, v, cot)
    want = _forward_and_grads(
        lambda *a: xla_attention(*a, padding_mask=padding_mask, causal=True, sliding_window=window), q, k, v, cot)
    np.testing.assert_allclose(np.asarray(got[0]) * row_ok[:, :, None, None], np.asarray(want[0]) * row_ok[:, :, None, None],
                               atol=5e-5, rtol=5e-5)
    for a, b_, name in zip(got[1:], want[1:], ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5, err_msg=name)


@pytest.mark.parametrize("own", ["queries", "keys"])
@pytest.mark.parametrize("window", [None, 100, 128, 256, 300, 1024, 1500])
@pytest.mark.parametrize("block", [128, 256, 1024])
def test_tile_pieces_cover_the_band_and_test_only_where_a_pair_can_fail(block, window, own):
    """Over every tile of a row of 4 blocks: the pieces of the tiles the grid
    visits cover each pair the mask keeps exactly once, a pair outside a piece
    is never kept, a piece that makes no causal (window) test holds no pair
    that fails it, and no tile outside ``band.steps`` holds a kept pair."""
    seq = 4 * block
    band = fa._band(seq, block, window)
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    keep = (j <= i) & ((i - j < window) if band.window is not None else True)
    covered = np.zeros((seq, seq), np.int32)
    for qb in range(band.blocks):
        for delta in range(min(band.steps, qb + 1)):
            kb = qb - delta
            for own_at, own_n, other_at, other_n, causal, edge in fa._tile_pieces(band, delta, own=own):
                (q_at, q_n, k_at, k_n) = (own_at, own_n, other_at, other_n) if own == "queries" else (other_at, other_n, own_at, own_n)
                rows, cols = slice(qb * block + q_at, qb * block + q_at + q_n), slice(kb * block + k_at, kb * block + k_at + k_n)
                covered[rows, cols] += 1
                assert causal or (j[:, cols] <= i[rows]).all()
                assert edge is not None or band.window is None or (i[rows] - j[:, cols] < window).all()
                assert other_at % 128 == 0 and other_n % 128 == 0
    assert covered.max() <= 1 and (covered[keep] == 1).all()
    assert band.tiles == sum(min(qb + 1, band.steps) for qb in range(band.blocks))


def test_the_band_at_the_cells_shapes():
    """8192 tokens in blocks of 1024 with a window of 1024: a block meets two
    blocks, 15 tiles of the causal triangle's 36; without a window all 36."""
    assert fa._band(8192, 1024, 1024)[2:] == (1024, 2) and fa._band(8192, 1024, 1024).tiles == 15
    assert fa._band(8192, 1024, None)[2:] == (None, 8) and fa._band(8192, 1024, None).tiles == 36
    assert fa._band(8192, 1024, 8192).window is None  # a window as long as the row is no window
