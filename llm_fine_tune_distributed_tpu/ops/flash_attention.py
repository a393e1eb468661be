"""Pallas (Mosaic) flash attention for TPU — forward AND backward kernels.

TPU-native replacement for the reference's flash-attn-2 CUDA dependency
(reference ``requirements.txt:10``, ``training.py:101``). Blockwise online-
softmax attention computed in VMEM tiles: the [seq, seq] score matrix never
materializes in HBM, in either direction.

Formulation (FlashAttention-2 style):
  fwd   per (batch, q_head, q_block): stream K/V blocks up to the causal
        limit, carrying running max ``m``, normalizer ``l`` and the
        unnormalized accumulator; emit O and LSE = m + log(l).
  bwd   delta = rowsum(dO * O); then
        dq  per (batch, q_head, q_block):   ds = p * (dO V^T - delta); dq = ds K
        dk/dv per (batch, KV head, k_block): dv += p^T dO; dk += ds^T q,
        accumulated over the KV head's query group inside the kernel.

GQA is handled by BlockSpec index maps (K/V indexed with ``head // groups``
in fwd/dq; q/dO indexed per-group in dk/dv) — K/V are never repeated in HBM
and dk/dv stay at KV-head width. Dense-cache decode uses the XLA cache path,
not this kernel; quantized PAGED decode has its own fused kernel below
(``paged_decode_attention`` — block-table gather + per-block dequant +
online softmax in one VMEM pass).

Layout contract (matches ops/attention.py): q [b, sq, hq, d], k/v
[b, sk, hkv, d], output [b, sq, hq, d] in q.dtype. Masking is expressed as
per-position ``segments`` [b, s] int32 — attention flows within equal ids
only (0 = padding tail; sequence packing passes its real segment ids, plain
right-padded batches pass the 1/0 padding mask); softmax runs in float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1.0e30

# Whole K/V/Q of a program reside in VMEM, so VMEM bounds the sequence (ring
# attention, parallel/ring_attention.py, covers longer ones).
# Scoped-VMEM budget, set per pallas_call so that every entry point compiles
# the same kernel (the compiler's process-wide default is 16 MiB, and the
# backward at seq 4096 needs more). A call asks for its pipelined blocks,
# double-buffered at their tiled size, plus room for the body's [BQ, BK] f32
# temporaries; nothing may ask for more than the cap, which leaves the rest
# of a v5e core's 128 MiB to XLA's own fusions around the kernel.
_VMEM_BODY_BYTES = 16 * 1024 * 1024
_VMEM_CAP_BYTES = 100 * 1024 * 1024


def _tiled_bytes(shape, dtype) -> int:
    """Bytes of one VMEM buffer of ``shape``: the last dim pads to 128 lanes
    and the one before to the dtype's sublane tile (8 rows of 32 bits)."""
    itemsize = np.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    sublanes = 8 * (4 // itemsize)
    rows = -(-rows // sublanes) * sublanes
    lanes = -(-lanes // 128) * 128
    return int(np.prod(lead, dtype=np.int64)) * rows * lanes * itemsize


def _vmem_budget(operands) -> int:
    """``operands``: (block_shape, dtype, index_map) of every pipelined
    operand of a kernel, inputs and outputs. Each kernel below lists its
    operands once; its BlockSpecs and its budget are both built from that
    list, so the two cannot drift apart."""
    return 2 * sum(_tiled_bytes(s, d) for s, d, _ in operands) + _VMEM_BODY_BYTES


def _compiler_params(operands):
    return pltpu.CompilerParams(vmem_limit_bytes=_vmem_budget(operands))


def _block_specs(operands):
    return [pl.BlockSpec(shape, index_map) for shape, _, index_map in operands]


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(seg_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_k, groups):
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)  # [BQ, d]
    bq, d = q.shape
    q_start = iq * bq
    # 0 = padding, >0 = packed segment id; ref-indexed with pl.ds (Mosaic
    # has no dynamic_slice on loaded arrays)
    q_seg = seg_ref[0, pl.ds(q_start, bq), 0]

    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    # causal upper bound: K blocks whose start exceeds the last q position of
    # this block contribute nothing
    n_blocks = (q_start + bq + block_k - 1) // block_k

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        k_seg = seg_ref[0, pl.ds(j * block_k, block_k), 0]
        # same-segment test subsumes padding: pad queries (seg 0) attend only
        # the pad tail (incl. themselves at k==q, keeping softmax finite),
        # real queries never see pad keys or other segments
        mask = (k_pos <= q_pos) & (q_seg[:, None] == k_seg[None, :])
        s = jnp.where(mask, s, _NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)  # exp(-1e30 - m) underflows anyway; be exact
        l = l * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, :, 0] = m + jnp.log(l_safe)


def _fwd(q, k, v, segments, *, scale, block_q, block_k, groups, interpret):
    b, hq, sq, d = q.shape
    sk = k.shape[2]
    grid = (b, hq, sq // block_q)
    out_shape = (
        jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        # trailing unit dim: TPU tiling wants the block's last dim equal to
        # the array's (1) and the second-to-last divisible by 8 (block_q)
        jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
    )
    kernel = functools.partial(_fwd_kernel, scale=scale, block_k=block_k, groups=groups)
    ins, outs = _fwd_operands(q.dtype, sk, d, block_q, groups)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=_block_specs(ins),
        out_specs=tuple(_block_specs(outs)),
        out_shape=out_shape,
        compiler_params=_compiler_params(ins + outs),
        interpret=interpret,
        name="flash_attention_fwd",
    )(segments[:, :, None], q, k, v)


def _fwd_operands(dtype, sk, d, block_q, groups):
    """Grid (batch, q head, q block): segments, q, k, v -> o, lse."""
    q_blk = lambda b_, h, i: (b_, h, i, 0)  # noqa: E731
    kv_head = lambda b_, h, i: (b_, h // groups, 0, 0)  # noqa: E731
    ins = [
        ((1, sk, 1), jnp.int32, lambda b_, h, i: (b_, 0, 0)),
        ((1, 1, block_q, d), dtype, q_blk),
        ((1, 1, sk, d), dtype, kv_head),
        ((1, 1, sk, d), dtype, kv_head),
    ]
    outs = [
        ((1, 1, block_q, d), dtype, q_blk),
        ((1, 1, block_q, 1), jnp.float32, q_blk),
    ]
    return ins, outs


def _dq_operands(dtype, sq, d, block_q, groups):
    """Grid (batch, q head, q block): segments, q, k, v, do, lse, delta -> dq."""
    q_blk = lambda b_, h, i: (b_, h, i, 0)  # noqa: E731
    kv_head = lambda b_, h, i: (b_, h // groups, 0, 0)  # noqa: E731
    ins = [
        ((1, sq, 1), jnp.int32, lambda b_, h, i: (b_, 0, 0)),
        ((1, 1, block_q, d), dtype, q_blk),
        ((1, 1, sq, d), dtype, kv_head),
        ((1, 1, sq, d), dtype, kv_head),
        ((1, 1, block_q, d), dtype, q_blk),
        ((1, 1, block_q, 1), jnp.float32, q_blk),
        ((1, 1, block_q, 1), jnp.float32, q_blk),
    ]
    outs = [((1, 1, block_q, d), dtype, q_blk)]
    return ins, outs


def _dkv_operands(dtype, sq, d, block_k, groups):
    """Grid (batch, KV head, k block): the q/do/lse/delta blocks span the
    head's whole query group -> dk, dv at KV-head width."""
    group = lambda b_, h, j: (b_, h, 0, 0)  # noqa: E731
    k_blk = lambda b_, h, j: (b_, h, j, 0)  # noqa: E731
    ins = [
        ((1, sq, 1), jnp.int32, lambda b_, h, j: (b_, 0, 0)),
        ((1, groups, sq, d), dtype, group),
        ((1, 1, block_k, d), dtype, k_blk),
        ((1, 1, block_k, d), dtype, k_blk),
        ((1, groups, sq, d), dtype, group),
        ((1, groups, sq, 1), jnp.float32, group),
        ((1, groups, sq, 1), jnp.float32, group),
    ]
    outs = [((1, 1, block_k, d), dtype, k_blk), ((1, 1, block_k, d), dtype, k_blk)]
    return ins, outs


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _dq_kernel(seg_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, block_k):
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]
    delta = delta_ref[0, 0, :, 0]
    bq, d = q.shape
    q_start = iq * bq
    q_seg = seg_ref[0, pl.ds(q_start, bq), 0]
    n_blocks = (q_start + bq + block_k - 1) // block_k

    def body(j, dq_acc):
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        k_seg = seg_ref[0, pl.ds(j * block_k, block_k), 0]
        mask = (k_pos <= q_pos) & (q_seg[:, None] == k_seg[None, :])
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None])
        return dq_acc + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(0, n_blocks, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(seg_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, block_q, groups):
    """Per (batch, KV head, k_block): accumulate dk/dv over this KV head's
    ``groups`` query heads and all causal q blocks — dk/dv stay at KV-head
    width (no group-factor HBM inflation)."""
    jk = pl.program_id(2)
    k_blk = k_ref[0, 0].astype(jnp.float32)  # [BK, d]
    v_blk = v_ref[0, 0].astype(jnp.float32)
    bk, d = k_blk.shape
    sq = q_ref.shape[2]
    k_start = jk * bk
    k_seg = seg_ref[0, pl.ds(k_start, bk), 0]
    # causal: only q blocks at/after this k block contribute
    start_block = k_start // block_q
    n_blocks = sq // block_q

    def make_body(g):
        def body(i, carry):
            dk_acc, dv_acc = carry
            q_blk = q_ref[0, g, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
            do_blk = do_ref[0, g, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
            lse_blk = lse_ref[0, g, pl.ds(i * block_q, block_q), 0]
            delta_blk = delta_ref[0, g, pl.ds(i * block_q, block_q), 0]
            s = jax.lax.dot_general(
                q_blk, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [BQ, BK]
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            q_seg = seg_ref[0, pl.ds(i * block_q, block_q), 0]
            mask = (k_pos <= q_pos) & (q_seg[:, None] == k_seg[None, :])
            p = jnp.where(mask, jnp.exp(s - lse_blk[:, None]), 0.0)
            dv_acc = dv_acc + jax.lax.dot_general(
                p, do_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            dp = jax.lax.dot_general(
                do_blk, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds = p * (dp - delta_blk[:, None])
            dk_acc = dk_acc + jax.lax.dot_general(
                ds, q_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            return dk_acc, dv_acc

        return body

    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)
    for g in range(groups):  # static unroll over the KV head's query group
        dk, dv = jax.lax.fori_loop(start_block, n_blocks, make_body(g), (dk, dv))
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, segments, o, lse, do, *, scale, block_q, block_k, groups, interpret):
    """Head-major inputs: q/o/do/lse [b, hq, ...], k/v [b, hkv, s, d]."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None]  # [b,hq,sq,1]

    ins, outs = _dq_operands(q.dtype, sq, d, block_q, groups)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_k=block_k),
        grid=(b, hq, sq // block_q),
        in_specs=_block_specs(ins),
        out_specs=_block_specs(outs)[0],
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        compiler_params=_compiler_params(ins + outs),
        interpret=interpret,
        name="flash_attention_dq",
    )(segments[:, :, None], q, k, v, do, lse, delta)

    ins, outs = _dkv_operands(q.dtype, sq, d, block_k, groups)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q, groups=groups),
        grid=(b, hkv, sq // block_k),
        in_specs=_block_specs(ins),
        out_specs=tuple(_block_specs(outs)),
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, sq, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sq, d), v.dtype),
        ),
        compiler_params=_compiler_params(ins + outs),
        interpret=interpret,
        name="flash_attention_dkv",
    )(segments[:, :, None], q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper (public entry)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _make_flash_fn(scale: float, block_q: int, block_k: int, groups: int, interpret: bool):
    """One custom_vjp closure per static configuration."""

    @jax.custom_vjp
    def fn(q, k, v, segments):
        o, _ = _fwd(
            q, k, v, segments,
            scale=scale, block_q=block_q, block_k=block_k, groups=groups,
            interpret=interpret,
        )
        return o

    def fn_fwd(q, k, v, segments):
        o, lse = _fwd(
            q, k, v, segments,
            scale=scale, block_q=block_q, block_k=block_k, groups=groups,
            interpret=interpret,
        )
        return o, (q, k, v, segments, o, lse)

    def fn_bwd(res, do):
        q, k, v, segments, o, lse = res
        dq, dk, dv = _bwd(
            q, k, v, segments, o, lse, do,
            scale=scale, block_q=block_q, block_k=block_k, groups=groups,
            interpret=interpret,
        )
        dsegments = np.zeros(segments.shape, jax.dtypes.float0)
        return dq, dk, dv, dsegments

    fn.defvjp(fn_fwd, fn_bwd)
    return fn


def _pick_block(s: int) -> int:
    import os

    override = os.environ.get("FLASH_BLOCK", "")
    if override:
        blk = int(override)  # perf-sweep knob
        if blk % 128:
            raise ValueError(
                f"FLASH_BLOCK={blk} violates the kernel's 128-lane alignment"
            )
        if s % blk:
            raise ValueError(
                f"FLASH_BLOCK={blk} does not divide seq length {s}"
            )
        return blk
    for blk in (512, 256, 128):
        if s % blk == 0:
            return blk
    return 0


def flash_unsupported_reason(
    q, k, v, *, sliding_window=None, causal: bool = True
) -> Optional[str]:
    """Why the kernel cannot take this call (``None``: it can). Static, run
    at trace time by ops/attention.py, which records the reason beside the
    path it took instead."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, the kernel is compiled for TPU only"
    if not causal or sliding_window is not None:
        return "non-causal or sliding-window mask"
    if sq != sk:
        return f"q len {sq} != kv len {sk} (decode/cache path)"
    block = _pick_block(sq)
    if block == 0:
        return f"seq {sq} is not a multiple of 128"
    if d % 128 != 0:
        return f"head dim {d} is not a multiple of the 128 lanes"
    if hq % hkv:
        return f"q heads {hq} not a multiple of kv heads {hkv}"
    # the dk/dv kernel holds a whole kv head's query group in VMEM and is the
    # largest of the three; a shape it cannot hold takes xla/ring instead of
    # dying inside the compiler (at SmolLM3 head shapes: seq <= 6144)
    need = _vmem_budget(sum(_dkv_operands(q.dtype, sq, d, block, hq // hkv), []))
    if need > _VMEM_CAP_BYTES:
        return (
            f"backward needs {need >> 20} MiB of VMEM at seq {sq}, over the "
            f"{_VMEM_CAP_BYTES >> 20} MiB the kernel may ask for"
        )
    return None


# ---------------------------------------------------------------------------
# fused paged decode attention (int8 KV pool)
# ---------------------------------------------------------------------------


def _paged_decode_kernel(
    tables_ref, lengths_ref, ks_ref, vs_ref,  # scalar-prefetch (SMEM)
    q_ref, k_ref, v_ref,  # VMEM inputs
    o_ref,  # VMEM output
    m_ref, l_ref, acc_ref,  # VMEM scratch, persistent across the block dim
    *, scale, groups,
):
    """One (batch row, table slot) step of online-softmax decode, all heads.

    The grid's innermost dim walks the row's block table; the BlockSpec
    index maps have already gathered THIS slot's pool block into VMEM via
    the prefetched table, so the kernel never sees the pool — no [b, nb*L]
    gather materializes anywhere. The block arrives whole, ``[L, hkv, d]``:
    Mosaic only takes blocks whose last two dims are full or tile-aligned,
    and a one-kv-head slice of the ``(hkv, d)`` minor dims is neither. It
    is read as its flat ``[L*hkv, d]`` view (row ``l*hkv + h``; the same
    bytes, int8 packs four sublane rows to a word either way) and every q
    head is multiplied against every row; a column mask keeps only the
    rows of the q head's own kv head. Decode is bandwidth-bound and the
    block is read once for all heads, so the ``hkv``-fold extra MXU work is
    free. Dequantization folds into the math: k absmax scales the logits'
    columns, v absmax scales the probabilities' columns. The scales were
    gathered per table slot outside (``[b*nb*hkv]`` f32 in SMEM — a
    ``(1, 1)`` VMEM block of the ``[num_blocks, hkv]`` scale pool breaks
    the same tiling rule). The (m, l, acc) carry lives in scratch that
    persists across the innermost grid dim; the output block flushes once,
    on the last table slot.
    """
    b_i = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32)  # [hq, d]
    hq, d = q.shape
    _, block_len, hkv, _ = k_ref.shape
    cols = block_len * hkv
    k_blk = k_ref[0].reshape(cols, d).astype(jnp.float32)  # int8 codes
    v_blk = v_ref[0].reshape(cols, d).astype(jnp.float32)

    col = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 1)
    col_head = col % hkv
    q_head = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 0) // groups
    base = (b_i * nb + i) * hkv
    k_scale = jnp.zeros((hq, cols), jnp.float32)
    v_scale = jnp.zeros((hq, cols), jnp.float32)
    for h in range(hkv):  # static: hkv scalars spread over their columns
        k_scale = jnp.where(col_head == h, ks_ref[base + h] / 127.0, k_scale)
        v_scale = jnp.where(col_head == h, vs_ref[base + h] / 127.0, v_scale)

    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (scale * k_scale)  # [hq, L*hkv]
    # gathered index IS logical position (models/transformer._block): slot i
    # of the table covers positions [i*L, (i+1)*L); visible iff < length.
    # Null-table slots gather block 0 (zero codes, zero scale) at positions
    # at/above length, so they are masked here exactly like the XLA path.
    k_pos = i * block_len + col // hkv
    mask = (k_pos < lengths_ref[b_i]) & (col_head == q_head)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]  # [hq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # [hq, L*hkv]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p * v_scale, v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _flush():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


PAGED_DECODE_MODES = ("fused", "xla", "interpret")


def paged_decode_mode() -> str:
    """How ``models/transformer._block`` reads the int8 paged pool at
    decode. On a TPU it is ``"fused"``: the Pallas kernel, compiled — if
    Mosaic refuses it the run fails, nothing falls back. Elsewhere it is
    ``"xla"`` (dequantizing gather + masked attention), so CPU tests never
    depend on Mosaic. ``PAGED_DECODE`` set by hand overrides the choice;
    ``interpret`` (the kernel under the Pallas interpreter, CPU-runnable
    coverage of the kernel math) is reachable no other way."""
    import os

    override = os.environ.get("PAGED_DECODE", "").lower()
    if override:
        if override not in PAGED_DECODE_MODES:
            raise ValueError(
                f"PAGED_DECODE={override!r}: expected one of {PAGED_DECODE_MODES}"
            )
        return override
    return "fused" if jax.default_backend() == "tpu" else "xla"


def paged_decode_attention(
    q, k_pool, v_pool, k_scale, v_scale, block_tables, *,
    lengths, scale=None, interpret: bool = False,
):
    """Fused decode attention over an int8 block-paged KV pool.

    ``q [b, 1, hq, d]`` (one decode token per row), ``k_pool``/``v_pool``
    int8 ``[num_blocks, L, hkv, d]`` with absmax scales ``[num_blocks,
    hkv]`` f32 (models/transformer.init_paged_cache int8 layout),
    ``block_tables [b, nb]`` int32, ``lengths [b]`` int32 (visible positions
    per row, i.e. query position + 1). Returns ``[b, 1, hq, d]`` in q.dtype.

    Replaces the XLA sequence gather-pool -> dequantize -> mask -> softmax,
    whose gathered ``[b, nb*L, hkv, d]`` view round-trips through HBM every
    decode tick. Here the block table is a scalar-prefetch operand, so the
    BlockSpec index maps DMA exactly the table's blocks into VMEM (the paged
    analog of the fwd kernel's GQA index maps) and each is read once, in its
    1-byte form. Speed on a chip: not measured. Lowering for v5e at SmolLM3
    head shapes is pinned by tests/test_tpu_compile.py, agreement with the
    XLA gather on the chip by chip_smoke.py, and the kernel math under the
    interpreter by tests/test_quantized_serving.py.
    """
    b, s, hq, d = q.shape
    if s != 1:
        raise ValueError(f"paged decode takes one query token per row, got s={s}")
    num_blocks, block_len, hkv, _ = k_pool.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    groups = hq // hkv
    nb = block_tables.shape[1]
    if scale is None:
        scale = float(1.0 / np.sqrt(d))
    tables = block_tables.astype(jnp.int32)
    # per-slot scales, [b*nb*hkv]: a tiny XLA gather instead of a block spec
    ks = k_scale.astype(jnp.float32)[tables].reshape(-1)
    vs = v_scale.astype(jnp.float32)[tables].reshape(-1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda bi, i, t, ln, a, c: (bi, 0, 0)),
            pl.BlockSpec(
                (1, block_len, hkv, d), lambda bi, i, t, ln, a, c: (t[bi, i], 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, block_len, hkv, d), lambda bi, i, t, ln, a, c: (t[bi, i], 0, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, hq, d), lambda bi, i, t, ln, a, c: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),  # m
            pltpu.VMEM((hq, 1), jnp.float32),  # l
            pltpu.VMEM((hq, d), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=float(scale), groups=groups),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables, lengths.astype(jnp.int32), ks, vs, q[:, 0], k_pool, v_pool)
    return out.reshape(b, 1, hq, d)


def pallas_flash_attention(
    q, k, v, *, padding_mask=None, segment_ids=None, interpret: bool = False
):
    """q [b, sq, hq, d], k/v [b, sk, hkv, d] -> [b, sq, hq, d] (q.dtype).

    Masking is expressed as per-position segments [b, sk] int32: attention
    flows only within equal segment ids (plus causal). ``segment_ids`` comes
    from the packing pipeline (data/packing.py, 0 = pad tail); without it,
    ``padding_mask`` (1 = real) degenerates to the two-segment real/pad case.
    Softmax in f32; causal.
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    groups = hq // hkv
    if segment_ids is not None:
        segments = segment_ids.astype(jnp.int32)
    elif padding_mask is not None:
        segments = padding_mask.astype(jnp.int32)
    else:
        segments = jnp.ones((b, sq), jnp.int32)

    block = _pick_block(sq)
    if block == 0:
        raise ValueError(
            f"flash attention requires seq length divisible by 128, got {sq} "
            f"(use ops.attention.attention() for automatic XLA fallback)"
        )
    fn = _make_flash_fn(float(1.0 / np.sqrt(d)), block, block, groups, interpret)
    # head-major layout for clean blocking
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = fn(qt, kt, vt, segments)
    return out.transpose(0, 2, 1, 3)
