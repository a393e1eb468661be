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
        accumulated over the KV head's query group inside the kernel, on
        transposed scores [keys, queries] so that no tile is transposed.

Operands and accumulators: every matmul of the three kernels takes both
operands in ``MXU_OPERAND_DTYPE`` (float32: q, k, v, dO cast up from the dtype
they arrive in, p and dS as computed) and accumulates in float32; scores,
scale, softmax statistics, lse, delta and the dq/dk/dv accumulators are
float32 throughout; only the stores round to the input dtype. Why float32
operands are the fast ones on this chip is said at ``MXU_OPERAND_DTYPE``.

Tiles: a sequence is cut into blocks (``_pick_block``: the whole sequence up
to 2048, else at most 1024). A block pair wholly below the diagonal is one
[block, block] tile; the pair on the diagonal is cut into strips of 256 rows
(``_diagonal_pieces``), each as wide as the causal mask lets it see, so what
lies above the diagonal is left out up to the strips' own corners.

Two sets of the three kernels. The RESIDENT ones (a causal row without a
window whose operands fit: the whole K/V of a kv head beside a Q block in
forward and dq, a kv head's whole query group beside a K block in dk/dv) loop
over the other axis inside the kernel. The STREAMED ones (a sliding window, or
a row whose group does not fit: 8 queries a kv head at 8192 would ask dk/dv
for 218 MiB) put the other axis on the grid: one [block, block] tile a grid
step, the running statistics and the dq, dk, dv accumulators in VMEM scratch,
and with a window only the blocks inside the band on the grid at all
(``_band``: at 8192 with a window of 1024, two blocks of 1024 a block, not
eight), the band's edge tiles cut into strips as the diagonal is.

Masking: a tile does only the masking it needs. The causal test is made in
the strips on the diagonal alone; the same-segment test in programs whose
tiles hold more than one segment id (a per-program flag in SMEM, computed from
the segment ids outside the kernel: one branch a program, none in the tile
loop).

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
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (the cap under this module's own name: tests and benchmarks/chipbench/tools lower it HERE to force the streamed kernels)
from llm_fine_tune_distributed_tpu.ops.tiling import VMEM_CAP_BYTES as _VMEM_CAP_BYTES, lanes, tiled_bytes

_NEG_INF = -1.0e30

# In the resident kernels the whole K/V (or a kv head's whole query group)
# of a program resides in VMEM, so VMEM bounds the sequence they take; past
# it the streamed kernels hold one tile of each operand, whatever the row's
# length. Scoped-VMEM budget, set per pallas_call so that every entry point compiles
# the same kernel (the compiler's process-wide default is 16 MiB, and the
# backward at seq 4096 needs more). A call asks for its pipelined blocks,
# double-buffered at their tiled size, plus room for the body's [BQ, BK] f32
# temporaries; nothing may ask for more than the cap (``ops/tiling.py``).
_VMEM_BODY_BYTES = 16 * 1024 * 1024


def _vmem_budget(operands, d: int = 128) -> int:
    """``operands``: (block_shape, dtype, index_map) of every pipelined
    operand of a kernel, inputs and outputs. Each kernel below lists its
    operands once; its BlockSpecs and its budget are both built from that
    list, so the two cannot drift apart. ``d``: the q/k head width; the body's
    float32 copies of its blocks and its accumulators grow with it, so heads
    of two lane registers (latent attention's 192, padded to 256) get twice
    the body's room."""
    return 2 * sum(tiled_bytes(s, t) for s, t, _ in operands) + _VMEM_BODY_BYTES * max(1, lanes(d) // 128)


def _compiler_params(operands, d: int = 128):
    return pltpu.CompilerParams(vmem_limit_bytes=_vmem_budget(operands, d))


def _block_specs(operands):
    return [pl.BlockSpec(shape, index_map) for shape, _, index_map in operands]


# ---------------------------------------------------------------------------
# shared by the three kernels: operand dtype, masks, the diagonal's strips
# ---------------------------------------------------------------------------


# What the ten dot_generals of the three kernels are handed: q, k, v and dO
# cast up from the dtype they arrive in, p and dS as the float32 they are
# computed in, accumulation in float32. At the default matmul precision the
# MXU rounds a float32 operand to bfloat16 as it takes it in, one pass, at
# the same rows a cycle as a bfloat16 operand: the cast up costs the MXU
# nothing, while bfloat16 operands cost the vector unit, which sets the
# kernels' pace, a pack of every p and dS tile and an unpack and repack of
# every K and V tile (measured on a v5e, PR 25: forward 5%, dq 13% slower).
MXU_OPERAND_DTYPE = jnp.float32


def _operand(x):
    return x.astype(MXU_OPERAND_DTYPE)


def _tile_mask(q_seg, k_seg, q_start, k_start, shape, *, segments, on_diagonal, q_axis=0, window=None):
    """Bool tile of ``shape``, True = attend, or None where the tile needs no
    mask at all. Queries from ``q_start`` run along axis ``q_axis`` and keys
    from ``k_start`` along the other; ``q_seg`` and ``k_seg`` are their
    segment ids, one a column and one a row (read only under ``segments``).
    Each test is made only where a tile can fail it (both flags are static):

    ``segments``: the same-segment test, which packing needs and which
    subsumes padding: pad queries (seg 0) attend only the pad tail (incl.
    themselves at k == q, keeping softmax finite), real queries never see pad
    keys or other segments. Tiles whose queries and keys all carry one id
    pass it everywhere and skip it.
    ``on_diagonal``: the causal test, needed only where the diagonal crosses
    the tile; a tile wholly below it skips the two iotas and the compare.
    ``window``: the sliding-window test (a key more than ``window - 1`` behind
    its query is out), handed over only where the band's lower edge crosses
    the tile."""
    mask = (q_seg() == k_seg()) if segments else None
    if on_diagonal or window is not None:
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
        tests = ([k_pos <= q_pos] if on_diagonal else []) + ([k_pos > q_pos - window] if window is not None else [])
        for test in tests:
            mask = test if mask is None else test & mask
    return mask


def _keep(mask, x, fill):
    return x if mask is None else jnp.where(mask, x, fill)


def _with_or_without_segments(one_segment, program):
    """Run ``program(segments=...)``, the whole of a kernel program, without
    the segment test where ``one_segment`` (a scalar read from SMEM) says
    that every tile it visits holds a single segment id. One branch a
    program: a branch inside the tile loop costs more than the test saves."""
    pl.when(one_segment != 0)(functools.partial(program, segments=False))
    pl.when(one_segment == 0)(functools.partial(program, segments=True))


def _diagonal_pieces(block, *, own):
    """The block x block tile on the diagonal, cut into strips so that most
    of what lies above the diagonal is never computed: ``(own_at, own_n,
    other_at, other_n)`` of each piece, offsets from the tile's corner.
    ``own`` is the axis a kernel accumulates along, cut into strips of 256
    (of 128 where 256 does not divide the block); each strip meets only the
    part of the other axis the causal mask lets it see. By queries (forward,
    dq): a strip of queries meets the keys up to its own end. By keys
    (dk/dv): a strip of keys meets the queries from its own start. On a v5e
    (PR 25) strips of 256 beat 128 and 512 in dq and dk/dv and tied with 512
    in the forward. (``_tile_pieces``, which also knows a window's edge, on
    the one tile of a row that is one block.)"""
    return [piece[:4] for piece in _tile_pieces(_band(block, block, None), 0, own=own)]


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(one_segment_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale):
    batch, iq = pl.program_id(0), pl.program_id(2)
    q = _operand(q_ref[0, 0])  # [B, d]
    block = q.shape[0]
    d_v = v_ref.shape[3]  # v heads may be narrower than q/k heads (latent attention)
    q_start = iq * block

    def program(*, segments):
        def update(carry, j, q_at, q_n, k_at, k_n, *, on_diagonal):
            """One online-softmax step of this block's queries [q_at, q_at +
            q_n) (static) against the keys [k_at, k_at + k_n) of K block
            ``j``. Per-row statistics are [rows, 1] columns from start to
            store: a 1-D value lives along the lanes and costs a relayout each
            way each time it meets a tile."""
            m, l, acc = carry
            keys = pl.ds(j * block + k_at, k_n)
            k_blk = _operand(k_ref[0, 0, keys, :])
            v_blk = _operand(v_ref[0, 0, keys, :])
            s = jax.lax.dot_general(
                q[q_at : q_at + q_n], k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [q_n, k_n] float32
            # 0 = padding, >0 = packed segment id; ref-indexed with pl.ds
            # (Mosaic has no dynamic_slice on loaded arrays)
            mask = _tile_mask(
                lambda: segq_ref[0, pl.ds(q_start + q_at, q_n), :],
                lambda: segk_ref[0, j][:, k_at : k_at + k_n],
                q_start + q_at, j * block + k_at, (q_n, k_n),
                segments=segments, on_diagonal=on_diagonal,
            )
            s = _keep(mask, s, _NEG_INF)

            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            if segments:
                # a row whose every key so far is masked (its tiles held only
                # another packed segment) still has m_new = -1e30, so
                # exp(s - m_new) is 1 there, not 0: kept out of l and acc here,
                # not left for the alpha of the row's first real tile
                # (exp(-1e30 - m) = 0) to wipe. Under the causal mask alone a
                # row's own key is never masked, m_new is finite and the
                # masked keys' exp is exactly 0 already.
                p = _keep(mask, p, 0.0)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l, acc

        # the K blocks wholly below the diagonal, whole (a sequence that is one
        # block has none, and no code for them); then the block on it in
        # pieces; blocks past it contribute nothing and are not visited
        carry = (
            jnp.full((block, 1), _NEG_INF, jnp.float32),
            jnp.zeros((block, 1), jnp.float32),
            jnp.zeros((block, d_v), jnp.float32),
        )
        if k_ref.shape[2] > block:
            carry = jax.lax.fori_loop(
                0, iq, lambda j, c: update(c, j, 0, block, 0, block, on_diagonal=False), carry
            )
        for q_at, q_n, k_at, k_n in _diagonal_pieces(block, own="queries"):
            rows = slice(q_at, q_at + q_n)
            m, l, acc = update(
                tuple(x[rows] for x in carry), iq, q_at, q_n, k_at, k_n, on_diagonal=True
            )
            l_safe = jnp.maximum(l, 1e-30)
            o_ref[0, 0, rows, :] = (acc / l_safe).astype(o_ref.dtype)
            lse_ref[0, 0, rows, :] = m + jnp.log(l_safe)

    _with_or_without_segments(one_segment_ref[batch * pl.num_programs(2) + iq], program)


def _one_segment(segments, block, *, from_start):
    """[b * s / block] int32, 1 where every position from the row's start up
    to and including the block (``from_start``), or from the block to the
    row's end, carries one segment id: what the tiles of a program span."""
    b, s = segments.shape
    blocks = segments.reshape(b, s // block, block)
    lo = jax.lax.cummin(blocks.min(-1), axis=1, reverse=not from_start)
    hi = jax.lax.cummax(blocks.max(-1), axis=1, reverse=not from_start)
    return (lo == hi).astype(jnp.int32).reshape(-1)


def _key_rows(segments, block):
    """[b, s] -> [b, s / B, 1, B]: one K block's segment ids along the lanes,
    picked by a leading index (the column ``[b, s, 1]`` that the queries use
    is sliced along sublanes)."""
    b, s = segments.shape
    return segments.reshape(b, s // block, 1, block)


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _limit(operands, d, vmem_limit_bytes):
    """A resident call's compiler parameters: its own budget, or the limit a caller that knows its body's room
    better hands over (``ops/eva_attention.py``: one query head a kv head at a block of 2048 lists few operands,
    and the body's strips against 2048 keys need more than the 16 MiB the budget leaves them)."""
    return _compiler_params(operands, d) if vmem_limit_bytes is None else pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)


def _fwd(q, k, v, segments, *, scale, block, groups, interpret, vmem_limit_bytes=None):
    b, hq, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[3]
    out_shape = (
        jax.ShapeDtypeStruct((b, hq, sq, d_v), q.dtype),
        # trailing unit dim: TPU tiling wants the block's last dim equal to
        # the array's (1) and the second-to-last divisible by 8 (block)
        jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
    )
    ins, outs = _fwd_operands(q.dtype, sk, d, block, groups, d_v)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(b, hq, sq // block),
        in_specs=[_SMEM] + _block_specs(ins),
        out_specs=tuple(_block_specs(outs)),
        out_shape=out_shape,
        compiler_params=_limit(ins + outs, d, vmem_limit_bytes),
        interpret=interpret,
        name="flash_attention_fwd",
    )(
        _one_segment(segments, block, from_start=True),
        segments[:, :, None], _key_rows(segments, block), q, k, v,
    )


def _fwd_operands(dtype, sk, d, block, groups, d_v=None):
    """Grid (batch, q head, q block): segments (column, key rows), q, k, v
    -> o, lse. (The one-segment flags ride in SMEM, outside this list.)
    ``d`` is the width of q and k heads, ``d_v`` that of v and o heads (``d``
    where it is not given)."""
    d_v = d_v or d
    q_blk = lambda b_, h, i: (b_, h, i, 0)  # noqa: E731
    kv_head = lambda b_, h, i: (b_, h // groups, 0, 0)  # noqa: E731
    ins = [
        ((1, sk, 1), jnp.int32, lambda b_, h, i: (b_, 0, 0)),
        ((1, sk // block, 1, block), jnp.int32, lambda b_, h, i: (b_, 0, 0, 0)),
        ((1, 1, block, d), dtype, q_blk),
        ((1, 1, sk, d), dtype, kv_head),
        ((1, 1, sk, d_v), dtype, kv_head),
    ]
    outs = [
        ((1, 1, block, d_v), dtype, q_blk),
        ((1, 1, block, 1), jnp.float32, q_blk),
    ]
    return ins, outs


def _dq_operands(dtype, sq, d, block, groups, d_v=None):
    """Grid (batch, q head, q block): segments (column, key rows), q, k, v,
    do, lse, delta -> dq."""
    d_v = d_v or d
    q_blk = lambda b_, h, i: (b_, h, i, 0)  # noqa: E731
    kv_head = lambda b_, h, i: (b_, h // groups, 0, 0)  # noqa: E731
    ins = [
        ((1, sq, 1), jnp.int32, lambda b_, h, i: (b_, 0, 0)),
        ((1, sq // block, 1, block), jnp.int32, lambda b_, h, i: (b_, 0, 0, 0)),
        ((1, 1, block, d), dtype, q_blk),
        ((1, 1, sq, d), dtype, kv_head),
        ((1, 1, sq, d_v), dtype, kv_head),
        ((1, 1, block, d_v), dtype, q_blk),
        ((1, 1, block, 1), jnp.float32, q_blk),
        ((1, 1, block, 1), jnp.float32, q_blk),
    ]
    outs = [((1, 1, block, d), dtype, q_blk)]
    return ins, outs


def _dkv_operands(dtype, sq, d, block, groups, d_v=None):
    """Grid (batch, KV head, k block): the q/do/lse/delta blocks span the
    head's whole query group -> dk, dv at KV-head width."""
    d_v = d_v or d
    group = lambda b_, h, j: (b_, h, 0, 0)  # noqa: E731
    k_blk = lambda b_, h, j: (b_, h, j, 0)  # noqa: E731
    ins = [
        ((1, sq, 1), jnp.int32, lambda b_, h, j: (b_, 0, 0)),
        ((1, groups, sq, d), dtype, group),
        ((1, 1, block, d), dtype, k_blk),
        ((1, 1, block, d_v), dtype, k_blk),
        ((1, groups, sq, d_v), dtype, group),
        ((1, groups, sq, 1), jnp.float32, group),
        ((1, groups, sq, 1), jnp.float32, group),
    ]
    outs = [((1, 1, block, d), dtype, k_blk), ((1, 1, block, d_v), dtype, k_blk)]
    return ins, outs


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _dq_kernel(one_segment_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale):
    batch, iq = pl.program_id(0), pl.program_id(2)
    q = _operand(q_ref[0, 0])
    do = _operand(do_ref[0, 0])
    lse = lse_ref[0, 0]  # [B, 1]
    delta = delta_ref[0, 0]
    block, d = q.shape
    q_start = iq * block

    def program(*, segments):
        def update(dq_acc, j, q_at, q_n, k_at, k_n, *, on_diagonal):
            """This block's queries [q_at, q_at + q_n) (static) against the
            keys [k_at, k_at + k_n) of K block ``j``."""
            rows = slice(q_at, q_at + q_n)
            keys = pl.ds(j * block + k_at, k_n)
            k_blk = _operand(k_ref[0, 0, keys, :])
            v_blk = _operand(v_ref[0, 0, keys, :])
            s = jax.lax.dot_general(
                q[rows], k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale
            mask = _tile_mask(
                lambda: segq_ref[0, pl.ds(q_start + q_at, q_n), :],
                lambda: segk_ref[0, j][:, k_at : k_at + k_n],
                q_start + q_at, j * block + k_at, (q_n, k_n),
                segments=segments, on_diagonal=on_diagonal,
            )
            p = _keep(mask, jnp.exp(s - lse[rows]), 0.0)
            dp = jax.lax.dot_general(
                do[rows], v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds = p * (dp - delta[rows])
            return dq_acc + jax.lax.dot_general(
                ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        # as in the forward kernel: whole blocks below the diagonal, then the
        # block on it in pieces
        dq = jnp.zeros((block, d), jnp.float32)
        if k_ref.shape[2] > block:
            dq = jax.lax.fori_loop(
                0, iq, lambda j, acc: update(acc, j, 0, block, 0, block, on_diagonal=False), dq
            )
        for q_at, q_n, k_at, k_n in _diagonal_pieces(block, own="queries"):
            rows = slice(q_at, q_at + q_n)
            piece = update(dq[rows], iq, q_at, q_n, k_at, k_n, on_diagonal=True)
            dq_ref[0, 0, rows, :] = (piece * scale).astype(dq_ref.dtype)

    _with_or_without_segments(one_segment_ref[batch * pl.num_programs(2) + iq], program)


def _as_row(column):
    """[n, 1] -> [1, n] (n a multiple of 128) through the transpose unit: the
    column spread over 128 lanes, transposed, one row kept."""
    return jnp.transpose(jnp.broadcast_to(column, (column.shape[0], 128)))[:1, :]


def _dkv_kernel(one_segment_ref, seg_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, groups):
    """Per (batch, KV head, k_block): accumulate dk/dv over this KV head's
    ``groups`` query heads and all causal q blocks — dk/dv stay at KV-head
    width (no group-factor HBM inflation). The scores are held transposed,
    [keys, queries]: then all four matmuls run in the orientations the MXU
    has (K Q^T, V dO^T, P^T dO, dS^T Q with P^T and dS^T as they stand) and no
    score tile goes through the transpose unit; what does is a Q block's lse,
    delta and segment ids, [B, 1] columns that such a tile needs as rows."""
    batch, jk = pl.program_id(0), pl.program_id(2)
    k_all = _operand(k_ref[0, 0])  # [B, d]
    v_all = _operand(v_ref[0, 0])
    block, d = k_all.shape
    d_v = v_all.shape[1]
    k_start = jk * block
    pieces = _diagonal_pieces(block, own="keys")

    def program(*, segments):
        def update(carry, g, i, k_at, k_n, q_at, q_n, *, on_diagonal):
            """This block's keys [k_at, k_at + k_n) (static) against the
            queries [q_at, q_at + q_n) of Q block ``i`` of head ``g``."""
            dk_acc, dv_acc = carry
            keys = slice(k_at, k_at + k_n)
            rows = pl.ds(i * block + q_at, q_n)
            q_blk = _operand(q_ref[0, g, rows, :])
            do_blk = _operand(do_ref[0, g, rows, :])
            s_t = jax.lax.dot_general(
                k_all[keys], q_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [k_n, q_n]
            mask = _tile_mask(
                lambda: _as_row(seg_ref[0, rows, :]),
                lambda: seg_ref[0, pl.ds(k_start + k_at, k_n), :],
                i * block + q_at, k_start + k_at, (k_n, q_n),
                segments=segments, on_diagonal=on_diagonal, q_axis=1,
            )
            p_t = _keep(mask, jnp.exp(s_t - _as_row(lse_ref[0, g, rows, :])), 0.0)
            dv_acc = dv_acc + jax.lax.dot_general(
                p_t.astype(do_blk.dtype), do_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp_t = jax.lax.dot_general(
                v_all[keys], do_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds_t = p_t * (dp_t - _as_row(delta_ref[0, g, rows, :]))
            dk_acc = dk_acc + jax.lax.dot_general(
                ds_t.astype(q_blk.dtype), q_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return dk_acc, dv_acc

        def head(g, carry):
            """One query head of the KV head's group: the Q block on the
            diagonal in pieces (each with accumulators of its own, there are
            no rows to put back), the Q blocks after it whole; earlier ones
            see none of these keys."""
            whole, on_diagonal = carry
            on_diagonal = tuple(
                update(acc, g, jk, *piece, on_diagonal=True)
                for acc, piece in zip(on_diagonal, pieces)
            )
            if q_ref.shape[2] > block:
                whole = jax.lax.fori_loop(
                    jk + 1, q_ref.shape[2] // block,
                    lambda i, c: update(c, g, i, 0, block, 0, block, on_diagonal=False),
                    whole,
                )
            return whole, on_diagonal

        zeros = lambda n: (jnp.zeros((n, d), jnp.float32), jnp.zeros((n, d_v), jnp.float32))  # noqa: E731
        (dk, dv), on_diagonal = jax.lax.fori_loop(
            0, groups, head, (zeros(block), tuple(zeros(k_n) for _, k_n, _, _ in pieces))
        )
        for (k_at, k_n, _, _), (dk_piece, dv_piece) in zip(pieces, on_diagonal):
            keys = slice(k_at, k_at + k_n)
            dk_ref[0, 0, keys, :] = ((dk[keys] + dk_piece) * scale).astype(dk_ref.dtype)
            dv_ref[0, 0, keys, :] = (dv[keys] + dv_piece).astype(dv_ref.dtype)

    _with_or_without_segments(one_segment_ref[batch * pl.num_programs(2) + jk], program)


def _bwd(q, k, v, segments, o, lse, do, *, scale, block, groups, interpret, vmem_limit_bytes=None):
    """Head-major inputs: q/o/do/lse [b, hq, ...], k/v [b, hkv, s, d]."""
    b, hq, sq, d = q.shape
    hkv, d_v = k.shape[1], v.shape[3]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None]  # [b,hq,sq,1]
    seg_column = segments[:, :, None]

    ins, outs = _dq_operands(q.dtype, sq, d, block, groups, d_v)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(b, hq, sq // block),
        in_specs=[_SMEM] + _block_specs(ins),
        out_specs=_block_specs(outs)[0],
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        compiler_params=_limit(ins + outs, d, vmem_limit_bytes),
        interpret=interpret,
        name="flash_attention_dq",
    )(
        _one_segment(segments, block, from_start=True),
        seg_column, _key_rows(segments, block), q, k, v, do, lse, delta,
    )

    ins, outs = _dkv_operands(q.dtype, sq, d, block, groups, d_v)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, groups=groups),
        grid=(b, hkv, sq // block),
        in_specs=[_SMEM] + _block_specs(ins),
        out_specs=tuple(_block_specs(outs)),
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, sq, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sq, d_v), v.dtype),
        ),
        compiler_params=_limit(ins + outs, d, vmem_limit_bytes),
        interpret=interpret,
        name="flash_attention_dkv",
    )(_one_segment(segments, block, from_start=False), seg_column, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# streamed kernels: the other axis on the grid, a window's band and no more
# ---------------------------------------------------------------------------


class _Band(NamedTuple):
    """Which [block, block] tiles of a row's score matrix a layer needs.
    Query ``i`` sees key ``j`` iff ``i - window < j <= i`` (``window`` None:
    every ``j <= i``). A block of queries (keys) then meets ``steps`` blocks
    of keys (queries): its own and the ``steps - 1`` before (after) it. The
    grids of the three streamed kernels are built from this and visit no tile
    outside it; ``tiles`` is what they visit."""

    block: int
    blocks: int  # a row's blocks
    window: Optional[int]
    steps: int

    @property
    def tiles(self) -> int:
        return sum(min(i + 1, self.steps) for i in range(self.blocks))


def _band(seq: int, block: int, window: Optional[int]) -> _Band:
    blocks = seq // block
    reach = seq if window is None else min(window, seq)
    return _Band(block, blocks, None if window is None or window >= seq else window, min(blocks, -(-(reach - 1) // block) + 1))


def _is_cut(band: _Band, delta: int) -> bool:
    """Whether the diagonal or the band's lower edge crosses the tile
    ``delta`` blocks below the diagonal (some pair in it is masked)."""
    return delta == 0 or (band.window is not None and delta * band.block + band.block - 1 >= band.window)


def _tile_pieces(band: _Band, delta: int, *, own):
    """The tile ``delta`` blocks below the diagonal (queries of block ``i``
    against keys of block ``i - delta``), as ``(own_at, own_n, other_at,
    other_n, causal, window)`` pieces, offsets from the tile's corner. A tile
    wholly inside the band is one piece with no test. A tile that the
    diagonal or the band's lower edge crosses is cut along ``own`` (the axis
    the kernel accumulates along: "queries" in forward and dq, "keys" in
    dk/dv) into strips of 256 as ``_diagonal_pieces`` cuts the diagonal; each
    strip meets the part of the other axis it can see, rounded out to whole
    lane registers, and makes only the tests (``causal`` a bool, ``window``
    the width or None) that some pair in it can fail."""
    block, w = band.block, band.window
    if not _is_cut(band, delta):
        return [(0, block, 0, block, False, None)]
    shift = delta * block  # query position - key position = row - column + shift
    n = 256 if block % 256 == 0 else 128
    pieces = []
    for at in range(0, block, n):
        last = at + n - 1
        if own == "queries":  # rows at..last see columns in (row + shift - w, row + shift]
            lo, hi = (0 if w is None else at + shift - w + 1), last + shift + 1
        else:  # columns at..last are seen by rows in [column - shift, column - shift + w)
            lo, hi = at - shift, (block if w is None else last - shift + w)
        lo, hi = max(0, lo // 128 * 128), min(block, -(-hi // 128) * 128)
        if hi <= lo:
            continue
        if own == "queries":
            causal, edge = hi - 1 > at + shift, w is not None and lo <= last + shift - w
        else:
            causal, edge = last > lo + shift, w is not None and at <= hi - 1 + shift - w
        pieces.append((at, n, lo, hi - lo, causal, w if edge else None))
    return pieces


def _by_offset(band: _Band, delta, run):
    """``run(offset)`` for the tile ``delta`` (traced) blocks below the
    diagonal: a branch of its own for each offset whose tile is cut into
    pieces (the diagonal, the band's edge: two or three in all), one for all
    the whole tiles between them."""
    cut = [d for d in range(band.steps) if _is_cut(band, d)]
    for d in cut:
        pl.when(delta == d)(functools.partial(run, d))
    whole = [d for d in range(band.steps) if d not in cut]
    if whole:
        pl.when(jnp.logical_and(delta >= whole[0], delta <= whole[-1]))(functools.partial(run, whole[0]))


def _fwd_stream_kernel(one_segment_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale, band):
    """Grid (batch, q head, q block, step): step ``t`` brings in the K/V
    block ``steps - 1 - t`` before the Q block's own (the band's far edge
    first, the diagonal last); the online softmax's m, l and accumulator live
    in scratch across the steps and the block's output leaves on the last."""
    batch, iq, t = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    delta = band.steps - 1 - t

    @pl.when(t == 0)
    def _start():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def program(*, segments):
        def tile(offset):
            q = _operand(q_ref[0, 0])
            for q_at, q_n, k_at, k_n, causal, window in _tile_pieces(band, offset, own="queries"):
                rows, keys = slice(q_at, q_at + q_n), slice(k_at, k_at + k_n)
                v_blk = _operand(v_ref[0, 0, keys, :])
                s = jax.lax.dot_general(
                    q[rows], _operand(k_ref[0, 0, keys, :]), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale
                mask = _tile_mask(
                    lambda: segq_ref[0, rows, :], lambda: segk_ref[0, 0, :, keys],
                    q_at + offset * band.block, k_at, (q_n, k_n),
                    segments=segments, on_diagonal=causal, window=window,
                )
                s = _keep(mask, s, _NEG_INF)
                m = m_ref[rows]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                if segments or window is not None:
                    # a row with no key in this piece (behind the band's edge, or
                    # another segment) still has m_new = -1e30 and exp(0) = 1
                    # there: kept out of l and acc here (the resident kernel's note)
                    p = _keep(mask, p, 0.0)
                l_ref[rows] = l_ref[rows] * alpha + jnp.sum(p, axis=1, keepdims=True)
                acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
                    p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
                )
                m_ref[rows] = m_new

        _by_offset(band, delta, tile)

    # (a block nearer the row's start than the band is wide has no K/V block that far back)
    pl.when(delta <= iq)(lambda: _with_or_without_segments(one_segment_ref[batch], program))

    @pl.when(t == band.steps - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_safe)


def _dq_stream_kernel(one_segment_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *, scale, band):
    """Grid and steps as the streamed forward; dq accumulates in scratch."""
    batch, iq, t = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    delta = band.steps - 1 - t

    @pl.when(t == 0)
    def _start():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def program(*, segments):
        def tile(offset):
            q, do = _operand(q_ref[0, 0]), _operand(do_ref[0, 0])
            for q_at, q_n, k_at, k_n, causal, window in _tile_pieces(band, offset, own="queries"):
                rows, keys = slice(q_at, q_at + q_n), slice(k_at, k_at + k_n)
                k_blk, v_blk = _operand(k_ref[0, 0, keys, :]), _operand(v_ref[0, 0, keys, :])
                s = jax.lax.dot_general(
                    q[rows], k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                ) * scale
                mask = _tile_mask(
                    lambda: segq_ref[0, rows, :], lambda: segk_ref[0, 0, :, keys],
                    q_at + offset * band.block, k_at, (q_n, k_n),
                    segments=segments, on_diagonal=causal, window=window,
                )
                p = _keep(mask, jnp.exp(s - lse_ref[0, 0, rows, :]), 0.0)
                dp = jax.lax.dot_general(
                    do[rows], v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                ds = p * (dp - delta_ref[0, 0, rows, :])
                acc_ref[rows] = acc_ref[rows] + jax.lax.dot_general(
                    ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
                )

        _by_offset(band, delta, tile)

    pl.when(delta <= iq)(lambda: _with_or_without_segments(one_segment_ref[batch], program))

    @pl.when(t == band.steps - 1)
    def _finish():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_stream_kernel(one_segment_ref, segq_ref, segk_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, band):
    """Grid (batch, KV head, k block, query head of the group, step): step
    ``t`` brings in the Q block ``t`` after the K block's own (q, dO, lse and
    delta of ONE head: the group is walked by the grid, not held), on
    transposed scores as the resident kernel; dk and dv accumulate in scratch
    over the group and the steps and leave on the last of both."""
    batch, jk, g, t = pl.program_id(0), pl.program_id(2), pl.program_id(3), pl.program_id(4)
    first = jnp.logical_and(g == 0, t == 0)
    last = jnp.logical_and(g == pl.num_programs(3) - 1, t == band.steps - 1)

    @pl.when(first)
    def _start():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def program(*, segments):
        def tile(offset):
            for k_at, k_n, q_at, q_n, causal, window in _tile_pieces(band, offset, own="keys"):
                keys, rows = slice(k_at, k_at + k_n), slice(q_at, q_at + q_n)
                q_blk, do_blk = _operand(q_ref[0, 0, rows, :]), _operand(do_ref[0, 0, rows, :])
                s_t = jax.lax.dot_general(
                    _operand(k_ref[0, 0, keys, :]), q_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [k_n, q_n]
                mask = _tile_mask(
                    lambda: _as_row(segq_ref[0, rows, :]), lambda: segk_ref[0, keys, :],
                    q_at + offset * band.block, k_at, (k_n, q_n),
                    segments=segments, on_diagonal=causal, window=window, q_axis=1,
                )
                p_t = _keep(mask, jnp.exp(s_t - _as_row(lse_ref[0, 0, rows, :])), 0.0)
                dv_acc[keys] = dv_acc[keys] + jax.lax.dot_general(
                    p_t.astype(do_blk.dtype), do_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
                )
                dp_t = jax.lax.dot_general(
                    _operand(v_ref[0, 0, keys, :]), do_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                ds_t = p_t * (dp_t - _as_row(delta_ref[0, 0, rows, :]))
                dk_acc[keys] = dk_acc[keys] + jax.lax.dot_general(
                    ds_t.astype(q_blk.dtype), q_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
                )

        _by_offset(band, t, tile)

    # (a block nearer the row's end than the band is wide has no Q block that far on)
    pl.when(jk + t < band.blocks)(lambda: _with_or_without_segments(one_segment_ref[batch], program))

    @pl.when(last)
    def _finish():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _stream_operands(kernel, dtype, d, d_v, band: _Band, groups):
    """(ins, outs, scratch) of a streamed kernel: as the resident kernels'
    lists, every block one ``[block, width]`` tile. The block of the OTHER
    axis is picked by the step; a step that would reach past the row's start
    (end) names the nearest block there is, which is already in VMEM, and the
    kernel skips it."""
    block, steps = band.block, band.steps
    f32 = jnp.float32
    if kernel == "dkv":
        own = lambda b_, h, j, g, t: (b_, h, j, 0)  # noqa: E731
        other = lambda b_, h, j, g, t: (b_, h * groups + g, jnp.minimum(j + t, band.blocks - 1), 0)  # noqa: E731
        ins = [
            ((1, block, 1), jnp.int32, lambda b_, h, j, g, t: (b_, jnp.minimum(j + t, band.blocks - 1), 0)),
            ((1, block, 1), jnp.int32, lambda b_, h, j, g, t: (b_, j, 0)),
            ((1, 1, block, d), dtype, other),
            ((1, 1, block, d), dtype, own),
            ((1, 1, block, d_v), dtype, own),
            ((1, 1, block, d_v), dtype, other),
            ((1, 1, block, 1), f32, other),
            ((1, 1, block, 1), f32, other),
        ]
        outs = [((1, 1, block, d), dtype, own), ((1, 1, block, d_v), dtype, own)]
        return ins, outs, [((block, d), f32), ((block, d_v), f32)]
    behind = lambda i, t: jnp.maximum(i - (steps - 1) + t, 0)  # noqa: E731
    own = lambda b_, h, i, t: (b_, h, i, 0)  # noqa: E731
    other = lambda b_, h, i, t: (b_, h // groups, behind(i, t), 0)  # noqa: E731
    ins = [
        ((1, block, 1), jnp.int32, lambda b_, h, i, t: (b_, i, 0)),
        ((1, 1, 1, block), jnp.int32, lambda b_, h, i, t: (b_, behind(i, t), 0, 0)),
        ((1, 1, block, d), dtype, own),
        ((1, 1, block, d), dtype, other),
        ((1, 1, block, d_v), dtype, other),
    ]
    if kernel == "fwd":
        outs = [((1, 1, block, d_v), dtype, own), ((1, 1, block, 1), f32, own)]
        return ins, outs, [((block, 1), f32), ((block, 1), f32), ((block, d_v), f32)]
    ins += [((1, 1, block, d_v), dtype, own), ((1, 1, block, 1), f32, own), ((1, 1, block, 1), f32, own)]
    return ins, [((1, 1, block, d), dtype, own)], [((block, d), f32)]


# name of each streamed kernel in the program (and in a device trace): the
# window's calls and the causal ones are told apart by it
def _stream_name(kernel: str, band: _Band) -> str:
    return f"flash_attention_{'causal' if band.window is None else 'window'}_{kernel}"


# {(kernel name, band): (tiles its grid visits in one head's row, tiles of the
# row's causal triangle)} of every streamed kernel built (traced) in this
# process: what a grid was built to visit, for whoever wants to count it. The
# band carries the row's blocks, their size and the window, so two shapes
# traced in one process do not overwrite each other
GRID_TILES: dict = {}


def _stream_call(kernel, body, band: _Band, q, k, v, groups, out_shape, interpret, **static):
    b, hq, _, d = q.shape
    hkv, d_v = k.shape[1], v.shape[3]
    ins, outs, scratch = _stream_operands(kernel, q.dtype, d, d_v, band, groups)
    grid = (b, hkv, band.blocks, groups, band.steps) if kernel == "dkv" else (b, hq, band.blocks, band.steps)
    name = _stream_name(kernel, band)
    GRID_TILES[name, band] = (band.tiles, band.blocks * (band.blocks + 1) // 2)
    budget = _vmem_budget(ins + outs, d) + sum(tiled_bytes(shape, t) for shape, t in scratch)
    return pl.pallas_call(
        functools.partial(body, band=band, **static),
        grid=grid,
        in_specs=[_SMEM] + _block_specs(ins),
        out_specs=tuple(_block_specs(outs)) if len(outs) > 1 else _block_specs(outs)[0],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(shape, t) for shape, t in scratch],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=budget,
            dimension_semantics=("parallel",) * 3 + ("arbitrary",) * (len(grid) - 3),
        ),
        interpret=interpret,
        name=name,
    )


def _whole_row_one_segment(segments):
    """[b] int32, 1 where a row carries one segment id from end to end (a
    full row): its streamed programs make no segment test at all."""
    return (segments.min(axis=1) == segments.max(axis=1)).astype(jnp.int32)


def _fwd_stream(q, k, v, segments, *, scale, block, groups, window, interpret):
    b, hq, sq, _ = q.shape
    band = _band(sq, block, window)
    out_shape = (
        jax.ShapeDtypeStruct((b, hq, sq, v.shape[3]), q.dtype),
        jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
    )
    return _stream_call("fwd", _fwd_stream_kernel, band, q, k, v, groups, out_shape, interpret, scale=scale)(
        _whole_row_one_segment(segments), segments[:, :, None], _key_rows(segments, block), q, k, v
    )


def _bwd_stream(q, k, v, segments, o, lse, do, *, scale, block, groups, window, interpret):
    b, hq, sq, d = q.shape
    band = _band(sq, block, window)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None]
    one_segment, seg_column = _whole_row_one_segment(segments), segments[:, :, None]
    dq = _stream_call(
        "dq", _dq_stream_kernel, band, q, k, v, groups, jax.ShapeDtypeStruct(q.shape, q.dtype), interpret, scale=scale
    )(one_segment, seg_column, _key_rows(segments, block), q, k, v, do, lse, delta)
    dk, dv = _stream_call(
        "dkv", _dkv_stream_kernel, band, q, k, v, groups,
        (jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)), interpret, scale=scale,
    )(one_segment, seg_column, seg_column, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper (public entry)
# ---------------------------------------------------------------------------


# What the forward rule of the custom_vjp below calls its two outputs
# (``jax.ad_checkpoint.checkpoint_name``). A ``jax.checkpoint`` whose policy
# saves these names keeps them for the backward kernels; under any other policy
# the names are inert.
KEPT_ACROSS_REMAT = ("flash_o", "flash_lse")


def worth_keeping_across_remat(seq: int, d_qk: int, d_v: int, hidden_size: int, window: Optional[int] = None) -> bool:
    """Whether a rematerialized block should keep the forward kernel's ``o``
    and ``lse`` instead of running the kernel a second time in the backward
    pass. From shapes alone: per byte of ``o`` a causal forward costs
    ``seq * (d_qk + d_v) / (2 * d_v)`` FLOPs (``seq^2 * (d_qk + d_v)`` a head
    for ``2 * seq * d_v`` bytes), the kernel's work growing with the square of
    the row and what is kept with the row; per byte of its output a
    projection from ``hidden_size`` costs ``hidden_size``, which is what a
    kept byte buys anywhere else in a block. Keep where the first exceeds the
    second. At the benchmark's cells: SmolLM3-3B 1024 against 2048 and
    Mistral-7B 2048 against 4096, recompute; Moonlight 4096 x 320 / 256 = 5120
    against 2048, keep. With the kernel's measured share of its roofline
    against the matmuls' (48 to 62% against about 80%, PERF.md section 5) the
    crossing lies at 0.6 to 0.8 of ``hidden_size`` and the three fall on the
    same sides, so the plain form stands (PERF.md, PR 27).

    With a ``window`` the kernel's work grows with the keys a query sees and
    not with the row: ``seq`` above stands for twice the mean number of keys a
    query sees, which under the causal mask alone is the row's length and
    inside a band ``window * (2 - window / seq)``. Mellum at 8192: its window
    layers 1920 against 2304, recompute; its global layers 8192, keep."""
    if window is not None and window < seq:
        seq = window * (2 - window / seq)
    return seq * (d_qk + d_v) > 2 * d_v * hidden_size


@functools.lru_cache(maxsize=None)
def _make_flash_fn(scale: float, block: int, groups: int, interpret: bool, window: Optional[int] = None, streamed: bool = False):
    """One custom_vjp closure per static configuration (``streamed``: the
    kernels with the other axis on the grid; ``window`` goes with them). The forward and the
    backward are jitted on their own: a model calls this once a layer (and
    again under remat), and each call of a bare ``pallas_call`` would trace
    its kernel body and lower it to Mosaic anew, in Python, in every process,
    compile cache or not (PR 25: 0.6 to 1.0 s a layer for these kernels, 77 s
    of a warm start at 36 layers). Behind a jit the callers share one traced
    jaxpr and one lowered function.

    The backward kernels need the forward's ``o`` and row statistics ``lse``.
    Under ``jax.checkpoint`` a block keeps its input only, and the forward
    kernel runs a second time in the backward pass to get the two back. The
    forward rule therefore names them (``KEPT_ACROSS_REMAT``), and the block's
    remat policy (models/transformer.py) saves the names on rows long enough
    that the second run costs more than holding them
    (``worth_keeping_across_remat``): q, k and v are still rebuilt from the
    block's input, the second forward is dead code and XLA drops it. The
    primal output is the named ``o`` too (the output projection's backward
    reads it). ``lse`` is kept as the kernel writes it, ``[b, hq, sq, 1]``
    float32, and goes from the forward kernel into the backward kernels
    untouched. In HBM that layout pads the unit dimension to a tile's 128
    lanes (128 MiB a layer at 4 x 16 x 4096 for 1 MiB of numbers), and the
    compact ``[b, hq, sq]`` was tried (PERF.md, PR 27): the TPU compiler's
    peak for the Moonlight step is 12.91 GiB compact and 13.05 GiB as
    written, but the two relayouts a layer (a reduce over the padded
    dimension, a copy back) and what they did to XLA's schedule around them
    took back a third of the gain on the chip (device busy time of 7 steps
    7.30 s before, 7.13 s compact, 7.04 s as written)."""
    static = dict(scale=scale, block=block, groups=groups, interpret=interpret)
    fwd, bwd = _fwd, _bwd
    if streamed:
        fwd, bwd = functools.partial(_fwd_stream, window=window), functools.partial(_bwd_stream, window=window)

    @jax.jit
    def forward(q, k, v, segments):
        return fwd(q, k, v, segments, **static)

    @jax.jit
    def backward(q, k, v, segments, o, lse, do):
        return bwd(q, k, v, segments, o, lse, do, **static)

    @jax.custom_vjp
    def fn(q, k, v, segments):
        return forward(q, k, v, segments)[0]

    def fn_fwd(q, k, v, segments):
        o, lse = forward(q, k, v, segments)
        o = checkpoint_name(o, KEPT_ACROSS_REMAT[0])
        lse = checkpoint_name(lse, KEPT_ACROSS_REMAT[1])
        return o, (q, k, v, segments, o, lse)

    def fn_bwd(res, do):
        q, k, v, segments, o, lse = res
        dq, dk, dv = backward(q, k, v, segments, o, lse, do)
        dsegments = np.zeros(segments.shape, jax.dtypes.float0)
        return dq, dk, dv, dsegments

    fn.defvjp(fn_fwd, fn_bwd)
    return fn


# Up to this length a sequence is one block: no tile lies below the diagonal,
# the strips of the diagonal tile are all the work, each strip's softmax is
# done in one step, and the widest score tile is [_PIECE_ROWS, s]. Longer
# sequences take blocks of at most 1024: a whole [block, block] score tile
# below the diagonal has to fit the kernels' VMEM (2048 x 2048 does not).
# On a v5e (PR 25, the three kernels alone): seq 2048 as one block ran 1.22,
# 1.07 and 1.14 times faster than as two of 1024, those 1.07, 1.10 and 1.10
# times faster than four of 512.
_ONE_BLOCK_SEQ = 2048


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
    if s % 128:
        return 0
    if s <= _ONE_BLOCK_SEQ:
        return s
    return next(blk for blk in (1024, 512, 256, 128) if s % blk == 0)


def _resident_need(dtype, seq, d, d_v, block, groups) -> int:
    """VMEM the largest resident kernel asks for: dk/dv, which holds a kv
    head's whole query group."""
    return _vmem_budget(sum(_dkv_operands(dtype, seq, d, block, groups, d_v), []), d)


def _streamed(dtype, seq, d, d_v, block, groups, window) -> bool:
    """Which set of kernels takes a call: the resident ones wherever they can
    (no window, and the query group fits the cap), else the streamed ones.
    From the call's shapes alone."""
    return (window is not None and window < seq) or _resident_need(dtype, seq, d, d_v, block, groups) > _VMEM_CAP_BYTES


def program_label(q, k, v, *, sliding_window=None) -> str:
    """What ``flash_unsupported_reason`` accepted, in words, for the record
    of the paths a model took (ops/attention.py): which set of kernels, and
    the window."""
    d, d_v, seq = q.shape[3], v.shape[3], q.shape[1]
    streamed = _streamed(q.dtype, seq, d, d_v, _pick_block(seq), q.shape[2] // k.shape[2], sliding_window)
    kind = "causal" if sliding_window is None or sliding_window >= seq else f"window {sliding_window}"
    return f"{'streamed' if streamed else 'resident'} {kind}"


def flash_unsupported_reason(
    q, k, v, *, sliding_window=None, causal: bool = True
) -> Optional[str]:
    """Why the kernel cannot take this call (``None``: it can). Static, run
    at trace time by ops/attention.py, which records the reason beside the
    path it took instead."""
    b, sq, hq, d = q.shape
    sk, hkv, d_v = k.shape[1], k.shape[2], v.shape[3]
    if jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, the kernel is compiled for TPU only"
    if not causal:
        return "non-causal mask"
    if sliding_window is not None and sliding_window < 1:
        return f"sliding window {sliding_window} sees no key"
    if sq != sk:
        return f"q len {sq} != kv len {sk} (decode/cache path)"
    block = _pick_block(sq)
    if block == 0:
        return f"seq {sq} is not a multiple of 128"
    if (d_v % 128 != 0 or (d % 128 != 0 and d == d_v)) and (d, d_v) != (64, 64):
        # v heads fill whole lane registers; q/k heads wider than them (latent
        # attention: 192 against 128) are taken as they lie; one head size for
        # all three has to be aligned, or HALF a register (Granite 4.0-H: 64),
        # which is taken as it lies too: a block's last dimension is the whole
        # head, it pads to 128 lanes in VMEM (``tiled_bytes`` counts that) and
        # the MXU contracts 64 in one pass; nothing is padded in HBM
        return f"head dim {d_v} is not a multiple of the 128 lanes"
    if hq % hkv:
        return f"q heads {hq} not a multiple of kv heads {hkv}"
    # A row whose query group the resident dk/dv kernel cannot hold (at
    # SmolLM3's head shapes past 6144) and a layer with a window take the
    # streamed kernels, whose blocks are one tile each whatever the row's
    # length: their need grows with the block and the head widths alone.
    if _streamed(q.dtype, sq, d, d_v, block, hq // hkv, sliding_window):
        ins, outs, scratch = _stream_operands("dkv", q.dtype, d, d_v, _band(sq, block, sliding_window), hq // hkv)
        need = _vmem_budget(ins + outs, d) + sum(tiled_bytes(shape, t) for shape, t in scratch)
        if need > _VMEM_CAP_BYTES:
            return (
                f"streamed backward needs {need >> 20} MiB of VMEM at blocks of {block} x {d}, over the "
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
    # gathered index IS logical position (models/transformer._cache_write_and_view): slot i
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
    """How ``models/transformer._cache_write_and_view`` reads the int8 paged pool at
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
    q, k, v, *, padding_mask=None, segment_ids=None, sliding_window=None, interpret: bool = False,
    head_major: bool = False,
):
    """q [b, sq, hq, d], k [b, sk, hkv, d], v [b, sk, hkv, d_v] ->
    [b, sq, hq, d_v] (q.dtype). The softmax scale is ``d ** -0.5``.

    ``head_major``: q, k and v arrive as the kernels read them, ``[b, hq, sq,
    d]``, ``[b, hkv, sk, d]``, ``[b, hkv, sk, d_v]`` (``ops/rope.heads_in``
    writes them so), and the three transposes on the way in are not made, nor
    their three on the way back for dq, dk, dv; the output is ``[b, sq, hq,
    d_v]`` either way. Every other caller keeps the ``[b, s, h, d]`` contract
    of ``ops.attention.attention``.

    q/k heads may be wider than v heads and need not fill whole lane
    registers (latent attention: 128 + 64 rope dimensions against v heads of
    128). Mosaic takes the 192 lanes as they lie: in VMEM a block pads to 256
    and the MXU contracts 192 in two passes of 128 either way, so padding q
    and k with zero lanes outside the kernel buys nothing; measured on a v5e it
    cost 6% of the forward and 4% of forward + backward in the pad's copies
    (PERF.md, PR 26).

    Masking is expressed as per-position segments [b, sk] int32: attention
    flows only within equal segment ids (plus causal). ``segment_ids`` comes
    from the packing pipeline (data/packing.py, 0 = pad tail); without it,
    ``padding_mask`` (1 = real) degenerates to the two-segment real/pad case.
    Softmax in f32; causal; with ``sliding_window`` a query sees the
    ``sliding_window`` keys up to and including its own.
    """
    b, hq, sq, d = q.shape if head_major else (q.shape[0], q.shape[2], q.shape[1], q.shape[3])
    groups = hq // k.shape[1 if head_major else 2]
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
    if _streamed(q.dtype, sq, d, v.shape[3], block, groups, sliding_window):
        fn = _make_flash_fn(float(1.0 / np.sqrt(d)), block, groups, interpret, sliding_window, True)
    else:
        fn = _make_flash_fn(float(1.0 / np.sqrt(d)), block, groups, interpret)
    if not head_major:  # head-major layout for clean blocking
        q, k, v = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    return fn(q, k, v, segments).transpose(0, 2, 1, 3)
