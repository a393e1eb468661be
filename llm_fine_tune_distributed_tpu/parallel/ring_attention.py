"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has NO long-context path — context is fixed at 1024 tokens and
its only attention optimization is flash-attn-2 for memory (SURVEY.md §5.7;
reference ``training.py:282``, ``requirements.txt:10``). This module is the
TPU-native long-context design the survey calls for: each device in the
``seq`` mesh axis holds one contiguous chunk of the sequence, K/V chunks
rotate around the ICI ring with ``jax.lax.ppermute``, and every device
accumulates its queries' attention with the blockwise online-softmax
recurrence (the same math as the Pallas flash kernel in
ops/flash_attention.py, lifted from VMEM blocks to mesh shards).

Peak memory per device is O(seq/N * seq/N) score tiles instead of O(seq^2),
and the N-1 ppermute hops overlap with the blockwise compute — XLA pipelines
the collective-permute against the einsums, which is what makes this the
idiomatic TPU expression of context parallelism (vs. all-gathering K/V).

Called inside ``jax.shard_map`` (fully manual over all mesh axes): batch is
sharded over (data, fsdp), heads over tensor, sequence over seq. Gradients
flow through ``ppermute`` (reverse permutation on backward), so the same code
path trains — no separate backward kernel needed.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -2.0e38  # finite: (-inf) arithmetic breeds NaNs in the recurrence


def _local_ring_attention(q, k, v, padding_mask, segment_ids=None, *, axis_name: str,
                          axis_size: int, causal: bool):
    """Blockwise attention over ring-rotated K/V chunks.

    Runs on ONE device's shards inside shard_map:
      q: [b, lq, h, d]   — this device's query chunk (lq = seq / axis_size)
      k, v: [b, lk, hk, d] — this device's K/V chunk, rotated each step
      padding_mask: [b, lk] (1 = real token) rotated alongside, or None.
      segment_ids: [b, lq] packing segments (data/packing.py) or None. The
        query-side chunk stays resident; a key-side copy rotates with K/V and
        attention is restricted to equal ids — packed rows keep segments
        contiguous, so row-position causality + id equality reproduces the
        block-diagonal causal mask exactly (parity pinned in
        tests/test_ring_attention.py).
    """
    my_idx = jax.lax.axis_index(axis_name)
    b, lq, num_heads, d = q.shape
    lk, num_kv = k.shape[1], k.shape[2]
    groups = num_heads // num_kv

    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    # [b, lq, hk, g, d] — GQA grouping computed once.
    qg = (q.astype(jnp.float32) * scale).reshape(b, lq, num_kv, groups, d)
    q_pos = my_idx * lq + jnp.arange(lq)

    # Online-softmax carry: running max m, denominator l, weighted output o.
    o = jnp.zeros((b, num_kv, groups, lq, d), jnp.float32)
    m = jnp.full((b, num_kv, groups, lq), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, num_kv, groups, lq), jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    cur_k, cur_v, cur_pad, cur_seg = k, v, padding_mask, segment_ids

    for t in range(axis_size):
        # After t forward rotations this device holds chunk (my_idx - t).
        kv_idx = (my_idx - t) % axis_size
        k_pos = kv_idx * lk + jnp.arange(lk)

        scores = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, cur_k.astype(jnp.float32)
        )  # [b, hk, g, lq, lk]
        if causal:
            cmask = k_pos[None, :] <= q_pos[:, None]  # [lq, lk]
            scores = jnp.where(cmask[None, None, None], scores, _NEG_INF)
        if cur_pad is not None:
            pm = cur_pad.astype(bool)[:, None, None, None, :]
            scores = jnp.where(pm, scores, _NEG_INF)
        if segment_ids is not None:
            sm = segment_ids[:, :, None] == cur_seg[:, None, :]  # [b, lq, lk]
            scores = jnp.where(sm[:, None, None], scores, _NEG_INF)

        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, cur_v.astype(jnp.float32))
        m = m_new

        if t < axis_size - 1:
            cur_k = jax.lax.ppermute(cur_k, axis_name, perm)
            cur_v = jax.lax.ppermute(cur_v, axis_name, perm)
            if cur_pad is not None:
                cur_pad = jax.lax.ppermute(cur_pad, axis_name, perm)
            if cur_seg is not None:
                cur_seg = jax.lax.ppermute(cur_seg, axis_name, perm)

    # Fully-masked rows (pad queries) have l == 0; their output is dropped by
    # the loss mask, so any finite value works.
    out = o / jnp.maximum(l, 1e-30)[..., None]
    # [b, hk, g, lq, d] -> [b, lq, h, d]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, lq, num_heads, d)
    return out.astype(q.dtype)


def shard_map_seq_attention(local, mesh: Mesh, axis_name: str, q, k, v,
                            padding_mask=None, segment_ids=None):
    """Shared global-view plumbing for BOTH sequence-parallel strategies:
    shard q/k/v (+ optional per-row operands) over the mesh and shard_map the
    local kernel. ``local(q, k, v, padding_mask, segment_ids)`` runs on one
    device's chunks. One source of truth so the optional-operand binding
    cannot drift between ring and Ulysses entries. ``axis_name=None`` leaves
    the sequence whole: the flash kernel on a multi-device mesh
    (ops/attention.py), sharded over batch and heads only."""
    qkv_spec = P(("data", "fsdp"), axis_name, "tensor", None)
    row_spec = P(("data", "fsdp"), axis_name)

    has_pad = padding_mask is not None
    has_seg = segment_ids is not None

    def run(q_, k_, v_, *rest):
        rest = list(rest)
        p_ = rest.pop(0) if has_pad else None
        s_ = rest.pop(0) if has_seg else None
        return local(q_, k_, v_, p_, s_)

    fn = jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(qkv_spec,) * 3
        + ((row_spec,) if has_pad else ())
        + ((row_spec,) if has_seg else ()),
        out_specs=qkv_spec,
        check_vma=False,
    )
    args = (q, k, v) + ((padding_mask,) if has_pad else ()) + (
        (segment_ids,) if has_seg else ()
    )
    return fn(*args)


def seq_parallel_static_preconditions(
    seq_len: int, num_heads: int, num_kv: int, mesh: Optional[Mesh], *,
    axis_name: str = "seq", sliding_window: Optional[int] = None,
    causal: bool = True,
) -> bool:
    """The MODEL/CONFIG-decidable half of the seq-parallel preconditions:
    live seq axis, causal non-windowed attention, seq length and (kv) heads
    divisible by the mesh. Shared by the runtime predicates below AND the
    trainer's static remat resolution (train/step.static_seq_parallel_size) —
    one source of truth so a precondition added here can never make runtime
    fall back while the remat policy still divides per-chip seq (ADVICE r4)."""
    if mesh is None or axis_name not in mesh.shape or mesh.shape[axis_name] <= 1:
        return False
    if sliding_window is not None or not causal:
        return False  # cross-chunk window bookkeeping not implemented
    n_seq = mesh.shape[axis_name]
    tensor = mesh.shape.get("tensor", 1)
    return (
        seq_len % n_seq == 0
        and num_heads % tensor == 0
        and num_kv % tensor == 0
        and (num_heads // tensor) % max(num_kv // tensor, 1) == 0
    )


def seq_parallel_preconditions(q, k, mesh: Optional[Mesh], *, axis_name: str = "seq",
                               sliding_window: Optional[int] = None,
                               causal: bool = True) -> bool:
    """Checks shared by BOTH sequence-parallel strategies (ring here, Ulysses
    in parallel/ulysses.py): the static preconditions above plus the
    batch/shape facts only known at dispatch time. Keeping one source of
    truth stops the two ``*_supported`` predicates from drifting apart."""
    if q.shape[1] != k.shape[1]:
        return False  # decode/KV-cache path (q_len != kv_len): positions would lie
    if not seq_parallel_static_preconditions(
        q.shape[1], q.shape[2], k.shape[2], mesh,
        axis_name=axis_name, sliding_window=sliding_window, causal=causal,
    ):
        return False
    batch_ways = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
    return q.shape[0] % batch_ways == 0


def ring_attention_supported(q, k, mesh: Optional[Mesh], *, axis_name: str = "seq",
                             sliding_window: Optional[int] = None, causal: bool = True) -> bool:
    return seq_parallel_preconditions(
        q, k, mesh, axis_name=axis_name, sliding_window=sliding_window, causal=causal
    )


def ring_attention(q, k, v, *, mesh: Mesh, axis_name: str = "seq", padding_mask=None,
                   segment_ids=None, causal: bool = True):
    """Global-view entry: shard q/k/v over the mesh and run the ring.

    q: [batch, seq, heads, dim]; k, v: [batch, seq, kv_heads, dim];
    padding_mask: optional [batch, seq], 1 = real token;
    segment_ids: optional [batch, seq] packing segments (packed long-context
    runs keep their seq axis).
    Layout contract matches ops/attention.py; call sites go through
    ``ops.attention.attention(impl="ring", mesh=...)``.
    """
    local = partial(
        _local_ring_attention, axis_name=axis_name,
        axis_size=mesh.shape[axis_name], causal=causal,
    )
    return shard_map_seq_attention(
        local, mesh, axis_name, q, k, v,
        padding_mask=padding_mask, segment_ids=segment_ids,
    )
