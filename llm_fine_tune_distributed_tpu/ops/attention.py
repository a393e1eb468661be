"""Attention implementations and dispatch.

Replaces the reference's flash-attn-2 CUDA kernels
(reference ``requirements.txt:10``, ``training.py:101``) with TPU paths:

- ``"xla"``:   plain masked attention — XLA fuses this well at seq<=1024 and it
               is the numerically-transparent fallback.
- ``"flash"``: Pallas (Mosaic) blockwise flash attention kernel (ops/flash_attention.py).
- ``"ring"``:  ring attention over a sequence-parallel mesh axis (parallel/ring_attention.py),
               selected by the trainer when mesh.seq > 1.
- ``"ulysses"``: all-to-all sequence parallelism (parallel/ulysses.py) — heads
               re-partitioned over the seq axis so each device runs full-sequence
               flash attention on its head subset.

All implementations take/return the same layout:
  q: [batch, q_len, num_heads, head_dim]
  k,v: [batch, kv_len, num_kv_heads, head_dim]   (GQA: num_heads % num_kv_heads == 0)
and compute softmax in float32.
"""

from __future__ import annotations

import collections
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

_NEG_INF = -2.0e38  # large finite negative; avoids NaN from (-inf) - (-inf)

# Trace-time dispatch ledger: which implementation each attention() call
# actually resolved to (post-fallback). A sequence-parallel impl silently
# degrading to flash/XLA is the difference between a live seq axis and dead
# parallelism (a "ulysses parity test" that really
# exercised the fallback), so the resolution is recorded where it happens and
# parallel/diagnostics.assert_seq_parallel() lets tests/users pin the path.
_DISPATCH_COUNTS: collections.Counter = collections.Counter()


# Why a requested "flash" resolved to "xla" instead (reason -> count). The
# choice is made from what the call can observe (backend, shape, VMEM); it is
# never a rescue from a kernel that failed to compile — that raises.
_FLASH_FALLBACK_REASONS: collections.Counter = collections.Counter()

# Which of the flash kernel's programs each traced call took, by the kind of
# layer that called it (flash_attention.program_label: "resident causal",
# "streamed causal", "streamed window 1024").
_FLASH_PROGRAMS: collections.Counter = collections.Counter()

# The dtypes q, k, v had where the flash kernel was traced (what its matmuls
# are handed is the kernel's MXU_OPERAND_DTYPE, printed beside them).
_FLASH_INPUT_DTYPES: set = set()


def dispatch_count(impl: str) -> int:
    """How many attention() calls resolved to ``impl`` (trace-time count)."""
    return _DISPATCH_COUNTS[impl]


def dispatch_summary() -> str:
    """One line for entry points to print: the paths traced so far and, for
    each flash request that took XLA attention, why."""
    from llm_fine_tune_distributed_tpu.ops.flash_attention import MXU_OPERAND_DTYPE

    programs = ", ".join(f"{label} x{n}" for label, n in sorted(_FLASH_PROGRAMS.items()))
    dtypes = {
        "flash": f" ({'/'.join(sorted(_FLASH_INPUT_DTYPES))} inputs, "
        f"{jnp.dtype(MXU_OPERAND_DTYPE).name} MXU operands; {programs})"
    }
    paths = ", ".join(
        f"{k}={v}{dtypes.get(k, '')}" for k, v in sorted(_DISPATCH_COUNTS.items())
    )
    why = "; ".join(f"{r} (x{n})" for r, n in _FLASH_FALLBACK_REASONS.items())
    return f"attention paths traced: {paths or 'none'}" + (
        f" | flash -> xla because: {why}" if why else ""
    )


def _causal_mask(q_len: int, kv_len: int, sliding_window: Optional[int] = None):
    """[q_len, kv_len] bool mask; True = attend. Supports decode offset where
    q positions are the last q_len of kv_len."""
    q_pos = jnp.arange(q_len)[:, None] + (kv_len - q_len)
    k_pos = jnp.arange(kv_len)[None, :]
    mask = k_pos <= q_pos
    if sliding_window is not None:
        mask &= k_pos > q_pos - sliding_window
    return mask


def softcap(x, cap):
    """Gemma2 logit soft-capping: cap * tanh(x / cap).

    Single definition shared by attention scores, unembed, and the
    vocab-streamed CE — the streamed loss must stay bit-identical to the
    materialized-logits path, so the formula must not fork."""
    return cap * jnp.tanh(x / cap)


def xla_attention(
    q,
    k,
    v,
    *,
    padding_mask=None,
    segment_ids=None,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    mask=None,
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
):
    """Reference masked attention with GQA, f32 softmax.

    padding_mask: optional [batch, kv_len] bool/int, 1 = real token.
    segment_ids: optional [batch, kv_len] int32 packing segments — attention
      is restricted to equal ids (block-diagonal; 0 = pad tail).
    mask: optional explicit [batch, q_len, kv_len] bool mask (True = attend);
      when given it replaces the causal mask (used by the KV-cache decode path).
    """
    b, q_len, num_heads, head_dim = q.shape
    kv_len, num_kv = k.shape[1], k.shape[2]
    groups = num_heads // num_kv
    v_dim = v.shape[3]  # latent attention: v heads narrower than q/k heads

    if scale is None:
        scale = 1.0 / jnp.sqrt(head_dim).astype(jnp.float32)
    # [b, q, kv_heads, groups, d]
    qg = q.reshape(b, q_len, num_kv, groups, head_dim)
    # scores: [b, kv_heads, groups, q, kv]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale
    if logit_softcap is not None:
        # Gemma2: cap BEFORE masking (HF Gemma2Attention eager path)
        scores = softcap(scores, logit_softcap)

    if mask is not None:
        scores = jnp.where(mask[:, None, None], scores, _NEG_INF)
    elif causal:
        cmask = _causal_mask(q_len, kv_len, sliding_window)
        scores = jnp.where(cmask[None, None, None], scores, _NEG_INF)
    if padding_mask is not None:
        pm = padding_mask.astype(bool)[:, None, None, None, :]
        scores = jnp.where(pm, scores, _NEG_INF)
    if segment_ids is not None:
        # note: a fully-masked row is safe — _NEG_INF is finite, so softmax
        # degrades to uniform garbage on pad rows, which the loss mask drops
        same = segment_ids[:, None, :] == segment_ids[:, :, None]  # [b, q, kv]
        scores = jnp.where(same[:, None, None], scores, _NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, q_len, num_heads, v_dim).astype(q.dtype)


def _warn_seq_axis_unused(impl: str, q, mesh) -> None:
    """A sequence-parallel impl that cannot apply falls back to "flash"
    (linear memory), which itself degrades to XLA attention only when truly
    unsupported.

    A missing/size-1 seq axis is the ordinary single-device case — fall back
    quietly. A PROVISIONED seq axis with an unsupported shape (e.g. ulysses
    capped by kv heads, or an indivisible seq length) means the user's
    parallelism is silently dead — be loud, because at long-context shapes
    the difference between the flash kernel and quadratic XLA attention is
    an OOM."""
    if mesh is not None and mesh.shape.get("seq", 1) > 1:
        import warnings

        warnings.warn(
            f"attention_impl={impl!r} requested but unsupported for shape "
            f"q={tuple(q.shape)} on mesh {dict(mesh.shape)} — the seq axis is "
            "NOT being used; falling back to flash/XLA attention (check head/"
            "kv-head divisibility by the seq axis and seq-length alignment)",
            stacklevel=3,
        )


class _Route(NamedTuple):
    """The program a call of ``attention()`` runs, read from its arguments
    alone by ``_route``: nothing is counted, warned or traced there, so the
    question can be asked before q, k and v exist (``head_major_reason``)."""

    path: str  # "xla" | "ulysses" | "ring" | "ulysses_manual" | "ring_manual" | "flash"
    why_not_flash: Optional[str] = None  # path "xla" for a call that asked for more
    unused_seq_impl: Optional[str] = None  # "ring" | "ulysses" asked for and not applicable
    shards: Tuple[int, int] = (1, 1)  # path "flash": batch and head shards, each a kernel call under a shard_map


def _route(q, k, v, *, impl, mesh, scale, logit_softcap, sliding_window, causal) -> _Route:
    """``q``, ``k``, ``v``: arrays or shapes ``[b, s, h, d]``. THE chain of
    questions: ``attention()`` runs what it answers and nothing else decides."""
    unused = None
    if scale is not None or logit_softcap is not None:
        if impl in ("ring_manual", "ulysses_manual"):
            # inside a shard_map manual over seq, a block-local xla fallback
            # would silently drop cross-shard attention — refuse instead
            raise ValueError(
                f"{impl} does not support custom scale / logit softcap"
            )
        # loud when a provisioned seq axis goes unused (same contract as
        # the shape-based fallback)
        return _Route(
            "xla", "a custom scale or logit softcap takes XLA attention", impl if impl in ("ring", "ulysses") else None
        )
    if impl == "ulysses":
        from llm_fine_tune_distributed_tpu.parallel.ulysses import ulysses_attention_supported

        if ulysses_attention_supported(q, k, mesh, sliding_window=sliding_window, causal=causal):
            return _Route("ulysses")
        impl, unused = "flash", "ulysses"
    if impl == "ring":
        from llm_fine_tune_distributed_tpu.parallel.ring_attention import ring_attention_supported

        if ring_attention_supported(q, k, mesh, sliding_window=sliding_window, causal=causal):
            return _Route("ring")
        impl, unused = "flash", "ring"
    if impl in ("ulysses_manual", "ring_manual"):
        if sliding_window is not None:
            raise ValueError(f"{impl.split('_')[0]} attention has no sliding-window support")
        return _Route(impl)
    if impl == "flash":
        # The Pallas kernel runs compiled or the run fails: it is skipped
        # only for a reason visible here, at trace time, and the reason is
        # kept for dispatch_summary().
        from llm_fine_tune_distributed_tpu.ops.flash_attention import flash_unsupported_reason

        # Mosaic kernels cannot be partitioned by GSPMD: on a mesh of more
        # than one device the kernel runs per shard under a shard_map over
        # the batch (data, fsdp) and head (tensor) axes, and eligibility is
        # judged on the per-shard shape.
        sharded = mesh is not None and mesh.size > 1
        shards = (mesh.shape["data"] * mesh.shape["fsdp"], mesh.shape["tensor"]) if sharded else (1, 1)
        if q.shape[0] % shards[0] or k.shape[2] % shards[1]:
            reason = (
                f"batch {q.shape[0]} x kv heads {k.shape[2]} do not divide "
                f"over mesh {dict(mesh.shape)}"
            )
        else:
            reason = flash_unsupported_reason(
                *(_shard_shape(x, shards) for x in (q, k, v)), sliding_window=sliding_window, causal=causal
            )
        return _Route("flash", None, unused, shards) if reason is None else _Route("xla", reason, unused)
    if impl == "xla":
        return _Route("xla")
    raise ValueError(f"unknown attention impl {impl!r}")


def _shard_shape(x, shards):
    return jax.ShapeDtypeStruct((x.shape[0] // shards[0], x.shape[1], x.shape[2] // shards[1], x.shape[3]), x.dtype)


def _rows_first(x):
    """The ``[b, s, h, d]`` shape of a head-major ``[b, h, s, d]`` operand."""
    return jax.ShapeDtypeStruct((x.shape[0], x.shape[2], x.shape[1], x.shape[3]), x.dtype)


def head_major_reason(q, k, v, *, impl, mesh=None, scale=None, logit_softcap=None, sliding_window=None) -> Optional[str]:
    """Why a causal call of ``attention()`` with these arguments (``q``, ``k``,
    ``v``: shapes ``[b, s, h, d]``) would NOT run the flash kernels on the
    whole row in one device's program; None: it would, and the same call
    may hand its operands head-major (``attention(..., head_major=True)``).
    ``_route``'s answer, worded."""
    route = _route(
        q, k, v, impl=impl, mesh=mesh, scale=scale, logit_softcap=logit_softcap, sliding_window=sliding_window, causal=True
    )
    if route.path != "flash":
        return route.why_not_flash or f"attention_impl is {impl!r}"
    if route.shards != (1, 1):
        return f"a mesh of {mesh.size} devices: the kernel runs per shard, inside a shard_map over [b, s, h, d]"
    return None


def attention(
    q,
    k,
    v,
    *,
    impl: str = "xla",
    padding_mask=None,
    segment_ids=None,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    mesh=None,
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
    head_major: bool = False,
):
    """Dispatch to the selected attention implementation (``_route`` decides
    which, from the arguments alone).

    ``mesh`` is consulted by the sequence-parallel paths (ring and ulysses);
    the trainer passes the active mesh whenever ``attention_impl`` is one of
    those. Without a mesh (or with an unsupported shape) they fall back to
    the flash kernel, which itself degrades to XLA attention when it cannot
    apply.

    ``scale`` / ``logit_softcap`` (Gemma2 query_pre_attn_scalar and
    attn_logit_softcapping): only the XLA path implements them, so a
    non-default value routes there directly — the tanh softcap breaks the
    flash kernel's running-max algebra, and correctness beats kernel speed
    for the families that need it.

    ``head_major``: q ``[b, hq, s, d]``, k ``[b, hkv, s, d]`` and v ``[b, hkv,
    s, d_v]`` arrive as the flash kernels read them (``ops/rope.heads_in``
    writes them so); the output is ``[b, s, hq, d_v]`` either way. Only for a
    call ``head_major_reason`` has no objection to: any other raises.
    """
    asked = dict(impl=impl, mesh=mesh, scale=scale, logit_softcap=logit_softcap, sliding_window=sliding_window, causal=causal)
    route = _route(*((_rows_first(x) for x in (q, k, v)) if head_major else (q, k, v)), **asked)
    if head_major and (route.path, route.shards) != ("flash", (1, 1)):
        raise ValueError(f"head-major operands are for the flash kernels in one device's program; this call takes {route}")
    if route.unused_seq_impl is not None:
        _warn_seq_axis_unused(route.unused_seq_impl, q, mesh)
    if scale is not None or logit_softcap is not None:
        return xla_attention(
            q, k, v, padding_mask=padding_mask, segment_ids=segment_ids,
            causal=causal, sliding_window=sliding_window,
            scale=scale, logit_softcap=logit_softcap,
        )
    if route.path in ("ulysses_manual", "ring_manual") and segment_ids is not None:
        # the pipeline schedule (the only manual-context caller) rejects
        # packing up front; reaching here would silently drop the mask
        raise ValueError(f"{route.path} has no segment support")
    _DISPATCH_COUNTS[route.path] += 1
    if route.path == "ulysses":
        from llm_fine_tune_distributed_tpu.parallel.ulysses import ulysses_attention

        return ulysses_attention(
            q, k, v, mesh=mesh, padding_mask=padding_mask,
            segment_ids=segment_ids, causal=causal
        )
    if route.path == "ring":
        from llm_fine_tune_distributed_tpu.parallel.ring_attention import ring_attention

        return ring_attention(
            q, k, v, mesh=mesh, padding_mask=padding_mask,
            segment_ids=segment_ids, causal=causal
        )
    if route.path in ("ulysses_manual", "ring_manual"):
        # The caller is ALREADY inside a shard_map that is manual over the
        # "seq" axis (the pipeline schedule, pipe x ring composition):
        # q/k/v here are one device's sequence CHUNKS, so dispatch straight
        # to the local kernel (its all_to_all/all_gather ride that axis) —
        # wrapping the global-view entry would illegally nest a manual "seq"
        # shard_map.
        if route.path == "ulysses_manual":
            from llm_fine_tune_distributed_tpu.parallel.ulysses import _local_ulysses_attention

            return _local_ulysses_attention(
                q, k, v, padding_mask,
                axis_name="seq", causal=causal, attention_impl="flash",
            )
        from llm_fine_tune_distributed_tpu.parallel.ring_attention import _local_ring_attention

        return _local_ring_attention(
            q, k, v, padding_mask,
            axis_name="seq", axis_size=mesh.shape["seq"], causal=causal,
        )
    if route.path == "flash":
        from llm_fine_tune_distributed_tpu.ops.flash_attention import pallas_flash_attention, program_label

        seen = [_shard_shape(_rows_first(x) if head_major else x, route.shards) for x in (q, k, v)]
        _FLASH_INPUT_DTYPES.add(jnp.dtype(q.dtype).name)
        _FLASH_PROGRAMS[program_label(*seen, sliding_window=sliding_window)] += 1
        if route.shards == (1, 1):
            return pallas_flash_attention(
                q, k, v, padding_mask=padding_mask, segment_ids=segment_ids, sliding_window=sliding_window,
                head_major=head_major,
            )
        from llm_fine_tune_distributed_tpu.parallel.ring_attention import (
            shard_map_seq_attention,
        )

        return shard_map_seq_attention(
            lambda q_, k_, v_, p_, s_: pallas_flash_attention(
                q_, k_, v_, padding_mask=p_, segment_ids=s_, sliding_window=sliding_window
            ),
            mesh, None, q, k, v,
            padding_mask=padding_mask, segment_ids=segment_ids,
        )
    if route.why_not_flash is not None:
        _FLASH_FALLBACK_REASONS[route.why_not_flash] += 1
    return xla_attention(
        q, k, v, padding_mask=padding_mask, segment_ids=segment_ids,
        causal=causal, sliding_window=sliding_window,
    )
