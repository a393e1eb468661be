"""NF4 (NormalFloat4) blockwise quantization — the QLoRA storage format
(Dettmers et al. 2023), built TPU-first.

BASELINE.json config #5 names "Llama-3-70B QLoRA multi-host SFT (nf4 quant +
Pallas matmul)". The reference repo itself has no quantization code (SURVEY.md
§2.1 "not present" list; QLoRA appears only in its external-doc article), so
this subsystem is first-party.

Storage layout (chosen for the TPU memory system, not a CUDA translation):
- A weight ``W [in, out]`` is quantized along the **contraction (in) axis** in
  blocks of ``block_size`` rows per column: ``absmax [in/block, out]``.
  Per-column blocks keep the scale grid aligned with how a matmul tile
  consumes rows, so a fused kernel rescales with a plain broadcast.
- 4-bit codes are packed 8-per-int32 into ``packed [in/8, out]``; nibble ``s``
  of word ``r`` holds logical row ``8 r + s``. int32 is the native TPU
  vector-memory word — int4/uint8 tiles have harsh (32, 128) sublane minimums
  and poor op coverage on the VPU, while int32 shift/mask decode vectorizes
  cleanly.
- Optional **double quantization** compresses the f32 absmax tensor to int8
  with one f32 scale per group of 256 scales plus a global mean offset
  (the QLoRA paper's second-level scheme), cutting scale overhead from
  0.5 bit/param to ~0.13 bit/param at block 64.

Effective bits/param at block 64: 4 + 32/64 = 4.5 (single quant) or
4 + 8/64 + ~32/(64*256) = ~4.13 (double quant).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# The 16 NF4 code points: quantiles of N(0,1) normalized to [-1, 1]
# (exact constants from the QLoRA reference implementation).
NF4_CODEBOOK = np.array(
    [
        -1.0,
        -0.6961928009986877,
        -0.5250730514526367,
        -0.39491748809814453,
        -0.28444138169288635,
        -0.18477343022823334,
        -0.09105003625154495,
        0.0,
        0.07958029955625534,
        0.16093020141124725,
        0.24611230194568634,
        0.33791524171829224,
        0.44070982933044434,
        0.5626170039176941,
        0.7229568362236023,
        1.0,
    ],
    dtype=np.float32,
)

DEFAULT_BLOCK_SIZE = 64
ABSMAX_GROUP = 256  # double-quant group size (QLoRA paper)


def _nearest_code(x: np.ndarray) -> np.ndarray:
    """Index of the nearest NF4 code point for each normalized value."""
    # midpoints between consecutive code points -> searchsorted buckets
    mids = (NF4_CODEBOOK[1:] + NF4_CODEBOOK[:-1]) / 2.0
    return np.searchsorted(mids, x).astype(np.int32)


def quantize_nf4(
    w,
    block_size: int = DEFAULT_BLOCK_SIZE,
    double_quant: bool = True,
) -> Dict[str, Any]:  # values: np.ndarray, or jax.Array ("nf4" on the device path)
    """Quantize ``w [in, out]`` to NF4 (one-shot at load/startup).

    Large leaves on an accelerator backend quantize on-device and return the
    packed codes as device arrays; small leaves / CPU take a numpy path.

    Returns a flat dict of arrays (ready to live as sibling param-tree leaves):
      ``nf4``            int32 [in/8, out]   — packed 4-bit codes
      ``absmax``         f32   [in/block, out]        (single quant), or
      ``absmax_q``       int8  [in/block, out]        (double quant)
      ``absmax_scale``   f32   [n_groups]
      ``absmax_offset``  f32   []
    """
    if getattr(w, "ndim", None) != 2:
        raise ValueError(f"quantize_nf4 expects a 2-D weight, got {np.shape(w)}")
    k, n = w.shape
    if k % 8:
        raise ValueError(f"in-dim {k} not divisible by the int32 pack factor 8")
    if k % block_size:
        raise ValueError(f"in-dim {k} not divisible by block_size {block_size}")

    if w.size >= 1 << 22 and jax.default_backend() != "cpu":
        # Device-accelerated quantization: the numpy path takes ~10+ minutes
        # for a 3B model's block linears; one jitted pass per leaf on the
        # accelerator does the same in milliseconds. The packed codes STAY on
        # device (they are about to live there as frozen params anyway); only
        # the small absmax comes to host for the double-quant step.
        packed, absmax = _quantize_codes_jax(jnp.asarray(w, jnp.float32), block_size)
        absmax = np.asarray(absmax)
    else:
        w = np.asarray(w, dtype=np.float32)
        # per-(block, column) absmax
        blocks = w.reshape(k // block_size, block_size, n)
        absmax = np.abs(blocks).max(axis=1)  # [k/block, n]
        safe = np.where(absmax == 0.0, 1.0, absmax)
        normalized = blocks / safe[:, None, :]
        codes = _nearest_code(normalized.reshape(k, n))

        # pack 8 consecutive rows per int32 word (nibble s = row 8r+s)
        codes = codes.reshape(k // 8, 8, n).astype(np.uint32)
        packed = np.zeros((k // 8, n), dtype=np.uint32)
        for s in range(8):
            packed |= codes[:, s, :] << np.uint32(4 * s)
        packed = packed.astype(np.int32)
    out = {"nf4": packed}  # np (small path) or on-device jnp (jax path)

    if not double_quant:
        out["absmax"] = absmax.astype(np.float32)
        return out

    flat = absmax.reshape(-1)
    offset = np.float32(flat.mean())
    centered = flat - offset
    pad = (-centered.size) % ABSMAX_GROUP
    grouped = np.pad(centered, (0, pad)).reshape(-1, ABSMAX_GROUP)
    gmax = np.abs(grouped).max(axis=1)
    gscale = np.where(gmax == 0.0, 1.0, gmax) / 127.0
    q = np.clip(np.round(grouped / gscale[:, None]), -127, 127).astype(np.int8)
    out["absmax_q"] = q.reshape(-1)[: centered.size].reshape(absmax.shape)
    out["absmax_scale"] = gscale.astype(np.float32)
    out["absmax_offset"] = np.asarray(offset, np.float32)
    return out


@functools.partial(jax.jit, static_argnames=("block_size",))
def _quantize_codes_jax(w, block_size: int):
    """Device-side NF4 quantize: returns (packed int32 [k/8, n], absmax f32).

    Bit-identical to the numpy path: same absmax grid, same midpoint
    bucketing (searchsorted over the 15 code midpoints), same nibble layout.
    """
    k, n = w.shape
    blocks = w.reshape(k // block_size, block_size, n)
    absmax = jnp.abs(blocks).max(axis=1)
    safe = jnp.where(absmax == 0.0, 1.0, absmax)
    normalized = (blocks / safe[:, None, :]).reshape(k, n)
    mids = jnp.asarray((NF4_CODEBOOK[1:] + NF4_CODEBOOK[:-1]) / 2.0)
    codes = jnp.searchsorted(mids, normalized.reshape(-1)).reshape(k, n)
    codes = codes.reshape(k // 8, 8, n).astype(jnp.uint32)
    packed = jnp.zeros((k // 8, n), jnp.uint32)
    for s in range(8):
        packed = packed | (codes[:, s, :] << jnp.uint32(4 * s))
    return packed.astype(jnp.int32), absmax


def _dequant_absmax(q: Dict, dtype=jnp.float32):
    """Recover the f32 absmax [in/block, out] from either storage form."""
    if "absmax" in q:
        return q["absmax"].astype(dtype)
    shape = q["absmax_q"].shape
    flat = q["absmax_q"].astype(dtype).reshape(-1)
    pad = (-flat.size) % ABSMAX_GROUP
    grouped = jnp.pad(flat, (0, pad)).reshape(-1, ABSMAX_GROUP)
    deq = grouped * q["absmax_scale"][:, None].astype(dtype)
    return (deq.reshape(-1)[: flat.size] + q["absmax_offset"].astype(dtype)).reshape(shape)


def unpack_codes(packed):
    """int32 [k/8, n] -> int32 codes [k, n] (nibble s of word r = row 8r+s)."""
    k8, n = packed.shape
    u = packed.astype(jnp.uint32)
    nibbles = [(u >> jnp.uint32(4 * s)) & jnp.uint32(0xF) for s in range(8)]
    return jnp.stack(nibbles, axis=1).reshape(k8 * 8, n).astype(jnp.int32)


def dequantize_nf4(q: Dict, dtype=jnp.bfloat16):
    """Reconstruct the bf16/f32 weight [in, out] (pure XLA).

    Under ``jax.checkpoint``-wrapped blocks only one layer's dequantized
    weight is live at a time, so peak HBM stays ~4.5 bits/param for the
    frozen base — the QLoRA memory profile without a custom allocator.
    """
    packed = q["nf4"]
    k = packed.shape[0] * 8
    codes = unpack_codes(packed)
    codebook = jnp.asarray(NF4_CODEBOOK, dtype=jnp.float32)
    w = codebook[codes]  # [k, n] f32
    absmax = _dequant_absmax(q, jnp.float32)
    block = k // absmax.shape[0]
    w = w.reshape(absmax.shape[0], block, -1) * absmax[:, None, :]
    return w.reshape(k, -1).astype(dtype)


def nf4_matmul(x, q: Dict, impl: str = "auto", compute_dtype=jnp.bfloat16):
    """``x [. , in] @ dequant(q) [in, out]``.

    impl: "xla" (dequantize then jnp.dot; XLA fuses decode into the operand
    read where it can) or "auto" (resolves to "xla").

    A fused Pallas decode kernel was built and RETIRED after head-to-head
    measurement on a v5e chip (round-2 shootout, over a device link that
    is gone; its record was deleted in PR 21): at the 3B train-microbatch shape (M=2048, K=2048,
    N=11008) fused-pallas ran 7.8ms vs 6.7ms XLA vs 5.6ms bf16, and at
    batch-1 decode both NF4 paths sat ~6.5ms vs 20us bf16. The bottleneck
    is not HBM (a fused kernel's win) but the exact nibble decode itself:
    any exact NF4 expansion — select chain, binary select tree, one-hot
    compare + MXU dot, Lagrange polynomial — costs ~16 VPU ops per weight,
    and the VPU is ~100x slower than the MXU on this chip. NF4's value here
    is MEMORY (4.5 bits/param at rest, one layer decoded at a time under
    remat/liveness), not speed; for decode SPEED use int8 weight-only
    (ops/int8.py: 1 multiply per weight, measured 1.5x bf16).
    """
    if impl == "auto":
        impl = "xla"
    if impl != "xla":
        raise ValueError(
            f"unknown nf4 matmul impl {impl!r} (the fused Pallas kernel was "
            "retired after losing to the XLA path on v5e — see nf4_matmul "
            "docstring; use impl='xla' or int8 weight-only for speed)"
        )
    w = dequantize_nf4(q, dtype=compute_dtype)
    return x.astype(compute_dtype) @ w


# Canonical sibling-leaf naming scheme for a quantized ``kernel``. Every
# consumer (models/transformer._linear, parallel/qlora) derives its key lists
# from these two tuples — do not re-encode the scheme elsewhere.
QUANT_SUFFIXES = ("nf4", "absmax", "absmax_q", "absmax_scale", "absmax_offset")
# longest-first so suffix matching is unambiguous ("_absmax_q" before "_absmax")
DEQUANT_MARKERS = ("_absmax_offset", "_absmax_scale", "_absmax_q", "_absmax", "_nf4")


def quantized_keys(prefix: str) -> tuple:
    """The sibling leaf names a quantized ``{prefix}`` may occupy."""
    return tuple(f"{prefix}_{s}" for s in QUANT_SUFFIXES)


def quantized_layout(shape, block_size: int = DEFAULT_BLOCK_SIZE, double_quant: bool = True):
    """suffix -> (shape, dtype) for quantize_nf4's output arrays.

    The single source of truth for the storage layout — used by shape-level
    planners (parallel/qlora.quantize_frozen_abstract) so the abstract and
    real quantizers cannot drift. Rejects exactly the shapes quantize_nf4
    rejects, so a planner cannot produce a layout the quantizer won't.
    """
    k, n = shape
    if k % 8:
        raise ValueError(f"in-dim {k} not divisible by the int32 pack factor 8")
    if k % block_size:
        raise ValueError(f"in-dim {k} not divisible by block_size {block_size}")
    out = {"nf4": ((k // 8, n), jnp.int32)}
    if double_quant:
        n_scales = (k // block_size) * n
        out["absmax_q"] = ((k // block_size, n), jnp.int8)
        out["absmax_scale"] = ((math.ceil(n_scales / ABSMAX_GROUP),), jnp.float32)
        out["absmax_offset"] = ((), jnp.float32)
    else:
        out["absmax"] = ((k // block_size, n), jnp.float32)
    return out


# ---------------------------------------------------------------------------
# stacked (MoE expert) weights [E, in, out]
# ---------------------------------------------------------------------------


def _validate_stacked_in_dim(k: int, block_size: int) -> None:
    """Shared by quantize_nf4_stacked and quantized_layout_stacked so the
    abstract layout rejects exactly the shapes the real quantizer rejects."""
    if k % 8:
        raise ValueError(f"per-expert in-dim {k} not divisible by the pack factor 8")
    if k % block_size:
        raise ValueError(f"per-expert in-dim {k} not divisible by block_size {block_size}")


def quantize_nf4_stacked(w, block_size: int = DEFAULT_BLOCK_SIZE, double_quant: bool = True):
    """NF4-quantize a stacked expert weight ``[E, in, out]`` (ops/moe.py
    layout). Internally reshapes to ``[E*in, out]`` — with ``in`` a multiple
    of ``block_size`` no absmax block crosses an expert boundary, so each
    expert quantizes exactly as it would standalone. The packed codes and
    absmax keep the leading expert dim (``nf4 [E, in/8, out]``) so the
    expert-parallel sharding rules apply unchanged.
    """
    e, k, n = w.shape
    _validate_stacked_in_dim(k, block_size)
    q = quantize_nf4(w.reshape(e * k, n), block_size, double_quant)
    q["nf4"] = jnp.asarray(q["nf4"]).reshape(e, k // 8, n)
    for key in ("absmax", "absmax_q"):
        if key in q:
            q[key] = jnp.asarray(q[key]).reshape(e, k // block_size, n)
    return q


def dequantize_nf4_stacked(q: Dict, dtype=jnp.bfloat16):
    """Inverse of ``quantize_nf4_stacked``: NF4 leaves -> ``[E, in, out]``."""
    e, k8, n = q["nf4"].shape
    flat = {"nf4": q["nf4"].reshape(e * k8, n)}
    for key in ("absmax", "absmax_q"):
        if key in q:
            arr = q[key]
            flat[key] = arr.reshape(e * arr.shape[1], n)
    for key in ("absmax_scale", "absmax_offset"):
        if key in q:
            flat[key] = q[key]
    return dequantize_nf4(flat, dtype=dtype).reshape(e, k8 * 8, n)


def _quantize_per_layer(w, quantize_fn, block_size, double_quant):
    """Quantize leading-dim slices independently and stack every produced
    leaf under that layer dim. Shared by the two layered quantizers so their
    layer-slicing semantics cannot diverge."""
    outs = [quantize_fn(w[i], block_size, double_quant) for i in range(w.shape[0])]
    return {
        k: jnp.stack([jnp.asarray(o[k]) for o in outs]) for k in outs[0]
    }


def _dequantize_per_layer(q: Dict, dequantize_fn, dtype):
    """Inverse of ``_quantize_per_layer`` for either per-layer layout."""
    L = q["nf4"].shape[0]
    return jnp.stack([
        dequantize_fn({k: v[i] for k, v in q.items()}, dtype=dtype)
        for i in range(L)
    ])


def quantize_nf4_layered(w, block_size: int = DEFAULT_BLOCK_SIZE, double_quant: bool = True):
    """NF4-quantize a pipe-stacked kernel ``[L, in, out]`` LAYER BY LAYER.

    Unlike ``quantize_nf4_stacked`` (which flattens to ``[E*in, out]`` and
    keeps one global double-quant scale vector), every produced leaf here
    carries the leading layer dim — ``absmax_scale [L, G]``,
    ``absmax_offset [L]`` — because the pipeline schedule's ``lax.scan``
    slices the whole leaf tree per layer (parallel/pipeline.py:run_stage)
    and each slice must be a complete standalone ``quantize_nf4`` layout.
    Double-quant groups therefore never cross layer boundaries.
    """
    _validate_stacked_in_dim(w.shape[1], block_size)
    return _quantize_per_layer(w, quantize_nf4, block_size, double_quant)


def dequantize_nf4_layered(q: Dict, dtype=jnp.bfloat16):
    """Inverse of ``quantize_nf4_layered``: per-layer leaves -> [L, in, out]."""
    return _dequantize_per_layer(q, dequantize_nf4, dtype)


def quantized_layout_layered(shape, block_size: int = DEFAULT_BLOCK_SIZE, double_quant: bool = True):
    """``quantized_layout`` for a pipe-stacked ``[L, in, out]`` kernel: every
    leaf gains the leading layer dim (see quantize_nf4_layered)."""
    l, k, n = shape
    per_layer = quantized_layout((k, n), block_size, double_quant)
    return {key: ((l, *s), dt) for key, (s, dt) in per_layer.items()}


def quantize_nf4_layered_stacked(w, block_size: int = DEFAULT_BLOCK_SIZE, double_quant: bool = True):
    """NF4-quantize a pipe-stacked MoE expert weight ``[L, E, in, out]``
    LAYER BY LAYER (qlora x pipe x MoE — the dominant bytes of an MoE model).

    Each layer quantizes via ``quantize_nf4_stacked`` and the leaves stack a
    leading layer dim — ``nf4 [L, E, in/8, out]``, ``absmax_q
    [L, E, in/block, out]``, ``absmax_scale [L, G]``, ``absmax_offset [L]`` —
    so the pipeline schedule's per-layer ``lax.scan`` slice is a complete
    standalone ``quantize_nf4_stacked`` layout that ops/moe.py's
    ``dequantize_nf4_stacked`` consumes unchanged. Double-quant groups never
    cross layer boundaries (same invariant as ``quantize_nf4_layered``)."""
    _validate_stacked_in_dim(w.shape[2], block_size)
    return _quantize_per_layer(w, quantize_nf4_stacked, block_size, double_quant)


def dequantize_nf4_layered_stacked(q: Dict, dtype=jnp.bfloat16):
    """Inverse of ``quantize_nf4_layered_stacked``: leaves -> [L, E, in, out]."""
    return _dequantize_per_layer(q, dequantize_nf4_stacked, dtype)


def quantized_layout_layered_stacked(shape, block_size: int = DEFAULT_BLOCK_SIZE, double_quant: bool = True):
    """``quantized_layout`` for a pipe-stacked ``[L, E, in, out]`` expert
    weight: every per-layer stacked leaf gains the leading layer dim."""
    l, e, k, n = shape
    per_layer = quantized_layout_stacked((e, k, n), block_size, double_quant)
    return {key: ((l, *s), dt) for key, (s, dt) in per_layer.items()}


def quantized_layout_stacked(shape, block_size: int = DEFAULT_BLOCK_SIZE, double_quant: bool = True):
    """``quantized_layout`` for a stacked ``[E, in, out]`` expert weight.

    Rejects exactly the shapes ``quantize_nf4_stacked`` rejects (the
    PER-EXPERT in-dim must divide the pack factor and block size — the
    flattened e*in passing those checks is not sufficient)."""
    e, k, n = shape
    _validate_stacked_in_dim(k, block_size)
    flat = quantized_layout((e * k, n), block_size, double_quant)
    out = {"nf4": ((e, k // 8, n), jnp.int32)}
    for key in ("absmax", "absmax_q"):
        if key in flat:
            (shape2, dtype) = flat[key]
            out[key] = ((e, k // block_size, n), dtype)
    for key in ("absmax_scale", "absmax_offset"):
        if key in flat:
            out[key] = flat[key]
    return out


def quantize_params_nf4(params, predicate=None, block_size: int = DEFAULT_BLOCK_SIZE):
    """Replace every matching transformer-block linear with its NF4 sibling
    leaves — the NF4 counterpart of ``ops/int8.quantize_params_int8``
    (``--quantize-weights nf4`` on the inference entry points).

    Same predicate and same exclusions as the int8 path: embeddings, the
    lm_head and the MoE router gate stay full precision. A leaf whose in-dim
    does not divide ``block_size`` (small presets) falls back to the largest
    valid block — the pack-factor minimum of 8 — instead of failing; an
    in-dim not divisible by 8 has no NF4 form at all and raises, exactly as
    the int8 quantizer is loud about predicate hits it cannot serve.
    """
    from llm_fine_tune_distributed_tpu.ops.int8 import quantize_params_int8  # noqa: F401 (predicate parity documented there)
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict, unflatten_dict

    def is_stacked_expert(path: str) -> bool:
        return path.endswith(("/experts/w1", "/experts/w2", "/experts/w3"))

    if predicate is None:
        predicate = lambda path: "/layers/" in path and (
            (path.endswith("/kernel") and not path.endswith("block_sparse_moe/gate/kernel"))
            or is_stacked_expert(path)
        )

    flat = flatten_dict(params)
    out = {}
    for path, leaf in flat.items():
        if not predicate(path):
            out[path] = leaf
            continue
        if getattr(leaf, "ndim", 0) == 2 and path.endswith("/kernel"):
            k, quantize_fn = leaf.shape[0], quantize_nf4
        elif getattr(leaf, "ndim", 0) == 3 and is_stacked_expert(path):
            k, quantize_fn = leaf.shape[1], quantize_nf4_stacked
        else:
            raise ValueError(
                f"predicate matched {path!r} (ndim="
                f"{getattr(leaf, 'ndim', None)}) but only 2-D .../kernel "
                "leaves and stacked 3-D expert weights have an NF4 form"
            )
        bs = block_size if k % block_size == 0 else 8
        q = quantize_fn(leaf, block_size=bs)
        for suffix in QUANT_SUFFIXES:
            if suffix in q:
                out[f"{path}_{suffix}"] = jnp.asarray(q[suffix])
    return unflatten_dict(out)
