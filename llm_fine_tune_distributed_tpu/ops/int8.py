"""Int8 weight-only quantization for inference.

Batch-1 autoregressive decode is weight-bandwidth-bound: every generated
token reads every matmul weight once, so tokens/sec is HBM GB/s divided by
the weight-stream size. NF4 (ops/nf4.py) halves that stream twice over but
its nibble unpack is VPU-bound on v5e (measured 20 tok/s vs 73 bf16 for the
3B flagship, benchmarks/decode_bench.py). Int8 sits in the sweet spot:

- the weight stream halves (int8 at rest vs bf16);
- dequantization is ONE convert + ONE broadcast multiply, which XLA fuses
  into the matmul operand read — no unpack, no codebook lookup;
- symmetric per-output-channel scales keep matmul semantics exact up to the
  8-bit rounding (no zero points to fold).

Storage: sibling leaves ``kernel_int8 [in, out] int8`` +
``kernel_int8_scale [out] f32`` (per-output-channel absmax / 127), consumed
by ``models/transformer._linear`` exactly like the NF4 leaves. This is an
INFERENCE format — the trainer never produces it; ``quantize_params_int8``
converts a loaded checkpoint in one pass (CLI flag ``--quantize int8`` on
the inference entry points).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

INT8_SUFFIXES = ("int8", "int8_scale")


def quantize_int8(w) -> Dict[str, jax.Array]:
    """``w [in, out]`` -> {"int8": int8 [in, out], "int8_scale": f32 [out]}."""
    w = jnp.asarray(w)
    if w.ndim != 2:
        raise ValueError(f"quantize_int8 expects a 2-D weight, got {w.shape}")
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)  # [out]
    scale = jnp.where(absmax == 0.0, 1.0, absmax) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[None, :]), -127, 127)
    return {"int8": q.astype(jnp.int8), "int8_scale": scale.astype(jnp.float32)}


def dequantize_int8(q: Dict, dtype=jnp.bfloat16):
    """Inverse: int8 codes * per-channel scale -> [in, out] in ``dtype``."""
    return (
        q["int8"].astype(jnp.float32) * q["int8_scale"][None, :].astype(jnp.float32)
    ).astype(dtype)


def int8_matmul(x, q: Dict, compute_dtype=jnp.bfloat16):
    """``x [..., in] @ dequant(q)``. The convert+scale fuses into the matmul
    operand read under XLA; the HBM stream is the int8 codes. The scale is
    applied in f32 and the product cast once, so this path and
    ``dequantize_int8`` agree exactly (up to the single cast) instead of
    compounding a bf16-rounded scale on top of the 8-bit rounding."""
    w = (q["int8"].astype(jnp.float32) * q["int8_scale"][None, :]).astype(compute_dtype)
    return x.astype(compute_dtype) @ w


def quantize_int8_stacked(w) -> Dict[str, jax.Array]:
    """Stacked expert weight ``[E, in, out]`` -> int8 codes + per-(expert,
    channel) scales ``[E, out]`` (each expert quantized independently)."""
    w = jnp.asarray(w)
    if w.ndim != 3:
        raise ValueError(f"quantize_int8_stacked expects [E, in, out], got {w.shape}")
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=1)  # [E, out]
    scale = jnp.where(absmax == 0.0, 1.0, absmax) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[:, None, :]), -127, 127)
    return {"int8": q.astype(jnp.int8), "int8_scale": scale.astype(jnp.float32)}


def dequantize_int8_stacked(q: Dict, dtype=jnp.bfloat16):
    """Inverse: [E, in, out] in ``dtype``."""
    return (
        q["int8"].astype(jnp.float32) * q["int8_scale"][:, None, :].astype(jnp.float32)
    ).astype(dtype)


# the single source of truth for inference quantization modes (CLI choices,
# server fail-fast check, and maybe_quantize all reference this)
QUANTIZE_MODES = ("none", "int8", "nf4")

# paged-KV-pool quantization modes (--quantize-kv): per-block int8 with a
# sibling absmax-scale pool (models/transformer.init_paged_cache)
KV_QUANT_MODES = ("none", "int8")


def maybe_quantize(params, mode: str):
    """Shared inference-entry helper (CLI + server): apply the selected
    weight-only quantization mode to a loaded params pytree."""
    if mode not in QUANTIZE_MODES:
        raise ValueError(
            f"unknown quantize mode {mode!r} (expected one of {QUANTIZE_MODES})"
        )
    if mode == "none":
        return params
    if mode == "nf4":
        from llm_fine_tune_distributed_tpu.ops.nf4 import quantize_params_nf4

        print("Quantizing block linears to NF4 (weight-only) ...")
        return quantize_params_nf4(params)
    print("Quantizing block linears to int8 (weight-only) ...")
    return quantize_params_int8(params)


def quantize_params_int8(params, predicate=None):
    """Replace every matching 2-D ``.../kernel`` leaf (transformer-block
    linears by default) with its int8 sibling leaves. Works on the nested
    params pytree; non-matching leaves pass through untouched.

    Embeddings and the lm_head stay full precision: the embedding gather
    reads one row per token (not bandwidth-bound) and the unembed feeds the
    sampling distribution where 8-bit rounding is most visible. The MoE
    router gate also stays exact — same reasoning as the NF4 path
    (parallel/qlora._is_quantizable): it is ~0.01% of the bytes and 8-bit
    rounding there would perturb every routing decision.
    """
    def is_stacked_expert(path: str) -> bool:
        return path.endswith(("/experts/w1", "/experts/w2", "/experts/w3"))

    if predicate is None:
        predicate = lambda path: "/layers/" in path and (
            (path.endswith("/kernel") and not path.endswith("block_sparse_moe/gate/kernel"))
            or is_stacked_expert(path)
        )

    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict, unflatten_dict

    flat = flatten_dict(params)
    out = {}
    for path, leaf in flat.items():
        if not predicate(path):
            out[path] = leaf
        elif getattr(leaf, "ndim", 0) == 2 and path.endswith("/kernel"):
            q = quantize_int8(leaf)
            for suffix in INT8_SUFFIXES:
                out[f"{path}_{suffix}"] = q[suffix]
        elif getattr(leaf, "ndim", 0) == 3 and is_stacked_expert(path):
            q = quantize_int8_stacked(leaf)
            for suffix in INT8_SUFFIXES:
                out[f"{path}_{suffix}"] = q[suffix]
        else:
            # a predicate hit with no int8 form (embedding, norm, odd shape)
            # would produce orphaned leaves no consumer reads — be loud
            raise ValueError(
                f"predicate matched {path!r} (ndim="
                f"{getattr(leaf, 'ndim', None)}) but only 2-D .../kernel "
                "leaves and stacked 3-D expert weights have an int8 form"
            )
    return unflatten_dict(out)


# ---------------------------------------------------------------------------
# Paged KV pool quantization (--quantize-kv int8)
#
# The pool keeps the bf16 layout's [num_blocks, block_len, kv_heads, head_dim]
# shape in int8 plus a sibling absmax pool [num_blocks, kv_heads] f32 indexed
# by the SAME block ids the block tables carry (infer/paged.py allocates ids,
# never bytes, so it is untouched). Per-(block, kv-head) scales rather than
# per-block: the k/v magnitude spread across heads is the dominant error term
# at 8 bits, and the extra scale column costs 4 bytes per head per block
# against block_len * head_dim codes. Codes are symmetric absmax/127, like
# the weight path; scale 0 means "never written" and dequantizes to exactly
# 0.0, which keeps the null block (id 0) all-zero by construction.
# ---------------------------------------------------------------------------


def quantize_kv_write(codes, scales, blk, off, x):
    """Scatter new K or V tokens into an int8 paged pool, growing per-block
    scales as needed.

    ``codes`` int8 [num_blocks, block_len, kv_heads, d], ``scales`` f32
    [num_blocks, kv_heads] (per-block-per-head absmax), ``blk``/``off``
    int32 [b, s] (pool block id / slot within block per token), ``x``
    [b, s, kv_heads, d]. Returns ``(new_codes, new_scales)``.

    A write may raise a block's absmax, so the block's EXISTING codes are
    re-expressed under the grown scale (gather touched blocks, multiply by
    old/new, round, scatter back). Blocks whose scale did not grow rescale
    by exactly 1.0 — an int8 -> f32 -> round -> int8 identity — so blocks
    not written this call (in particular COW-shared prefix blocks, which are
    never written again after their last prefill token) stay bit-stable.
    Duplicate block ids within one call (a prefill chunk spanning a block)
    compute identical rescaled content from the already-maxed new scales, so
    overlapping scatters agree regardless of order. Writes routed to the
    null block (id 0 — dead rows, clamped redirects) are forced to zero
    codes and a zero scale, so block 0 dequantizes to 0.0 forever.
    """
    xf = x.astype(jnp.float32)
    null = blk == 0  # [b, s]
    tok_amax = jnp.where(
        null[..., None], 0.0, jnp.max(jnp.abs(xf), axis=-1)
    )  # [b, s, h]
    new_scales = scales.at[blk].max(tok_amax)
    old_blk = scales[blk]  # [b, s, h]
    new_blk = new_scales[blk]
    safe_new = jnp.where(new_blk == 0.0, 1.0, new_blk)
    ratio = jnp.where(new_blk == 0.0, 0.0, old_blk / safe_new)
    touched = codes[blk].astype(jnp.float32)  # [b, s, L, h, d]
    rescaled = jnp.clip(
        jnp.round(touched * ratio[:, :, None, :, None]), -127, 127
    ).astype(jnp.int8)
    new_codes = codes.at[blk].set(rescaled)
    q = jnp.clip(jnp.round(xf * (127.0 / safe_new[..., None])), -127, 127)
    q = jnp.where(null[..., None, None], 0, q.astype(jnp.int8))
    new_codes = new_codes.at[blk, off].set(q)
    return new_codes, new_scales


def dequantize_kv_gather(codes, scales, block_tables, dtype=jnp.bfloat16):
    """Gather a row's table blocks out of an int8 paged pool into the dense
    [b, nb * block_len, kv_heads, d] view ``models/transformer._cache_write_and_view``
    attends over (the XLA fallback for the fused Pallas decode kernel —
    ops/flash_attention.paged_decode_attention). The gathered index IS the
    logical position, exactly like the bf16 layout, so the caller's position
    mask applies unchanged; null-table entries gather block 0, whose scale
    is pinned at 0 so they dequantize to exact zeros."""
    b, nb = block_tables.shape
    _, L, h, d = codes.shape
    flat = block_tables.reshape(-1)
    blocks = codes[flat].astype(jnp.float32).reshape(b, nb, L, h, d)
    sc = (scales[flat] / 127.0).reshape(b, nb, 1, h, 1)
    return (blocks * sc).astype(dtype).reshape(b, nb * L, h, d)
