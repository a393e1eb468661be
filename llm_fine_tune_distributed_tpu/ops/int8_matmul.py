"""w8a8 int8 matmul for the frozen-trunk training fast path.

The serving stack's int8 path (ops/int8.py) is *weight-only*: codes dequantize
to bf16 and the matmul runs on the bf16 MXU path — right for bandwidth-bound
batch-1 decode, wrong for the compute-bound training forward. Here both
operands are int8 so the MXU runs its int8 mode (~2x bf16 throughput on
v4/v5e):

- weights: the serving format unchanged — ``kernel_int8 [in, out]`` codes with
  per-output-channel ``kernel_int8_scale [out]`` f32 (absmax/127, symmetric);
- activations: quantized dynamically per ROW (per token) — absmax over the
  feature dim, symmetric, recomputed every step so no calibration pass;
- the product accumulates in int32 (``preferred_element_type``) and a single
  fused f32 rescale ``acc * (row_scale x col_scale)`` dequantizes.

Error model: both roundings are absmax-symmetric, so the result is exact up
to one 8-bit rounding per operand — the parity tests
(tests/test_frozen_trunk.py) pin the band against the bf16 reference.

``TRUNK_MATMUL`` env override (``xla`` | ``pallas`` | ``interpret``) picks the
implementation, PAGED_DECODE-style: ``xla`` is the default everywhere (XLA
lowers the s8xs8->s32 ``dot_general`` onto the MXU int8 path natively, so the
Pallas kernel is a fallback, not the default); ``pallas`` forces the fused
kernel; ``interpret`` runs the kernel under the Pallas interpreter —
CPU-runnable, tier-1 coverage of the kernel math.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

TRUNK_MATMUL_MODES = ("xla", "pallas", "interpret")


def trunk_matmul_mode() -> str:
    """Implementation of the w8a8 trunk matmul: ``xla`` on every backend
    unless ``TRUNK_MATMUL`` is set by hand — bench arms and the
    interpret/XLA parity tests set it to pin each arm's path. ``pallas``
    runs the kernel compiled or fails; ``interpret`` is reachable no other
    way than by asking for it."""
    override = os.environ.get("TRUNK_MATMUL", "").lower()
    if not override:
        return "xla"
    if override not in TRUNK_MATMUL_MODES:
        raise ValueError(
            f"TRUNK_MATMUL={override!r}: expected one of {TRUNK_MATMUL_MODES}"
        )
    return override


def quantize_rows_int8(x) -> Tuple[jax.Array, jax.Array]:
    """Dynamic per-row activation quantization: ``x [..., in]`` ->
    ``(codes int8 [..., in], scale f32 [...])`` with absmax/127 symmetric
    scales over the trailing (feature) dim. All-zero rows get scale 1.0 and
    all-zero codes, so they dequantize to exact zeros."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)  # [...]
    scale = jnp.where(absmax == 0.0, 1.0, absmax) / 127.0
    codes = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return codes, scale


def _w8a8_xla(xq, x_scale, wq, w_scale, compute_dtype):
    """s8 x s8 -> s32 ``dot_general`` + fused f32 rescale. XLA maps the int8
    contraction onto the MXU int8 path on TPU; on CPU it is a plain int32
    GEMM — bit-identical math either way."""
    acc = jax.lax.dot_general(
        xq, wq,
        dimension_numbers=(((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [..., out] int32
    out = acc.astype(jnp.float32) * x_scale[..., None] * w_scale
    return out.astype(compute_dtype)


# ---------------------------------------------------------------------------
# Pallas variant. One fused kernel per (row-block, col-block) grid cell: the
# int8 operand tiles stream HBM -> VMEM, ``jnp.dot`` hits the MXU with an
# int32 accumulator, and the per-row/per-col scales apply before write-back —
# the f32 [M, N] product never round-trips through HBM unscaled. K is kept
# whole per cell (trunk projections have K = hidden or intermediate; the
# largest flagship tile, 128 x 11008 int8 x 2 operands + 128 x 512 f32 out,
# sits well under the ~16MB VMEM budget).
# ---------------------------------------------------------------------------

_BM = 128   # row tile (tokens)
_BN = 512   # output-channel tile


def _w8a8_kernel(xq_ref, wq_ref, xs_ref, ws_ref, out_ref):
    # precision pinned: an integer contraction has none, and Mosaic refuses
    # the int8 matmul ("Bad lhs type") if a process-wide
    # jax_default_matmul_precision=highest leaks into it
    acc = jnp.dot(
        xq_ref[:], wq_ref[:], preferred_element_type=jnp.int32,
        precision=jax.lax.Precision.DEFAULT,
    )
    # scales arrive as 2-D tiles ([bm, 1] rows / [1, bn] cols) — Mosaic wants
    # >=2-D operands, and the broadcast shapes are already matmul-aligned
    out_ref[:] = (acc.astype(jnp.float32) * xs_ref[:] * ws_ref[:]).astype(
        out_ref.dtype
    )


@functools.partial(jax.jit, static_argnames=("compute_dtype", "interpret"))
def _w8a8_pallas(xq, x_scale, wq, w_scale, compute_dtype, interpret=False):
    from jax.experimental import pallas as pl

    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2, (xq.shape, wq.shape)
    bm, bn = min(_BM, m), min(_BN, n)
    pad_m = (-m) % bm
    if pad_m:
        xq = jnp.pad(xq, ((0, pad_m), (0, 0)))
        x_scale = jnp.pad(x_scale, (0, pad_m))
    pad_n = (-n) % bn
    if pad_n:
        wq = jnp.pad(wq, ((0, 0), (0, pad_n)))
        w_scale = jnp.pad(w_scale, (0, pad_n))
    out = pl.pallas_call(
        _w8a8_kernel,
        grid=((m + pad_m) // bm, (n + pad_n) // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pad_m, n + pad_n), compute_dtype),
        interpret=interpret,
    )(xq, wq, x_scale[:, None], w_scale[None, :])
    return out[:m, :n]


def int8_w8a8_matmul(x, q: Dict, compute_dtype=jnp.bfloat16, impl=None):
    """``x [..., in]`` x serving-format int8 weight ``q`` (``{"int8" [in,
    out], "int8_scale" [out]}``) -> ``[..., out]`` in ``compute_dtype``,
    computed w8a8: dynamic per-row activation quantization, int8 x int8
    contraction with an int32 accumulator, one fused scale dequant.

    ``impl`` defaults to :func:`trunk_matmul_mode`. This op sits behind the
    trunk-boundary ``stop_gradient`` (train/step.py) so it never needs a
    VJP; the rounding is non-differentiable by construction.
    """
    impl = impl or trunk_matmul_mode()
    if impl not in TRUNK_MATMUL_MODES:
        raise ValueError(
            f"unknown trunk matmul impl {impl!r} (expected one of {TRUNK_MATMUL_MODES})"
        )
    xq, x_scale = quantize_rows_int8(x)
    wq, w_scale = q["int8"], q["int8_scale"].astype(jnp.float32)
    if impl == "xla":
        return _w8a8_xla(xq, x_scale, wq, w_scale, compute_dtype)
    lead = xq.shape[:-1]
    out = _w8a8_pallas(
        xq.reshape(-1, xq.shape[-1]),
        x_scale.reshape(-1),
        wq,
        w_scale,
        compute_dtype,
        interpret=impl == "interpret",
    )
    return out.reshape(*lead, out.shape[-1])
