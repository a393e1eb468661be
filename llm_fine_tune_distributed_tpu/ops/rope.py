"""Rotary position embeddings (HF Llama "rotate_half" convention).

Must match HF numerics exactly so imported safetensors weights reproduce the
reference model's logits (the reference loads HF SmolLM3-3B,
reference ``training.py:97-102``). HF applies RoPE by splitting the head dim
in half (NOT even/odd interleaving):

    rotate_half(x) = concat(-x[..., d/2:], x[..., :d/2])
    x_rot = x * cos + rotate_half(x) * sin

with ``cos/sin = f(outer(positions, inv_freq))`` tiled twice along the last dim.

``apply_rope`` is that form as XLA operations over ``[b, s, heads, d]``: every
backend's path, what serving with a cache runs, and the REFERENCE the fused
pass below is held to (``tests/test_attn_in.py``).

**The IN pass** (``heads_in``; PR 41). Between a softmax layer's projections
and the flash kernels stand the per-head q/k norms (where the model has them),
the rotation, and the hand-over to the head-major layout the kernels read. As
XLA operations that is a float32 fusion that slices every head at lane 64 of
its one 128-lane register, a norm pass or two, three transposes forward and
three back, each again under remat. On a TPU it is ONE Pallas kernel forward
(``attn_in_fwd``) and one backward (``attn_in_bwd``, behind a ``custom_vjp``,
each behind a ``jax.jit`` so that every layer of one shape shares one traced
and lowered body):

- it reads the projections' outputs as they lie, flat ``[b, s, heads * d]``
  (a gated layer's gate is no business of the pass: ``models/transformer.
  _heads_qkv`` cuts ``q_proj``'s ``[q | gate]`` LEAF by column and makes two
  products, so q and the gate are separate flat arrays from birth and the
  gate's cotangent feeds its own product; through the pass, which had to
  read it as an array of its own to write it beside dq, it cost 128 MiB a
  layer at the step's peak: PERF.md, PR 41);
- norms q and k a head (statistics in float32; the caller hands ``1 + w``
  where the norm is zero-centred), rotates them in float32 with the rotation
  as lane rolls of the head's leading registers (``pltpu.roll`` by half the
  table's width, the sign folded into the sine; a table narrower than its
  registers, ``partial_rotary_factor``, takes two rolls and a select on the
  lane, cos padded with 1 and sin with 0), ONE rounding to the compute dtype
  at the output (the XLA form rounds after the norm too);
- writes q, k and v HEAD-MAJOR, ``[b, heads, s, d]``: the layout contract of
  ``ops/flash_attention.pallas_flash_attention(..., head_major=True)``;
- backward, takes dq, dk, dv head-major as the flash kernels wrote them,
  un-rotates (the same rolls, the table itself rolled), runs the norm's
  backward from the kept flat q and k, and writes the projections' flat
  cotangents. It keeps its inputs and nothing else, so under full remat
  nothing new lives across the boundary.

Whether a layer rotates at all reaches the kernels as DATA (one int32 in SMEM
that turns a trip's tables into cos 1, sin 0): a model's layers with rope and
without run one program (one text a shape, not two), and a traced ``rope``
(a layer scan) is the same operand. A grid step holds ``_token_block`` tokens
of one group of ``_heads_a_step`` query heads and, at a kv head's first group,
its k and v head; the loop inside runs ``ROWS`` tokens a trip and is not
unrolled.

**When it engages** is read from the call, no switch: ``models/transformer.
_softmax_mixer`` asks for it where ``attention()`` would run the flash kernels
on the whole row in one device's program (``ops/attention.head_major_reason``,
the answer of ``attention()``'s own ``_route``: a TPU, no custom scale or
softcap, one device or none, a head of whole 128-lane registers; ring or
Ulysses attention with no live seq axis falls back to those kernels and takes
the pass with them), there is no cache entry and no explicit mask, and
``why_not_fused`` has no objection (tables ``[b or 1, s, width]``). Under a mesh
of several devices the flash kernel runs per shard inside a ``shard_map`` that
takes ``[b, s, h, d]``: there the pass runs NOT AT ALL and the XLA form
stands, as it does on a CPU, with a cache, under ring or Ulysses attention over
a seq axis and in the pipeline's stages. ``CALLS`` says which form each traced
call took.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_fine_tune_distributed_tpu.ops.tiling import VMEM_CAP_BYTES, by_eights, lanes, tiled_bytes


def rope_inv_freq(
    head_dim: int,
    theta: float,
    *,
    scaling_type=None,
    factor: float = 1.0,
    low_freq_factor: float = 1.0,
    high_freq_factor: float = 4.0,
    original_max_position: int = 8192,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
):
    """Per-frequency inverse wavelengths, with optional context extension.

    ``scaling_type``:
      - None: plain RoPE.
      - "linear": positions effectively divided by ``factor`` (HF "linear").
      - "llama3": HF's Llama-3.1 smoothed NTK scheme
        (modeling_rope_utils._compute_llama3_parameters) — long wavelengths
        (> original_max/low_freq_factor) are slowed by ``factor``, short ones
        (< original_max/high_freq_factor) untouched, with linear interpolation
        in between. Matching HF exactly is required for imported Llama-3.1+
        checkpoints to reproduce reference logits.
      - "yarn": HF ``_compute_yarn_parameters`` (truncate true): dimension i
        keeps its frequency below ``low``, is divided by ``factor`` above
        ``high``, a linear ramp between; ``low``/``high`` are the (floored /
        ceiled, clamped) dimensions whose wavelength turns ``beta_fast`` /
        ``beta_slow`` times over the original length. The attention factor on
        cos and sin is ``yarn_attention_factor``, applied by ``rope_cos_sin``.
    """
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if scaling_type in (None, "default"):
        return inv_freq
    if scaling_type == "linear":
        return inv_freq / factor
    if scaling_type == "llama3":
        low_freq_wavelen = original_max_position / low_freq_factor
        high_freq_wavelen = original_max_position / high_freq_factor
        wavelen = 2.0 * jnp.pi / inv_freq
        scaled = inv_freq / factor
        smooth = (original_max_position / wavelen - low_freq_factor) / (
            high_freq_factor - low_freq_factor
        )
        smoothed = (1.0 - smooth) * scaled + smooth * inv_freq
        out = jnp.where(wavelen > low_freq_wavelen, scaled, inv_freq)
        is_medium = (wavelen <= low_freq_wavelen) & (wavelen >= high_freq_wavelen)
        return jnp.where(is_medium, smoothed, out)
    if scaling_type == "yarn":
        def turns_at(rotations):  # the dimension that turns this often over the original length
            return head_dim * math.log(original_max_position / (rotations * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(turns_at(beta_fast)), 0)
        high = min(math.ceil(turns_at(beta_slow)), head_dim - 1)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
        return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
    raise ValueError(f"unsupported rope scaling type: {scaling_type!r}")


def yarn_attention_factor(factor: float, attention_factor=None) -> float:
    """What YaRN multiplies cos and sin by (so the scores carry its square):
    the config's own number, else HF's default ``0.1 ln(factor) + 1``."""
    if attention_factor is not None:
        return float(attention_factor)
    return 1.0 if factor <= 1 else 0.1 * math.log(factor) + 1.0


def rope_cos_sin(positions, head_dim: int, theta: float, dtype=jnp.float32, *, config=None):
    """Compute cos/sin tables for given positions.

    Args:
      positions: int array [...,] token positions (any leading shape).
      head_dim: per-head dimension (must be even).
      theta: RoPE base frequency.
      config: optional ModelConfig; when given, its rope_scaling_* fields
        select the context-extension scheme (Llama-3.1 "llama3", "linear",
        "yarn" with its attention factor on both tables). Without it the
        tables are plain rope's.

    Returns:
      (cos, sin) arrays of shape positions.shape + (head_dim,).
    """
    # f32 throughout: bf16 position phases destroy long-context accuracy.
    scale = 1.0
    if config is not None and config.rope_scaling_type:
        if config.rope_scaling_type == "yarn":
            scale = yarn_attention_factor(config.rope_scaling_factor, config.rope_attention_factor)
        inv_freq = rope_inv_freq(
            head_dim,
            theta,
            scaling_type=config.rope_scaling_type,
            factor=config.rope_scaling_factor,
            low_freq_factor=config.rope_low_freq_factor,
            high_freq_factor=config.rope_high_freq_factor,
            original_max_position=config.rope_original_max_position,
            beta_fast=config.rope_beta_fast,
            beta_slow=config.rope_beta_slow,
        )
    else:
        inv_freq = rope_inv_freq(head_dim, theta)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., half]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [..., head_dim]
    return (jnp.cos(emb) * scale).astype(dtype), (jnp.sin(emb) * scale).astype(dtype)


def _rotate_half(x):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(q, k, cos, sin):
    """Apply rotary embedding to q and k.

    Args:
      q: [batch, seq, num_heads, head_dim]
      k: [batch, seq, num_kv_heads, head_dim]
      cos/sin: [batch, seq, head_dim] (or broadcastable); narrower tables
        rotate the first ``cos.shape[-1]`` dimensions of a head (rotate-half
        inside them) and the rest pass (HF ``partial_rotary_factor``)

    Returns rotated (q, k), same dtypes as inputs.
    """
    width = cos.shape[-1]
    if width < q.shape[-1]:
        q_rot, k_rot = apply_rope(q[..., :width], k[..., :width], cos, sin)
        return (jnp.concatenate([q_rot, q[..., width:]], axis=-1),
                jnp.concatenate([k_rot, k[..., width:]], axis=-1))
    # Broadcast over the heads axis.
    c = cos[..., None, :]
    s = sin[..., None, :]
    q_dtype, k_dtype = q.dtype, k.dtype
    q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
    c32, s32 = c.astype(jnp.float32), s.astype(jnp.float32)
    q_rot = q32 * c32 + _rotate_half(q32) * s32
    k_rot = k32 * c32 + _rotate_half(k32) * s32
    return q_rot.astype(q_dtype), k_rot.astype(k_dtype)


# -- the IN pass: q/k norms, rotation and the head-major layout as one kernel each way (TPU) --------------------------

_F32 = jnp.float32
ROWS = 256             # tokens a trip of the inner loop (ops/gated_delta.ROWS: at 32 a trip waits out its own latencies)
_STEP_BYTES = 2 << 20  # the query heads' block of a grid step, at most: what sizes the token block
_MAX_HEADS_A_STEP = 8  # the kernel's text grows with the heads a step holds

# {((rows, seq, q heads, kv heads, head dim, table width, "norm" | "", "gate" | ""), form): calls traced} of every
# layer of heads' q/k/v hand-over traced in this process (``models/transformer._softmax_mixer``); the form is ``fused``, or
# ``xla (<why>)`` (as ``ops/gated_delta.CALLS`` says which program ran the rule; here one shape can take both: with a
# cache, without). The benchmark's ``attn_in_fused_calls_pct`` reads it.
CALLS: dict = {}


def count_call(shape, why_xla) -> None:
    key = (tuple(shape), "fused" if why_xla is None else f"xla ({why_xla})")
    CALLS[key] = CALLS.get(key, 0) + 1


def calls_summary() -> str:
    """One line for entry points to print beside ``dispatch_summary()``."""
    said = "; ".join(f"{list(shape)}: {form} x {n}" for (shape, form), n in sorted(CALLS.items(), key=str))
    return f"q/k norms, rope and layout traced as: {said or 'nothing traced'}"


def why_not_fused(rows: int, seq: int, head_dim: int, cos) -> "str | None":
    """Why the IN pass cannot take a call that the flash kernels take on a TPU (``ops/attention.head_major_reason`` has
    been asked; None: it can). From the head's width and the tables' shape: the pass reads a head's lanes of the
    projections' FLAT output, a block ``[tokens, d]`` at lane ``d j``, which Mosaic takes at whole 128-lane registers
    only (heads of 64, which the flash kernels take head-major as they lie, keep the XLA form's transposes)."""
    if head_dim % 128:
        return f"head dim {head_dim} is not whole 128-lane registers: the pass reads a head's lanes of the flat projections"
    if cos.ndim != 3 or cos.shape[0] not in (1, rows) or cos.shape[1] != seq or cos.shape[2] % 2 or cos.shape[2] > head_dim:
        return f"tables {tuple(cos.shape)} are not [{rows} or 1, {seq}, even width <= {head_dim}]"
    return None


def _heads_a_step(groups: int) -> int:
    """Query heads a grid step holds: a kv head's whole group where the text allows, else its largest part."""
    return next(p for p in range(min(groups, _MAX_HEADS_A_STEP), 0, -1) if groups % p == 0)


def _token_block(seq: int, heads: int, head_dim: int, itemsize: int) -> int:
    """Tokens a grid step holds, from the shape: the most whose query block stays under ``_STEP_BYTES`` (whole trips
    of ``ROWS`` where the row has them; a short row is one block). ``seq`` is whole 128s, as the flash kernels ask."""
    return next(t for t in (2048, 1024, 512, 256, 128)
                if seq % t == 0 and (t == 128 or t * heads * head_dim * itemsize <= _STEP_BYTES))


def _partner(x, half: int):
    """``x[pi(j)]`` at every lane ``j`` of ``x [rows, registers]``: the lane a rotation of ``2 half`` lanes pairs with
    ``j``. One roll where the rotated lanes fill the registers; else two and a select (past the table the result is
    whatever: the sine is 0 there)."""
    lanes = x.shape[1]
    if 2 * half == lanes:
        return pltpu.roll(x, half, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < half, pltpu.roll(x, lanes - half, 1), pltpu.roll(x, half, 1))


def _signed_sine(sin, half: int):
    """``rotate_half``'s sign folded into the table: ``-sin`` under the first ``half`` lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, sin.shape, 1)
    return jnp.where(lane < half, -sin, sin)


def _tables_of_a_trip(cos_ref, sin_ref, on_ref, rows, half: int):
    """A trip's cos and signed sine; cos 1 and sin 0 where the layer does not rotate (``on_ref``: the plan's bool, or
    the traced one, as data: one program for a model's layers with rope and without)."""
    on = on_ref[0] != 0
    return jnp.where(on, cos_ref[0, rows, :], 1.0), jnp.where(on, _signed_sine(sin_ref[0, rows, :], half), 0.0)


def _rotated(x, cos, sine, half: int):
    """``x * cos + rotate_half(x) * sin`` over the leading ``cos.shape[1]`` lanes of ``x [rows, d]`` float32
    (``sine``: ``_signed_sine``, or its transpose's table); the lanes past them pass."""
    lead = cos.shape[1]
    a = x[:, :lead]
    a = a * cos + _partner(a, half) * sine
    return a if lead == x.shape[1] else jnp.concatenate([a, x[:, lead:]], axis=1)


def _rows(i, n):
    return pl.ds(pl.multiple_of(i * n, n), n)


def _in_kernel(*refs, heads, groups, norm, eps, half, trip):
    """``heads`` query heads' blocks, k's, v's, cos, sin, whether to rotate at all (one int32 in SMEM), (the two norms'
    multipliers,) then q, k, v out."""
    q_refs, (k_ref, v_ref, cos_ref, sin_ref, on_ref), refs = refs[:heads], refs[heads:heads + 5], refs[heads + 5:]
    (wq, wk), (qo_ref, ko_ref, vo_ref) = ((refs[0][...], refs[1][...]) if norm else (None, None)), refs[-3:]
    first = (pl.program_id(2) * heads) % groups == 0     # the kv head's first group of query heads: its k and v too

    def normed(x, w):
        return x if w is None else x * (jax.lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps) * w)

    def body(i, _):
        rows = _rows(i, trip)
        cos, sine = _tables_of_a_trip(cos_ref, sin_ref, on_ref, rows, half)
        for p, q_ref in enumerate(q_refs):
            qo_ref[0, p, rows, :] = _rotated(normed(q_ref[0, rows, :].astype(_F32), wq), cos, sine, half).astype(qo_ref.dtype)

        @pl.when(first)
        def _():
            ko_ref[0, 0, rows, :] = _rotated(normed(k_ref[0, rows, :].astype(_F32), wk), cos, sine, half).astype(ko_ref.dtype)
            vo_ref[0, 0, rows, :] = v_ref[0, rows, :]

        return 0

    jax.lax.fori_loop(0, k_ref.shape[1] // trip, body, 0)


def _in_back_kernel(*refs, heads, groups, norm, eps, half, width, trip):
    """(``heads`` query heads' kept blocks and k's,) dq, dk, dv head-major, cos, sin, (the multipliers,) then the flat
    cotangents of q, k and v(, and 8 partial sums of each multiplier's cotangent over the whole grid)."""
    kept, refs = (refs[:heads + 1], refs[heads + 1:]) if norm else ((), refs)
    (dq_ref, dk_ref, dv_ref, cos_ref, sin_ref, on_ref), refs = refs[:6], refs[6:]
    (wq, wk), refs = ((refs[0][...], refs[1][...]), refs[2:]) if norm else ((None, None), refs)
    dxq_ref, dxk_ref, dxv_ref = refs[:3]
    d = dk_ref.shape[3]
    first = (pl.program_id(2) * heads) % groups == 0

    if norm:
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0) & (pl.program_id(2) == 0))
        def _():
            refs[3][...] = jnp.zeros_like(refs[3])
            refs[4][...] = jnp.zeros_like(refs[4])

    def back(dz, cos, sine, x_ref, rows, w, dw_ref):
        """A head's cotangent through the rotation and, from the kept input, through the norm:
        y = n w, n = x r, r = rsqrt(mean x^2 + eps):  dx = r (g - n mean(g n)), g = dy w."""
        dy = _rotated(dz, cos, sine, half)
        if w is None:
            return dy
        x = x_ref[0, rows, :].astype(_F32)
        r = jax.lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps)
        n, g = x * r, dy * w
        dw_ref[...] += by_eights(dy * n)
        return r * (g - n * jnp.mean(g * n, axis=1, keepdims=True))

    def body(i, _):
        rows = _rows(i, trip)
        cos, sine = _tables_of_a_trip(cos_ref, sin_ref, on_ref, rows, half)
        sine = _partner(sine, half)                                      # the transposed rotation's table: s'[pi(j)]
        if width < cos.shape[1]:
            sine = jnp.where(jax.lax.broadcasted_iota(jnp.int32, sine.shape, 1) < width, sine, 0.0)
        for p in range(heads):
            dx = back(dq_ref[0, p, rows, :].astype(_F32), cos, sine, kept[p] if norm else None, rows, wq, refs[3] if norm else None)
            dxq_ref[0, rows, p * d:(p + 1) * d] = dx.astype(dxq_ref.dtype)

        @pl.when(first)
        def _():
            dx = back(dk_ref[0, 0, rows, :].astype(_F32), cos, sine, kept[heads] if norm else None, rows, wk, refs[4] if norm else None)
            dxk_ref[0, rows, :] = dx.astype(dxk_ref.dtype)
            dxv_ref[0, rows, :] = dv_ref[0, 0, rows, :]

        return 0

    jax.lax.fori_loop(0, dk_ref.shape[2] // trip, body, 0)


def _tables(cos, sin):
    """The tables as the kernels read them: float32, padded to whole registers with cos 1 and sin 0."""
    cos, sin = cos.astype(_F32), sin.astype(_F32)
    pad = lanes(cos.shape[2]) - cos.shape[2]
    if pad:
        cos = jnp.concatenate([cos, jnp.ones((*cos.shape[:2], pad), _F32)], axis=2)
        sin = jnp.concatenate([sin, jnp.zeros((*sin.shape[:2], pad), _F32)], axis=2)
    return cos, sin


def _plan(b, s, d, heads, kv_heads, itemsize):
    """``(groups, query heads a grid step, tokens a grid step, tokens a trip)`` of a call, from its shape."""
    groups = heads // kv_heads
    a_step = _heads_a_step(groups)
    tokens = _token_block(s, a_step, d, itemsize)
    return groups, a_step, tokens, min(ROWS, tokens)


def _specs(b, s, d, groups, a_step, tokens, cos):
    """Spec makers over the grid ``(row, token block, group of a_step query heads)``."""
    kv = lambda j: (j * a_step) // groups  # noqa: E731
    return dict(
        flat_q=lambda p: pl.BlockSpec((1, tokens, d), lambda i, t, j: (i, t, j * a_step + p)),
        flat_kv=pl.BlockSpec((1, tokens, d), lambda i, t, j: (i, t, kv(j))),
        flat_group=pl.BlockSpec((1, tokens, a_step * d), lambda i, t, j: (i, t, j)),
        major_q=pl.BlockSpec((1, a_step, tokens, d), lambda i, t, j: (i, j, t, 0)),
        major_kv=pl.BlockSpec((1, 1, tokens, d), lambda i, t, j: (i, kv(j), t, 0)),
        table=pl.BlockSpec((1, tokens, cos.shape[2]), lambda i, t, j: (i if cos.shape[0] == b else 0, t, 0)),  # (one row serves all)
        flag=pl.BlockSpec(memory_space=pltpu.SMEM),
        weight=pl.BlockSpec((1, d), lambda i, t, j: (0, 0)),
        sums=pl.BlockSpec((8, d), lambda i, t, j: (0, 0)),
    )


def _params(interpret, specs, operands, semantics):
    if interpret:
        return {}
    blocks = 2 * sum(tiled_bytes(spec.block_shape, x.dtype) for spec, x in zip(specs, operands) if spec.block_shape)
    return dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=min(blocks + (16 << 20), VMEM_CAP_BYTES)))


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "width", "eps", "interpret"))
def attn_in_fwd(xq, xk, xv, cos, sin, on, wq, wk, *, heads, kv_heads, width, eps, interpret):
    """The IN pass. ``xq [b, s, heads * d]``, ``xk`` and ``xv`` ``[b, s, kv_heads * d]`` as the projections wrote them, the tables ``[b or 1, s, registers]`` (``_tables``:
    ``width`` rotated lanes, padded), ``on [1]`` int32 (0: no rotation), the norms' multipliers ``[d]`` float32 or None -> q
    ``[b, heads, s, d]``, k and v ``[b, kv_heads, s, d]``, typed like their inputs. Float32 inside, one rounding at
    the output."""
    b, s, d = xk.shape[0], xk.shape[1], xk.shape[2] // kv_heads
    groups, a_step, tokens, trip = _plan(b, s, d, heads, kv_heads, xk.dtype.itemsize)
    at = _specs(b, s, d, groups, a_step, tokens, cos)
    norm = wq is not None
    operands = [xq] * a_step + [xk, xv, cos, sin, on] + ([wq.reshape(1, d), wk.reshape(1, d)] if norm else [])
    in_specs = ([at["flat_q"](p) for p in range(a_step)] + [at["flat_kv"]] * 2 + [at["table"]] * 2 + [at["flag"]]
                + [at["weight"]] * (2 * norm))
    out_specs = [at["major_q"], at["major_kv"], at["major_kv"]]
    out_shape = [jax.ShapeDtypeStruct((b, h, s, d), x.dtype) for h, x in ((heads, xq), (kv_heads, xk), (kv_heads, xv))]
    return pl.pallas_call(
        functools.partial(_in_kernel, heads=a_step, groups=groups, norm=norm, eps=eps, half=width // 2, trip=trip),
        grid=(b, s // tokens, heads // a_step), in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        name="attn_in_fwd", interpret=interpret,
        **_params(interpret, in_specs + out_specs, operands + out_shape, ("parallel", "parallel", "arbitrary")),
    )(*operands)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "width", "eps", "interpret"))
def attn_in_bwd(xq, xk, dq, dk, dv, cos, sin, on, wq, wk, *, heads, kv_heads, width, eps, interpret):
    """The IN pass's backward pass: dq ``[b, heads, s, d]``, dk and dv ``[b, kv_heads, s, d]`` as the flash kernels
    wrote them, the kept ``xq`` and ``xk`` where there is a norm (None without) -> the cotangents of ``xq``, ``xk``,
    ``xv``, and of the multipliers (float32; None without a norm)."""
    b, _, s, d = dq.shape
    norm = wq is not None
    groups, a_step, tokens, trip = _plan(b, s, d, heads, kv_heads, dk.dtype.itemsize)
    at = _specs(b, s, d, groups, a_step, tokens, cos)
    operands = (([xq] * a_step + [xk]) if norm else []) + [dq, dk, dv, cos, sin, on] + (
        [wq.reshape(1, d), wk.reshape(1, d)] if norm else [])
    in_specs = (([at["flat_q"](p) for p in range(a_step)] + [at["flat_kv"]]) if norm else []) + [
        at["major_q"], at["major_kv"], at["major_kv"]] + [at["table"]] * 2 + [at["flag"]] + [at["weight"]] * (2 * norm)
    out_specs = [at["flat_group"], at["flat_kv"], at["flat_kv"]] + [at["sums"]] * (2 * norm)
    out_shape = [jax.ShapeDtypeStruct((b, s, n * d), x.dtype) for n, x in ((heads, dq), (kv_heads, dk), (kv_heads, dv))] + [
        jax.ShapeDtypeStruct((8, d), _F32)] * (2 * norm)
    dxq, dxk, dxv, *dw = pl.pallas_call(
        functools.partial(_in_back_kernel, heads=a_step, groups=groups, norm=norm, eps=eps, half=width // 2, width=width,
                          trip=trip),
        grid=(b, s // tokens, heads // a_step), in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        name="attn_in_bwd", interpret=interpret,
        **_params(interpret, in_specs + out_specs, operands + out_shape, ("arbitrary",) * 3),
    )(*operands)
    return (dxq, dxk, dxv, *(x.sum(axis=0) for x in dw)) if norm else (dxq, dxk, dxv, None, None)


@functools.lru_cache(maxsize=None)
def _in_pass(heads, kv_heads, width, eps, interpret):
    """The IN pass as one differentiable function of ``(xq, xk, xv, cos, sin, on, wq, wk)``; it keeps its inputs (of q
    and k only what the norm's backward reads: nothing without a norm) and nothing else."""
    static = dict(heads=heads, kv_heads=kv_heads, width=width, eps=eps, interpret=interpret)

    @jax.custom_vjp
    def run(xq, xk, xv, cos, sin, on, wq, wk):
        return tuple(attn_in_fwd(xq, xk, xv, cos, sin, on, wq, wk, **static))

    def fwd(xq, xk, xv, cos, sin, on, wq, wk):
        kept = (xq, xk) if wq is not None else (None, None)
        return run(xq, xk, xv, cos, sin, on, wq, wk), (*kept, cos, sin, on, wq, wk)

    def bwd(kept, cotangents):
        xq, xk, cos, sin, on, wq, wk = kept
        dxq, dxk, dxv, dwq, dwk = attn_in_bwd(xq, xk, *cotangents, cos, sin, on, wq, wk, **static)
        as_kept = lambda dw, w: None if w is None else dw.astype(w.dtype)  # noqa: E731
        return (dxq, dxk, dxv, jnp.zeros_like(cos), jnp.zeros_like(sin), np.zeros(on.shape, jax.dtypes.float0),
                as_kept(dwq, wq), as_kept(dwk, wk))

    run.defvjp(fwd, bwd)
    return run


def heads_in(xq, xk, xv, cos, sin, *, heads, kv_heads, q_weight=None, k_weight=None, eps=1e-6, rope=True,
             interpret=False):
    """The fused IN pass (module docstring) over the projections' flat outputs ``[b, s, heads * d]``: ``(q [b, heads,
    s, d], k and v [b, kv_heads, s, d])``. ``q_weight``, ``k_weight``: the per-head norms'
    MULTIPLIERS ``[d]`` (``1 + w`` where the norm is zero-centred), None without norms. ``rope``: the plan's bool, or
    a traced bool scalar; either way it reaches the kernel as data, and a layer without rope runs the program of the
    layers with. The caller has asked ``why_not_fused``."""
    as_f32 = lambda w: None if w is None else w.astype(_F32)  # noqa: E731
    return _in_pass(heads, kv_heads, cos.shape[2], float(eps), interpret)(
        xq, xk, xv, *_tables(cos, sin), jnp.asarray(rope, jnp.int32).reshape(1), as_f32(q_weight), as_f32(k_weight))
