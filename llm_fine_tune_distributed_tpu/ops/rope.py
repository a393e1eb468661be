"""Rotary position embeddings (HF Llama "rotate_half" convention).

Must match HF numerics exactly so imported safetensors weights reproduce the
reference model's logits (the reference loads HF SmolLM3-3B,
reference ``training.py:97-102``). HF applies RoPE by splitting the head dim
in half (NOT even/odd interleaving):

    rotate_half(x) = concat(-x[..., d/2:], x[..., :d/2])
    x_rot = x * cos + rotate_half(x) * sin

with ``cos/sin = f(outer(positions, inv_freq))`` tiled twice along the last dim.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


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
