"""Llama-family decoder-only transformer as pure JAX functions over a pytree.

Design notes (TPU-first, not a torch translation):

- Params are a plain nested dict whose paths mirror HF checkpoint names
  (``model.layers.0.self_attn.q_proj`` ...), so safetensors import/export is a
  rename-free transpose (models/hf_io.py) and sharding rules match on path.
- No module framework: ``forward`` is a pure function — trivially jittable,
  shardable with NamedSharding on the params pytree, and rematerializable per
  block with ``jax.checkpoint`` (the analog of the reference's
  ``gradient_checkpointing=True``, reference ``training.py:280``).
- Master params stay float32; compute casts to bfloat16 at use (the MXU path).
  Softmax/RMSNorm/RoPE run in float32.
- Covers SmolLM3 (GQA + NoPE-interleaved RoPE + tied embeddings), Llama-3,
  Mistral (sliding window) via ModelConfig — the model surface of the
  reference's ``AutoModelForCausalLM`` usage (reference ``training.py:97-102``).

Linear weights are stored in JAX kernel layout ``[in, out]`` under the leaf
name ``kernel`` (transpose of torch ``weight``); norm/embedding leaves are
``weight`` in torch layout.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from llm_fine_tune_distributed_tpu.config import LayerPlan, ModelConfig
from llm_fine_tune_distributed_tpu.observe.xla import scope
from llm_fine_tune_distributed_tpu.ops import eva_attention, gated_delta, moe, ssd
from llm_fine_tune_distributed_tpu.ops import rope as rope_ops
from llm_fine_tune_distributed_tpu.ops.attention import attention, head_major_reason, softcap, xla_attention
from llm_fine_tune_distributed_tpu.ops.int8 import (
    KV_QUANT_MODES,
    dequantize_kv_gather,
    quantize_kv_write,
)
from llm_fine_tune_distributed_tpu.ops.norms import rms_norm
from llm_fine_tune_distributed_tpu.ops.rope import apply_rope, rope_cos_sin

Params = Dict[str, Any]


def _linear(x, p, compute_dtype, quant_impl: str = "auto", adapter_idx=None,
            w8a8: bool = False):
    """x @ kernel (+ bias), with optional additive LoRA branch.

    LoRA params, when present (parallel/lora.py), live beside the kernel as
    ``lora_a [in, r]`` / ``lora_b [r, out]`` and contribute
    ``(alpha/r) * x @ A @ B`` (external-doc LoRA config: r=16, alpha=8).

    Multi-tenant POOLED adapters (infer/adapters.py) instead store stacked
    leaves ``lora_a_pool [max_adapters, in, r]`` / ``lora_b_pool
    [max_adapters, r, out]`` / ``lora_scale_pool [max_adapters]`` beside the
    kernel, and ``adapter_idx`` ([batch] int32) selects each row's adapter
    with a batched gather — different tenants co-batch in ONE dispatch.
    Pool row 0 is the identity adapter (all-zero A and B), so rows with
    idx 0 contribute an exactly-zero delta and stay bit-identical to the
    base model. The pool arrays are shape-stable: hot-loading or evicting
    an adapter is a value update, never a recompile.

    NF4-quantized kernels (QLoRA frozen base, ops/nf4.py) replace ``kernel``
    with sibling leaves ``kernel_nf4`` (+ absmax scales); the matmul then
    runs through the fused Pallas decode kernel or the XLA dequant path.
    Int8 weight-only kernels (inference, ops/int8.py) replace it with
    ``kernel_int8`` + ``kernel_int8_scale``. ``w8a8=True`` (the frozen-trunk
    training fast path, TrainConfig.frozen_compute="int8") runs those same
    leaves as a true int8 x int8 MXU matmul with dynamic per-row activation
    quantization instead of the weight-only dequant; adapters, biases, and
    every non-projection op stay in ``compute_dtype``.
    """
    if "kernel_int8" in p:
        q = {"int8": p["kernel_int8"], "int8_scale": p["kernel_int8_scale"]}
        if w8a8:
            from llm_fine_tune_distributed_tpu.ops.int8_matmul import (
                int8_w8a8_matmul,
            )

            y = int8_w8a8_matmul(x, q, compute_dtype=compute_dtype)
        else:
            from llm_fine_tune_distributed_tpu.ops.int8 import int8_matmul

            y = int8_matmul(x, q, compute_dtype=compute_dtype)
    elif "kernel_nf4" in p:
        from llm_fine_tune_distributed_tpu.ops.nf4 import QUANT_SUFFIXES, nf4_matmul

        q = {s: p[f"kernel_{s}"] for s in QUANT_SUFFIXES if f"kernel_{s}" in p}
        y = nf4_matmul(
            x.astype(compute_dtype), q, impl=quant_impl, compute_dtype=compute_dtype
        )
    else:
        y = x @ p["kernel"].astype(compute_dtype)
    if "lora_a" in p:
        a = p["lora_a"].astype(compute_dtype)
        b = p["lora_b"].astype(compute_dtype)
        y = y + (x @ a) @ b * p["lora_scale"].astype(compute_dtype)
    if adapter_idx is not None and "lora_a_pool" in p:
        # Batched gather: row i computes with adapter adapter_idx[i]'s A/B.
        # Mirrors the single-adapter branch's arithmetic ((x @ A) @ B * s)
        # so a pooled row matches the same adapter served via lora leaves.
        a = jnp.take(p["lora_a_pool"], adapter_idx, axis=0).astype(compute_dtype)
        bp = jnp.take(p["lora_b_pool"], adapter_idx, axis=0).astype(compute_dtype)
        sc = jnp.take(p["lora_scale_pool"], adapter_idx, axis=0).astype(compute_dtype)
        delta = jnp.einsum("bsr,bro->bso", jnp.einsum("bsi,bir->bsr", x, a), bp)
        y = y + delta * sc[:, None, None]
    if "bias" in p:
        y = y + p["bias"].astype(compute_dtype)
    return y


# ---------------------------------------------------------------------------
# The parts of a block. ``ModelConfig.layer(i)`` names layer i's attention and
# feed-forward; each kind is its half of ``init_params``, its function, and one
# row of the table after its group. ``lin`` is ``_linear`` bound to the block.
# ---------------------------------------------------------------------------


def _init_heads_attention(keys, config: ModelConfig, dense, dtype):
    h, d = config.hidden_size, config.resolved_head_dim
    qd, kvd = config.num_heads * d, config.num_kv_heads * d
    attn = {
        # with an output gate each head's query and gate lie side by side (HF Qwen3NextAttention;
        # HF afmoe keeps the gate as a gate_proj of its own, which models/hf_io.py joins by head)
        "q_proj": {"kernel": dense(next(keys), (h, qd * (2 if config.attention_output_gate else 1)))},
        "k_proj": {"kernel": dense(next(keys), (h, kvd))},
        "v_proj": {"kernel": dense(next(keys), (h, kvd))},
        "o_proj": {"kernel": dense(next(keys), (qd, h))},
    }
    if config.attention_bias:
        # HF Llama applies attention_bias to q/k/v/o alike; Qwen2 skips
        # the o_proj bias (attention_out_bias=False).
        attn["q_proj"]["bias"] = jnp.zeros((qd,), dtype)
        attn["k_proj"]["bias"] = jnp.zeros((kvd,), dtype)
        attn["v_proj"]["bias"] = jnp.zeros((kvd,), dtype)
        if config.attention_out_bias:
            attn["o_proj"]["bias"] = jnp.zeros((h,), dtype)
    if config.qk_norm:
        one = jnp.zeros if config.zero_centered_norm else jnp.ones
        attn["q_norm"] = {"weight": one((d,), dtype)}
        attn["k_norm"] = {"weight": one((d,), dtype)}
    return attn


def _scaled_queries(xq, config: ModelConfig):
    """``q_proj``'s output under the config's attention scale (Granite's ``attention_multiplier``: the scores are ``m q
    k^T`` where every other model here has ``d ** -0.5 q k^T``), folded into q as ``m d ** 0.5`` so that every attention
    path, the flash kernels among them, keeps its one scale; the product's epilogue, and exact where the factor is a
    power of two (Granite 4.0-H: 0.015625 x 8). None: the projection's output as it came, no operation."""
    if config.attention_multiplier is None:
        return xq
    return xq * jnp.asarray(config.attention_multiplier * config.resolved_head_dim ** 0.5, xq.dtype)


def _heads_qkv(attn_p, hid, cos, sin, config: ModelConfig, lin, rope):
    """q ``[b, s, heads, d]``, k and v ``[b, s, kv_heads, d]`` from their own
    projections, and the output gate ``[b, s, heads * d]`` (None without
    ``attention_output_gate``). ``rope``: the plan's bool, or a traced bool
    scalar where the layer index is data (the pipeline's layer scan over
    NoPE-interleaved layers): then both are computed and one selected. Tables
    narrower than the head rotate its first dimensions (``ops/rope.apply_rope``).

    This is the XLA form of what stands between the projections and the
    attention call, under the scope ``attn_in``: every backend's, serving's,
    and the reference ``_heads_qkv_head_major`` (the fused pass) is held to."""
    b, s, _ = hid.shape
    d = config.resolved_head_dim
    xq, xk, xv = lin(hid, attn_p["q_proj"]), lin(hid, attn_p["k_proj"]), lin(hid, attn_p["v_proj"])
    with scope("attn_in"):
        gate = None
        if config.attention_output_gate:
            q = xq.reshape(b, s, config.num_heads, 2 * d)
            q, gate = q[..., :d], q[..., d:].reshape(b, s, config.num_heads * d)
        else:
            q = xq.reshape(b, s, config.num_heads, d)
        q = _scaled_queries(q, config)
        k = xk.reshape(b, s, config.num_kv_heads, d)
        v = xv.reshape(b, s, config.num_kv_heads, d)
        if config.qk_norm:
            # Qwen3, afmoe: per-head RMSNorm over head_dim, before RoPE (HF Qwen3Attention);
            # zero-centred where the model's norms are (Qwen3-Next)
            zc = config.zero_centered_norm
            with scope("qk_norm"):
                q = rms_norm(q, attn_p["q_norm"]["weight"], config.rms_norm_eps, zero_centered=zc)
                k = rms_norm(k, attn_p["k_norm"]["weight"], config.rms_norm_eps, zero_centered=zc)
        if not isinstance(rope, bool):
            qr, kr = apply_rope(q, k, cos, sin)
            q = jnp.where(rope, qr, q)
            k = jnp.where(rope, kr, k)
        elif rope:
            q, k = apply_rope(q, k, cos, sin)
    return q, k, v, gate


def _heads_qkv_head_major(attn_p, hid, cos, sin, config: ModelConfig, lin, rope):
    """``_heads_qkv``'s q, k, v HEAD-MAJOR, ``[b, heads, s, d]`` and ``[b,
    kv_heads, s, d]`` as the flash kernels read them, through the fused IN
    pass (``ops/rope.heads_in``: q/k norms, rope and the layout in one kernel,
    under the same scope ``attn_in``), and the gate ``[b, s, heads * d]``.
    The pass reads the projections' flat outputs. Where ``q_proj`` holds each
    head's ``[q | gate]`` it is the LEAF that is cut by head (``_by_columns``),
    and q and the gate are two products: the gate never meets the pass, and
    its cotangent feeds its own product (as ONE product the gate's cotangent
    had to lie beside dq in one array, 128 MiB more a layer at the step's
    peak: PERF.md, PR 41)."""
    d = config.resolved_head_dim
    if config.attention_output_gate:
        xq, gate = _by_columns(hid, attn_p["q_proj"], (0, d, 2 * d), lin, heads=config.num_heads)
    else:
        xq, gate = lin(hid, attn_p["q_proj"]), None
    xq, xk, xv = _scaled_queries(xq, config), lin(hid, attn_p["k_proj"]), lin(hid, attn_p["v_proj"])
    weights = {}
    if config.qk_norm:  # the pass takes a norm's MULTIPLIER: 1 + w where the model's norms are zero-centred
        one = 1.0 if config.zero_centered_norm else 0.0
        weights = {f"{x}_weight": one + attn_p[f"{x}_norm"]["weight"].astype(jnp.float32) for x in "qk"}
    with scope("attn_in"):
        q, k, v = rope_ops.heads_in(
            xq, xk, xv, cos, sin, heads=config.num_heads, kv_heads=config.num_kv_heads, eps=config.rms_norm_eps,
            rope=rope, **weights)
    return q, k, v, gate


def _init_latent_attention(keys, config: ModelConfig, dense, dtype):
    # HF DeepseekV3Attention names, q_lora_rank null
    h, nh, r = config.hidden_size, config.num_heads, config.kv_lora_rank
    dn, dr, dv = config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim
    return {
        "q_proj": {"kernel": dense(next(keys), (h, nh * (dn + dr)))},
        "kv_a_proj_with_mqa": {"kernel": dense(next(keys), (h, r + dr))},
        "kv_a_layernorm": {"weight": jnp.ones((r,), dtype)},
        "kv_b_proj": {"kernel": dense(next(keys), (r, nh * (dn + dv)))},
        "o_proj": {"kernel": dense(next(keys), (nh * dv, h))},
    }


def _latent_qkv(attn_p, hid, cos, sin, config: ModelConfig, lin, rope=True):
    """q, k, v of latent attention (MLA) in its training form, for the
    ordinary attention paths: ``hid [b, s, h]`` -> q, k ``[b, s, heads,
    qk_nope + qk_rope]`` and v ``[b, s, heads, v_head_dim]``. k and v come up
    from one normed latent of ``kv_lora_rank``; the rope key (one per token)
    is rotated once and shared by every head where the layer's plan says
    ``rope`` (DeepSeek-V3, Moonlight: every layer), and taken as it comes where
    it does not (Kimi Linear's ``mla_use_nope``: no position signal at all, the
    scores run over all ``qk_nope + qk_rope`` columns unrotated). ``cos``/``sin``
    are tables of ``qk_rope_head_dim``; halves are rotated (HF de-interleaves
    DeepSeek's stored pairs first; ``models/hf_io.py`` does that to the
    weights)."""
    b, s, _ = hid.shape
    nh, r = config.num_heads, config.kv_lora_rank
    dn, dr, dv = config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim
    q = lin(hid, attn_p["q_proj"]).reshape(b, s, nh, dn + dr)
    latent = lin(hid, attn_p["kv_a_proj_with_mqa"])
    c_kv = rms_norm(latent[..., :r], attn_p["kv_a_layernorm"]["weight"], config.rms_norm_eps)
    kv = lin(c_kv, attn_p["kv_b_proj"]).reshape(b, s, nh, dn + dv)
    q_pe, k_pe = q[..., dn:], latent[..., r:].reshape(b, s, 1, dr)
    if not isinstance(rope, bool):  # (the pipeline's layer scan: the layer index is data)
        rotated = apply_rope(q_pe, k_pe, cos, sin)
        q_pe, k_pe = jnp.where(rope, rotated[0], q_pe), jnp.where(rope, rotated[1], k_pe)
    elif rope:
        q_pe, k_pe = apply_rope(q_pe, k_pe, cos, sin)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, nh, dr))], axis=-1)
    return q, k, kv[..., dn:], None


def _why_not_head_major(hid, cos, config, plan, *, attention_impl, mesh, scale, mask, cache_entry):
    """Why a layer of heads cannot hand its q, k, v to the flash kernels head-major through the fused IN pass
    (``_heads_qkv_head_major``), or None: read from what the call can see, no switch. It can where ``attention()``
    would run the kernels on the whole row in one device's program (``ops/attention.head_major_reason``: training and
    full-row evaluation on a TPU, a head of whole lane registers) and the pass takes the tables; with a cache entry,
    an explicit mask, ring or Ulysses attention over a live seq axis, the pipeline's stages, a mesh of several devices
    or a CPU the XLA form stands."""
    if cache_entry is not None:
        return "a cache entry"
    if mask is not None:
        return "an explicit mask"
    b, s, _ = hid.shape
    d = config.resolved_head_dim
    q, k = (jax.ShapeDtypeStruct((b, s, n, d), hid.dtype) for n in (config.num_heads, config.num_kv_heads))
    return head_major_reason(
        q, k, k, impl=attention_impl, mesh=mesh, scale=scale, logit_softcap=config.attn_logit_softcap, sliding_window=plan.window,
    ) or rope_ops.why_not_fused(b, s, d, cos)


def _softmax_mixer(qkv, qkv_head_major=None):
    """The mixer of a layer of softmax attention, over ``qkv`` (``_heads_qkv``
    or ``_latent_qkv``): q, k, v, the cache where there is one, the attention
    dispatch, the output gate where the model has one, ``o_proj``.
    ``qkv_head_major``: the same q, k, v through a fused pass that writes them
    as the flash kernels read them, for the kind of layer that has one; the
    call takes it where ``_why_not_head_major`` has no objection, and
    ``ops/rope.CALLS`` counts which form each traced call took and why."""

    def mixer(
        attn_p, hid, cos, sin, *, config, plan, lin, rope, compute_dtype, attention_impl, mesh,
        padding_mask, segment_ids, mask, cache_entry, cache_pos, block_tables,
    ):
        b, s, _ = hid.shape
        # Gemma2: query_pre_attn_scalar scale, logit softcap (None for Llama-family)
        scale = None if config.query_pre_attn_scalar is None else float(config.query_pre_attn_scalar) ** -0.5
        asked = dict(
            impl=attention_impl, padding_mask=padding_mask, segment_ids=segment_ids, causal=True, sliding_window=plan.window,
            mesh=mesh, scale=scale, logit_softcap=config.attn_logit_softcap,
        )
        why_xla = "this kind of layer has no fused pass"
        if qkv_head_major is not None:
            why_xla = _why_not_head_major(
                hid, cos, config, plan, attention_impl=attention_impl, mesh=mesh, scale=scale, mask=mask, cache_entry=cache_entry)
            rope_ops.count_call(
                (b, s, config.num_heads, config.num_kv_heads, config.resolved_head_dim, cos.shape[-1],
                 "norm" * config.qk_norm, "gate" * config.attention_output_gate), why_xla)
        q, k, v, gate = (qkv_head_major if why_xla is None else qkv)(attn_p, hid, cos, sin, config, lin, rope)
        heads_width = config.num_heads * v.shape[-1]
        if why_xla is None:
            new_entry, out = None, attention(q, k, v, head_major=True, **asked)
        else:
            k, v, new_entry, out = _cache_write_and_view(
                cache_entry, q, k, v, cache_pos, block_tables, plan=plan, compute_dtype=compute_dtype, scale=scale,
                fusable=padding_mask is None and plan.window is None and config.attn_logit_softcap is None,
            )
        if out is None and mask is not None:
            out = xla_attention(
                q, k, v, mask=mask, causal=False, scale=scale, logit_softcap=config.attn_logit_softcap
            )
        elif out is None:
            out = attention(q, k, v, **asked)
        out = out.reshape(b, s, heads_width)
        if gate is not None:
            with scope("attn_gate"):  # outside the kernel: it multiplies what the kernel wrote
                out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
        return lin(out, attn_p["o_proj"]), new_entry

    return mixer


def _init_linear_attention(keys, config: ModelConfig, dense, dtype):
    """HF ``Qwen3NextGatedDeltaNet``'s leaves. The columns of ``in_proj_qkvz``
    lie ``[q | k | v | z]`` and of ``in_proj_ba`` ``[b | a]``, each part by
    head (HF interleaves both by key head: ``models/hf_io.py`` moves the
    columns); ``conv1d/weight`` is ``[taps, q | k | v channels]`` (torch's
    ``[channels, 1, taps]`` transposed). ``A_log = log U(0, 16)`` and
    ``dt_bias = 1`` as HF draws them; the gated norm's weight is plain (1)."""
    h, taps = config.hidden_size, config.linear_conv_kernel_dim
    hv = config.linear_num_value_heads
    kd = config.linear_num_key_heads * config.linear_key_head_dim
    vd = hv * config.linear_value_head_dim
    return {
        "in_proj_qkvz": {"kernel": dense(next(keys), (h, 2 * kd + 2 * vd))},
        "in_proj_ba": {"kernel": dense(next(keys), (h, 2 * hv))},
        "conv1d": {"weight": dense(next(keys), (taps, 2 * kd + vd))},
        "A_log": jnp.log(jax.random.uniform(next(keys), (hv,), jnp.float32, 1e-3, 16.0)).astype(dtype),
        "dt_bias": jnp.ones((hv,), dtype),
        "norm": {"weight": jnp.ones((config.linear_value_head_dim,), dtype)},
        "out_proj": {"kernel": dense(next(keys), (vd, h))},
    }


_CUT_BY_COLUMN = ("kernel", "lora_b", "bias")


def _by_columns(hid, p, cuts, lin, heads: int = 1):
    """``lin(hid, p)`` as one array for each run of output columns ``cuts[i] .. cuts[i + 1]`` (with ``heads``: of every
    head's ``cuts[-1]`` columns, a run's heads side by side: ``q_proj``'s ``[q | gate]`` by head). Where the leaf can be
    cut (a plain kernel, with or without LoRA and a bias) it is the LEAF that is cut, a few MB once a call, and each run
    is a product of its own: the runs and their cotangents are separate arrays from birth, and no ``[b, s, .]``
    activation is sliced, nor its cotangent padded and added (PERF.md, PR 39). Any other leaf (a quantized kernel, a
    pool of adapters) makes one product, whose output is cut."""
    runs = list(zip(cuts, cuts[1:]))

    def run_of(x, lo, hi):
        if heads == 1:
            return x[..., lo:hi]
        return x.reshape(*x.shape[:-1], heads, cuts[-1])[..., lo:hi].reshape(*x.shape[:-1], heads * (hi - lo))

    if set(p) - {*_CUT_BY_COLUMN, "lora_a", "lora_scale"}:
        y = lin(hid, p)
        return [run_of(y, lo, hi) for lo, hi in runs]
    return [lin(hid, {name: run_of(x, lo, hi) if name in _CUT_BY_COLUMN else x for name, x in p.items()}) for lo, hi in runs]


def _whole_rows_only(config: ModelConfig, segment_ids, cache_entry, kind="linear-attention", module="ops/gated_delta.py"):
    """What a mixer that is a recurrence over time refuses, and why."""
    if segment_ids is not None:
        raise NotImplementedError(
            f"model {config.name!r} has {kind} layers and the batch is packed (segment_ids): the "
            "recurrent state and the convolution would have to restart at every segment boundary, which "
            f"{module} does not do yet (ROADMAP.md, Reach D); train it with packing off"
        )
    if cache_entry is not None:
        raise NotImplementedError(
            f"a {kind} layer has the training form only; its cache is a state, not keys and values"
        )


def _linear_mixer(attn_p, hid, cos, sin, *, config, lin, segment_ids, cache_entry, **_):
    """A Gated DeltaNet mixer (``ops/gated_delta.py``): q, k, v through a
    causal convolution and silu, q and k l2-normed a head (``mixer_in``, one
    pass), the gated delta rule per value head in place of softmax attention,
    a norm gated by ``silu(z)`` (``gated_norm``, one pass), ``out_proj``;
    everything between the projections flat, ``[b, s, heads x d]``. No rope
    (``cos``/``sin`` unused), no mask: the rule is causal, and what a
    right-padded row computes at its pads reaches no real token."""
    _whole_rows_only(config, segment_ids, cache_entry)
    b, s, _ = hid.shape
    hk, hv = config.linear_num_key_heads, config.linear_num_value_heads
    dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
    kd, vd = hk * dk, hv * dv
    xq, xk, xv, z = _by_columns(hid, attn_p["in_proj_qkvz"], (0, kd, 2 * kd, 2 * kd + vd, 2 * kd + 2 * vd), lin)
    ba = lin(hid, attn_p["in_proj_ba"]).astype(jnp.float32)
    with scope("gdn_conv"):  # convolution, silu, l2 norms and q's scale: one pass, flat
        q, k, v = gated_delta.mixer_in(xq, xk, xv, attn_p["conv1d"]["weight"], hk)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(attn_p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., hv:] + attn_p["dt_bias"].astype(jnp.float32)
    )  # a log decay, <= 0
    with scope("gdn_scan"):
        o = gated_delta.gated_delta_rule(
            q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk), v.reshape(b, s, hv, dv), g, beta)
    with scope("gdn_gate_norm"):
        o = gated_delta.gated_norm(o.reshape(b, s, vd), z, attn_p["norm"]["weight"], config.rms_norm_eps)
    return lin(o.astype(hid.dtype), attn_p["out_proj"]), None


def _init_kda_attention(keys, config: ModelConfig, dense, dtype):
    """HF ``KimiDeltaAttention``'s leaves (moonshotai ``kimi_linear``), under the subtree and the names the other
    linear mixer has where the part is the same (``conv1d``, ``norm``, ``out_proj``; HF: three ``*_conv1d``, ``o_norm``,
    ``o_proj``: ``models/hf_io.py``). ``conv1d/weight`` is the ONE ``[taps, q | k | v channels]`` leaf the in pass
    reads (HF keeps a convolution each for q, k and v). The decay's pair ``f_a_proj`` / ``f_b_proj`` and the output
    gate's ``g_a_proj`` / ``g_b_proj`` have no bias; ``A_log`` is one a head (``log U(1, 16)``), ``dt_bias`` one a
    channel, drawn so that ``softplus(dt_bias)`` lies in [0.001, 0.1] (log-uniform), as the family draws them."""
    h, taps = config.hidden_size, config.linear_conv_kernel_dim
    hv, dk = config.linear_num_value_heads, config.linear_key_head_dim
    kd, vd = hv * dk, hv * config.linear_value_head_dim
    dt = jnp.exp(jax.random.uniform(next(keys), (kd,), jnp.float32, math.log(1e-3), math.log(0.1)))
    return {
        "q_proj": {"kernel": dense(next(keys), (h, kd))},
        "k_proj": {"kernel": dense(next(keys), (h, kd))},
        "v_proj": {"kernel": dense(next(keys), (h, vd))},
        "conv1d": {"weight": dense(next(keys), (taps, 2 * kd + vd))},
        "b_proj": {"kernel": dense(next(keys), (h, hv))},
        "f_a_proj": {"kernel": dense(next(keys), (h, config.linear_decay_rank))},
        "f_b_proj": {"kernel": dense(next(keys), (config.linear_decay_rank, kd))},
        "A_log": jnp.log(jax.random.uniform(next(keys), (hv,), jnp.float32, 1.0, 16.0)).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),  # softplus^-1
        "g_a_proj": {"kernel": dense(next(keys), (h, config.linear_gate_rank))},
        "g_b_proj": {"kernel": dense(next(keys), (config.linear_gate_rank, vd))},
        "norm": {"weight": jnp.ones((config.linear_value_head_dim,), dtype)},
        "out_proj": {"kernel": dense(next(keys), (vd, h))},
    }


def _kda_mixer(attn_p, hid, cos, sin, *, config, lin, segment_ids, cache_entry, **_):
    """A Kimi Delta Attention mixer: ``_linear_mixer``'s passes and rule around what differs. q, k, v come from
    projections of their own (so nothing is cut by column); the decay is a VECTOR over a head's ``d_k`` channels,
    ``g = -exp(A_log[head]) softplus((x W_fa) W_fb + dt_bias)``, which ``gated_delta_rule`` reads from ``g``'s shape;
    beta ``sigmoid(x W_b)`` a head; the norm after the rule is gated by ``sigmoid((x W_ga) W_gb)``. The four low-rank
    products and the softplus stand under the scope ``kda_gates``. As many key heads as value heads."""
    _whole_rows_only(config, segment_ids, cache_entry)
    b, s, _ = hid.shape
    hv, dk, dv = config.linear_num_value_heads, config.linear_key_head_dim, config.linear_value_head_dim
    xq, xk, xv = lin(hid, attn_p["q_proj"]), lin(hid, attn_p["k_proj"]), lin(hid, attn_p["v_proj"])
    beta = jax.nn.sigmoid(lin(hid, attn_p["b_proj"]).astype(jnp.float32))
    with scope("kda_gates"):
        decay_in = lin(lin(hid, attn_p["f_a_proj"]), attn_p["f_b_proj"]).astype(jnp.float32)
        gate = lin(lin(hid, attn_p["g_a_proj"]), attn_p["g_b_proj"])
        g = -jnp.exp(attn_p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
            decay_in + attn_p["dt_bias"].astype(jnp.float32)).reshape(b, s, hv, dk)  # a log decay a channel, <= 0
    with scope("gdn_conv"):
        q, k, v = gated_delta.mixer_in(xq, xk, xv, attn_p["conv1d"]["weight"], hv)
    with scope("gdn_scan"):
        o = gated_delta.gated_delta_rule(
            q.reshape(b, s, hv, dk), k.reshape(b, s, hv, dk), v.reshape(b, s, hv, dv), g, beta)
    with scope("gdn_gate_norm"):
        o = gated_delta.gated_norm(o.reshape(b, s, hv * dv), gate, attn_p["norm"]["weight"], config.rms_norm_eps,
                                   activation="sigmoid")
    return lin(o.astype(hid.dtype), attn_p["out_proj"]), None


def _init_eva_attention(keys, config: ModelConfig, dense, dtype):
    """A layer of heads (no bias, as many key heads as query heads) with EVA attention's two leaves a head, ``[heads,
    d]`` each: ``adaptive_phi``, the pooling's query, and ``adaptive_mu_k``, what every summary key is moved by; both
    drawn ``clip(normal, -1, 1) * d ** -0.5`` as the family draws them."""
    attn = _init_heads_attention(keys, config, dense, dtype)
    shape, scale = (config.num_heads, config.resolved_head_dim), config.resolved_head_dim ** -0.5
    for name in ("adaptive_phi", "adaptive_mu_k"):
        attn[name] = (jnp.clip(jax.random.normal(next(keys), shape, jnp.float32), -1.0, 1.0) * scale).astype(dtype)
    return attn


def _eva_mixer(attn_p, hid, cos, sin, *, config, plan, lin, rope, attention_impl, mesh, segment_ids, mask, cache_entry, **_):
    """An EVA mixer (``ops/eva_attention.py``): the projections, rope and the hand-over of the layout as any layer of
    heads makes them (``_heads_qkv``, or the fused IN pass where the operator runs its kernels), then the operator in
    the attention call's place: softmax over the token's own aligned window and one learned summary a chunk of every
    earlier window. No padding mask: the operator is causal, and what a right-padded row computes at its pads
    reaches no real token."""
    if segment_ids is not None:
        raise NotImplementedError(
            f"model {config.name!r} has EVA attention and the batch is packed (segment_ids): windows and chunks would "
            "have to restart at every document's start, which ops/eva_attention.py does not do yet (ROADMAP.md, "
            "Reach); train it with packing off"
        )
    if cache_entry is not None or mask is not None:
        raise NotImplementedError(
            "an EVA layer has the training form only; its cache is a window of keys and values beside a growing list "
            "of summaries, not one buffer of keys and values"
        )
    b, s, _ = hid.shape
    d = config.resolved_head_dim
    # the fused IN pass hands q, k, v over head-major, as the operator's kernels read them: where those run
    if eva_attention._program((b, config.num_heads, s, d), hid.dtype, window=config.eva_window, chunk=config.eva_chunk, mesh=mesh) != "kernels":
        why_xla = "the operator runs its XLA form"
    elif attention_impl != "flash":
        why_xla = f"attention_impl is {attention_impl!r}"
    else:
        why_xla = rope_ops.why_not_fused(b, s, d, cos)
    rope_ops.count_call((b, s, config.num_heads, config.num_kv_heads, d, cos.shape[-1], "", ""), why_xla)
    q, k, v, _ = (_heads_qkv_head_major if why_xla is None else _heads_qkv)(attn_p, hid, cos, sin, config, lin, rope)
    out = eva_attention.eva_attention(
        q, k, v, attn_p["adaptive_phi"], attn_p["adaptive_mu_k"], window=config.eva_window, chunk=config.eva_chunk,
        scale=d ** -0.5, head_major=why_xla is None, mesh=mesh,
    )
    return lin(out.reshape(b, s, config.num_heads * d), attn_p["o_proj"]), None


def _init_ssd_attention(keys, config: ModelConfig, dense, dtype):
    """HF ``GraniteMoeHybridMambaLayer``'s leaves (the Bamba mixer) under the subtree ``mamba``. The columns of
    ``in_proj`` lie ``[z | x | B | C | dt]`` (inner, inner, groups x N, groups x N, heads); ``conv1d/weight`` is
    ``[taps, x | B | C channels]`` (torch's ``[channels, 1, taps]`` transposed) beside its ``bias``. Drawn as the family
    draws them: ``A_log = log(1 .. heads)``, ``D = 1``, ``dt_bias = softplus^-1`` of a log-uniform draw in [0.001, 0.1],
    the gated norm's weight 1 over the whole inner width, no bias on either projection."""
    h, heads = config.hidden_size, config.mamba_n_heads
    inner, bc = heads * config.mamba_d_head, 2 * config.mamba_n_groups * config.mamba_d_state
    dt = jnp.exp(jax.random.uniform(next(keys), (heads,), jnp.float32, math.log(1e-3), math.log(0.1)))
    return {
        "in_proj": {"kernel": dense(next(keys), (h, 2 * inner + bc + heads))},
        "conv1d": {"weight": dense(next(keys), (config.mamba_d_conv, inner + bc)), "bias": jnp.zeros((inner + bc,), dtype)},
        "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)).astype(dtype),
        "D": jnp.ones((heads,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),  # softplus^-1
        "norm": {"weight": jnp.ones((inner,), dtype)},
        "out_proj": {"kernel": dense(next(keys), (inner, h))},
    }


def _ssd_mixer(attn_p, hid, cos, sin, *, config, lin, mesh, segment_ids, cache_entry, **_):
    """A Mamba-2 mixer (``ops/ssd.py``): ``in_proj``'s LEAF cut by column into z, x, ``[B | C]`` and dt (four products,
    no ``[b, s, 8512]`` activation sliced: ``_by_columns``); x and ``[B | C]`` through the causal convolution with its
    bias and silu, ``dt = softplus(dt + dt_bias)`` (``ssd_in``); the state-space scan a head with the decay ``exp(dt
    A)``, ``A = -exp(A_log)``, one B and C a group of heads, the skip ``D x`` (``ssd_scan``); the gate ``silu(z)`` and
    THEN one norm over a group's channels (``ssd_gate_norm``); ``out_proj``. No rope (``cos``/``sin`` unused), no
    mask: the scan is causal, and what a right-padded row computes at its pads reaches no real token."""
    _whole_rows_only(config, segment_ids, cache_entry, kind="state-space", module="ops/ssd.py")
    b, s, _ = hid.shape
    heads, p, n, groups = config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state, config.mamba_n_groups
    inner, gn = heads * p, groups * n
    z, x, bc, dt = _by_columns(hid, attn_p["in_proj"], (0, inner, 2 * inner, 2 * inner + 2 * gn, 2 * inner + 2 * gn + heads), lin)
    with scope("ssd_in"):
        x, bc, dt = ssd.mixer_in(x, bc, dt, attn_p["conv1d"]["weight"], attn_p["conv1d"]["bias"], attn_p["dt_bias"])
    with scope("ssd_scan"):
        y = ssd.ssd_scan(
            x.reshape(b, s, heads, p), dt, -jnp.exp(attn_p["A_log"].astype(jnp.float32)),
            bc[..., :gn].reshape(b, s, groups, n), bc[..., gn:].reshape(b, s, groups, n), attn_p["D"].astype(jnp.float32), mesh=mesh)
    with scope("ssd_gate_norm"):
        y = ssd.gated_norm(y.reshape(b, s, inner), z, attn_p["norm"]["weight"], config.rms_norm_eps, groups=groups)
    return lin(y.astype(hid.dtype), attn_p["out_proj"]), None


# LayerPlan.attention -> (the layer's subtree of that kind, the scope its device
# time is read under, its half of init_params, the mixer: normed input ->
# (output [b, s, hidden], new cache entry))
_ATTENTION = {
    "heads": ("self_attn", "attn", _init_heads_attention, _softmax_mixer(_heads_qkv, _heads_qkv_head_major)),
    "latent": ("self_attn", "attn", _init_latent_attention, _softmax_mixer(_latent_qkv)),
    "linear": ("linear_attn", "linear_attn", _init_linear_attention, _linear_mixer),
    "kda": ("linear_attn", "linear_attn", _init_kda_attention, _kda_mixer),
    "eva": ("self_attn", "attn", _init_eva_attention, _eva_mixer),
    "ssd": ("mamba", "linear_attn", _init_ssd_attention, _ssd_mixer),
}


def _init_dense_mlp(keys, config: ModelConfig, dense, dtype):
    h, f = config.hidden_size, config.intermediate_size
    mlp = {
        "gate_proj": {"kernel": dense(next(keys), (h, f))},
        "up_proj": {"kernel": dense(next(keys), (h, f))},
        "down_proj": {"kernel": dense(next(keys), (f, h))},
    }
    if config.mlp_bias:
        mlp["gate_proj"]["bias"] = jnp.zeros((f,), dtype)
        mlp["up_proj"]["bias"] = jnp.zeros((f,), dtype)
        mlp["down_proj"]["bias"] = jnp.zeros((h,), dtype)
    return mlp


def _dense_mlp(p, hid, lin, config: ModelConfig, **_):
    gate = lin(hid, p["gate_proj"])
    up = lin(hid, p["up_proj"])
    # Named so remat_policy="mlp" can save JUST this [b, s, f] product: the
    # gate/up matmuls are ~58% of a block's param FLOPs, so saving their
    # fused output avoids most of full-remat's recompute at one tensor per
    # layer of extra HBM (vs. two for saving gate and up separately).
    if config.hidden_act == "gelu_tanh":
        act = jax.nn.gelu(gate.astype(jnp.float32), approximate=True).astype(gate.dtype)
    elif config.hidden_act == "gelu":
        act = jax.nn.gelu(gate.astype(jnp.float32), approximate=False).astype(gate.dtype)
    else:
        act = jax.nn.silu(gate)
    prod = checkpoint_name(act * up, "mlp_act")
    return lin(prod, p["down_proj"]), {}


def _capacity_experts(p, hid, lin, config: ModelConfig, *, compute_dtype, mesh, padding_mask, segment_ids, serving):
    """Mixtral's experts through the one-hot capacity dispatch. Counts the
    layer's load-balancing loss (float32 scalar)."""
    # token-level real/pad mask for routing: packed batches encode pads
    # as segment 0; the cache path's padding_mask covers the KV buffer
    # (wrong length for the current chunk) and is skipped
    token_mask = None
    if segment_ids is not None:
        token_mask = segment_ids > 0
    elif padding_mask is not None and padding_mask.shape[-1] == hid.shape[1]:
        token_mask = padding_mask
    y, aux = moe.moe_mlp(
        p, hid, config, compute_dtype, mesh=mesh, token_mask=token_mask,
        # decode/prefill (KV cache live) is dropless like HF Mixtral:
        # capacity drops would make outputs depend on batch/chunk shape
        dropless=serving,
    )
    return y, {"router_aux": aux}


def _grouped_experts(p, hid, lin, config: ModelConfig, *, compute_dtype, mesh, **_):
    """The routed experts held here (no capacity, no dropped token), beside
    the shared experts where the model has them (DeepSeek-V3 does, Mellum does
    not). Counts the (token, expert) pairs of each held expert (``[held]`` int32)."""
    if mesh is not None and dict(mesh.shape).get("expert", 1) > 1:
        raise NotImplementedError(
            "grouped experts over a mesh's expert axis: the exchange is not written yet "
            "(ROADMAP.md, Reach A); name this process's share in ModelConfig.held_experts"
        )
    y, load = moe.grouped_moe_mlp(p, hid, config, compute_dtype)
    with scope("shared_expert"):
        shared = p.get("shared_experts")
        if shared is not None:
            prod = checkpoint_name(
                jax.nn.silu(lin(hid, shared["gate_proj"])) * lin(hid, shared["up_proj"]), "mlp_act"
            )
            out = lin(prod, shared["down_proj"])
            if "shared_expert_gate" in p:  # Qwen3-Next: one column, a sigmoid on the whole shared expert
                out = out * jax.nn.sigmoid(lin(hid, p["shared_expert_gate"]).astype(jnp.float32)).astype(out.dtype)
            y = y + out
    return y, {"expert_load": load}


def _one_key(init):
    """ops/moe's inits take one key and split it themselves."""
    return lambda keys, config, dense, dtype: init(next(keys), config, dtype)


# LayerPlan.feed_forward -> (the layer's subtree of that kind, its half of
# init_params, normed input -> (output, what the layer counted))
_FEED_FORWARD = {
    "dense": ("mlp", _init_dense_mlp, _dense_mlp),
    "capacity_experts": ("block_sparse_moe", _one_key(moe.init_moe_params), _capacity_experts),
    "grouped_experts": ("mlp", _one_key(moe.init_grouped_moe_params), _grouped_experts),
}


def report_shapes(config: ModelConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    """The structure of ``forward_with_report``'s report for this model, from
    the plan alone: what a caller needs to carry the report through a scan."""
    kinds = [config.layer(i).feed_forward for i in range(config.num_layers)]
    shapes = {}
    if "capacity_experts" in kinds:
        shapes["router_aux"] = jax.ShapeDtypeStruct((), jnp.float32)
    if "grouped_experts" in kinds:
        shapes["expert_load"] = jax.ShapeDtypeStruct(
            (kinds.count("grouped_experts"), len(config.held_expert_ids)), jnp.int32
        )
    return shapes


# how what layers counted becomes the report's: summed, or stacked [layers, ...]
_OVER_LAYERS = {"router_aux": lambda each: sum(each, jnp.float32(0.0)), "expert_load": jnp.stack}


def _report(counted_by_layer) -> Dict[str, jax.Array]:
    """What the layers counted, as one pytree (``report_shapes``)."""
    keys = dict.fromkeys(k for counted in counted_by_layer for k in counted)
    return {k: _OVER_LAYERS[k]([c[k] for c in counted_by_layer if k in c]) for k in keys}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(rng, config: ModelConfig, dtype=jnp.float32) -> Params:
    """Random init (normal 0.02, HF convention). Returns the params pytree."""
    h, v = config.hidden_size, config.vocab_size
    kda = any(config.layer(i).attention == "kda" for i in range(config.num_layers))
    keys = iter(jax.random.split(rng, 2 + config.num_layers * (17 if kda else 9)))  # (a layer draws at most 9, a KDA mixer's 14, a Mamba-2 layer 7)

    def dense(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)

    # Gemma zero-centered RMSNorm stores the weight as an offset from 1
    # (init 0); Llama-style stores the multiplier itself (init 1).
    def norm_init():
        if config.zero_centered_norm:
            return {"weight": jnp.zeros((h,), dtype)}
        return {"weight": jnp.ones((h,), dtype)}

    layers = {}
    for i in range(config.num_layers):
        plan = config.layer(i)
        mixer_subtree, _, mixer_init, _ = _ATTENTION[plan.attention]
        layer = {
            "input_layernorm": norm_init(),
            mixer_subtree: mixer_init(keys, config, dense, dtype),
            "post_attention_layernorm": norm_init(),
        }
        if config.sandwich_norms:
            # Gemma2, afmoe: post_attention_layernorm norms the attention OUTPUT;
            # pre_feedforward replaces Llama's post_attention pre-MLP role
            # (HF afmoe's names, pre_mlp / post_mlp_layernorm: models/hf_io.py)
            layer["pre_feedforward_layernorm"] = norm_init()
            layer["post_feedforward_layernorm"] = norm_init()
        subtree, init, _ = _FEED_FORWARD[plan.feed_forward]
        layer[subtree] = init(keys, config, dense, dtype)
        layers[str(i)] = layer

    params: Params = {
        "model": {
            "embed_tokens": {"weight": dense(next(keys), (v, h))},
            "layers": layers,
            "norm": norm_init(),
        }
    }
    if not config.tie_word_embeddings:
        # (several next-token heads lie side by side by column: head i's vocabulary at columns [i * v, (i + 1) * v))
        params["lm_head"] = {"kernel": dense(next(keys), (h, v * config.num_pred_heads))}
    return params


def _cache_write_and_view(entry, q, k, v, cache_pos, block_tables, *, plan, fusable: bool, scale, compute_dtype):
    """Write this chunk's ``k``/``v`` into a layer's cache entry and hand back
    what to attend over: ``(k, v, new_entry, out)``. ``out`` is None, or the
    attention output already made (the int8 pool's fused decode kernel; k and
    v are then None). No entry: ``k``, ``v`` as they came. The three layouts
    are told apart here and nowhere else in the model: the dense buffer
    (``init_cache``; ``block_tables`` None), the paged pool
    (``init_paged_cache``) in bf16, and in int8 with ``k_scale``/``v_scale``.
    ``fusable``: the scores are plain causal ones (no padding mask, window or
    softcap), all the fused kernel computes; ``scale`` None = ``d ** -0.5``."""
    if entry is None:
        return k, v, None, None
    if plan.attention == "latent":
        raise NotImplementedError(
            "latent attention (kv_lora_rank) has the training form only; its cache is a third layout"
        )
    b, s = k.shape[:2]
    if block_tables is None:
        # Decode/prefill with a fixed-size KV buffer: write k,v at cache_pos.
        # A scalar cache_pos writes the same slots for every row (single
        # prompt / aligned batch); a [batch] vector writes per-row slots —
        # ragged batched decode, where row i's token t lives at slot
        # len_i + t so the slot == position invariant holds per row.
        # Out-of-bounds slots DROP (jax scatter default): a speculative
        # verify chunk overrunning the buffer on a slot's final tick
        # cannot clobber other rows' live KV.
        if getattr(cache_pos, "ndim", 0) == 1:
            slots = cache_pos[:, None] + jnp.arange(s)[None, :]  # [b, s]
            ck = entry["k"].at[jnp.arange(b)[:, None], slots].set(k.astype(entry["k"].dtype))
            cv = entry["v"].at[jnp.arange(b)[:, None], slots].set(v.astype(entry["v"].dtype))
        else:
            ck = jax.lax.dynamic_update_slice(entry["k"], k.astype(entry["k"].dtype), (0, cache_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(entry["v"], v.astype(entry["v"].dtype), (0, cache_pos, 0, 0))
        return ck, cv, {"k": ck, "v": cv}, None

    # Paged cache: the entry is the GLOBAL pool [num_blocks, L, kv_heads,
    # d] and the row's block table maps logical position p to pool cell
    # (table[p // L], p % L). Writes scatter each chunk token at its
    # logical position through the table; reads gather the table's blocks
    # back into one [b, nb*L] view whose index IS the logical position —
    # so the caller's position mask applies to the view unchanged, and a
    # row's decode cost tracks the blocks its table exposes (nb), not a
    # global buffer ceiling. Unused table entries hold the null block
    # (id 0): their view positions sit above every live query, hence
    # always masked; dead rows get an all-null table from the engine so
    # their (frozen-position) writes land in null-block garbage instead
    # of a block since reassigned to a live row.
    L = entry["k"].shape[1]
    nb = block_tables.shape[1]
    offset = cache_pos[:, None] if getattr(cache_pos, "ndim", 0) == 1 else cache_pos
    pos = jnp.broadcast_to(offset + jnp.arange(s)[None, :], (b, s))
    # NOTE the clip: a position past the table view REDIRECTS its write
    # into the view's LAST entry instead of dropping it (the dense buffer
    # above drops out-of-bounds scatters). Callers whose writes can run
    # past a row's logical end — the speculative verify step writes K
    # positions past the last accepted token — must size the table view
    # to cover pos + K (engine-side block headroom), or live KV gets
    # overwritten.
    blk = jnp.take_along_axis(block_tables, jnp.clip(pos // L, 0, nb - 1), axis=1)
    off = pos % L
    if "k_scale" not in entry:
        ck = entry["k"].at[blk, off].set(k.astype(entry["k"].dtype))
        cv = entry["v"].at[blk, off].set(v.astype(entry["v"].dtype))
        flat = block_tables.reshape(-1)
        k = ck[flat].reshape(b, nb * L, ck.shape[2], ck.shape[3])
        v = cv[flat].reshape(b, nb * L, cv.shape[2], cv.shape[3])
        return k, v, {"k": ck, "v": cv}, None

    # Int8 pool (--quantize-kv int8): codes keep the bf16 layout's
    # [nb, L, h, d] shape, per-(block, kv-head) absmax scales live in
    # sibling pools indexed by the same block ids. Writes quantize at
    # insert (growing a block's scale rescales its resident codes;
    # untouched blocks are bit-stable — ops/int8.quantize_kv_write);
    # reads either fuse gather+dequant+attention into the Pallas
    # decode kernel (TPU, s == 1) or fall back to the dequantizing
    # XLA gather.
    from llm_fine_tune_distributed_tpu.ops.flash_attention import (
        paged_decode_attention,
        paged_decode_mode,
    )

    ck, k_sc = quantize_kv_write(entry["k"], entry["k_scale"], blk, off, k)
    cv, v_sc = quantize_kv_write(entry["v"], entry["v_scale"], blk, off, v)
    new_entry = {"k": ck, "v": cv, "k_scale": k_sc, "v_scale": v_sc}
    mode = paged_decode_mode()
    if mode != "xla" and s == 1 and fusable:
        # fused Pallas kernel: block-table gather + per-block dequant +
        # online softmax in one VMEM pass — the gathered [b, nb*L] view
        # never materializes in HBM. Decode (s == 1) only; prefill
        # chunks and speculative verify use the XLA gather below.
        out = paged_decode_attention(
            q, ck, cv, k_sc, v_sc, block_tables,
            lengths=pos[:, 0] + 1,
            scale=float(scale) if scale is not None else float(q.shape[-1]) ** -0.5,
            interpret=(mode == "interpret"),
        )
        return None, None, new_entry, out
    k = dequantize_kv_gather(ck, k_sc, block_tables, compute_dtype)
    v = dequantize_kv_gather(cv, v_sc, block_tables, compute_dtype)
    return k, v, new_entry, None


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _block(
    lp: Params,
    x,
    cos,
    sin,
    padding_mask,
    segment_ids,
    mask,
    cache_entry,
    cache_pos,
    *,
    config: ModelConfig,
    plan: LayerPlan,
    attention_impl: str,
    compute_dtype,
    mesh=None,
    quant_impl: str = "auto",
    rope_flag=None,
    block_tables=None,
    adapter_idx=None,
    w8a8: bool = False,
):
    """One transformer block, composed from its layer's ``plan``
    (``ModelConfig.layer``): ``x + [norm](mixer(norm(x)))``, then ``x +
    [norm](feed_forward(norm(x)))``, the two output norms where the model has
    four a block (``sandwich_norms``), around any mixer and any feed-forward.
    Returns ``(x, new_cache_entry, counted)``: ``counted`` is what the
    feed-forward counted (``_FEED_FORWARD``).

    ``mask`` ([batch, q, kv] bool): the explicit attention mask where the
    caller made one (packing with a window, the KV cache), the windowed
    variant on a layer with a window; None = causal attention from
    ``padding_mask`` / ``segment_ids`` through the attention dispatch.
    ``rope_flag`` (traced bool scalar) overrides the static ``plan.rope`` —
    used by the pipeline's layer scan, where the layer index is data.
    ``block_tables`` ([batch, nb] int32): the cache entry is the PAGED pool.
    """
    eps, zc = config.rms_norm_eps, config.zero_centered_norm
    lin = partial(_linear, compute_dtype=compute_dtype, quant_impl=quant_impl, adapter_idx=adapter_idx, w8a8=w8a8)

    subtree, mixer_scope, _, mixer = _ATTENTION[plan.attention]
    with scope(mixer_scope):
        hid = rms_norm(x, lp["input_layernorm"]["weight"], eps, zero_centered=zc)
        if config.fp32_residual:  # the stream and its two adds in float32, the norms' outputs in the compute dtype
            hid = hid.astype(compute_dtype)
        attn_out, new_entry = mixer(
            lp[subtree], hid, cos, sin, config=config, plan=plan, lin=lin,
            rope=plan.rope if rope_flag is None else rope_flag, compute_dtype=compute_dtype,
            attention_impl=attention_impl, mesh=mesh, padding_mask=padding_mask, segment_ids=segment_ids,
            mask=mask, cache_entry=cache_entry, cache_pos=cache_pos, block_tables=block_tables,
        )
        if config.sandwich_norms:
            # Gemma2, afmoe: post_attention_layernorm norms the mixer's OUTPUT
            with scope("out_norm"):
                attn_out = rms_norm(attn_out, lp["post_attention_layernorm"]["weight"], eps, zero_centered=zc)
        x = x + _residual(attn_out, config)

    with scope("mlp"):
        pre_ffn = "pre_feedforward_layernorm" if config.sandwich_norms else "post_attention_layernorm"
        hid = rms_norm(x, lp[pre_ffn]["weight"], eps, zero_centered=zc)
        if config.fp32_residual:
            hid = hid.astype(compute_dtype)
        subtree, _, feed_forward = _FEED_FORWARD[plan.feed_forward]
        y, counted = feed_forward(
            lp[subtree], hid, lin, config, compute_dtype=compute_dtype, mesh=mesh,
            padding_mask=padding_mask, segment_ids=segment_ids, serving=cache_entry is not None,
        )
        if config.sandwich_norms:
            # whatever the feed-forward is: of a layer of routed experts the norm takes the SUM of the routed
            # and the shared experts' outputs (afmoe's post_mlp_layernorm), of a share of them the partial sum
            with scope("out_norm"):
                y = rms_norm(y, lp["post_feedforward_layernorm"]["weight"], eps, zero_centered=zc)
        x = x + _residual(y, config)
    return x, new_entry, counted


def _residual(y, config: ModelConfig):
    """What a half of a block adds to the stream: its output, times ``residual_multiplier`` where the model has one
    (Granite; in the output's dtype, as HF multiplies). At 1 no operation is emitted."""
    return y if config.residual_multiplier == 1.0 else y * jnp.asarray(config.residual_multiplier, y.dtype)


# {kind of block (its mixer, and its window where it has one): (remat policy, the names kept besides)} of every
# rematerialized block a forward of this process was traced with (``_remat_policy``), as ``gated_delta.CALLS`` says which
# program a rule took: whether a block kept the flash kernels', the delta rule's or the expert layer's named values.
REMAT_KEEPS: dict = {}


def remat_summary() -> str:
    """One line for entry points to print beside ``dispatch_summary()``: e.g. ``a rematerialized block keeps, besides
    what its policy does: kda (full): gdn_o, gdn_states, kda_t_kk, kda_p, moe_*; latent (full): flash_o, flash_lse,
    moe_*`` (a Kimi Delta Attention block on a TPU; a Gated DeltaNet block reads ``linear (full): gdn_o, gdn_states,
    moe_*``, and either ``... (full): moe_*`` where the rule is XLA's scan)."""
    from llm_fine_tune_distributed_tpu.ops.moe import KEPT_ACROSS_REMAT as routed

    def said(names):
        own = [name for name in names if name not in routed]
        return ", ".join(own + ["moe_*"] * (len(own) < len(names))) or "nothing"

    kinds = "; ".join(f"{kind} ({policy}): {said(names)}" for kind, (policy, names) in sorted(REMAT_KEEPS.items()))
    return f"a rematerialized block keeps, besides what its policy does: {kinds or 'no block traced'}"


def keeps_flash_outputs(config: ModelConfig, seq: int, window: Optional[int] = None) -> bool:
    """Whether a rematerialized block of softmax attention of this model, with this
    ``window`` (None: global attention), keeps the flash forward kernel's output and row
    statistics at rows of ``seq`` tokens: the rule of
    ``ops/flash_attention.worth_keeping_across_remat`` at this model's head
    widths. What the kernel sees is the whole row on every mesh that calls it
    (batch and heads are sharded around it, Ulysses hands it the whole
    sequence of a head subset; ring attention does not call it).

    An EVA layer (``eva_window``) keeps the same two names of its operator by the same rule, with the row's length
    standing for twice the mean number of keys a query reads, tokens of its window and summaries of the earlier ones
    together (at rows of 32,768, windows of 2048 and chunks of 16: 3,969 against a hidden size of 4096, recompute;
    what would be kept there is 256 MiB of ``o`` and 512 MiB of ``lse`` in its padded layout a layer)."""
    from llm_fine_tune_distributed_tpu.ops.flash_attention import worth_keeping_across_remat

    if config.eva_window:
        seq = 2 * eva_attention.pairs_a_row(seq, config.eva_window, config.eva_chunk) // seq

    if config.kv_lora_rank:
        d_qk, d_v = config.qk_nope_head_dim + config.qk_rope_head_dim, config.v_head_dim
    else:
        d_qk = d_v = config.resolved_head_dim
    return worth_keeping_across_remat(seq, d_qk, d_v, config.hidden_size, window=window)


def keeps_scan_output(config: ModelConfig) -> tuple:
    """Which of the gated delta rule's names (``ops/gated_delta.KEPT_ACROSS_REMAT``, ``KEPT_BY_CHANNEL``) a rematerialized block with a
    linear-attention mixer keeps, from what the code can observe: the program the rule runs as
    (``gated_delta._program`` at this model's linear heads: the backend and the widths) and, for the XLA form, shapes.

    **The kernels** (a TPU, heads of whole lanes): both, the output ``gdn_o`` (``[b, s, value heads * d_v]``) and the
    state each step of the forward sweep starts from, ``gdn_states`` (``[b, value heads, s / 512, d_k, d_v]`` float32),
    so that the recomputed pass holds no forward sweep. The backward sweep reads the states and the gated norm reads
    ``o``, so with ``o`` alone kept the whole sweep still runs a second time (counted in the compiled step: 6 forward
    sweeps for 3 layers either way). A sweep is a chain of dependent products on one MXU at a tenth of the recurrence's
    roofline, and the count below, which prices a step's output half at the matmuls' rate, is not about it. By the
    chip (PERF.md, PR 44's traces; a microbatch of 2 rows of 8192 at 32 value heads of 128 keeps 192 MiB a layer, 128
    of ``o`` and 64 of states): the scalar rule's sweep 4.78 ms a call, 28.7 ms of the Qwen3-Next cell's step for 576
    MiB more held (0.050 ms a MiB), the sweep with a decay a channel 9.00 ms a call, 72 ms of the Kimi cell's step for
    768 MiB (0.094), against 0.04 ms a MiB for the routing PR 29 keeps and about 0.014 for a projection's output.
    What comes back is the one ``reduce_precision`` pass ``jax.checkpoint`` puts on a saved residual's producer, 0.4
    ms a call over ``o``.

    **The kernels of the rule with a decay a channel** (a Kimi Delta Attention mixer: ``linear_decay_rank``): those two
    and what ``kda_rule_fwd`` writes for its backward sweep to read instead of making it again, ``kda_t_kk`` (each
    chunk's ``T`` beside its decayed ``k k^T``, float32 ``[b, value heads, s, 128]``, 256 MiB at that microbatch) and
    ``kda_p`` (two chunks' ``P`` side by side, ``[b, value heads, s / 2, 128]``, 64 MiB): residuals of the sweep like the
    states, so a policy that misses one of them runs the sweep twice. By the chip (PERF.md, PR 48): the backward sweep
    24.5 -> 17.8 ms a call and the forward one 9.0 -> 9.15, 52 ms of the Kimi cell's step for 1,280 MiB held from a
    microbatch's forward pass to its backward pass (0.041 ms a MiB), no ``reduce_precision`` (the forward pass reads
    neither), and the expert layers unmoved. ``W`` (one one-pass product a chunk, 128 MiB a layer) is made again.

    **The XLA form** (a CPU, heads that are no whole lanes): ``gdn_o`` alone, where the rule of
    ``worth_keeping_across_remat`` says so from shapes; that form carries its state through its own scan, and with
    ``o`` kept the recomputed scan only carries the state forward and the output half of each step (``Q S`` and
    ``(decay * Q K^T) D``) is dead code. Per byte of ``o`` that half costs ``d_k + chunk * (1 + d_k / (r d_v))`` FLOPs
    (``2 d_k d_v`` for ``Q S``, ``2 chunk d_v`` for the product with D and ``2 chunk d_k / r`` for ``Q K^T`` a token
    and value head, for ``2 d_v`` bytes), against ``hidden_size`` a byte a projection's output buys. The row's length
    is not in it: the rule's work grows with the row as what is kept does. Qwen3-Next: 128 + 64 x 1.5 = 224 against
    2048, Kimi Linear 128 + 64 x 2 = 256 against 2304: recompute (and on the chip: PERF.md, PR 32)."""
    d_k, d_v = config.linear_key_head_dim, config.linear_value_head_dim
    if gated_delta._program(d_k=d_k, d_v=d_v) == "kernels":
        return gated_delta.KEPT_BY_CHANNEL if config.linear_decay_rank else gated_delta.KEPT_ACROSS_REMAT
    r = config.linear_num_value_heads // config.linear_num_key_heads
    return gated_delta.KEPT_ACROSS_REMAT[:1] if d_k + gated_delta.CHUNK * (1 + d_k / (r * d_v)) > config.hidden_size else ()


def keeps_routing(config: ModelConfig) -> bool:
    """Whether a rematerialized block of this model can hold an expert layer
    that keeps its routing and gathered rows (``ops/moe.grouped_moe_mlp``)."""
    return any(config.layer(i).feed_forward == "grouped_experts" for i in range(config.num_layers))


def _remat_policy(
    remat_policy: Optional[str], config: ModelConfig, seq: int, window: Optional[int] = None, attention: str = "heads"
):
    """What ``jax.checkpoint`` keeps of a block (of this kind of ``attention``
    and this ``window``, None for global attention) besides its input. ``full``
    (and None): nothing, the whole block is recomputed, least memory. The
    selective policies save the expensive tensors and recompute the cheap
    elementwise operations, trading HBM for fewer recomputed FLOPs (a v5e is
    compute-bound here): ``dots`` / ``dots_no_batch`` the matmuls' outputs,
    ``mlp`` only the [b, s, f] SwiGLU product (``mlp_act``).

    Under every one of them alike, ``full`` included, a block on long rows
    (``keeps_flash_outputs``: from the shapes and the keys a query of this
    layer sees, no switch; at 8192 tokens a layer with a window of 1024 does
    not, its global neighbour does) also keeps the flash
    forward kernel's ``o`` and ``lse``, so that the kernel runs once a layer
    and not a second time in the backward pass. Where the kernel is not in
    the block (XLA attention) the two names are in no program and the policy
    is the plain one. A block whose mixer is the linear recurrence keeps what
    ``keeps_scan_output`` names of the rule: where the rule runs as the Pallas
    sweeps every output of the forward one (two, or four with a decay a
    channel), so that the forward sweep runs once a layer too; where it is
    XLA's scan its output, by a rule of the mixer's widths. A block whose mixer
    is the state-space scan keeps nothing of it (``ops/ssd.KEPT_ACROSS_REMAT``
    names what a later rule may keep: ``y`` and the sweeps' states are 80 MiB
    a layer at Granite's row of 8192, 2.8 GiB for its 36 scans where the step
    has 1.8 GiB of room, so such a rule has to choose by layer and be
    measured: PERF.md section 7).

    And a model with a ``grouped_experts`` layer (``keeps_routing``: the
    layer's kind, no switch) keeps what that layer names
    (``ops/moe.KEPT_ACROSS_REMAT``), so that the router's product, the top k,
    the two sorts and the row gather run once a layer. No rule of shapes: the
    rows are as large as the block's input, which every policy keeps anyway,
    and at 16,384 tokens of 2048 the about 85 MiB a layer took 16 ms of a
    1,006 ms step to make again (0.04 ms a MiB; ISSUE 29 had counted on 0.1),
    as much as the best of what else such a block could keep and above the
    0.011 of a byte of ``o`` at 1024 tokens (PERF.md, PRs 27 and 29). A model
    without such a layer gets the policy object it got before.

    What each kind of block keeps is recorded (``REMAT_KEEPS``) for
    ``remat_summary()``, the line entry points print."""
    from llm_fine_tune_distributed_tpu.ops import flash_attention, moe

    saveable = jax.checkpoint_policies
    policies = {
        "full": None,
        "dots": saveable.checkpoint_dots,
        "dots_no_batch": saveable.dots_with_no_batch_dims_saveable,
        "mlp": saveable.save_only_these_names("mlp_act"),
    }
    remat_policy = remat_policy or "full"
    if remat_policy not in policies:
        raise ValueError(
            f"unknown remat_policy {remat_policy!r}; expected one of {sorted(policies)}"
        )
    policy = policies[remat_policy]
    if attention == "ssd":
        names = ()
    elif attention in ("linear", "kda"):
        names = keeps_scan_output(config)
    else:
        names = flash_attention.KEPT_ACROSS_REMAT if keeps_flash_outputs(config, seq, window) else ()
    if keeps_routing(config):
        names += moe.KEPT_ACROSS_REMAT
    REMAT_KEEPS[attention if window is None else f"{attention}, window {window}"] = (remat_policy, names)
    if not names:
        return policy
    kept = saveable.save_only_these_names(*names)
    return kept if policy is None else saveable.save_from_both_policies(policy, kept)


def rope_tables(config: ModelConfig, positions):
    """``{rope_kind: (cos, sin)}`` for ``positions``, one pair for each kind
    some layer's plan names (``LayerPlan.rope_kind``: "plain", or "scaled" by
    the config's context extension; a model whose window layers keep plain
    rope while its global layers extend theirs has both), at the width this
    model's attention rotates: the whole head, its first
    ``partial_rotary_factor`` of it, or latent attention's rope part."""
    width = config.qk_rope_head_dim if config.kv_lora_rank else config.rotary_dim
    kinds = {config.layer(i).rope_kind for i in range(config.num_layers)}
    return {
        kind: rope_cos_sin(positions, width, config.rope_theta, config=config if kind == "scaled" else None)
        for kind in sorted(kinds)
    }


def forward_with_report(
    params: Params,
    input_ids,
    config: ModelConfig,
    *,
    positions=None,
    padding_mask=None,
    segment_ids=None,
    cache: Optional[Dict[str, Any]] = None,
    cache_pos: int | jax.Array = 0,
    block_tables=None,
    attention_impl: str = "xla",
    compute_dtype=jnp.bfloat16,
    remat: bool = False,
    remat_policy: Optional[str] = None,
    logits_dtype=jnp.float32,
    activation_sharding=None,
    output_hidden: bool = False,
    quant_impl: str = "auto",
    adapter_idx=None,
    frozen_layers: int = 0,
    frozen_compute: str = "bf16",
) -> Tuple[jax.Array, Optional[Dict[str, Any]], Dict[str, jax.Array]]:
    """Run the model. ``forward`` is this without the report.

    Args:
      input_ids: int32 [batch, seq].
      positions: int32 [batch, seq] absolute positions (default arange, or
        cache_pos offset when a cache is passed).
      padding_mask: [batch, seq] 1=real token (training path).
      cache: optional KV cache dict (see ``init_cache``); when given,
        attention runs over the full cache buffer with a position mask.
      cache_pos: where this chunk starts in the cache — a scalar (all rows
        aligned) or a [batch] vector for per-row starts (ragged batched
        decode: row i's slots stay equal to its logical positions).
      block_tables: optional [batch, nb] int32 — switches ``cache`` to the
        PAGED layout (``init_paged_cache``): one global block pool shared by
        all rows, each row's table mapping logical position p to pool cell
        (table[p // block_len], p % block_len). The attention view per row is
        the gathered nb*block_len positions its table exposes.
      adapter_idx: optional [batch] int32 — per-row slot into the stacked
        multi-tenant LoRA pools (infer/adapters.py) attached beside target
        kernels. Row i's projections add adapter adapter_idx[i]'s low-rank
        delta; index 0 is the identity (zero) adapter. Ignored when the
        params tree carries no ``lora_*_pool`` leaves.
      remat: rematerialize each block on backward
        (analog of reference ``gradient_checkpointing=True``, training.py:280).
      frozen_layers / frozen_compute: the frozen-trunk fast path
        (TrainConfig.frozen_compute="int8"): with ``frozen_compute="int8"``
        and no cache, layers ``[0, frozen_layers)`` run their projection
        matmuls w8a8 on pre-quantized ``kernel_int8`` siblings, skip remat,
        and end in a boundary ``stop_gradient``. ``"bf16"`` (default) and
        the cache path are bit-identical to a model without these kwargs.
      output_hidden: return the final-norm hidden states [batch, seq, hidden]
        (in ``compute_dtype``) instead of logits — the chunked-loss path
        (train/step.py) unembeds chunk-by-chunk so the [batch, seq, vocab]
        float32 logits tensor never materializes in HBM.
      activation_sharding: optional ``NamedSharding`` for the [batch, seq,
        hidden] activations (normally batch over (data, fsdp)). Constraining
        activations explicitly keeps XLA/Shardy propagation on the intended
        layout — without it, propagation can try to shard the hidden dim with
        the same axis as the batch dim and fail (or silently pick a slow
        layout). Set by the trainer whenever a mesh is in use.

    Returns:
      (logits [batch, seq, vocab] in ``logits_dtype``, updated cache or None,
      report). The report is what the layers counted, one pytree whose
      structure is fixed for a ``ModelConfig`` (``report_shapes``):
      ``router_aux``, the capacity experts' load-balancing loss summed over
      layers (float32 scalar); ``expert_load`` ``[expert layers, held
      experts]`` int32, the (token, expert) pairs each held expert of each
      layer of grouped experts was given. ``{}`` for a model with neither.
    """
    b, s = input_ids.shape
    if positions is None:
        # scalar cache_pos broadcasts; a [batch] vector gives per-row offsets
        # (ragged batched decode)
        offset = (
            cache_pos[:, None] if getattr(cache_pos, "ndim", 0) == 1 else cache_pos
        )
        positions = jnp.arange(s, dtype=jnp.int32)[None, :] + offset
        positions = jnp.broadcast_to(positions, (b, s)).astype(jnp.int32)

    def constrain(h):
        if activation_sharding is not None:
            return jax.lax.with_sharding_constraint(h, activation_sharding)
        return h

    # Sequence parallelism (ring / ulysses) shard_maps over the mesh, and the
    # MoE dispatch constrains its expert blocks to it; recover the mesh from
    # the activation sharding so call sites stay unchanged. (The attention
    # dispatch ignores it for non-sequence-parallel impls.)
    mesh = getattr(activation_sharding, "mesh", None)

    with scope("embed"):
        embed = params["model"]["embed_tokens"]["weight"].astype(compute_dtype)
        if mesh is not None and (
            dict(mesh.shape).get("tensor", 1) > 1 or dict(mesh.shape).get("data", 1) > 1
        ):
            # Embedding-lookup layout: shard the table by vocab (tensor, else
            # fsdp) and gather the hidden dim. FSDP shards the table's hidden dim
            # with the same mesh axis that shards the ids' batch dim; on tensor>1
            # or data>1 meshes GSPMD resolves that conflict by replicating the
            # gather output and repartitioning it ("involuntary full
            # rematerialization", spmd_partitioner.cc warnings). With the table
            # vocab-sharded, each device gathers from its vocab shard (masked +
            # psum) and the output lands directly on the activation layout.
            # (1, fsdp, 1, *) meshes reshard the (small) gather output cleanly
            # without help, so they skip this.
            embed = _lookup_table_constraint(embed, mesh)
        x = constrain(embed[input_ids])
        if config.embed_scale:
            # Gemma normalizer: HF multiplies by a sqrt(hidden) scalar cast to
            # the activation dtype first — mirror the cast for bf16 bit-parity
            x = x * jnp.asarray(config.hidden_size**0.5, dtype=x.dtype)
        if config.embedding_multiplier != 1.0:  # Granite: a constant (12), in the activation's dtype as HF multiplies
            x = x * jnp.asarray(config.embedding_multiplier, dtype=x.dtype)
        if config.fp32_residual:
            x = x.astype(jnp.float32)
    tables = rope_tables(config, positions)

    explicit_mask = None
    windowed_mask = None
    if segment_ids is not None:
        if cache is not None:
            raise ValueError("segment_ids (packing) and KV cache are exclusive")
        # Packed batch (data/packing.py): attention is restricted to equal
        # segment ids (block-diagonal causal). The segment ids flow into the
        # attention dispatch so the Pallas flash kernel (which masks by
        # segment natively) stays usable; only the sliding-window case needs
        # an explicit mask (window distance uses per-segment positions).
        if config.sliding_window is not None:
            idx = jnp.arange(s, dtype=jnp.int32)
            causal = idx[None, None, :] <= idx[None, :, None]
            same_seg = segment_ids[:, :, None] == segment_ids[:, None, :]
            explicit_mask = causal & same_seg
            q_pos, k_pos = positions[:, :, None], positions[:, None, :]
            segment_ids = None  # consumed into the explicit mask
    elif cache is not None:
        # Mask over the fixed-size buffer: key j visible to query i iff
        # j <= position(i), and within the sliding window if configured.
        # Paged caches mask the gathered [nb * block_len] view — gathered
        # index IS logical position, so the same rule applies verbatim.
        kv_len = cache["layers"]["0"]["k"].shape[1]  # the buffer's length, or a block's
        if block_tables is not None:
            kv_len *= block_tables.shape[1]
        k_pos = jnp.arange(kv_len, dtype=jnp.int32)[None, None, :]
        q_pos = positions[:, :, None]
        explicit_mask = k_pos <= q_pos
        if padding_mask is not None:
            # With a cache, padding_mask must cover the WHOLE buffer
            # [batch, kv_len] (1 = real token at that cache slot), so batched
            # generate over ragged prompts can mask pad keys already written.
            if padding_mask.shape[-1] != kv_len:
                raise ValueError(
                    f"with a KV cache, padding_mask must be [batch, {kv_len}] "
                    f"(full buffer), got {padding_mask.shape}"
                )
            explicit_mask &= padding_mask.astype(bool)[:, None, :]
    if explicit_mask is not None and config.sliding_window is not None:
        # the windowed variant for the layers the window applies to (global
        # layers, Gemma2's odd ones, keep the plain mask), made after the
        # padding so that it carries the pad bits too
        windowed_mask = explicit_mask & (k_pos > q_pos - config.sliding_window)

    new_layers = {}
    counted_by_layer = []
    # Frozen-trunk fast path (TrainConfig.frozen_compute="int8"): layers
    # [0, frozen_layers) carry pre-quantized kernel_int8 siblings and run
    # their projections w8a8 (ops/int8_matmul). The trunk is a pure
    # inference forward: no remat wrap (nothing will ever replay it) and a
    # stop_gradient at the boundary so no cotangent enters it — the
    # compile-cost guard (tests/test_frozen_trunk.py) pins both.
    trunk_layers = frozen_layers if (frozen_compute == "int8" and cache is None) else 0
    remat = remat and cache is None
    # one policy for each kind of layer (by its mixer and window), not one a layer
    plans = [config.layer(i) for i in range(config.num_layers)]
    kinds = {(plan.attention, plan.window) for plan in plans} if remat else ()
    kept_of_a_block = {(a, w): _remat_policy(remat_policy, config, s, w, a) for a, w in kinds}
    for i in range(config.num_layers):
        entry = cache["layers"][str(i)] if cache is not None else None
        in_trunk = i < trunk_layers
        if in_trunk and i == 0:
            # trunk ENTRY stop_gradient: with tied embeddings the trunk's
            # input lookup carries a tangent (embed_tokens is trainable);
            # killing it here — not just at the exit boundary below — means
            # autodiff never traces the trunk at all, which the Pallas
            # w8a8 kernel requires (pallas_call has no JVP rule) and which
            # drops the same embedding-through-trunk gradient the exit
            # boundary drops anyway (documented approximation).
            x = jax.lax.stop_gradient(x)
        plan = plans[i]
        block_fn = partial(
            _block,
            config=config,
            plan=plan,
            attention_impl=attention_impl,
            compute_dtype=compute_dtype,
            mesh=mesh,
            quant_impl=quant_impl,
            block_tables=block_tables,
            adapter_idx=adapter_idx,
            w8a8=in_trunk,
        )
        if remat and not in_trunk:
            block_fn = jax.checkpoint(block_fn, policy=kept_of_a_block[plan.attention, plan.window])
        with scope("layer", i):
            x, new_entry, counted = block_fn(
                params["model"]["layers"][str(i)],
                x,
                *tables[plan.rope_kind],
                padding_mask,
                segment_ids,
                explicit_mask if plan.window is None else windowed_mask,  # (made whenever an explicit one is)
                entry,
                cache_pos,
            )
        x = constrain(x)
        if in_trunk and i == trunk_layers - 1:
            # trunk/trainable boundary: the only gradient path through the
            # trunk is the (tied) embedding's contribution via the input
            # lookup — deliberately dropped here (documented approximation,
            # docs/architecture.md "Training fast path") so the trunk
            # backward is dead code the compiler eliminates.
            x = jax.lax.stop_gradient(x)
        counted_by_layer.append(counted)
        new_layers[str(i)] = new_entry

    with scope("final_norm"):
        x = rms_norm(
            x,
            params["model"]["norm"]["weight"],
            config.rms_norm_eps,
            zero_centered=config.zero_centered_norm,
        )

    new_cache = {"layers": new_layers} if cache is not None else None
    if output_hidden:
        out = x.astype(compute_dtype)
    else:
        with scope("loss_head"):  # the full-logits path: train/step.py scopes its cross-entropy alike
            out = unembed(
                params, x, config, compute_dtype=compute_dtype, logits_dtype=logits_dtype, mesh=mesh
            )
    return out, new_cache, _report(counted_by_layer)


def forward(params: Params, input_ids, config: ModelConfig, **kwargs) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    """``forward_with_report``, by its keywords, without the report: ``(out, cache)``."""
    return forward_with_report(params, input_ids, config, **kwargs)[:2]


def _lookup_table_constraint(table, mesh, vocab_dim: int = 0):
    """Constrain a [vocab, hidden]-shaped (or transposed) weight so only the
    vocab dim stays sharded and the hidden dim is gathered. Shared by the
    embedding lookup and the unembed matmul — both places where FSDP's
    hidden-dim sharding collides with the batch-sharded activations and GSPMD
    would otherwise fall back to replicate-then-repartition
    (spmd_partitioner.cc "Involuntary full rematerialization" warnings).

    The vocab dim shards over ``tensor`` when live (Megatron layout), else
    over ``fsdp`` — the table stays distributed either way (never fully
    replicated for a large-vocab model); GSPMD lowers the lookup to a masked
    local gather + psum over the vocab shards, with only activation-sized
    collectives on the hot path."""
    axes = dict(mesh.shape)
    vocab_ax = None
    for ax in ("tensor", "fsdp"):
        if axes.get(ax, 1) > 1 and table.shape[vocab_dim] % axes[ax] == 0:
            vocab_ax = ax
            break
    if vocab_ax is None:
        # nothing to shard (single-chip mesh, or indivisible vocab): a
        # no-op constraint would still be an HLO boundary that blocks XLA
        # from fusing the weight cast into the matmul — measurably slower
        # inside the remat'd chunked-CE loop
        return table
    spec = [None, None]
    spec[vocab_dim] = vocab_ax
    return jax.lax.with_sharding_constraint(
        table, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec))
    )


def unembed(params: Params, hidden, config: ModelConfig, *, compute_dtype=jnp.bfloat16, logits_dtype=jnp.float32, mesh=None):
    """Project hidden states [..., hidden] -> logits [..., vocab] (tied or not).

    With a ``mesh``, the projection weight is constrained like the embedding
    lookup table (vocab over ``tensor``, hidden gathered): under FSDP the
    weight moves to the data, the batch-sharded activations stay put —
    without this, GSPMD reshards the activations (and their cotangents) to
    the weight's hidden-dim sharding through a replicate-then-repartition
    fallback on data>1 meshes."""
    h = hidden.astype(compute_dtype)
    if config.tie_word_embeddings:
        embed = params["model"]["embed_tokens"]["weight"].astype(compute_dtype)
        if mesh is not None:
            embed = _lookup_table_constraint(embed, mesh, vocab_dim=0)
        logits = jnp.einsum("...h,vh->...v", h, embed)
    else:
        kernel = params["lm_head"]["kernel"].astype(compute_dtype)
        if mesh is not None:
            kernel = _lookup_table_constraint(kernel, mesh, vocab_dim=1)
        logits = h @ kernel
    logits = logits.astype(logits_dtype)
    if config.logits_scaling != 1.0:  # Granite: the logits DIVIDED by a constant, elementwise, so each chunk of a chunked loss scales its own
        logits = logits / jnp.asarray(config.logits_scaling, logits.dtype)
    if config.final_logit_softcap is not None:
        # Gemma2 final_logit_softcapping — elementwise, so it composes with
        # both CE chunking schemes (each slice caps its own logits)
        logits = softcap(logits, config.final_logit_softcap)
    return logits


def init_cache(
    config: ModelConfig, batch_size: int, max_len: int, dtype=jnp.bfloat16,
    mesh=None,
):
    """Fixed-size KV cache buffers for autoregressive decoding.

    With ``mesh`` the buffers are allocated directly under the KV partition
    rules (``parallel/sharding.kv_cache_spec``: kv-head dim over ``tensor``)
    — zeros compile straight into sharded device buffers, so a pool that
    only fits *sharded* never stages unsharded on one chip."""
    d = config.resolved_head_dim
    shape = (batch_size, max_len, config.num_kv_heads, d)

    def alloc():
        return {
            "layers": {
                str(i): {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
                for i in range(config.num_layers)
            }
        }

    return _alloc_kv(alloc, mesh)


def _alloc_kv(alloc, mesh):
    """Run a zeros-allocating thunk, placing its leaves under the mesh's KV
    shardings when a mesh is given (jit out_shardings: works identically on
    single-process and process-spanning meshes)."""
    if mesh is None:
        return alloc()
    from llm_fine_tune_distributed_tpu.parallel.sharding import (
        kv_cache_shardings,
    )

    shapes = jax.eval_shape(alloc)
    shardings = kv_cache_shardings(shapes, mesh)
    return jax.jit(alloc, out_shardings=shardings)()


def init_paged_cache(
    config: ModelConfig,
    num_blocks: int,
    block_len: int,
    dtype=jnp.bfloat16,
    kv_quant: str = "none",
    mesh=None,
):
    """Global paged KV pool for the block-paged continuous engine: per layer
    one [num_blocks, block_len, kv_heads, head_dim] buffer shared by every
    decode slot, addressed through per-slot block tables (``forward``'s
    ``block_tables``). Block 0 is the NULL block (infer/paged.py): never
    allocated, mapped into unused table entries and dead rows so stray writes
    and gathers hit garbage that the position mask always hides.

    ``kv_quant="int8"`` keeps the same per-layer ``k``/``v`` shape in int8
    and adds sibling ``k_scale``/``v_scale`` pools — f32 per-(block, kv-head)
    absmax, indexed by the same block ids — halving HBM per cached token.
    ``_cache_write_and_view`` tells the layout by the ``k_scale`` key; the allocator and
    prefix cache (infer/paged.py) deal only in block ids and are untouched.
    Scales start at 0 ("never written"), so every block — the null block
    forever — dequantizes to exact zeros until its first real write.

    ``mesh`` allocates every pool leaf — the int8 code pools AND their
    scale siblings — directly under the KV partition rules (see
    ``init_cache``): kv-head dim over ``tensor``, block dim replicated, so
    one global block id still addresses the same block on every chip.
    """
    if kv_quant not in KV_QUANT_MODES:
        raise ValueError(
            f"unknown kv_quant mode {kv_quant!r} (expected one of {KV_QUANT_MODES})"
        )
    d = config.resolved_head_dim
    shape = (num_blocks, block_len, config.num_kv_heads, d)
    if kv_quant == "int8":
        scale_shape = (num_blocks, config.num_kv_heads)
        entry = lambda: {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(scale_shape, jnp.float32),
            "v_scale": jnp.zeros(scale_shape, jnp.float32),
        }
    else:
        entry = lambda: {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    return _alloc_kv(
        lambda: {"layers": {str(i): entry() for i in range(config.num_layers)}},
        mesh,
    )


def insert_cache_row(cache, row_cache, slot):
    """Write a batch-1 cache's K/V into row ``slot`` of a multi-row cache
    without touching the other rows — the continuous-batching prefill-insert
    (infer/engine.py): a freed slot adopts a freshly prefilled prompt while
    its neighbors keep decoding.

    ``row_cache`` buffers may be SHORTER than ``cache``'s (prompt-bucket vs
    full decode buffer): only the leading ``row_cache`` slots of the row are
    overwritten. Stale K/V beyond them is harmless under the slot == position
    invariant — every cache slot ``j`` is rewritten (by prompt prefill or by
    decode token ``j - prompt_len``) before any query position ``>= j`` can
    attend to it, and slots above the current position are always masked.

    ``slot`` may be a traced int32 scalar (one compiled insert program serves
    every slot index).
    """
    new_layers = {}
    for i, entry in cache["layers"].items():
        row = row_cache["layers"][i]
        new_layers[i] = {
            n: jax.lax.dynamic_update_slice(
                entry[n], row[n].astype(entry[n].dtype), (slot, 0, 0, 0)
            )
            for n in ("k", "v")
        }
    return {"layers": new_layers}
