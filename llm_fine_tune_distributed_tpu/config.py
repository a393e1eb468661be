"""Configuration system.

The reference configures training purely through environment variables
(``EPOCHS, BATCH_SIZE, LEARNING_RATE, DATA_DIR, OUTPUT_DIR`` — reference
``training.py:54-60``) with the model name, dataset path, grad-accum, seq-len,
eval cadence and freezing policy hard-coded. Here every knob is a dataclass
field, loadable from JSON/YAML, and every reference env var still works as an
override so the deployment-manifest contract (``deploy/pytorchjob.yaml:30-66``)
is preserved.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence


@dataclass(frozen=True)
class LayerPlan:
    """One layer's parts, as ``ModelConfig.layer(i)`` reads them from the
    config: exactly what the model code branches on."""

    # "heads" (q/k/v projections, GQA) | "latent" (MLA, one low-rank kv latent) |
    # "linear" (Gated DeltaNet: a recurrence over time, no softmax, no rope, no window) |
    # "kda" (Kimi Delta Attention: that recurrence with a decay a channel behind low-rank gates) |
    # "eva" (EvaByte: softmax over the query's own aligned window and one learned summary a chunk of every earlier window) |
    # "ssd" (a Mamba-2 mixer: a state-space scan with a scalar decay a head, one B and C a group of heads; no softmax, no rope)
    attention: str
    rope: bool  # rotary embedding on this layer's q and k (SmolLM3's NoPE layers: False)
    rope_kind: str  # which of the forward's cos/sin tables: "plain" | "scaled" (the config's context extension)
    window: Optional[int]  # sliding-window width, None = global attention
    feed_forward: str  # "dense" | "capacity_experts" (ops/moe.moe_mlp) | "grouped_experts" (grouped_moe_mlp + shared)


@dataclass(frozen=True)
class ModelConfig:
    """Architecture of a decoder-only transformer, one class for every family
    the framework runs: each block is a mixer (softmax attention over heads or
    over a latent, with or without a window, a rope, q/k norms and an output
    gate; or a linear recurrence) and a feed-forward (a dense MLP, or routed
    experts beside shared ones) between two or four norms. The family fields
    below become one layer's parts in ``layer(i)``, the one place the model
    code asks: Llama-3, Mistral (sliding window), Qwen-style (qkv bias),
    SmolLM3 (NoPE-interleaved RoPE: ``no_rope_layers[i] == 0`` means layer *i*
    applies no rotary embedding, as HF ``SmolLM3Config.no_rope_layers``),
    Gemma2 (four norms a block), Mixtral, DeepSeek-V3 / Moonlight, Mellum,
    Qwen3-Next, ``afmoe`` (Trinity: gated window layers with rope beside
    gated global layers without, four norms a block around routed experts),
    ``kimi_linear`` (Kimi Delta Attention layers beside latent attention
    without rope), ``evabyte`` (EVA attention in every layer, a float32
    residual stream, several next-token heads), and ``granitemoehybrid``
    (Granite 4.0-H: Mamba-2 state-space layers beside a few GQA layers without
    rope, the embedding, both residual adds, the attention scores and the
    logits each under a constant multiplier).
    """

    name: str = "unnamed"
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 11008
    num_layers: int = 36
    num_heads: int = 16
    num_kv_heads: int = 4
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    rope_theta: float = 2_000_000.0
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    # Qwen2-style: bias on q/k/v but NOT o_proj (HF Qwen2Attention). Only
    # consulted when attention_bias is True; Llama-style configs keep True.
    attention_out_bias: bool = True
    # Qwen3-style per-head RMSNorm on q and k (over head_dim, applied after
    # the projections, before RoPE — HF Qwen3Attention q_norm/k_norm).
    qk_norm: bool = False
    # --- Gemma2-style architecture knobs (HF Gemma2Config) ---
    # Gate activation: "silu" (Llama SwiGLU), "gelu_tanh" (Gemma GeGLU /
    # gelu_pytorch_tanh), or "gelu" (exact erf). MoE models support "silu"
    # only (enforced in __post_init__; ops/moe.py hardcodes the expert MLP).
    hidden_act: str = "silu"
    # Four norms per layer: post-attention and post-feedforward OUTPUT norms
    # in addition to the two pre-norms (HF Gemma2DecoderLayer ordering; afmoe's
    # pre_mlp_layernorm / post_mlp_layernorm), around whatever the layer's
    # feed-forward is: a layer of routed experts norms the SUM of the routed
    # and the shared experts' outputs
    sandwich_norms: bool = False
    # RMSNorm weight stored zero-centered: out = normed * (1 + w), w init 0
    zero_centered_norm: bool = False
    # Multiply embedding output by sqrt(hidden_size) (Gemma normalizer; afmoe's mup_enabled)
    embed_scale: bool = False
    # Soft caps: score -> cap * tanh(score / cap)
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    # Attention scale = query_pre_attn_scalar**-0.5 instead of head_dim**-0.5
    query_pre_attn_scalar: Optional[float] = None
    # Sliding window only on even layers (Gemma2's local/global alternation);
    # False = the window (if any) applies to every layer (Mistral)
    alternating_sliding_window: bool = False
    # RoPE context extension (HF config.rope_scaling). None = plain RoPE;
    # "llama3" = Llama-3.1 smoothed NTK; "linear" = position interpolation.
    rope_scaling_type: Optional[str] = None
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    # "yarn" (HF _compute_yarn_parameters): wavelengths beyond the original
    # length are interpolated by ``factor``, short ones kept, a linear ramp
    # between the dimensions that turn beta_fast and beta_slow times over the
    # original length; cos and sin are multiplied by the attention factor
    # (None: HF's default 0.1 ln(factor) + 1).
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: Optional[float] = None
    # The one kind of ``layer_types`` whose layers rotate with the scaled
    # table (HF ``rope_parameters`` keyed by layer type: Mellum extends its
    # global layers only); None = the scaling is every layer's (Llama-3.1).
    rope_scaling_layer_type: Optional[str] = None
    mlp_bias: bool = False
    # SmolLM3 NoPE: 1 = RoPE on this layer, 0 = no positional embedding
    # (afmoe: its full_attention layers). Empty tuple = RoPE everywhere
    # (Llama/Mistral).
    no_rope_layers: tuple = ()
    sliding_window: Optional[int] = None  # Mistral-style local attention
    # HF ``layer_types``, one entry a layer: "sliding_attention" (the window
    # applies) | "full_attention" (global) | "linear_attention" (a Gated
    # DeltaNet mixer, the ``linear_*`` fields below) | "mamba" (a Mamba-2
    # mixer, the ``mamba_*`` fields below). Empty = the window, if any, on
    # every layer (or on even ones, ``alternating_sliding_window``).
    layer_types: tuple = ()
    # --- Qwen3-Next (HF Qwen3NextConfig) ---
    # A "linear_attention" layer's mixer (ops/gated_delta.py): key heads and
    # value heads of their own widths (each key head serves value/key value
    # heads), a causal depthwise convolution of this many taps over q, k, v.
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # --- Kimi Linear (moonshotai ``kimi_linear``) ---
    # Both > 0 make a "linear_attention" layer a Kimi Delta Attention mixer: q, k, v
    # from projections and convolutions of their own, the decay a VECTOR over a
    # head's d_k channels, ``-exp(A_log[head]) softplus((x W_fa) W_fb + dt_bias)``
    # through a pair of matrices of this rank, and the norm after the rule gated by
    # ``sigmoid((x W_ga) W_gb)`` through another pair (as many key heads as value heads).
    linear_decay_rank: int = 0
    linear_gate_rank: int = 0
    # --- Granite 4.0-H (``granitemoehybrid``) ---
    # A "mamba" layer's mixer (ops/ssd.py; HF ``GraniteMoeHybridMambaLayer``): ``mamba_n_heads`` heads of
    # ``mamba_d_head`` channels (together the inner width), each with a state ``[mamba_d_head, mamba_d_state]`` under
    # ONE decay a head and token; B and C (``mamba_d_state`` wide) shared by the heads of a group; a causal depthwise
    # convolution of ``mamba_d_conv`` taps WITH a bias over ``[x | B | C]``.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    # The family's four constants; at 1 (None) no operation is emitted. The embedding's output times
    # ``embedding_multiplier``; both residual adds of a block ``x + residual_multiplier * f(norm(x))``; the logits
    # DIVIDED by ``logits_scaling``; the attention scores times ``attention_multiplier`` in place of head_dim ** -0.5.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None
    # --- EvaByte (``evabyte``, ``attention_class`` eva) ---
    # eva_window > 0 makes every layer's mixer EVA attention (ops/eva_attention.py): token n sees the tokens of its
    # own ALIGNED window ``[eva_window * (n // eva_window), n]`` exactly, and every EARLIER window through one pooled
    # key/value a chunk of ``eva_chunk`` tokens (two learned vectors a head, ``adaptive_phi`` and ``adaptive_mu_k``).
    eva_window: int = 0
    eva_chunk: int = 0
    # The head has this many times vocab_size columns: head i at position t answers token t + 1 + i, and the loss
    # is the mean of the heads' cross-entropies (train/step.py). 1 = the usual next-token head.
    num_pred_heads: int = 1
    # The residual stream and both adds of a block in float32 (``fp32_skip_add``); the norms' outputs and everything
    # after them stay in the compute dtype.
    fp32_residual: bool = False
    # The share of a head's dimensions that rope rotates, from dimension 0
    # (rotate-half inside them); the rest pass unrotated. 1.0 = the whole head.
    partial_rotary_factor: float = 1.0
    # q_proj is twice as wide, query and gate of each head side by side, and
    # the attention output is multiplied by sigmoid(gate) before o_proj.
    attention_output_gate: bool = False
    # The shared expert's output is multiplied by sigmoid(x w_s), one column.
    shared_expert_gate: bool = False
    dtype: str = "bfloat16"
    # Mixture-of-experts (Mixtral-style). 0 = dense MLP. When > 0 every
    # layer's MLP becomes num_experts SwiGLU experts with top-k routing
    # (ops/moe.py); expert weights shard over the mesh "expert" axis.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # per-(batch-row, expert) token capacity = ceil(k * seq / E) * this factor;
    # overflow tokens fall through on the residual path (GShard semantics)
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01  # load-balancing loss weight (Switch/Mixtral)
    # sequences longer than this are routed in independent chunks (GShard
    # "groups"), keeping the one-hot dispatch tensors linear in seq length:
    # [b * s/chunk, chunk, E, C_chunk] instead of [b, s, E, C]. Tokens
    # compete for capacity within their chunk only.
    moe_dispatch_chunk: int = 1024
    # --- Latent attention (MLA, HF DeepseekV3Attention; q_lora_rank null) ---
    # kv_lora_rank > 0 switches every softmax layer's attention (a model's
    # "linear_attention" layers stay what they are): k and v come from one
    # low-rank latent of this width (normed) plus ONE rope key of
    # qk_rope_head_dim shared by all heads; q/k heads are qk_nope_head_dim +
    # qk_rope_head_dim wide, v heads v_head_dim. head_dim is unused then.
    # mla_use_nope (Kimi Linear): no rotation at all, the qk_rope_head_dim
    # columns of q and of the shared key take part in the scores as they come.
    kv_lora_rank: int = 0
    mla_use_nope: bool = False
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- Routed experts without capacity (HF DeepseekV3MoE noaux_tc, Mellum) ---
    # n_routed_experts > 0 is the ROUTER's width: scores by router_scoring, top
    # num_experts_per_tok of them (plus a bias buffer where sigmoid), weights
    # normalised over the selected and scaled by routed_scaling_factor, no
    # auxiliary loss, no dropped token (ops/moe.py grouped_moe_mlp). The first
    # first_k_dense_replace layers keep the dense MLP of intermediate_size;
    # the rest hold experts of moe_intermediate_size beside n_shared_experts
    # shared ones (one SwiGLU of n_shared_experts * moe_intermediate_size).
    n_routed_experts: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    # How the router scores: "sigmoid" (DeepSeek-V3: independent scores, top k
    # of scores + the ``e_score_correction_bias`` buffer) | "softmax" (Mellum,
    # Qwen3-MoE: probabilities over all experts, top k of them, no bias leaf).
    # Either way the weights are the selected scores over their sum.
    router_scoring: str = "sigmoid"
    # Global ids of the routed experts THIS program holds (expert
    # parallelism's share; the stacked expert leaves have len(held_experts)
    # rows). Empty = all n_routed_experts. The router stays n_routed_experts
    # wide; what the absent experts would add is left out.
    held_experts: tuple = ()

    def __post_init__(self):
        if self.n_routed_experts:
            if self.num_experts:
                raise ValueError("num_experts (Mixtral dispatch) and n_routed_experts (grouped dispatch) are exclusive")
            held = self.held_expert_ids
            if len(set(held)) != len(held) or not all(0 <= e < self.n_routed_experts for e in held):
                raise ValueError(f"held_experts {held} must be distinct ids below n_routed_experts={self.n_routed_experts}")
            if not 0 < self.num_experts_per_tok <= self.n_routed_experts:
                raise ValueError("num_experts_per_tok must lie in 1..n_routed_experts")
            if self.router_scoring not in ("sigmoid", "softmax"):
                raise ValueError(f"router_scoring {self.router_scoring!r}: expected 'sigmoid' or 'softmax'")
        if self.layer_types:
            kinds = set(self.layer_types) - {"sliding_attention", "full_attention", "linear_attention", "mamba"}
            if kinds or len(self.layer_types) < self.num_layers:
                raise ValueError(
                    f"layer_types must name each of the {self.num_layers} layers 'sliding_attention', "
                    f"'full_attention' or 'linear_attention' (or 'mamba': a Mamba-2 mixer) (got {len(self.layer_types)} entries, unknown "
                    f"kinds {sorted(kinds)})"
                )
            if "sliding_attention" in self.layer_types[: self.num_layers] and self.sliding_window is None:
                raise ValueError("layer_types has sliding_attention layers but sliding_window is None")
            if "linear_attention" in self.layer_types[: self.num_layers]:
                hk, hv = self.linear_num_key_heads, self.linear_num_value_heads
                if min(hk, hv, self.linear_key_head_dim, self.linear_value_head_dim, self.linear_conv_kernel_dim) < 1:
                    raise ValueError("layer_types has linear_attention layers: set the linear_* fields")
                if hv % hk:
                    raise ValueError(f"linear_num_value_heads={hv} must be a multiple of linear_num_key_heads={hk}")
                if bool(self.linear_decay_rank) != bool(self.linear_gate_rank) or (self.linear_decay_rank and hv != hk):
                    raise ValueError(
                        "linear_decay_rank and linear_gate_rank go together (a Kimi Delta Attention mixer), with "
                        f"as many key heads as value heads (got ranks {self.linear_decay_rank}, "
                        f"{self.linear_gate_rank}, heads {hk} and {hv})"
                    )
            if "mamba" in self.layer_types[: self.num_layers]:
                sizes = (self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state, self.mamba_n_groups, self.mamba_d_conv)
                if min(sizes) < 1:
                    raise ValueError("layer_types has mamba layers: set the mamba_* fields")
                if self.mamba_n_heads % self.mamba_n_groups:
                    raise ValueError(f"mamba_n_groups={self.mamba_n_groups} must divide mamba_n_heads={self.mamba_n_heads}")
        if self.eva_window:
            if self.eva_chunk < 1 or self.eva_window % self.eva_chunk:
                raise ValueError(f"eva_window={self.eva_window} must be a multiple of eva_chunk={self.eva_chunk}")
            if self.num_kv_heads != self.num_heads or self.layer_types or self.kv_lora_rank or self.sliding_window:
                raise ValueError("EVA attention (eva_window) is every layer's mixer, with as many key heads as query "
                                 "heads: no layer_types, latent attention or sliding_window beside it")
        if self.num_pred_heads < 1 or (self.num_pred_heads > 1 and self.tie_word_embeddings):
            raise ValueError(f"num_pred_heads={self.num_pred_heads}: at least 1, and more than one needs an untied head")
        rotary = self.resolved_head_dim * self.partial_rotary_factor
        if not 0 < self.partial_rotary_factor <= 1 or rotary != int(rotary) or int(rotary) % 2:
            raise ValueError(
                f"partial_rotary_factor={self.partial_rotary_factor} of head_dim={self.resolved_head_dim} "
                "must give an even number of rotated dimensions"
            )
        if (self.num_experts or self.n_routed_experts) and self.hidden_act != "silu":
            # ops/moe.py's expert MLP hardcodes silu — reject at config
            # construction rather than silently training with the wrong
            # activation (same fail-fast contract as rope_scaling parsing)
            raise ValueError(
                f"MoE models support hidden_act='silu' only "
                f"(got {self.hidden_act!r})"
            )

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    @property
    def held_expert_ids(self) -> tuple:
        return tuple(self.held_experts) or tuple(range(self.n_routed_experts))

    @property
    def rotary_dim(self) -> int:
        """Dimensions of a head that rope rotates (``partial_rotary_factor``)."""
        return int(self.resolved_head_dim * self.partial_rotary_factor)

    @property
    def linear_layers(self) -> tuple:
        """Indices of the layers whose mixer is the linear recurrence."""
        return tuple(i for i, kind in enumerate(self.layer_types[: self.num_layers]) if kind == "linear_attention")

    @property
    def num_params(self) -> int:
        """Exact parameter count (matches HF model.num_parameters()): a layer
        is its norms, its mixer and its feed-forward, each counted by the kind
        ``layer(i)`` says it is."""
        h, v, f, L = self.hidden_size, self.vocab_size, self.intermediate_size, self.num_layers
        d = self.resolved_head_dim
        if self.kv_lora_rank:
            # MLA: q, kv_a (latent + shared rope key), latent norm, kv_b, o
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            r = self.kv_lora_rank
            softmax = (
                h * self.num_heads * qk + h * (r + self.qk_rope_head_dim) + r
                + r * self.num_heads * (self.qk_nope_head_dim + self.v_head_dim)
                + self.num_heads * self.v_head_dim * h
            )
        else:
            softmax = 2 * h * (self.num_heads + self.num_kv_heads) * d  # q, k, v, o
        if self.attention_bias:
            softmax += (self.num_heads + 2 * self.num_kv_heads) * d
            if self.attention_out_bias:
                softmax += h
        if self.qk_norm:
            softmax += 2 * d                  # q_norm, k_norm (per head_dim)
        if self.attention_output_gate:
            softmax += h * self.num_heads * d  # q_proj carries the gate
        kd = self.linear_num_key_heads * self.linear_key_head_dim
        vd = self.linear_num_value_heads * self.linear_value_head_dim
        taps, hv = self.linear_conv_kernel_dim, self.linear_num_value_heads
        inner, bc = self.mamba_n_heads * self.mamba_d_head, 2 * self.mamba_n_groups * self.mamba_d_state
        mixers = {
            "heads": softmax,
            "latent": softmax,
            "eva": softmax + 2 * self.num_heads * d,  # adaptive_phi, adaptive_mu_k
            # in_proj_qkvz, in_proj_ba, the convolution over q, k, v, A_log and dt_bias, the gated norm, out_proj
            "linear": h * (2 * kd + 2 * vd) + h * 2 * hv + (2 * kd + vd) * taps + 2 * hv + self.linear_value_head_dim + vd * h,
            # q, k, v and their convolutions, b_proj, the decay's pair with A_log (a head) and dt_bias (a channel),
            # the gate's pair, the gated norm, o_proj
            "kda": (
                h * (2 * kd + vd) + (2 * kd + vd) * taps + h * hv
                + self.linear_decay_rank * (h + kd) + hv + kd
                + self.linear_gate_rank * (h + vd) + self.linear_value_head_dim + vd * h
            ),
            # in_proj [z | x B C | dt], the convolution over [x | B | C] with its bias, A_log, D and dt_bias a head,
            # the gated norm over the inner width, out_proj
            "ssd": (
                h * (2 * inner + bc + self.mamba_n_heads) + (inner + bc) * (self.mamba_d_conv + 1)
                + 3 * self.mamba_n_heads + inner + inner * h
            ),
        }
        if self.num_experts:
            # router gate [h, E] + E SwiGLU experts (w1/w3 [h, f], w2 [f, h])
            dense = h * self.num_experts + self.num_experts * 3 * h * f
        else:
            dense = 3 * h * f + (2 * f + h if self.mlp_bias else 0)  # gate, up, down
        # expert layers: router [h, E] + (sigmoid) its bias buffer [E], the HELD
        # experts and the shared expert, instead of the dense MLP
        fe, e = self.moe_intermediate_size, self.n_routed_experts
        experts = (
            h * e + (e if self.router_scoring == "sigmoid" else 0)
            + 3 * h * fe * (len(self.held_expert_ids) + self.n_shared_experts)
            + (h if self.shared_expert_gate else 0)  # one column
        )
        feed_forwards = {"dense": dense, "capacity_experts": dense, "grouped_experts": experts}
        norms = (4 if self.sandwich_norms else 2) * h
        total = v * h + h  # the embedding, the final norm
        for i in range(L):
            plan = self.layer(i)
            total += norms + mixers[plan.attention] + feed_forwards[plan.feed_forward]
        if not self.tie_word_embeddings:
            total += v * h * self.num_pred_heads
        return total

    def layer(self, i: int) -> "LayerPlan":
        """What layer ``i`` is made of: the one place the family fields above
        become a layer's parts. ``models/transformer.py`` builds the block from
        it, and nothing outside ``models/`` and ``ops/`` asks the fields."""
        if self.num_experts:
            feed_forward = "capacity_experts"
        elif self.n_routed_experts and i >= self.first_k_dense_replace:
            feed_forward = "grouped_experts"
        else:
            feed_forward = "dense"
        # layer_types names each layer's kind (Mellum: three window layers to
        # one global); else Gemma2 alternates local (even layers) / global
        # (odd) and Mistral applies the window everywhere
        window = self.sliding_window
        kind = self.layer_types[i] if self.layer_types else None
        if kind in ("linear_attention", "mamba"):
            mixer = "ssd" if kind == "mamba" else "kda" if self.linear_decay_rank else "linear"
            return LayerPlan(attention=mixer, rope=False, rope_kind="plain", window=None, feed_forward=feed_forward)
        if self.layer_types:
            if kind == "full_attention":
                window = None
        elif self.alternating_sliding_window and i % 2 != 0:
            window = None
        scaled = bool(self.rope_scaling_type) and self.rope_scaling_layer_type in (
            None, self.layer_types[i] if self.layer_types else None
        )
        if self.eva_window:
            return LayerPlan(attention="eva", rope=True, rope_kind="plain", window=None, feed_forward=feed_forward)
        return LayerPlan(
            attention="latent" if self.kv_lora_rank else "heads",
            rope=bool(self.no_rope_layers[i]) if self.no_rope_layers else not self.mla_use_nope,
            rope_kind="scaled" if scaled else "plain",
            window=window,
            feed_forward=feed_forward,
        )

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh.

    Axis meaning (scaling-book style):
      - ``data``:  pure data parallelism (gradients psum'd; params replicated)
      - ``fsdp``:  data parallelism with parameters sharded (ZeRO-3); batch is
                   sharded over data*fsdp jointly
      - ``tensor``: tensor parallelism (Megatron-style within attention/MLP)
      - ``seq``  : sequence/context parallelism — ring attention or Ulysses
                   all-to-all, selected by ``attention_impl`` (optional)
      - ``expert``: expert parallelism for MoE models — expert weights and the
                   dispatched token blocks shard over this axis (ops/moe.py)
      - ``pipe`` : pipeline parallelism — transformer blocks stacked
                   [num_layers, ...] and sharded by depth; microbatch
                   activations flow stage-to-stage with ppermute
                   (parallel/pipeline.py)

    Sizes of -1 mean "absorb remaining devices" (at most one axis may be -1).
    This replaces the reference's implicit 1-D DDP world
    (``WORLD_SIZE``/``RANK``, reference ``training.py:19-23``).
    """

    data: int = 1
    fsdp: int = -1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1

    def axis_sizes(self, n_devices: int) -> dict:
        sizes = {"data": self.data, "fsdp": self.fsdp, "tensor": self.tensor,
                 "seq": self.seq, "expert": self.expert, "pipe": self.pipe}
        unknown = [k for k, v in sizes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {unknown}")
        fixed = 1
        for k, v in sizes.items():
            if v != -1:
                fixed *= v
        if unknown:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by fixed axes {sizes}")
            sizes[unknown[0]] = n_devices // fixed
        else:
            if fixed != n_devices:
                raise ValueError(f"mesh {sizes} does not cover {n_devices} devices")
        return sizes


@dataclass
class TrainConfig:
    """Full SFT training configuration.

    Defaults reproduce the reference recipe exactly:
    epochs=4, per-device batch=8, lr=5e-5 (scaled x data-parallel size,
    reference ``training.py:263``), grad-accum 4 (``:262``), clip 1.0 (``:264``),
    log every 2 steps + first (``:266-267``), eval every 10 (``:270-271``),
    save every 500 keep 3 (``:268,276``), bf16 (``:269``), seq len 1024 with
    packing off (``:282-283``), 90/10 split seed 42 (``:164``), freeze all but
    last 2 layers + lm_head (``:113-149``).
    """

    # model / data
    model_name: str = "HuggingFaceTB/SmolLM3-3B"
    model_preset: Optional[str] = "smollm3_3b"
    data_dir: str = "data"
    dataset_file: str = "qa_dataset.parquet"
    output_dir: str = "outputs"
    tokenizer_path: Optional[str] = None  # defaults to model_name
    # None = the wilderness-survival persona (reference C7, training.py:176-186)
    system_prompt: Optional[str] = None

    # optimization
    epochs: int = 4
    per_device_batch_size: int = 8
    gradient_accumulation_steps: int = 4
    learning_rate: float = 5e-5
    scale_lr_by_data_parallel: bool = True  # lr x world_size rule, training.py:263
    # "adamw" (HF Trainer default, reference parity) | "adafactor" (factored
    # second moment — near-zero optimizer-state HBM, the classic TPU choice
    # for big models) | "lion" (sign-momentum, one state slot)
    optimizer: str = "adamw"
    weight_decay: float = 0.0
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-8
    max_grad_norm: float = 1.0
    warmup_ratio: float = 0.0
    lr_schedule: str = "linear"  # HF Trainer default: linear decay to 0
    seed: int = 42

    # sequence / precision
    max_seq_length: int = 1024
    # packing=True packs multiple examples per row with an exact
    # block-diagonal segment mask (data/packing.py). Attention runs through
    # the explicit-mask XLA path (flash/ring impls apply to unpacked runs).
    packing: bool = False
    param_dtype: str = "float32"     # master weights
    compute_dtype: str = "bfloat16"  # activations / matmuls
    gradient_checkpointing: bool = True
    # remat granularity: "full" (recompute whole block — min memory),
    # "dots" / "dots_no_batch" (save matmul outputs — least recompute, most
    # HBM), "mlp" (save only the [s,f] SwiGLU product — the middle ground).
    # Under each of them, "full" included, a block on long rows also keeps
    # the flash forward kernel's output and row statistics (o and lse, one
    # [b, s, heads * d_v] tensor and one float32 a row and head), so that the
    # kernel is not run a second time in the backward pass: decided from the
    # shapes, where seq * (d_qk + d_v) / (2 * d_v) > hidden_size
    # (models/transformer._remat_policy; no setting switches it); and a block
    # whose feed-forward is grouped experts keeps its routing (scores,
    # selection, sorted tables) and the first chunk's gathered rows, about as
    # large as its input, so that router, sorts and gather run once a layer.
    # None = auto (resolved_remat_policy): picked by model size and PER-CHIP
    # sequence length, from earlier rounds' single-chip sweep (its record
    # was deleted in PR 21; not re-measured on the attached v5e).
    remat_policy: Optional[str] = None
    # loss on completion tokens only? TRL SFTTrainer default (packing=False,
    # no completion_only flag in the reference) trains on the full sequence.
    completion_only_loss: bool = False
    # Compute the cross-entropy in sequence chunks of this size so the
    # [batch, seq, vocab] float32 logits tensor never materializes (HBM saver
    # for large-vocab models; None = single full-sequence unembed).
    loss_chunk_size: Optional[int] = None
    # Stream the cross-entropy over VOCAB chunks with an online logsumexp
    # (train/step.vocab_chunked_ce_sum): the f32 logits never materialize in
    # fwd OR bwd. Mutually exclusive with loss_chunk_size. vocab_size must
    # divide by it (SmolLM3's 128256 = 8 x 16032 = 16 x 8016).
    loss_vocab_chunk: Optional[int] = None

    # objective: "sft" (the reference recipe) or "dpo" (preference pairs,
    # BASELINE.json config #4 — the TRL DPOTrainer capability, first-party)
    objective: str = "sft"
    dpo_beta: float = 0.1              # TRL DPOConfig default
    dpo_label_smoothing: float = 0.0   # conservative-DPO eps

    # freezing policy (reference training.py:113-149)
    freeze_strategy: str = "last_n_and_head"  # or "none" / "lora" / "qlora"
    unfreeze_last_n_layers: int = 2

    # frozen-trunk compute (ISSUE 20): "bf16" runs frozen layers exactly as
    # today; "int8" runs the projection matmuls of entirely-frozen leading
    # layers as w8a8 (per-channel int8 weights x per-row dynamic int8
    # activations on the MXU int8 path) with a stop_gradient at the
    # trunk/trainable boundary and no trunk remat. No-op when the freeze
    # policy leaves trainable leaves in every layer (lora/qlora/none).
    frozen_compute: str = "bf16"       # or "int8"

    # QLoRA quantization (freeze_strategy="qlora": NF4 frozen base)
    quant_block_size: int = 64        # NF4 scale block (QLoRA paper default)
    quant_double_quant: bool = True   # int8-compress the absmax scales
    quant_matmul_impl: str = "auto"   # "auto" | "xla" (fused pallas retired: ops/nf4.py)

    # LoRA (external-doc config: r=16, alpha=8, dropout=0.05, 7 proj targets)
    lora_rank: int = 16
    lora_alpha: float = 8.0
    lora_dropout: float = 0.05
    lora_target_modules: Sequence[str] = (
        "q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj", "down_proj",
    )

    # cadence
    logging_steps: int = 2
    logging_first_step: bool = True
    eval_steps: int = 10
    # per-device EVAL batch size. None = per_device_batch_size (reference HF
    # semantics). Forward-only eval holds no grads/optimizer traffic, so much
    # larger batches fit — fewer scan iterations per sweep, directly cutting
    # the eval pause the r4 hardware run measured at 60-100s.
    eval_batch_size: Optional[int] = None
    save_steps: int = 500
    save_total_limit: int = 3
    metric_for_best_model: str = "eval_loss"
    greater_is_better: bool = False
    load_best_model_at_end: bool = True
    # how load_best_model_at_end tracks the best weights:
    # - "per_eval": on-device snapshot at every eval improvement (finest
    #   granularity; costs one trainable-set copy of HBM)
    # - "checkpoint": restore the best SAVED checkpoint at end of run (HF's
    #   actual save-aligned semantics; zero steady-state cost — the right
    #   mode when HBM is tight, e.g. the 3B flagship on one 16 GB chip)
    # - "auto": per_eval while the trainable set is <512 MB, else checkpoint
    best_model_tracking: str = "auto"

    # data split
    validation_fraction: float = 0.1
    split_seed: int = 42
    drop_last: bool = True

    # mesh / distributed
    mesh: MeshConfig = field(default_factory=MeshConfig)
    # attention implementation: "xla" | "flash" (Pallas) | "ring" | "ulysses"
    attention_impl: str = "flash"

    # observability
    aim_repo: Optional[str] = None
    experiment_name: str = "smollm3-wilderness-finetuning-distributed"
    profile_dir: Optional[str] = None
    # training control plane (observe/trainplane.py): primary-host HTTP
    # server exposing /metrics, /v1/train/status, /v1/train/flight and
    # POST /v1/train/profile while the run steps. None = off; 0 = bind an
    # ephemeral port (tests/benches read it back from the plane object).
    train_port: Optional[int] = None
    # anomaly sentinels: trailing window (steps) a publish must keep clean
    # to get anomaly_clean=true, and the EWMA band width (sigmas) for the
    # loss-spike / grad-explosion detectors.
    anomaly_window_steps: int = 100
    anomaly_band_sigma: float = 6.0

    # native runtime (C++ layer, native/*.cc)
    use_native_loader: bool = True   # prefetching C++ batch pipeline, auto-fallback
    heartbeat: bool = False          # TCP failure detector (auto-on multi-host)
    heartbeat_port: int = 23457      # analog of reference master port 23456
    heartbeat_timeout_ms: int = 30000
    # cross-host param-consistency check every N steps (0 = off) — the
    # systematic form of the reference runbook's gradient-desync diagnosis
    # (docs/single-vs-distributed-comparison.md:571-580)
    desync_check_steps: int = 0
    # step watchdog (runtime/watchdog.py): seconds of training-loop silence
    # before reporting a wedged device link (0 = off). The single-process
    # analog of the multi-host heartbeat — a wedged device otherwise hangs
    # the run forever with a healthy-looking process.
    watchdog_timeout_s: float = 0.0
    watchdog_action: str = "warn"  # or "abort": os._exit for restart+resume

    # checkpoint payload / overlap
    # trainable-only: persist (step, trainable masters, optimizer state) +
    # a fingerprint of the frozen params, re-deriving the frozen 86.4% from
    # the base checkpoint/seed at restore — cuts the flagship checkpoint
    # 7.4 GB -> ~2.1 GB. Incompatible with cross-mesh-layout (pipe<->flat)
    # resume; use full checkpoints when planning an elastic layout change.
    checkpoint_trainable_only: bool = False
    # single-process runs: hand the device->host stream + Orbax write to a
    # background thread after an on-device snapshot, so the next train step
    # never blocks on checkpoint IO (transient HBM: one payload copy).
    # Multi-process saves always use Orbax's own async path.
    checkpoint_async_snapshot: bool = True

    # live deployment (train/publish.py -> infer/deploy.py): after each
    # checkpoint save, also publish the trainable weights + manifest
    # (frozen-param fingerprint, step, eval metrics) atomically to this
    # directory so a serving fleet started with --publish-watch-dir
    # hot-swaps them without a restart. keep_last bounds disk: only the
    # newest K publishes survive retention.
    publish_dir: Optional[str] = None
    publish_keep_last: int = 3
    # refuse to publish a checkpoint whose trailing anomaly window is
    # dirty (non-finite loss, loss spike, grad explosion) instead of
    # stamping it anomaly_clean=false — keeps diverging weights from ever
    # reaching the deployment watch dir.
    publish_require_clean: bool = False

    # resume
    resume_from_checkpoint: Optional[str] = None  # "latest" or a path

    def effective_batch_size(self, data_parallel_size: int) -> int:
        return self.per_device_batch_size * self.gradient_accumulation_steps * data_parallel_size

    def resolved_remat_policy(
        self, model_config: "ModelConfig", seq_parallel_size: int = 1
    ) -> str:
        """Resolve remat_policy=None ("auto") by model size AND per-chip
        sequence length. An explicit setting always wins.

        From earlier rounds' sweep on one v5e chip (SmolLM3-3B, bf16; the
        record was deleted in PR 21 and the speeds are claims to
        re-measure, the HBM sizes are the compiler's): at seq 1024/2048 the
        matmul-saving "dots_no_batch" is fastest; at seq 4096 its saved dot
        products (~256MB/layer) blow HBM (19.4G > 15.75G) while "mlp" (save
        only the [s,f] SwiGLU product) fits and runs 2.4x faster than
        full-block remat; at 8k even the mlp saves OOM (17.1G). Big models
        always take minimum-HBM "full".

        Whatever this resolves to, on rows longer than ``hidden_size * 2 *
        d_v / (d_qk + d_v)`` tokens (2048 for this model) a block also
        keeps the flash forward kernel's ``o`` and ``lse``
        (models/transformer._remat_policy): at 4096 that is 16 MiB of ``o``
        and 32 MiB of ``lse`` (in the padded layout the kernels read) a row
        and layer on top of what the policy keeps. The TPU compiler's count
        for the 4096 recipe of benchmarks/long_context.py (1 row x 8, "mlp",
        loss in chunks of 512; bfloat16 masters, float32 moments, last 2
        layers + tied head trained; a described v5e, PR 27,
        benchmarks/step_memory.py): 12.49 GiB before, 14.15 GiB with them
        kept, of 15.49 a program may use: it fits, and "mlp" stays the
        choice. The same step with the whole-sequence unembed was at the
        brim before (15.49 GiB) and is refused with them kept (by 1.53 GB):
        chunk the loss there.

        ``seq_parallel_size``: the mesh's seq-axis size. A ring/ulysses run
        at global seq 8192 over 4 chips holds 2048 tokens per chip — the
        HBM pressure the ledger keys on is per-chip, so auto resolves on
        ``max_seq_length / seq_parallel_size``."""
        if self.remat_policy is not None:
            return self.remat_policy
        if model_config.num_params >= 6e9:
            return "full"
        per_chip_seq = self.max_seq_length // max(seq_parallel_size, 1)
        if per_chip_seq >= 8192:
            return "full"
        return "mlp" if per_chip_seq >= 4096 else "dots_no_batch"

    def scaled_learning_rate(self, data_parallel_size: int) -> float:
        if self.scale_lr_by_data_parallel:
            return self.learning_rate * data_parallel_size
        return self.learning_rate

    # ---- env-var override surface (reference training.py:54-60 + pytorchjob.yaml:30-66)

    _ENV_MAP = {
        "EPOCHS": ("epochs", int),
        "BATCH_SIZE": ("per_device_batch_size", int),
        "LEARNING_RATE": ("learning_rate", float),
        "DATA_DIR": ("data_dir", str),
        "OUTPUT_DIR": ("output_dir", str),
        "AIM_REPO": ("aim_repo", str),
        "MODEL_NAME": ("model_name", str),
        # MODEL_PRESET=none: resolve the architecture from MODEL_NAME's
        # config.json (the pre-staged local HF checkpoint contract)
        "MODEL_PRESET": ("model_preset", lambda s: None if s.lower() == "none" else s),
        "TOKENIZER_PATH": ("tokenizer_path", str),
        "MAX_SEQ_LENGTH": ("max_seq_length", int),
        "GRAD_ACCUM_STEPS": ("gradient_accumulation_steps", int),
        "SEED": ("seed", int),
        "ATTENTION_IMPL": ("attention_impl", str),
        "OPTIMIZER": ("optimizer", str),
        "PARAM_DTYPE": ("param_dtype", str),
        "FREEZE_STRATEGY": ("freeze_strategy", str),
        "FROZEN_COMPUTE": ("frozen_compute", str),
        "REMAT_POLICY": ("remat_policy", str),
        "LOSS_CHUNK_SIZE": ("loss_chunk_size", int),
        "LOSS_VOCAB_CHUNK": ("loss_vocab_chunk", int),
        "RESUME_FROM_CHECKPOINT": ("resume_from_checkpoint", str),
        "CHECKPOINT_TRAINABLE_ONLY": ("checkpoint_trainable_only", "_env_bool"),
        "CHECKPOINT_ASYNC_SNAPSHOT": ("checkpoint_async_snapshot", "_env_bool"),
        "PUBLISH_DIR": ("publish_dir", str),
        "PUBLISH_KEEP_LAST": ("publish_keep_last", int),
        "PUBLISH_REQUIRE_CLEAN": ("publish_require_clean", "_env_bool"),
        "TRAIN_PORT": ("train_port", int),
        "ANOMALY_WINDOW_STEPS": ("anomaly_window_steps", int),
        "ANOMALY_BAND_SIGMA": ("anomaly_band_sigma", float),
        "WATCHDOG_TIMEOUT_S": ("watchdog_timeout_s", float),
        "WATCHDOG_ACTION": ("watchdog_action", str),
        "OBJECTIVE": ("objective", str),
        "DPO_BETA": ("dpo_beta", float),
        "LOGGING_STEPS": ("logging_steps", int),
        "EVAL_STEPS": ("eval_steps", int),
        "EVAL_BATCH_SIZE": ("eval_batch_size", int),
        "SAVE_STEPS": ("save_steps", int),
        "SAVE_TOTAL_LIMIT": ("save_total_limit", int),
        "EXPERIMENT_NAME": ("experiment_name", str),
    }

    @staticmethod
    def _env_bool(s: str) -> bool:
        v = s.strip().lower()
        if v in ("1", "true", "yes", "on"):
            return True
        if v in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"boolean env var must be 1/0/true/false/yes/no/on/off, got {s!r}")

    def apply_env_overrides(self, environ=None) -> "TrainConfig":
        env = os.environ if environ is None else environ
        for var, (attr, cast) in self._ENV_MAP.items():
            if var in env and env[var] != "":
                if cast == "_env_bool":
                    cast = self._env_bool
                setattr(self, attr, cast(env[var]))
        return self

    # ---- (de)serialization

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["lora_target_modules"] = list(self.lora_target_modules)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TrainConfig":
        d = dict(d)
        if "mesh" in d and isinstance(d["mesh"], dict):
            d["mesh"] = MeshConfig(**d["mesh"])
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown config keys: {sorted(unknown)}")
        return cls(**d)

    @classmethod
    def load(cls, path: str) -> "TrainConfig":
        """Load from a JSON or YAML file."""
        with open(path) as f:
            text = f.read()
        if path.endswith((".yaml", ".yml")):
            try:
                import yaml  # type: ignore
            except ImportError as e:
                raise ImportError("pyyaml not available; use JSON config") from e
            data = yaml.safe_load(text)
        else:
            data = json.loads(text)
        return cls.from_dict(data)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2)


def str_to_dtype(name: str):
    import jax.numpy as jnp

    return {
        "float32": jnp.float32,
        "f32": jnp.float32,
        "bfloat16": jnp.bfloat16,
        "bf16": jnp.bfloat16,
        "float16": jnp.float16,
    }[name]
