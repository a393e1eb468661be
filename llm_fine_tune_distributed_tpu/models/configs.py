"""Model presets for the supported decoder families.

The flagship is SmolLM3-3B (the reference's hard-coded model,
reference ``training.py:54``); the other presets cover the configs named in
BASELINE.json (Llama-3-8B FSDP, Mistral-7B DPO, Llama-3-70B QLoRA) plus the
Mixtral MoE family (expert parallelism, ops/moe.py). Values verified against
the HF ``transformers`` config classes
(``SmolLM3Config``/``LlamaConfig``/``MistralConfig``/``MixtralConfig``).
"""

from __future__ import annotations

import dataclasses

from llm_fine_tune_distributed_tpu.config import ModelConfig


def _smollm3_no_rope(num_layers: int, interval: int = 4) -> tuple:
    """SmolLM3 NoPE pattern: every `interval`-th layer (1-indexed) has no RoPE.

    Matches HF ``SmolLM3Config``: ``no_rope_layers[i] = 0 if (i+1) % 4 == 0``.
    """
    return tuple(0 if (i + 1) % interval == 0 else 1 for i in range(num_layers))


PRESETS = {
    # Tiny config for unit tests — same structure as SmolLM3 (GQA + NoPE).
    "tiny": ModelConfig(
        name="tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        rope_theta=10_000.0,
        max_position_embeddings=512,
        tie_word_embeddings=True,
        no_rope_layers=_smollm3_no_rope(4),
    ),
    # Tiny config with untied embeddings + sliding window (Mistral-style paths).
    "tiny_mistral": ModelConfig(
        name="tiny_mistral",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        rope_theta=10_000.0,
        max_position_embeddings=512,
        tie_word_embeddings=False,
        sliding_window=64,
    ),
    "smollm3_3b": ModelConfig(
        name="smollm3_3b",
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=11008,
        num_layers=36,
        num_heads=16,
        num_kv_heads=4,
        rope_theta=5_000_000.0,  # HuggingFaceTB/SmolLM3-3B release value
        max_position_embeddings=65536,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        no_rope_layers=_smollm3_no_rope(36),
    ),
    "llama3_8b": ModelConfig(
        name="llama3_8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=500_000.0,
        max_position_embeddings=8192,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    ),
    "llama3_1_8b": ModelConfig(
        # HF meta-llama/Llama-3.1-8B: same arch as llama3_8b, 128k context
        # via the "llama3" smoothed-NTK rope scaling
        name="llama3_1_8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=500_000.0,
        max_position_embeddings=131072,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        rope_scaling_type="llama3",
        rope_scaling_factor=8.0,
        rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0,
        rope_original_max_position=8192,
    ),
    "llama3_2_1b": ModelConfig(
        # HF meta-llama/Llama-3.2-1B: tied embeddings, llama3 rope factor 32
        name="llama3_2_1b",
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        rope_theta=500_000.0,
        max_position_embeddings=131072,
        rms_norm_eps=1e-5,
        tie_word_embeddings=True,
        rope_scaling_type="llama3",
        rope_scaling_factor=32.0,
        rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0,
        rope_original_max_position=8192,
    ),
    "llama3_2_3b": ModelConfig(
        # HF meta-llama/Llama-3.2-3B
        name="llama3_2_3b",
        vocab_size=128256,
        hidden_size=3072,
        intermediate_size=8192,
        num_layers=28,
        num_heads=24,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500_000.0,
        max_position_embeddings=131072,
        rms_norm_eps=1e-5,
        tie_word_embeddings=True,
        rope_scaling_type="llama3",
        rope_scaling_factor=32.0,
        rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0,
        rope_original_max_position=8192,
    ),
    "llama3_70b": ModelConfig(
        name="llama3_70b",
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        rope_theta=500_000.0,
        max_position_embeddings=8192,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    ),
    # Tiny MoE config (Mixtral structure) for unit tests / EP mesh tests.
    "tiny_moe": ModelConfig(
        name="tiny_moe",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        rope_theta=10_000.0,
        max_position_embeddings=512,
        tie_word_embeddings=False,
        num_experts=4,
        num_experts_per_tok=2,
    ),
    "mixtral_8x7b": ModelConfig(
        name="mixtral_8x7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=1_000_000.0,
        max_position_embeddings=32768,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        num_experts=8,
        num_experts_per_tok=2,
    ),
    "qwen2_7b": ModelConfig(
        # HF Qwen/Qwen2-7B: qkv bias without o_proj bias, untied embeddings
        name="qwen2_7b",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        rope_theta=1_000_000.0,
        max_position_embeddings=32768,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        attention_bias=True,
        attention_out_bias=False,
    ),
    "qwen3_8b": ModelConfig(
        # HF Qwen/Qwen3-8B: per-head q/k RMSNorm, no attention bias, untied
        name="qwen3_8b",
        vocab_size=151936,
        hidden_size=4096,
        intermediate_size=12288,
        num_layers=36,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1_000_000.0,
        max_position_embeddings=40960,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        qk_norm=True,
    ),
    "tiny_gemma2": ModelConfig(
        # unit-test scale Gemma2: every family knob live. vocab 512 = the
        # byte-chatml test tokenizer's vocab (256 bytes + specials + pad)
        name="tiny_gemma2",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10_000.0,
        max_position_embeddings=512,
        tie_word_embeddings=True,
        sliding_window=8,
        alternating_sliding_window=True,
        hidden_act="gelu_tanh",
        sandwich_norms=True,
        zero_centered_norm=True,
        embed_scale=True,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        query_pre_attn_scalar=16.0,
    ),
    "gemma2_9b": ModelConfig(
        # HF google/gemma-2-9b: GeGLU, sandwich norms, zero-centered RMSNorm,
        # scaled embeddings, attn/final logit softcaps, local/global
        # alternating sliding window, tied embeddings
        name="gemma2_9b",
        vocab_size=256000,
        hidden_size=3584,
        intermediate_size=14336,
        num_layers=42,
        num_heads=16,
        num_kv_heads=8,
        head_dim=256,
        rope_theta=10_000.0,
        max_position_embeddings=8192,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        sliding_window=4096,
        alternating_sliding_window=True,
        hidden_act="gelu_tanh",
        sandwich_norms=True,
        zero_centered_norm=True,
        embed_scale=True,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        query_pre_attn_scalar=256.0,
    ),
    "moonlight_16b_a3b": ModelConfig(
        # HF moonshotai/Moonlight-16B-A3B (model_type deepseek_v3): latent
        # attention with q/k heads of 128 + 64 rope dimensions and v heads of
        # 128, one leading dense layer, then 64 routed experts (top 6, sigmoid
        # scores, selection bias) beside 2 shared experts. Training path only
        # (infer/ refuses latent attention). Set held_experts to one process's
        # share of the experts for expert parallelism.
        name="moonlight_16b_a3b",
        vocab_size=163840,
        hidden_size=2048,
        intermediate_size=11264,
        num_layers=27,
        num_heads=16,
        num_kv_heads=16,
        rope_theta=50_000.0,
        max_position_embeddings=8192,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        n_routed_experts=64,
        num_experts_per_tok=6,
        moe_intermediate_size=1408,
        n_shared_experts=2,
        first_k_dense_replace=1,
        routed_scaling_factor=2.446,
    ),
    "tiny_mla_moe": ModelConfig(
        # Moonlight's structure at toy widths (tests, the benchmark's CPU
        # rehearsal): this process holds 4 of the 16 routed experts
        name="tiny_mla_moe",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=160,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        rope_theta=50_000.0,
        max_position_embeddings=512,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        n_routed_experts=16,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        n_shared_experts=2,
        first_k_dense_replace=1,
        routed_scaling_factor=2.446,
        held_experts=(0, 1, 2, 3),
    ),
    "mellum2_12b_a2_5b": ModelConfig(
        # HF JetBrains/Mellum2-12B-A2.5B-Instruct (model_type mellum): three
        # window layers (1024, plain rope) to one global layer (YaRN rope, its
        # factor on cos and sin), GQA 32/4 at 128; every layer's feed-forward
        # is 64 experts of 896 behind a softmax router, 8 a token, weights
        # normalised over the 8, no shared expert, no selection bias. Set
        # held_experts to one process's share for expert parallelism.
        name="mellum2_12b_a2_5b",
        vocab_size=98304,
        hidden_size=2304,
        intermediate_size=7168,  # the config's dense width; no layer is dense
        num_layers=28,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=500_000.0,
        max_position_embeddings=131072,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        sliding_window=1024,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 7,
        rope_scaling_type="yarn",
        rope_scaling_factor=16.0,
        rope_original_max_position=8192,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_attention_factor=1.2772588722239782,
        rope_scaling_layer_type="full_attention",
        n_routed_experts=64,
        num_experts_per_tok=8,
        moe_intermediate_size=896,
        router_scoring="softmax",
    ),
    "tiny_mellum": ModelConfig(
        # Mellum's structure at toy widths (tests, the benchmark's CPU
        # rehearsal): one period of the 3:1 pattern, a window shorter than the
        # rows, this process holding 4 of the 16 routed experts
        name="tiny_mellum",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=500_000.0,
        max_position_embeddings=2048,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        sliding_window=32,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        rope_scaling_type="yarn",
        rope_scaling_factor=16.0,
        rope_original_max_position=128,
        rope_scaling_layer_type="full_attention",
        n_routed_experts=16,
        num_experts_per_tok=4,
        moe_intermediate_size=32,
        router_scoring="softmax",
        held_experts=(0, 1, 2, 3),
    ),
    "qwen3_next_80b_a3b": ModelConfig(
        # HF Qwen/Qwen3-Next-80B-A3B-Instruct (model_type qwen3_next): three
        # Gated DeltaNet layers (16 key heads, 32 value heads of 128, a causal
        # convolution of 4 taps) to one gated softmax-attention layer (16
        # heads of 256 on 2, a quarter of each head rotated, zero-centred q/k
        # norms, a sigmoid gate on the output); every layer's feed-forward is
        # 512 experts of 512 behind a softmax router, 10 a token, beside one
        # shared expert behind a sigmoid gate; every norm zero-centred. Set
        # held_experts to one process's share for expert parallelism.
        name="qwen3_next_80b_a3b",
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=5120,  # the config's dense width; no layer is dense
        num_layers=48,
        num_heads=16,
        num_kv_heads=2,
        head_dim=256,
        rope_theta=10_000_000.0,
        max_position_embeddings=262144,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        qk_norm=True,
        zero_centered_norm=True,
        partial_rotary_factor=0.25,
        attention_output_gate=True,
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 12,
        linear_num_key_heads=16,
        linear_num_value_heads=32,
        linear_key_head_dim=128,
        linear_value_head_dim=128,
        linear_conv_kernel_dim=4,
        n_routed_experts=512,
        num_experts_per_tok=10,
        moe_intermediate_size=512,
        n_shared_experts=1,
        shared_expert_gate=True,
        router_scoring="softmax",
    ),
    "tiny_qwen3_next": ModelConfig(
        # Qwen3-Next's structure at toy widths (tests, the benchmark's CPU
        # rehearsal): one period of the 3:1 pattern, two value heads a key
        # head, a quarter of a head rotated, this process holding 4 of the 16
        # routed experts
        name="tiny_qwen3_next",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,
        rope_theta=10_000_000.0,
        max_position_embeddings=2048,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        qk_norm=True,
        zero_centered_norm=True,
        partial_rotary_factor=0.25,
        attention_output_gate=True,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        linear_num_key_heads=2,
        linear_num_value_heads=4,
        linear_key_head_dim=16,
        linear_value_head_dim=16,
        linear_conv_kernel_dim=4,
        n_routed_experts=16,
        num_experts_per_tok=4,
        moe_intermediate_size=32,
        n_shared_experts=1,
        shared_expert_gate=True,
        router_scoring="softmax",
        held_experts=(0, 1, 2, 3),
    ),
    "trinity_mini": ModelConfig(
        # HF arcee-ai/Trinity-Mini (model_type afmoe, 26B-A3B): three window
        # layers (2048, plain rope) to one global layer WITHOUT rope, GQA 32/4
        # at 128 with per-head q/k norms and a sigmoid gate on the attention
        # output (q_proj holds [q | gate] by head), four norms a block; two
        # leading dense layers of 6144, then 128 experts of 1024 behind a
        # sigmoid router with a selection bias, 8 a token, weights over their
        # sum times 2.826, beside one shared expert; embeddings times
        # sqrt(hidden). Set held_experts to one process's share for expert
        # parallelism.
        name="trinity_mini",
        vocab_size=200192,
        hidden_size=2048,
        intermediate_size=6144,
        num_layers=32,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=10_000.0,
        max_position_embeddings=131072,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        qk_norm=True,
        sandwich_norms=True,
        embed_scale=True,
        attention_output_gate=True,
        sliding_window=2048,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 8,
        no_rope_layers=(1, 1, 1, 0) * 8,
        n_routed_experts=128,
        num_experts_per_tok=8,
        moe_intermediate_size=1024,
        n_shared_experts=1,
        first_k_dense_replace=2,
        routed_scaling_factor=2.826,
    ),
    "tiny_trinity": ModelConfig(
        # Trinity's structure at toy widths (tests, the benchmark's CPU
        # rehearsal): one leading dense layer, then one period of the 3:1
        # pattern as the benchmark's cut reads it (layer 3 the global one), a
        # window shorter than the rows, this process holding 4 of the 16
        # routed experts
        name="tiny_trinity",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=160,
        num_layers=5,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10_000.0,
        max_position_embeddings=2048,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        qk_norm=True,
        sandwich_norms=True,
        embed_scale=True,
        attention_output_gate=True,
        sliding_window=32,
        layer_types=("sliding_attention",) * 3 + ("full_attention", "sliding_attention"),
        no_rope_layers=(1, 1, 1, 0, 1),
        n_routed_experts=16,
        num_experts_per_tok=4,
        moe_intermediate_size=32,
        n_shared_experts=1,
        first_k_dense_replace=1,
        routed_scaling_factor=2.826,
        held_experts=(0, 1, 2, 3),
    ),
    "kimi_linear_48b_a3b": ModelConfig(
        # HF moonshotai/Kimi-Linear-48B-A3B-Instruct (model_type kimi_linear):
        # three Kimi Delta Attention layers (32 heads of 128, a causal
        # convolution of 4 taps each for q, k, v, a decay a channel and an output
        # gate through low-rank pairs of 128) to one latent-attention layer
        # WITHOUT rope (q/k heads of 128 + 64, v heads of 128, a latent of 512),
        # the 27th layer latent too; one leading dense layer of 9216, then 256
        # experts of 1024 behind a sigmoid router with a selection bias, 8 a
        # token, weights over their sum times 2.446, beside one shared expert.
        # Training path only. Set held_experts to one process's share for
        # expert parallelism.
        name="kimi_linear_48b_a3b",
        vocab_size=163840,
        hidden_size=2304,
        intermediate_size=9216,
        num_layers=27,
        num_heads=32,
        num_kv_heads=32,
        head_dim=72,
        rope_theta=10_000.0,
        max_position_embeddings=1048576,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 6 + ("linear_attention",) * 2 + ("full_attention",),
        linear_num_key_heads=32,
        linear_num_value_heads=32,
        linear_key_head_dim=128,
        linear_value_head_dim=128,
        linear_conv_kernel_dim=4,
        linear_decay_rank=128,
        linear_gate_rank=128,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        mla_use_nope=True,
        n_routed_experts=256,
        num_experts_per_tok=8,
        moe_intermediate_size=1024,
        n_shared_experts=1,
        first_k_dense_replace=1,
        routed_scaling_factor=2.446,
    ),
    "tiny_kimi_linear": ModelConfig(
        # Kimi Linear's structure at toy widths (tests, the benchmark's CPU
        # rehearsal): the first five layers of the pattern as the benchmark's
        # cut reads them (a leading dense layer, layer 3 the latent one), this
        # process holding 4 of the 16 routed experts
        name="tiny_kimi_linear",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=160,
        num_layers=5,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        rope_theta=10_000.0,
        max_position_embeddings=2048,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        layer_types=("linear_attention",) * 3 + ("full_attention", "linear_attention"),
        linear_num_key_heads=4,
        linear_num_value_heads=4,
        linear_key_head_dim=16,
        linear_value_head_dim=16,
        linear_conv_kernel_dim=4,
        linear_decay_rank=16,
        linear_gate_rank=16,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        mla_use_nope=True,
        n_routed_experts=16,
        num_experts_per_tok=4,
        moe_intermediate_size=32,
        n_shared_experts=1,
        first_k_dense_replace=1,
        routed_scaling_factor=2.446,
        held_experts=(0, 1, 2, 3),
    ),
    "evabyte_6_5b": ModelConfig(
        # HF EvaByte/EvaByte (model_type evabyte, attention_class eva): a byte-level model, vocabulary 320. Every
        # layer a pre-norm Llama block (norms with a unit offset, no bias) whose mixer is EVA attention: exact
        # softmax inside the token's own aligned window of 2048, every earlier window through one learned summary a
        # chunk of 16 (ops/eva_attention.py); the residual stream in float32; eight next-byte heads side by side in
        # one untied lm_head (head i at position t answers byte t + 1 + i). 32 x 202,391,552 (4 x 4096^2 + 3 x 4096 x
        # 11008, + 8,192 of phi and mu, + 8,192 of norms) + 1,310,720 (embedding) + 10,485,760 (heads) + 4,096 (final
        # norm) = 6,488,330,240 parameters. Training path only.
        name="evabyte_6_5b",
        vocab_size=320,
        hidden_size=4096,
        intermediate_size=11008,
        num_layers=32,
        num_heads=32,
        num_kv_heads=32,
        rope_theta=100_000.0,
        max_position_embeddings=32768,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        zero_centered_norm=True,
        eva_window=2048,
        eva_chunk=16,
        num_pred_heads=8,
        fp32_residual=True,
    ),
    "tiny_evabyte": ModelConfig(
        # EvaByte's structure at toy widths (tests, the benchmark's CPU rehearsal): windows of 32 tokens in chunks
        # of 4, so a row of 160 is five windows
        name="tiny_evabyte",
        vocab_size=320,
        hidden_size=64,
        intermediate_size=176,
        num_layers=4,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        rope_theta=100_000.0,
        max_position_embeddings=2048,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        zero_centered_norm=True,
        eva_window=32,
        eva_chunk=4,
        num_pred_heads=8,
        fp32_residual=True,
    ),
    "granite_4_0_h_micro": ModelConfig(
        # HF ibm-granite/granite-4.0-h-micro (model_type granitemoehybrid): 40 pre-norm blocks, nine Mamba-2 layers
        # (ops/ssd.py: 64 heads of 64 with a state of 128 under one scalar decay a head, one B and C for all heads, a
        # convolution of 4 taps with a bias over [x | B | C], the gate before ONE norm over the 4096 inner channels)
        # to one GQA layer (32 / 8 heads of 64, NO rope, scores times attention_multiplier 1/64) at 5, 15, 25, 35; the
        # feed-forward a SwiGLU MLP of 8192 (HF's shared_mlp; num_local_experts 0: no routed part); the embedding
        # times 12, both residual adds times 0.22, the logits divided by 8; vocabulary 100,352, tied. 36 x 76,182,976
        # (mixer 25,847,232: in_proj 2048 x 8512, the convolution 4352 x 4 + 4352, A_log, D, dt_bias 64 each, the
        # gated norm 4096, out_proj 4096 x 2048; MLP 50,331,648; two norms 4096) + 4 x 60,821,504 (q, o 2048 x 2048;
        # k, v 2048 x 512) + 205,520,896 (the table) + 2048 (final norm) = 3,191,396,096 parameters. Training path only.
        name="granite_4_0_h_micro",
        vocab_size=100352,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=40,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=10_000.0,
        max_position_embeddings=131072,
        rms_norm_eps=1e-5,
        tie_word_embeddings=True,
        layer_types=tuple("full_attention" if i % 10 == 5 else "mamba" for i in range(40)),
        no_rope_layers=(0,) * 40,
        mamba_n_heads=64,
        mamba_d_head=64,
        mamba_d_state=128,
        mamba_n_groups=1,
        mamba_d_conv=4,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        attention_multiplier=0.015625,
    ),
    "tiny_granite_h": ModelConfig(
        # Granite 4.0-H's structure at toy widths (tests, the benchmark's CPU rehearsal): ten layers in the published
        # pattern (attention at 5), Mamba heads of 16 with a state of 32, attention heads of 16
        name="tiny_granite_h",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=176,
        num_layers=10,
        num_heads=4,
        num_kv_heads=2,
        rope_theta=10_000.0,
        max_position_embeddings=2048,
        rms_norm_eps=1e-5,
        tie_word_embeddings=True,
        layer_types=tuple("full_attention" if i % 10 == 5 else "mamba" for i in range(10)),
        no_rope_layers=(0,) * 10,
        mamba_n_heads=8,
        mamba_d_head=16,
        mamba_d_state=32,
        mamba_n_groups=1,
        mamba_d_conv=4,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        attention_multiplier=0.015625,
    ),
    "mistral_7b": ModelConfig(
        name="mistral_7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=1_000_000.0,  # v0.2+ (v0.1 used 10k + sliding_window=4096)
        max_position_embeddings=32768,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    ),
}


def get_preset(name: str) -> ModelConfig:
    try:
        return PRESETS[name]
    except KeyError:
        raise KeyError(f"unknown model preset {name!r}; available: {sorted(PRESETS)}")


def to_hf_dict(mc: ModelConfig) -> dict:
    """ModelConfig -> HF-style config.json dict (the trainer's saved-artifact
    contract; reference ``training.py:310-311`` writes HF config.json via
    save_model). Every architecture knob is explicit so ``from_hf_config``
    round-trips EXACTLY regardless of the model_type string — the
    model_type-prefix heuristics below never apply to this framework's own
    saves. Round-trip pinned by tests/test_hf_parity.py."""
    return {
        "model_type": mc.name,
        "vocab_size": mc.vocab_size,
        "hidden_size": mc.hidden_size,
        "intermediate_size": mc.intermediate_size,
        "num_hidden_layers": mc.num_layers,
        "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads,
        "head_dim": mc.head_dim,
        "rope_theta": mc.rope_theta,
        "max_position_embeddings": mc.max_position_embeddings,
        "rms_norm_eps": mc.rms_norm_eps,
        "tie_word_embeddings": mc.tie_word_embeddings,
        "attention_bias": mc.attention_bias,
        "attention_out_bias": mc.attention_out_bias,
        "qk_norm": mc.qk_norm,
        # Gemma2-family knobs (explicit keys beat the from_hf_config
        # model_type heuristics on reload)
        "hidden_act": mc.hidden_act,
        # gemma-family model_types resolve their activation from
        # hidden_activation (with a gelu_pytorch_tanh default that would
        # override an exact-GeLU hidden_act on round-trip — ADVICE r4);
        # write both keys so reload is exact for every family
        "hidden_activation": mc.hidden_act,
        "sandwich_norms": mc.sandwich_norms,
        "zero_centered_norm": mc.zero_centered_norm,
        "embed_scale": mc.embed_scale,
        "attn_logit_softcap": mc.attn_logit_softcap,
        "final_logit_softcap": mc.final_logit_softcap,
        "query_pre_attn_scalar": mc.query_pre_attn_scalar,
        "alternating_sliding_window": mc.alternating_sliding_window,
        # HF rope_scaling dict shape so any HF-compatible loader (and our
        # from_hf_config) reads the context extension
        "rope_scaling": (
            {
                "rope_type": mc.rope_scaling_type,
                "factor": mc.rope_scaling_factor,
                "low_freq_factor": mc.rope_low_freq_factor,
                "high_freq_factor": mc.rope_high_freq_factor,
                "original_max_position_embeddings": mc.rope_original_max_position,
                "beta_fast": mc.rope_beta_fast,
                "beta_slow": mc.rope_beta_slow,
                "attention_factor": mc.rope_attention_factor,
                "layer_type": mc.rope_scaling_layer_type,
            }
            if mc.rope_scaling_type
            else None
        ),
        "mlp_bias": mc.mlp_bias,
        "no_rope_layers": list(mc.no_rope_layers),
        "sliding_window": mc.sliding_window,
        "layer_types": list(mc.layer_types),
        "partial_rotary_factor": mc.partial_rotary_factor,
        "attention_output_gate": mc.attention_output_gate,
        "shared_expert_gate": mc.shared_expert_gate,
        "eva_window": mc.eva_window,
        "eva_chunk": mc.eva_chunk,
        "num_pred_heads": mc.num_pred_heads,
        "fp32_residual": mc.fp32_residual,
        "embedding_multiplier": mc.embedding_multiplier,
        "residual_multiplier": mc.residual_multiplier,
        "logits_scaling": mc.logits_scaling,
        "attention_multiplier": mc.attention_multiplier,
        **({
            "mamba_n_heads": mc.mamba_n_heads,
            "mamba_d_head": mc.mamba_d_head,
            "mamba_d_state": mc.mamba_d_state,
            "mamba_n_groups": mc.mamba_n_groups,
            "mamba_d_conv": mc.mamba_d_conv,
        } if "mamba" in mc.layer_types else {}),
        **({
            "linear_num_key_heads": mc.linear_num_key_heads,
            "linear_num_value_heads": mc.linear_num_value_heads,
            "linear_key_head_dim": mc.linear_key_head_dim,
            "linear_value_head_dim": mc.linear_value_head_dim,
            "linear_conv_kernel_dim": mc.linear_conv_kernel_dim,
            "linear_decay_rank": mc.linear_decay_rank,
            "linear_gate_rank": mc.linear_gate_rank,
        } if mc.linear_layers else {}),
        # MoE round trip (HF MixtralConfig naming — consumed by
        # models/configs.from_hf_config at inference load time)
        "num_local_experts": mc.num_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "router_aux_loss_coef": mc.router_aux_coef,
        # latent attention and routed experts with shared experts (HF
        # DeepseekV3Config naming; what this framework implements of it is
        # fixed, and written out so that another loader reads it too)
        **_deepseek_v3_keys(mc),
    }


def _deepseek_v3_keys(mc: ModelConfig) -> dict:
    if not (mc.kv_lora_rank or mc.n_routed_experts):
        return {}
    return {
        "kv_lora_rank": mc.kv_lora_rank,
        "mla_use_nope": mc.mla_use_nope,
        "q_lora_rank": None,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim,
        "v_head_dim": mc.v_head_dim,
        "n_routed_experts": mc.n_routed_experts,
        "n_shared_experts": mc.n_shared_experts,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "first_k_dense_replace": mc.first_k_dense_replace,
        "moe_layer_freq": 1,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "scoring_func": mc.router_scoring,
        "topk_method": "noaux_tc",
        "norm_topk_prob": True,
        "n_group": 1,
        "topk_group": 1,
        "held_experts": list(mc.held_experts),
    }


def _deepseek_v3_fields(g) -> dict:
    """ModelConfig fields of a ``deepseek_v3`` config (HF DeepseekV3Config),
    refusing by name whatever of it this framework does not implement, before
    any weight loads."""
    refused = {
        "q_lora_rank": (None,), "n_group": (1, None), "topk_group": (1, None),
        "scoring_func": ("sigmoid", "softmax"), "topk_method": ("noaux_tc",), "norm_topk_prob": (True,),
        "moe_layer_freq": (1, None), "num_nextn_predict_layers": (0, None),
        "attention_bias": (False, None),
    }
    if g("kv_lora_rank"):  # (DeepSeek's YaRN also rescales the softmax: not written for latent attention)
        refused["rope_scaling"] = (None,)
    for key, allowed in refused.items():
        if g(key) not in allowed:
            raise ValueError(
                f"deepseek_v3 config has {key}={g(key)!r}; implemented: {key} in {allowed} "
                "(q as one matrix, one expert group, sigmoid scores with a selection bias or softmax "
                "scores without, normalised top-k weights, every layer past the leading dense ones "
                "with experts, plain rope under latent attention)"
            )
    return dict(
        kv_lora_rank=g("kv_lora_rank"),
        mla_use_nope=bool(g("mla_use_nope", False)),
        qk_nope_head_dim=g("qk_nope_head_dim"),
        qk_rope_head_dim=g("qk_rope_head_dim"),
        v_head_dim=g("v_head_dim"),
        n_routed_experts=g("n_routed_experts") or 0,
        n_shared_experts=g("n_shared_experts") or 0,
        moe_intermediate_size=g("moe_intermediate_size") or 0,
        first_k_dense_replace=g("first_k_dense_replace") or 0,
        routed_scaling_factor=float(g("routed_scaling_factor", 1.0)),
        held_experts=tuple(g("held_experts") or ()),
        num_experts_per_tok=g("num_experts_per_tok"),
        router_scoring=g("scoring_func"),
    )


def _mellum_fields(g) -> dict:
    """ModelConfig fields of a ``mellum`` config (JetBrains Mellum 2): window
    and global layers by ``layer_types``, ``rope_parameters`` keyed by layer
    type (plain rope for the window layers, YaRN for the global ones), every
    layer's feed-forward ``num_experts`` experts behind a softmax router.
    Whatever of it this framework does not implement is refused by name,
    before any weight loads."""
    n = g("num_hidden_layers")
    layer_types = tuple(g("layer_types") or ())
    ropes = dict(g("rope_parameters") or {})
    window, full = dict(ropes.get("sliding_attention") or {}), dict(ropes.get("full_attention") or {})
    problems = []
    if set(g("mlp_layer_types") or ("sparse",)) != {"sparse"}:
        problems.append("mlp_layer_types other than 'sparse' on every layer")
    if not g("norm_topk_prob", True):
        problems.append("norm_topk_prob false")
    if window.get("rope_type", "default") != "default":
        problems.append(f"rope_parameters.sliding_attention.rope_type {window.get('rope_type')!r} (implemented: default)")
    if full.get("rope_type", "default") not in ("default", "yarn"):
        problems.append(f"rope_parameters.full_attention.rope_type {full.get('rope_type')!r} (implemented: default, yarn)")
    if window.get("rope_theta", full.get("rope_theta")) != full.get("rope_theta"):
        problems.append("a rope_theta for the window layers other than the global layers'")
    if len(layer_types) < n:
        problems.append(f"layer_types with {len(layer_types)} entries for {n} layers")
    if problems:
        raise ValueError("mellum config has " + "; ".join(problems))
    yarn = full.get("rope_type") == "yarn"
    return dict(
        layer_types=layer_types,
        sliding_window=g("sliding_window") if g("use_sliding_window", True) else None,
        rope_theta=float(full.get("rope_theta", 10_000.0)),
        rope_scaling_type="yarn" if yarn else None,
        rope_scaling_factor=float(full.get("factor", 1.0)),
        rope_original_max_position=int(full.get("original_max_position_embeddings", 8192)),
        rope_beta_fast=float(full.get("beta_fast") or 32.0),
        rope_beta_slow=float(full.get("beta_slow") or 1.0),
        rope_attention_factor=full.get("attention_factor"),
        rope_scaling_layer_type="full_attention" if yarn else None,
        n_routed_experts=g("num_experts"),
        num_experts_per_tok=g("num_experts_per_tok"),
        moe_intermediate_size=g("moe_intermediate_size"),
        router_scoring="softmax",
        held_experts=tuple(g("held_experts") or ()),
    )


def _qwen3_next_fields(g) -> dict:
    """ModelConfig fields of a ``qwen3_next`` config (HF Qwen3NextConfig):
    Gated DeltaNet layers and gated softmax-attention layers by
    ``layer_types`` (or ``full_attention_interval``), a quarter of each head
    rotated, every norm zero-centred, every layer's feed-forward
    ``num_experts`` experts behind a softmax router beside one gated shared
    expert. Whatever of it this framework does not implement is refused by
    name, before any weight loads."""
    n = g("num_hidden_layers")
    interval = g("full_attention_interval") or 4
    layer_types = tuple(g("layer_types") or (
        "full_attention" if (i + 1) % interval == 0 else "linear_attention" for i in range(n)
    ))
    moe_width, shared_width = g("moe_intermediate_size"), g("shared_expert_intermediate_size") or 0
    problems = []
    if (g("decoder_sparse_step") or 1) != 1 or list(g("mlp_only_layers") or ()):
        problems.append("layers with a dense feed-forward (decoder_sparse_step other than 1, mlp_only_layers)")
    if not g("norm_topk_prob", True):
        problems.append("norm_topk_prob false")
    if g("rope_scaling"):
        problems.append(f"rope_scaling {g('rope_scaling')!r} (implemented: none)")
    if g("attention_bias"):
        problems.append("attention_bias")
    if shared_width % moe_width:
        problems.append(f"a shared expert of {shared_width}, no multiple of the routed experts' {moe_width}")
    if len(layer_types) < n:
        problems.append(f"layer_types with {len(layer_types)} entries for {n} layers")
    if problems:
        raise ValueError("qwen3_next config has " + "; ".join(problems))
    return dict(
        layer_types=layer_types,
        qk_norm=True,
        zero_centered_norm=True,
        attention_bias=False,
        sliding_window=None,
        partial_rotary_factor=float(g("partial_rotary_factor", 1.0)),
        attention_output_gate=True,
        linear_num_key_heads=g("linear_num_key_heads"),
        linear_num_value_heads=g("linear_num_value_heads"),
        linear_key_head_dim=g("linear_key_head_dim"),
        linear_value_head_dim=g("linear_value_head_dim"),
        linear_conv_kernel_dim=g("linear_conv_kernel_dim"),
        n_routed_experts=g("num_experts"),
        num_experts_per_tok=g("num_experts_per_tok"),
        moe_intermediate_size=moe_width,
        n_shared_experts=shared_width // moe_width,
        shared_expert_gate=bool(shared_width),
        router_scoring="softmax",
        held_experts=tuple(g("held_experts") or ()),
    )


def _afmoe_fields(g) -> dict:
    """ModelConfig fields of an ``afmoe`` config (arcee-ai Trinity; HF
    AfmoeConfig): window and global layers by ``layer_types`` (or every
    ``global_attn_every_n_layers``-th global), rope on the window layers only,
    per-head q/k norms, a sigmoid gate on every layer's attention output, four
    norms a block, ``num_dense_layers`` leading dense layers, then
    ``num_experts`` experts behind a sigmoid router whose ``expert_bias``
    selects and does not weigh (``route_norm``, ``route_scale``) beside
    ``num_shared_experts`` shared ones; ``mup_enabled`` multiplies the
    embeddings by sqrt(hidden). The family's convention where the config has
    no key (the norms, the gate, which layers rotate) is HF's
    ``models/afmoe/modeling_afmoe.py``. ``load_balance_coeff`` is pretraining's
    (the bias is a buffer here: no step updates it). Whatever of it this
    framework does not implement is refused by name, before any weight loads."""
    n = g("num_hidden_layers")
    every = g("global_attn_every_n_layers") or 4
    layer_types = tuple(g("layer_types") or (
        "full_attention" if (i + 1) % every == 0 else "sliding_attention" for i in range(n)
    ))
    problems = []
    for key in ("n_group", "topk_group", "num_expert_groups", "num_limited_groups"):
        if (g(key) or 1) > 1:
            problems.append(f"{key} {g(key)} (implemented: one group of experts)")
    if not g("route_norm", True):
        problems.append("route_norm false (implemented: weights over the sum of the chosen scores)")
    if g("score_func", "sigmoid") != "sigmoid":
        problems.append(f"score_func {g('score_func')!r} (implemented: sigmoid)")
    if set(layer_types[:n]) - {"sliding_attention", "full_attention"} or len(layer_types) < n:
        problems.append(f"layer_types {sorted(set(layer_types))} with {len(layer_types)} entries for {n} layers")
    if problems:
        raise ValueError("afmoe config has " + "; ".join(problems))
    return dict(
        layer_types=layer_types,
        no_rope_layers=tuple(int(kind == "sliding_attention") for kind in layer_types),
        qk_norm=True,
        sandwich_norms=True,
        attention_output_gate=True,
        embed_scale=bool(g("mup_enabled", False)),
        n_routed_experts=g("num_experts"),
        num_experts_per_tok=g("num_experts_per_tok"),
        moe_intermediate_size=g("moe_intermediate_size"),
        n_shared_experts=g("num_shared_experts") or 0,
        first_k_dense_replace=g("num_dense_layers") or 0,
        routed_scaling_factor=float(g("route_scale", 1.0)),
        router_scoring="sigmoid",
        held_experts=tuple(g("held_experts") or ()),
    )


def _kimi_linear_fields(g) -> dict:
    """ModelConfig fields of a ``kimi_linear`` config (moonshotai Kimi Linear): Kimi Delta Attention layers and
    latent-attention layers by ``linear_attn_config``'s 1-based ``kda_layers`` / ``full_attn_layers``, the latter
    without rope where ``mla_use_nope``; ``first_k_dense_replace`` leading dense layers, then ``num_experts`` experts
    behind a sigmoid router whose bias selects and does not weigh (``moe_renormalize``, ``routed_scaling_factor``)
    beside ``num_shared_experts`` shared ones. The config has no key for the rank of the decay's and the output gate's
    pairs of matrices: the family's convention is the linear head's width (HF ``modeling_kimi.py``). Whatever of it this
    framework does not implement is refused by name, before any weight loads."""
    n = g("num_hidden_layers")
    linear = dict(g("linear_attn_config") or {})
    # (a cut in depth keeps the published lists and reads them up to num_hidden_layers)
    kda, full = ({i for i in linear.get(key) or () if i <= n} for key in ("kda_layers", "full_attn_layers"))
    problems = []
    for key in ("num_expert_group", "topk_group"):
        if (g(key) or 1) > 1:
            problems.append(f"{key} {g(key)} (implemented: one group of experts)")
    if (g("moe_layer_freq") or 1) != 1:
        problems.append(f"moe_layer_freq {g('moe_layer_freq')} (implemented: 1, every layer past the leading dense ones with experts)")
    if not g("moe_renormalize", True):
        problems.append("moe_renormalize false (implemented: weights over the sum of the chosen scores)")
    if g("moe_router_activation_func", "sigmoid") != "sigmoid":
        problems.append(f"moe_router_activation_func {g('moe_router_activation_func')!r} (implemented: sigmoid)")
    if g("q_lora_rank") is not None:
        problems.append(f"q_lora_rank {g('q_lora_rank')} (implemented: q as one matrix)")
    if (g("num_nextn_predict_layers") or 0) > 0:
        problems.append(f"num_nextn_predict_layers {g('num_nextn_predict_layers')} (implemented: no multi-token-prediction layer)")
    if g("rope_scaling"):
        problems.append(f"rope_scaling {g('rope_scaling')!r} (implemented: none)")
    if kda & full or (kda | full) != set(range(1, n + 1)):
        problems.append(f"linear_attn_config whose kda_layers and full_attn_layers do not name each of the layers 1..{n} once")
    if problems:
        raise ValueError("kimi_linear config has " + "; ".join(problems))
    width = linear["head_dim"]
    return dict(
        layer_types=tuple("linear_attention" if i + 1 in kda else "full_attention" for i in range(n)),
        linear_num_key_heads=linear["num_heads"],
        linear_num_value_heads=linear["num_heads"],
        linear_key_head_dim=width,
        linear_value_head_dim=width,
        linear_conv_kernel_dim=linear.get("short_conv_kernel_size", 4),
        linear_decay_rank=width,
        linear_gate_rank=width,
        kv_lora_rank=g("kv_lora_rank"),
        qk_nope_head_dim=g("qk_nope_head_dim"),
        qk_rope_head_dim=g("qk_rope_head_dim"),
        v_head_dim=g("v_head_dim"),
        mla_use_nope=bool(g("mla_use_nope", False)),
        n_routed_experts=g("num_experts"),
        num_experts_per_tok=g("num_experts_per_token"),
        moe_intermediate_size=g("moe_intermediate_size"),
        n_shared_experts=g("num_shared_experts") or 0,
        first_k_dense_replace=g("first_k_dense_replace") or 0,
        routed_scaling_factor=float(g("routed_scaling_factor", 1.0)),
        router_scoring="sigmoid",
        held_experts=tuple(g("held_experts") or ()),
        max_position_embeddings=g("max_position_embeddings") or g("model_max_length") or 4096,
    )


def _evabyte_fields(g) -> dict:
    """ModelConfig fields of an ``evabyte`` config (EvaByte/EvaByte): EVA attention in every layer by ``window_size``
    and ``chunk_size``, norms with a unit offset (``norm_add_unit_offset``), the residual stream in float32
    (``fp32_skip_add``), ``num_pred_heads`` next-byte heads in one untied head. Whatever of it this framework does not
    implement is refused by name, before any weight loads."""
    problems = []
    if g("attention_class", "eva") != "eva":
        problems.append(f"attention_class {g('attention_class')!r} (implemented: 'eva')")
    if g("num_chunks") is not None:
        problems.append(f"num_chunks {g('num_chunks')} (implemented: chunks of chunk_size tokens, their number by the row)")
    window, chunk = g("window_size"), g("chunk_size")
    if not window or not chunk or window % chunk:
        problems.append(f"window_size {window} that chunk_size {chunk} does not divide")
    if g("rope_scaling"):
        problems.append(f"rope_scaling {g('rope_scaling')!r} (implemented: none)")
    if g("tie_word_embeddings"):
        problems.append("tie_word_embeddings (implemented: an untied head of num_pred_heads x vocab_size columns)")
    if (g("num_key_value_heads") or g("num_attention_heads")) != g("num_attention_heads"):
        problems.append(f"num_key_value_heads {g('num_key_value_heads')} (implemented: one key head a query head)")
    if problems:
        raise ValueError("evabyte config has " + "; ".join(problems))
    return dict(
        eva_window=window,
        eva_chunk=chunk,
        num_pred_heads=g("num_pred_heads") or 1,
        fp32_residual=bool(g("fp32_skip_add", False)),
        zero_centered_norm=bool(g("norm_add_unit_offset", False)),
        sliding_window=None,
    )


def _granite_hybrid_fields(g) -> dict:
    """ModelConfig fields of a ``granitemoehybrid`` config (IBM Granite 4.0-H): ``layer_types`` of ``mamba`` and
    ``attention``, the Mamba-2 mixer's sizes, the SwiGLU MLP HF calls ``shared_mlp``, the family's four multipliers.
    Whatever of it this framework does not implement is refused by name, before any weight loads."""
    problems = []
    if g("num_local_experts"):
        problems.append(f"num_local_experts {g('num_local_experts')} (implemented: the shared MLP alone; the family's "
                        "larger models route experts beside it)")
    if g("mamba_proj_bias"):
        problems.append("mamba_proj_bias (implemented: no bias on in_proj and out_proj)")
    if not g("mamba_conv_bias", True):
        problems.append("mamba_conv_bias false (implemented: the convolution with its bias)")
    heads, groups = g("mamba_n_heads") or 0, g("mamba_n_groups") or 1
    if heads % groups:
        problems.append(f"mamba_n_groups {groups} that does not divide mamba_n_heads {heads}")
    if (g("mamba_expand") or 0) * (g("hidden_size") or 0) != heads * (g("mamba_d_head") or 0):
        problems.append(f"mamba_expand {g('mamba_expand')} x hidden_size that is not mamba_n_heads x mamba_d_head")
    if g("normalization_function", "rmsnorm") != "rmsnorm":
        problems.append(f"normalization_function {g('normalization_function')!r} (implemented: 'rmsnorm')")
    position = g("position_embedding_type", "nope")
    if position not in ("nope", "rope"):
        problems.append(f"position_embedding_type {position!r} (implemented: 'nope', 'rope')")
    if g("rope_scaling"):
        problems.append(f"rope_scaling {g('rope_scaling')!r} (implemented: none)")
    kinds = set(g("layer_types") or ()) - {"mamba", "attention"}
    if kinds or not g("layer_types"):
        problems.append(f"layer_types with {sorted(kinds) or 'no entry'} (implemented: 'mamba', 'attention')")
    if problems:
        raise ValueError("granitemoehybrid config has " + "; ".join(problems))
    # (the mixer's sizes and the four multipliers go by HF's own names: ``from_hf_config`` reads them as it reads this
    # framework's own save)
    return dict(
        layer_types=tuple("mamba" if kind == "mamba" else "full_attention" for kind in g("layer_types")),
        no_rope_layers=(int(position == "rope"),) * g("num_hidden_layers"),
        intermediate_size=g("shared_intermediate_size") or g("intermediate_size"),
        sliding_window=None,
    )


def load_model_config(path: str) -> ModelConfig:
    """Read ``path/config.json`` (HF layout) into a ModelConfig — the ONE
    place train-time (trainer._resolve_model_config) and inference-time
    (infer.load_model_dir) architecture resolution share, so the two can
    never diverge."""
    import json
    import os
    from types import SimpleNamespace

    cfg_path = os.path.join(path, "config.json")
    if not os.path.isfile(cfg_path):
        raise FileNotFoundError(f"no config.json under {path}")
    with open(cfg_path) as f:
        raw = json.load(f)
    return from_hf_config(SimpleNamespace(**raw))


def _parse_hidden_act(act) -> str:
    """Map HF activation names to the two implemented gate activations —
    reject anything else at load time (same contract as the rope_scaling
    check below: fail before multi-GB weights load, not inside jit)."""
    act = str(act)
    if act in ("silu", "swish"):
        return "silu"
    if act in ("gelu_tanh", "gelu_pytorch_tanh", "gelu_new"):
        return "gelu_tanh"
    if act == "gelu":
        return "gelu"  # exact (erf) GeLU — early Gemma configs
    raise ValueError(
        f"unsupported hidden_act {act!r}; supported: silu/swish, "
        "gelu (exact), gelu_pytorch_tanh (tanh-approx GeGLU)"
    )


def from_hf_config(hf_config) -> ModelConfig:
    """Build a ModelConfig from a HF transformers PretrainedConfig.

    Lets users point at any local HF checkpoint directory (``config.json``)
    for Llama-family models, mirroring the reference's
    ``AutoModelForCausalLM.from_pretrained`` flexibility
    (reference ``training.py:97-102``).
    """
    g = lambda k, default=None: getattr(hf_config, k, default)
    # The qwen*/gemma* model_type-prefix heuristics below were validated
    # against these exact HF model_types (logit-parity tests,
    # tests/test_hf_parity.py). An ADJACENT family member — e.g. gemma3_text
    # (5:1 local/global window pattern, qk-norm, per-layer rope base) or
    # qwen2_moe (different expert-config keys) — would match the prefix,
    # load without error, and produce wrong logits. Fail before the
    # multi-GB weights load instead (same contract as the rope_scaling and
    # hidden_act checks — ADVICE r4). Checkpoints written by this
    # framework's trainer carry every knob explicitly (_save_model_config
    # always writes sandwich_norms AND qk_norm), so they bypass the
    # heuristics and are accepted under any model_type name.
    mt = str(g("model_type") or "")
    _VALIDATED_HEURISTIC_TYPES = {"qwen2", "qwen3", "qwen3_next", "gemma", "gemma2"}  # (qwen3_next: its own fields below)
    framework_save = g("sandwich_norms") is not None and g("qk_norm") is not None
    if (
        mt.startswith(("qwen", "gemma"))
        and mt not in _VALIDATED_HEURISTIC_TYPES
        and not framework_save
    ):
        raise ValueError(
            f"unrecognized {mt!r} model_type: the qwen*/gemma* architecture "
            f"heuristics are validated only for {sorted(_VALIDATED_HEURISTIC_TYPES)} "
            "(adjacent variants like gemma3/qwen2_moe differ architecturally "
            "and would silently produce wrong logits). Convert the config to "
            "explicit keys or add a validated preset."
        )
    # deepseek_v3 (Moonlight, DeepSeek-V3): by model_type, or by this
    # framework's own save, which carries kv_lora_rank under any name
    deepseek = {}
    kimi = mt == "kimi_linear" and g("linear_attn_config") is not None  # (this framework's own save of one: its own keys)
    if not kimi and (mt == "deepseek_v3" or g("kv_lora_rank") or g("n_routed_experts")):
        deepseek = _deepseek_v3_fields(g)
    no_rope = g("no_rope_layers") or ()
    # HF rope_scaling dict: {"rope_type"|"type": "llama3"|"linear"|"default",
    # "factor", "low_freq_factor", "high_freq_factor",
    # "original_max_position_embeddings"} (Llama-3.1+ checkpoints).
    rs = g("rope_scaling") or {}
    if not isinstance(rs, dict):
        rs = dict(rs)
    rs_type = rs.get("rope_type", rs.get("type"))
    if rs_type in ("default", None):
        rs_type = None
    elif rs_type not in ("linear", "llama3", "yarn"):
        # reject at config-load time, not minutes later inside the first
        # forward's jit trace (after multi-GB weight loading)
        raise ValueError(
            f"unsupported rope_scaling type {rs_type!r}; supported: "
            "'llama3' (Llama-3.1 smoothed NTK), 'yarn', 'linear', 'default'"
        )
    # (an evabyte config is read, and refused by name, before the dataclass's own checks see its keys)
    evabyte = _evabyte_fields(g) if mt == "evabyte" and not framework_save else {}
    granite = _granite_hybrid_fields(g) if mt == "granitemoehybrid" and not framework_save else {}
    mc = ModelConfig(
        name=g("model_type", "hf_model"),
        vocab_size=g("vocab_size"),
        hidden_size=g("hidden_size"),
        intermediate_size=g("intermediate_size"),
        num_layers=g("num_hidden_layers"),
        num_heads=g("num_attention_heads"),
        num_kv_heads=g("num_key_value_heads") or g("num_attention_heads"),
        head_dim=g("head_dim"),
        rope_theta=g("rope_theta", 10_000.0),
        max_position_embeddings=g("max_position_embeddings", 4096),
        rms_norm_eps=g("rms_norm_eps", 1e-6),
        tie_word_embeddings=bool(g("tie_word_embeddings", False)),
        # HF Qwen2-family configs (qwen2, qwen2_moe, qwen2_vl, ...) carry no
        # attention_bias field — their attention has qkv bias (no o bias)
        # implicitly. An explicit attention_out_bias key (written by
        # trainer._save_model_config) wins over the model_type heuristic so
        # saved checkpoints round-trip regardless of their model_type string.
        attention_bias=bool(
            g("attention_bias", False)
            or str(g("model_type") or "").startswith("qwen2")
        ),
        attention_out_bias=bool(
            g(
                "attention_out_bias",
                not str(g("model_type") or "").startswith("qwen2"),
            )
        ),
        # Qwen3-family: per-head q/k RMSNorm is architectural (HF carries no
        # flag); an explicit qk_norm key (trainer._save_model_config) wins.
        qk_norm=bool(
            g("qk_norm", str(g("model_type") or "").startswith("qwen3"))
        ),
        # Gemma2 family: GeGLU/sandwich-norm/zero-centered/softcap knobs.
        # Explicit keys (written by trainer._save_model_config) win; the
        # model_type heuristic covers pristine HF gemma2 checkpoints.
        hidden_act=_parse_hidden_act(
            # Gemma family: HF's GemmaConfig/Gemma2Config resolve the
            # activation from hidden_activation, DEFAULTING to
            # gelu_pytorch_tanh and overriding a stale hidden_act="gelu"
            # (early gemma configs) with a warning — mirror that precedence.
            (g("hidden_activation") or "gelu_pytorch_tanh")
            if str(g("model_type") or "").startswith("gemma")
            else (g("hidden_act") or g("hidden_activation") or "silu")
        ),
        sandwich_norms=bool(
            g("sandwich_norms", str(g("model_type") or "").startswith("gemma2"))
        ),
        zero_centered_norm=bool(
            g(
                "zero_centered_norm",
                str(g("model_type") or "").startswith("gemma"),
            )
        ),
        embed_scale=bool(
            g("embed_scale", str(g("model_type") or "").startswith("gemma"))
        ),
        attn_logit_softcap=(
            g("attn_logit_softcap", None) or g("attn_logit_softcapping", None)
        ),
        final_logit_softcap=(
            g("final_logit_softcap", None) or g("final_logit_softcapping", None)
        ),
        query_pre_attn_scalar=g("query_pre_attn_scalar"),
        alternating_sliding_window=bool(
            g(
                "alternating_sliding_window",
                str(g("model_type") or "").startswith("gemma2"),
            )
        ),
        rope_scaling_type=rs_type,
        rope_scaling_factor=float(rs.get("factor", 1.0)),
        rope_low_freq_factor=float(rs.get("low_freq_factor", 1.0)),
        rope_high_freq_factor=float(rs.get("high_freq_factor", 4.0)),
        rope_original_max_position=int(
            rs.get("original_max_position_embeddings", 8192)
        ),
        rope_beta_fast=float(rs.get("beta_fast") or 32.0),
        rope_beta_slow=float(rs.get("beta_slow") or 1.0),
        rope_attention_factor=rs.get("attention_factor"),
        rope_scaling_layer_type=rs.get("layer_type"),
        mlp_bias=bool(g("mlp_bias", False)),
        no_rope_layers=tuple(no_rope),
        sliding_window=g("sliding_window") if g("use_sliding_window", True) else None,
        layer_types=granite.get("layer_types") or tuple(g("layer_types") or ()),
        # (explicit keys of this framework's own save; a granitemoehybrid config's come from _granite_hybrid_fields)
        mamba_n_heads=g("mamba_n_heads") or 0,
        mamba_d_head=g("mamba_d_head") or 0,
        mamba_d_state=g("mamba_d_state") or 0,
        mamba_n_groups=g("mamba_n_groups") or 1,
        mamba_d_conv=g("mamba_d_conv") or 4,
        embedding_multiplier=float(g("embedding_multiplier") or 1.0),
        residual_multiplier=float(g("residual_multiplier") or 1.0),
        logits_scaling=float(g("logits_scaling") or 1.0),
        attention_multiplier=g("attention_multiplier"),
        # (explicit keys of this framework's own save; a qwen3_next config's come from _qwen3_next_fields)
        partial_rotary_factor=float(g("partial_rotary_factor") or 1.0),
        attention_output_gate=bool(g("attention_output_gate", False)),
        shared_expert_gate=bool(g("shared_expert_gate", False)),
        linear_num_key_heads=g("linear_num_key_heads") or 0,
        linear_num_value_heads=g("linear_num_value_heads") or 0,
        linear_key_head_dim=g("linear_key_head_dim") or 0,
        linear_value_head_dim=g("linear_value_head_dim") or 0,
        linear_conv_kernel_dim=g("linear_conv_kernel_dim") or 4,
        linear_decay_rank=g("linear_decay_rank") or 0,
        linear_gate_rank=g("linear_gate_rank") or 0,
        # (explicit keys of this framework's own save; an evabyte config's come from _evabyte_fields)
        eva_window=g("eva_window") or 0,
        eva_chunk=g("eva_chunk") or 0,
        num_pred_heads=g("num_pred_heads") or 1,
        fp32_residual=bool(g("fp32_residual", False)),
        # MoE (HF MixtralConfig naming). router_aux_loss_coef=0.0 is a
        # legitimate explicit choice (aux disabled) — only None falls back.
        num_experts=0 if granite else g("num_local_experts", 0) or 0,
        num_experts_per_tok=g("num_experts_per_tok", 2) or 2,
        router_aux_coef=(
            0.01 if g("router_aux_loss_coef") is None else g("router_aux_loss_coef")
        ),
    )
    if mt == "mellum":
        return dataclasses.replace(mc, **_mellum_fields(g))
    if mt == "qwen3_next":
        return dataclasses.replace(mc, **_qwen3_next_fields(g))
    if mt == "afmoe" and not framework_save:  # (this framework's own save of one carries every field by its own key)
        return dataclasses.replace(mc, **_afmoe_fields(g))
    if kimi:
        return dataclasses.replace(mc, **_kimi_linear_fields(g))
    if evabyte:
        return dataclasses.replace(mc, **evabyte)
    if granite:
        return dataclasses.replace(mc, **granite)
    return dataclasses.replace(mc, **deepseek) if deepseek else mc
