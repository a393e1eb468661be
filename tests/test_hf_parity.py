"""Numerics parity against the HF torch implementation (SURVEY.md §7.3 risk #1).

Builds a tiny randomly-initialized HF SmolLM3 (and Llama/Mistral) torch model,
round-trips its state dict through our safetensors bridge, and asserts logits
match in float32. This gates RoPE convention (rotate_half), the NoPE layer
pattern, GQA, RMSNorm semantics, and weight transposition all at once.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from llm_fine_tune_distributed_tpu.models.configs import from_hf_config  # noqa: E402
from llm_fine_tune_distributed_tpu.models.hf_io import hf_state_dict_to_pytree  # noqa: E402
from llm_fine_tune_distributed_tpu.models.transformer import forward  # noqa: E402


def _torch_state_to_numpy(model):
    state = {}
    for k, v in model.state_dict().items():
        if k.endswith("rotary_emb.inv_freq"):
            continue
        state[k.replace("model.model.", "model.")] = v.detach().to(torch.float32).numpy()
    return state


def _compare(hf_model, hf_config, seq=12, atol=2e-4):
    cfg = from_hf_config(hf_config)
    state = _torch_state_to_numpy(hf_model)
    params = hf_state_dict_to_pytree(state, cfg, dtype=np.float32)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(2, seq))

    with torch.no_grad():
        ref = hf_model(torch.tensor(ids)).logits.to(torch.float32).numpy()

    ours, _ = forward(params, jnp.asarray(ids, jnp.int32), cfg, compute_dtype=jnp.float32)
    ours = np.asarray(ours)

    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=atol)


def test_smollm3_tiny_logit_parity():
    hf_cfg = transformers.SmolLM3Config(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=5,  # includes one NoPE layer (layer idx 3)
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        tie_word_embeddings=True,
        rope_theta=10000.0,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    torch.manual_seed(0)
    model = transformers.SmolLM3ForCausalLM(hf_cfg).eval()
    _compare(model, hf_cfg)


def test_llama_tiny_logit_parity():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=4,
        max_position_embeddings=128,
        tie_word_embeddings=False,
        rope_theta=10000.0,
        attention_bias=False,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    torch.manual_seed(1)
    model = transformers.LlamaForCausalLM(hf_cfg).eval()
    _compare(model, hf_cfg)


def test_mistral_tiny_logit_parity_with_sliding_window():
    hf_cfg = transformers.MistralConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        tie_word_embeddings=False,
        rope_theta=10000.0,
        sliding_window=8,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    torch.manual_seed(2)
    model = transformers.MistralForCausalLM(hf_cfg).eval()
    _compare(model, hf_cfg, seq=16)


def test_mixtral_tiny_logit_parity():
    """MoE routing semantics vs HF Mixtral: softmax-then-top-k-renormalize,
    per-expert SwiGLU, weighted combine. HF computes every selected expert
    (dropless), so our forward runs with ample capacity to match."""
    import dataclasses

    hf_cfg = transformers.MixtralConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=128,
        tie_word_embeddings=False,
        rope_theta=10000.0,
        sliding_window=None,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    torch.manual_seed(3)
    model = transformers.MixtralForCausalLM(hf_cfg).eval()

    cfg = from_hf_config(hf_cfg)
    assert cfg.num_experts == 4 and cfg.num_experts_per_tok == 2
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # dropless like HF
    state = _torch_state_to_numpy(model)
    params = hf_state_dict_to_pytree(state, cfg, dtype=np.float32)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(2, 12))
    with torch.no_grad():
        ref = model(torch.tensor(ids)).logits.to(torch.float32).numpy()
    ours, _ = forward(params, jnp.asarray(ids, jnp.int32), cfg, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-4, atol=2e-4)


def test_hf_zero_aux_coef_respected():
    """An explicit router_aux_loss_coef=0.0 in the HF config must survive
    import (0.0 is 'aux disabled', not 'use the default')."""
    hf_cfg = transformers.MixtralConfig(
        vocab_size=64, hidden_size=16, intermediate_size=32,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        router_aux_loss_coef=0.0,
    )
    assert from_hf_config(hf_cfg).router_aux_coef == 0.0


def test_qwen2_tiny_logit_parity():
    """Qwen2 family: qkv bias WITHOUT o_proj bias (attention_out_bias=False)
    — gates the bias-leaf init/IO asymmetry against HF Qwen2Attention."""
    hf_cfg = transformers.Qwen2Config(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        tie_word_embeddings=False,
        rope_theta=10000.0,
        use_sliding_window=False,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    torch.manual_seed(0)
    model = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    # HF initializes biases to zero; perturb them so parity actually
    # exercises the bias path
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("bias"):
                p.add_(torch.randn_like(p) * 0.1)
    cfg = from_hf_config(hf_cfg)
    assert cfg.attention_bias and not cfg.attention_out_bias
    _compare(model, hf_cfg)


def test_qwen3_tiny_logit_parity():
    """Qwen3 family: per-head q/k RMSNorm (qk_norm), no attention bias —
    gates norm placement (post-projection, pre-RoPE) against HF
    Qwen3Attention."""
    hf_cfg = transformers.Qwen3Config(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        max_position_embeddings=128,
        tie_word_embeddings=False,
        rope_theta=10000.0,
        use_sliding_window=False,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    torch.manual_seed(0)
    model = transformers.Qwen3ForCausalLM(hf_cfg).eval()
    # q_norm/k_norm init to ones; perturb so parity exercises the norm scale
    with torch.no_grad():
        for name, p in model.named_parameters():
            if "q_norm" in name or "k_norm" in name:
                p.add_(torch.randn_like(p) * 0.1)
    cfg = from_hf_config(hf_cfg)
    assert cfg.qk_norm and not cfg.attention_bias
    _compare(model, hf_cfg)


def test_qwen3_preset_param_count():
    """qwen3_8b preset num_params matches init arithmetic incl. the per-head
    q/k norm leaves (8.19B, HF Qwen3-8B)."""
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.utils.tree import count_params

    mc = get_preset("qwen3_8b")
    assert 8.0e9 < mc.num_params < 8.4e9
    tiny = mc.replace(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
    )
    params = init_params(jax.random.PRNGKey(0), tiny, dtype=jnp.float32)
    assert count_params(params) == tiny.num_params
    attn = params["model"]["layers"]["0"]["self_attn"]
    assert attn["q_norm"]["weight"].shape == (16,)
    assert attn["k_norm"]["weight"].shape == (16,)


def test_qwen2_preset_param_count():
    """qwen2_7b preset num_params matches the arch arithmetic with the
    o-bias excluded (7.62B, HF Qwen2-7B)."""
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.utils.tree import count_params

    mc = get_preset("qwen2_7b")
    assert 7.5e9 < mc.num_params < 7.8e9
    tiny = mc.replace(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=None,
    )
    params = init_params(jax.random.PRNGKey(0), tiny, dtype=jnp.float32)
    assert count_params(params) == tiny.num_params
    # o_proj carries no bias leaf
    assert "bias" not in params["model"]["layers"]["0"]["self_attn"]["o_proj"]
    assert "bias" in params["model"]["layers"]["0"]["self_attn"]["q_proj"]


def test_llama31_rope_scaling_logit_parity():
    """Llama-3.1 'llama3' smoothed-NTK rope scaling — gates rope_inv_freq's
    wavelength-banded rescale against HF _compute_llama3_parameters."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        tie_word_embeddings=False,
        rope_theta=10000.0,
        rope_scaling={
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 32,
        },
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg).eval()
    cfg = from_hf_config(hf_cfg)
    assert cfg.rope_scaling_type == "llama3"
    assert cfg.rope_scaling_factor == 8.0
    # seq past original_max_position so the slowed long wavelengths matter
    _compare(model, hf_cfg, seq=48)


def test_linear_rope_scaling_logit_parity():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        tie_word_embeddings=False,
        rope_theta=10000.0,
        rope_scaling={"rope_type": "linear", "factor": 4.0},
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg).eval()
    assert from_hf_config(hf_cfg).rope_scaling_type == "linear"
    _compare(model, hf_cfg, seq=40)


def test_yarn_rope_scaling_logit_parity():
    """YaRN: rope_inv_freq's ramp between interpolated and kept frequencies and
    the attention factor on cos and sin, against HF _compute_yarn_parameters
    (default factor 0.1 ln(8) + 1, and one given in the config)."""
    for extra in ({}, {"attention_factor": 1.3}):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256,
            tie_word_embeddings=False, rope_theta=10000.0,
            rope_scaling={"rope_type": "yarn", "factor": 8.0, "original_max_position_embeddings": 32,
                          "beta_fast": 4.0, "beta_slow": 1.0, **extra},
            pad_token_id=0, bos_token_id=1, eos_token_id=2,
        )
        torch.manual_seed(0)
        model = transformers.LlamaForCausalLM(hf_cfg).eval()
        cfg = from_hf_config(hf_cfg)
        assert (cfg.rope_scaling_type, cfg.rope_beta_fast, cfg.rope_attention_factor) == ("yarn", 4.0, extra.get("attention_factor"))
        _compare(model, hf_cfg, seq=48)


def test_unsupported_rope_scaling_rejected_at_load():
    """longrope/dynamic must fail at config load, not inside the first
    forward's jit trace after weights are already in HBM."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        rope_scaling={"rope_type": "dynamic", "factor": 4.0},
    )
    with pytest.raises(ValueError, match="unsupported rope_scaling"):
        from_hf_config(hf_cfg)


def test_gemma2_tiny_logit_parity():
    """Gemma2 family: GeGLU, sandwich norms, zero-centered RMSNorm, scaled
    embeddings, q/final softcaps, query_pre_attn_scalar, alternating
    local/global sliding window — all gated against HF Gemma2ForCausalLM
    (eager attention, the impl that honors softcapping)."""
    hf_cfg = transformers.Gemma2Config(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,  # layers 0/2 sliding, 1/3 global
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        max_position_embeddings=128,
        sliding_window=8,
        query_pre_attn_scalar=16.0,
        attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
        rope_theta=10000.0,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    hf_cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    model = transformers.Gemma2ForCausalLM(hf_cfg).eval()
    # zero-centered norms init at 0; perturb so (1+w) != 1 everywhere
    with torch.no_grad():
        for name, p in model.named_parameters():
            if "layernorm" in name or name.endswith("norm.weight"):
                p.add_(torch.randn_like(p) * 0.1)
    cfg = from_hf_config(hf_cfg)
    assert cfg.sandwich_norms and cfg.zero_centered_norm and cfg.embed_scale
    assert cfg.hidden_act == "gelu_tanh"
    assert cfg.attn_logit_softcap == 50.0 and cfg.final_logit_softcap == 30.0
    assert cfg.alternating_sliding_window and cfg.sliding_window == 8
    # seq > window so the local/global alternation actually differs
    _compare(model, hf_cfg, seq=24, atol=5e-4)


def test_llama32_presets_param_counts():
    """Llama-3.2 1B/3B presets: tied embeddings + llama3 rope factor 32 —
    published HF sizes 1.24B / 3.21B."""
    from llm_fine_tune_distributed_tpu.models.configs import get_preset

    p1 = get_preset("llama3_2_1b")
    assert 1.2e9 < p1.num_params < 1.3e9
    assert p1.tie_word_embeddings and p1.rope_scaling_factor == 32.0
    p3 = get_preset("llama3_2_3b")
    assert 3.1e9 < p3.num_params < 3.3e9


def test_exact_gelu_logit_parity():
    """hidden_act='gelu' (exact erf GeLU) against HF — LlamaConfig with the
    mlp activation swapped, the one non-tanh GeLU family path."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        tie_word_embeddings=False,
        rope_theta=10000.0,
        hidden_act="gelu",
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg).eval()
    assert from_hf_config(hf_cfg).hidden_act == "gelu"
    _compare(model, hf_cfg)


def test_gemma_hidden_act_precedence_and_moe_act_guard():
    """(a) Gemma-family configs resolve the activation from
    hidden_activation with a gelu_pytorch_tanh default — a stale
    hidden_act='gelu' (early gemma configs) must NOT select exact GeLU.
    (b) MoE + non-silu activation is rejected at config construction."""
    import types

    cfg = from_hf_config(types.SimpleNamespace(
        model_type="gemma", vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, hidden_act="gelu",
    ))
    assert cfg.hidden_act == "gelu_tanh"

    from llm_fine_tune_distributed_tpu.config import ModelConfig

    with pytest.raises(ValueError, match="silu"):
        ModelConfig(
            name="bad", vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=1, num_heads=2, num_kv_heads=2, num_experts=4,
            hidden_act="gelu_tanh",
        )


def test_saved_config_round_trips_exactly_for_every_preset():
    """to_hf_dict -> from_hf_config must be the identity for this
    framework's own saves (ADVICE r4: a gemma-family model trained with
    exact hidden_act='gelu' reloaded as 'gelu_tanh' because only hidden_act
    was written while the gemma branch reads hidden_activation). Pinned for
    ALL presets plus the exact-GeLU gemma corner."""
    import types

    from llm_fine_tune_distributed_tpu.models.configs import PRESETS, to_hf_dict

    cases = list(PRESETS.values()) + [
        PRESETS["tiny_gemma2"].replace(name="gemma2_tuned", hidden_act="gelu"),
    ]
    for mc in cases:
        restored = from_hf_config(types.SimpleNamespace(**to_hf_dict(mc)))
        assert restored == mc, (
            f"{mc.name}: save/load round-trip drifted: "
            f"{[(f, getattr(mc, f), getattr(restored, f)) for f in mc.__dataclass_fields__ if getattr(mc, f) != getattr(restored, f)]}"
        )


def test_unvalidated_gemma_qwen_model_types_rejected():
    """Adjacent family members (gemma3*, qwen2_moe, ...) match the
    model_type-prefix heuristics but differ architecturally — they must be
    rejected at config-load time, before weights load (ADVICE r4), while
    validated types and this framework's own saves still load."""
    import types

    base = dict(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
    )
    for bad in ("gemma3_text", "gemma3", "qwen2_moe", "qwen2_vl", "qwen3_moe"):
        with pytest.raises(ValueError, match="model_type"):
            from_hf_config(types.SimpleNamespace(model_type=bad, **base))
    # validated HF types still load
    for ok in ("gemma", "gemma2", "qwen2", "qwen3"):
        from_hf_config(types.SimpleNamespace(model_type=ok, **base))
    # framework saves carry explicit keys -> accepted under any name
    from llm_fine_tune_distributed_tpu.models.configs import get_preset, to_hf_dict

    d = to_hf_dict(get_preset("tiny_gemma2").replace(name="gemma3_style_tuned"))
    assert from_hf_config(types.SimpleNamespace(**d)).sandwich_norms
