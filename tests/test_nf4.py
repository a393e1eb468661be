"""NF4 quantization + QLoRA path (BASELINE.json config #5).

Covers: codebook round-trip error bounds, double-quant fidelity, pack/unpack
inversion, XLA dequant matmul vs full-precision reference, param-tree
quantize/dequantize transforms, and a tiny end-to-end QLoRA training run
(NF4 frozen base + LoRA adapters) with plain-safetensors export.

The fused Pallas kernel needs a real TPU (tests run on CPU); its numerics are
exercised here and by bench/infer
runs on hardware.
"""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
from llm_fine_tune_distributed_tpu.ops.nf4 import (
    NF4_CODEBOOK,
    dequantize_nf4,
    nf4_matmul,
    quantize_nf4,
    unpack_codes,
)
from llm_fine_tune_distributed_tpu.parallel.qlora import (
    dequantize_frozen,
    quantize_frozen,
    quantized_fraction,
)


def _j(q):
    return {k: jnp.asarray(v) for k, v in q.items()}


def test_pack_unpack_roundtrip():
    rng = np.random.RandomState(0)
    w = rng.randn(128, 64).astype(np.float32)
    q = quantize_nf4(w, block_size=64, double_quant=False)
    assert q["nf4"].shape == (16, 64) and q["nf4"].dtype == np.int32
    codes = np.asarray(unpack_codes(jnp.asarray(q["nf4"])))
    assert codes.shape == (128, 64)
    assert codes.min() >= 0 and codes.max() <= 15


def test_roundtrip_error_bounds():
    """Blockwise NF4: worst-case relative error within a block is bounded by
    half the largest codebook gap (~0.14 of the block absmax)."""
    rng = np.random.RandomState(1)
    w = rng.randn(256, 128).astype(np.float32)
    q = quantize_nf4(w, block_size=64, double_quant=False)
    deq = np.asarray(dequantize_nf4(_j(q), jnp.float32))
    gaps = np.diff(NF4_CODEBOOK)
    blocks = w.reshape(-1, 64, 128)
    absmax = np.abs(blocks).max(1, keepdims=True)
    bound = (gaps.max() / 2 + 1e-6) * absmax
    err = np.abs(deq.reshape(-1, 64, 128) - blocks)
    assert (err <= bound + 1e-6).all(), float((err - bound).max())


def test_double_quant_close_to_single():
    rng = np.random.RandomState(2)
    w = (rng.randn(512, 128) * rng.gamma(2.0, 1.0, (512, 128))).astype(np.float32)
    single = np.asarray(dequantize_nf4(_j(quantize_nf4(w, 64, False)), jnp.float32))
    double = np.asarray(dequantize_nf4(_j(quantize_nf4(w, 64, True)), jnp.float32))
    # int8 absmax quantization adds <1% relative error on the scales
    denom = np.abs(single).mean()
    assert np.abs(double - single).mean() / denom < 0.02
    q = quantize_nf4(w, 64, True)
    assert q["absmax_q"].dtype == np.int8
    # storage: 4 bits codes + 8 bits/block scales ≈ 4.13 bits/param total
    bits = (q["nf4"].nbytes + q["absmax_q"].nbytes + q["absmax_scale"].nbytes) * 8
    assert bits / w.size < 4.3


def test_nf4_matmul_xla_close_to_dense():
    rng = np.random.RandomState(3)
    w = rng.randn(512, 256).astype(np.float32)
    x = rng.randn(8, 512).astype(np.float32)
    q = _j(quantize_nf4(w, 64, True))
    y = np.asarray(nf4_matmul(jnp.asarray(x), q, impl="xla", compute_dtype=jnp.float32))
    deq = np.asarray(dequantize_nf4(q, jnp.float32))
    np.testing.assert_allclose(y, x @ deq, rtol=1e-4, atol=1e-3)
    # and the quantization error itself keeps the matmul in the right ballpark
    rel = np.abs(y - x @ w).mean() / np.abs(x @ w).mean()
    assert rel < 0.2, rel


def test_quantize_frozen_tree_and_inverse():
    rng = np.random.RandomState(4)
    frozen = {
        "model/layers/0/self_attn/q_proj/kernel": rng.randn(64, 64).astype(np.float32),
        "model/layers/0/mlp/down_proj/kernel": rng.randn(128, 64).astype(np.float32),
        "model/layers/0/input_layernorm/weight": np.ones((64,), np.float32),
        "model/embed_tokens/weight": rng.randn(512, 64).astype(np.float32),  # not /layers/
        "model/layers/0/self_attn/q_proj/lora_scale": np.float32(0.5),
    }
    q = quantize_frozen(frozen, block_size=64, double_quant=True)
    assert "model/layers/0/self_attn/q_proj/kernel_nf4" in q
    assert "model/layers/0/self_attn/q_proj/kernel" not in q
    assert "model/embed_tokens/weight" in q  # embeddings untouched
    assert "model/layers/0/input_layernorm/weight" in q
    # the two small kernels are NF4; the large untouched embedding dominates
    # total bytes, so the fraction is small but nonzero
    assert 0.0 < quantized_fraction(q) < 0.5

    back = dequantize_frozen({k: jnp.asarray(v) for k, v in q.items()}, jnp.float32)
    assert set(back) == set(frozen)
    orig = frozen["model/layers/0/mlp/down_proj/kernel"]
    rec = np.asarray(back["model/layers/0/mlp/down_proj/kernel"])
    assert np.abs(rec - orig).mean() / np.abs(orig).mean() < 0.1


def test_qlora_forward_matches_dequantized_dense():
    """A tiny model's forward through quantized frozen params must equal the
    forward through the explicitly dequantized dense params."""
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import forward, init_params
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict, unflatten_dict

    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    flat = flatten_dict(params)
    qflat = quantize_frozen(flat, block_size=64, double_quant=True)
    deqflat = dequantize_frozen(qflat, jnp.float32)

    ids = jnp.asarray(np.random.RandomState(5).randint(0, 512, (2, 32)), jnp.int32)
    out_q, _ = forward(unflatten_dict(qflat), ids, mc, compute_dtype=jnp.float32,
                       quant_impl="xla", logits_dtype=jnp.float32)
    out_d, _ = forward(unflatten_dict(deqflat), ids, mc, compute_dtype=jnp.float32,
                       logits_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_d), rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_qlora_end_to_end(tmp_path):
    """QLoRA SFT on the 8-device mesh: NF4 frozen base + trainable adapters,
    loss decreases, export decodes back to plain safetensors."""
    from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    jsonl = tmp_path / "qa.jsonl"
    rng = np.random.RandomState(0)
    with open(jsonl, "w") as f:
        for i in range(64):
            f.write(json.dumps({
                "topic": "Knots",
                "question": f"question {i}?",
                "answer": f"answer {i}: " + " ".join(["word"] * int(rng.randint(3, 8))),
            }) + "\n")
    convert_jsonl_to_parquet(str(jsonl), str(tmp_path / "qa_dataset.parquet"), verbose=False)

    out = tmp_path / "outputs"
    config = TrainConfig(
        model_name="tiny-random",
        model_preset="tiny",
        tokenizer_path="byte-chatml",
        data_dir=str(tmp_path),
        dataset_file="qa_dataset.parquet",
        output_dir=str(out),
        freeze_strategy="qlora",
        lora_rank=4,
        epochs=2,
        per_device_batch_size=2,
        gradient_accumulation_steps=2,
        learning_rate=5e-3,
        max_seq_length=128,
        eval_steps=100,
        logging_steps=2,
        save_steps=100,
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1),
        use_native_loader=False,
    )
    trainer = SFTTrainer(config)

    # frozen base is actually quantized
    assert any(k.endswith("kernel_nf4") for k in trainer.state.frozen)
    assert all(not k.endswith("/kernel") or "layers" not in k for k in trainer.state.frozen
               if "proj" in k), "block linears must be NF4, not dense"
    # only adapters train
    assert all(k.endswith(("lora_a", "lora_b")) for k in trainer.state.trainable)

    trainer.train()
    losses = [h["loss"] for h in trainer.metrics.history if "loss" in h]
    assert losses[-1] < losses[0], f"QLoRA loss did not decrease: {losses}"

    # exported model has plain kernels again (inference contract)
    from llm_fine_tune_distributed_tpu.models.hf_io import load_hf_checkpoint

    mc = trainer.model_config
    re_params = load_hf_checkpoint(str(out / "best_model"), mc, dtype=np.float32)
    flat = {k for k, _ in _tree_items(re_params)}
    assert any(k.endswith("q_proj/kernel") for k in flat)
    assert not any("nf4" in k for k in flat)


def _tree_items(tree, prefix=""):
    for k, v in tree.items():
        key = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _tree_items(v, key)
        else:
            yield key, v


def test_jax_quantizer_matches_numpy():
    """The device-side quantizer (_quantize_codes_jax) must be bit-identical
    to the numpy path — CI runs CPU, so call the jitted fn directly."""
    from llm_fine_tune_distributed_tpu.ops.nf4 import _quantize_codes_jax

    rng = np.random.RandomState(7)
    w = rng.randn(256, 128).astype(np.float32)
    # numpy reference (the small-leaf path)
    ref = quantize_nf4(w, 64, double_quant=False)
    packed_j, absmax_j = _quantize_codes_jax(jnp.asarray(w), 64)
    np.testing.assert_array_equal(np.asarray(packed_j), np.asarray(ref["nf4"]))
    np.testing.assert_allclose(np.asarray(absmax_j), np.asarray(ref["absmax"]), rtol=1e-6)


def test_pallas_impl_is_retired():
    """The fused Pallas kernel was retired (lost the v5e shootout); asking
    for it errors with the pointer to the rationale."""
    q = quantize_nf4(jnp.ones((256, 128)), block_size=64)
    with pytest.raises(ValueError, match="retired"):
        nf4_matmul(jnp.ones((4, 256)), q, impl="pallas")


def test_layered_stacked_roundtrip():
    """4-D [L, E, in, out] pipe-stacked expert quantization (qlora x pipe x
    MoE): per-layer slices are standalone stacked layouts and
    the roundtrip matches quantizing each layer independently."""
    from llm_fine_tune_distributed_tpu.ops.nf4 import (
        dequantize_nf4_layered_stacked,
        dequantize_nf4_stacked,
        quantize_nf4_layered_stacked,
        quantize_nf4_stacked,
        quantized_layout_layered_stacked,
    )

    rng = np.random.RandomState(2)
    w = rng.randn(2, 4, 64, 32).astype(np.float32)  # [L, E, in, out]
    q = quantize_nf4_layered_stacked(w, block_size=64, double_quant=True)
    assert q["nf4"].shape == (2, 4, 8, 32)
    assert q["absmax_q"].shape == (2, 4, 1, 32)
    assert q["absmax_scale"].ndim == 2 and q["absmax_scale"].shape[0] == 2
    assert q["absmax_offset"].shape == (2,)

    # the declared layout matches what the quantizer produced
    layout = quantized_layout_layered_stacked(w.shape, 64, True)
    for key, (shape, dtype) in layout.items():
        assert tuple(q[key].shape) == shape, key
        assert q[key].dtype == dtype, key

    deq = np.asarray(dequantize_nf4_layered_stacked(_j(q), jnp.float32))
    assert deq.shape == w.shape

    for i in range(2):
        # each layer slice is a complete standalone stacked layout — the
        # invariant the pipeline scan relies on (ops/moe consumes slices
        # with dequantize_nf4_stacked, never seeing the layer dim)
        per = quantize_nf4_stacked(w[i], block_size=64, double_quant=True)
        sliced = {k: jnp.asarray(v)[i] for k, v in q.items()}
        np.testing.assert_array_equal(
            np.asarray(sliced["nf4"]), np.asarray(per["nf4"])
        )
        np.testing.assert_allclose(
            np.asarray(dequantize_nf4_stacked(sliced, jnp.float32)),
            np.asarray(dequantize_nf4_stacked(_j(per), jnp.float32)),
            atol=1e-6,
        )
        np.testing.assert_allclose(deq[i], np.asarray(
            dequantize_nf4_stacked(_j(per), jnp.float32)), atol=1e-6)


def test_quantize_frozen_handles_pipe_stacked_experts():
    """quantize_frozen/dequantize_frozen round-trip the 4-D expert leaves the
    pipeline state carries, and the abstract planner agrees with the real
    quantizer leaf-for-leaf."""
    from llm_fine_tune_distributed_tpu.parallel.qlora import (
        quantize_frozen_abstract,
    )

    rng = np.random.RandomState(3)
    frozen = {
        "model/layers/@stacked/block_sparse_moe/experts/w1":
            rng.randn(2, 4, 64, 32).astype(np.float32),
        "model/layers/@stacked/block_sparse_moe/gate/kernel":
            rng.randn(2, 64, 4).astype(np.float32),
        "model/norm/weight": np.ones((64,), np.float32),
    }
    q = quantize_frozen(frozen, block_size=64)
    assert "model/layers/@stacked/block_sparse_moe/experts/w1_nf4" in q
    assert q["model/layers/@stacked/block_sparse_moe/experts/w1_nf4"].ndim == 4
    # router gate + norm pass through exact
    assert q["model/layers/@stacked/block_sparse_moe/gate/kernel"].shape == (2, 64, 4)

    abstract = quantize_frozen_abstract(
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in frozen.items()},
        block_size=64,
    )
    assert set(abstract) == set(q)
    for k in q:
        assert tuple(abstract[k].shape) == tuple(np.shape(q[k])), k

    deq = dequantize_frozen(q, jnp.float32)
    assert set(deq) == set(frozen)
    w = frozen["model/layers/@stacked/block_sparse_moe/experts/w1"]
    err = np.abs(np.asarray(deq["model/layers/@stacked/block_sparse_moe/experts/w1"]) - w)
    assert err.mean() < 0.1  # NF4 quantization noise, not garbage
