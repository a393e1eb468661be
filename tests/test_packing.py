"""Sequence packing (packing=True): exactness tests.

The load-bearing property: a packed row must produce IDENTICAL per-token
logits/losses to running each example alone — the segment mask and
per-segment positions make packing an exact transformation, not an
approximation."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
from llm_fine_tune_distributed_tpu.data.dataset import (
    build_sft_arrays,
    format_chat_example,
    tokenize_example,
)
from llm_fine_tune_distributed_tpu.data.packing import (
    build_packed_sft_arrays,
    pack_examples,
    packing_efficiency,
)
from llm_fine_tune_distributed_tpu.data.tokenizer import load_tokenizer
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import forward, init_params

SYS = "You are a helpful expert."
SEQ = 256


def _rows(n):
    return [
        {"full-question": f"q {i}?", "answer": f"answer {i} " + "word " * (3 + i % 5)}
        for i in range(n)
    ]


@pytest.fixture(scope="module")
def tok():
    return load_tokenizer("byte-chatml")


def test_pack_examples_layout(tok):
    examples = [
        tokenize_example(
            format_chat_example(r, SYS)["messages"], tok, SEQ
        )
        for r in _rows(8)
    ]
    packed = pack_examples(examples, SEQ)
    n_rows = packed["input_ids"].shape[0]
    assert n_rows < 8, "short examples should share rows"
    # segment ids increase from 1 within a row; 0 marks the padding tail
    for r in range(n_rows):
        seg = packed["segment_ids"][r]
        real = seg > 0
        assert packed["attention_mask"][r][real].all()
        assert not packed["attention_mask"][r][~real].any()
        segs = np.unique(seg[real])
        assert (segs == np.arange(1, len(segs) + 1)).all()
        # positions restart at each segment
        for sid in segs:
            pos = packed["positions"][r][seg == sid]
            assert (pos == np.arange(len(pos))).all()
    # total real tokens preserved
    assert packed["attention_mask"].sum() == sum(e.length for e in examples)
    assert 0.0 < packing_efficiency(packed) <= 1.0


@pytest.mark.slow
def test_packed_forward_matches_individual(tok):
    """Logits of each packed segment == logits of the example run alone."""
    config = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    rows = _rows(5)
    examples = [
        tokenize_example(format_chat_example(r, SYS)["messages"], tok, SEQ)
        for r in rows
    ]
    packed = pack_examples(examples, SEQ)

    packed_logits, _ = forward(
        params,
        jnp.asarray(packed["input_ids"]),
        config,
        padding_mask=jnp.asarray(packed["attention_mask"]),
        segment_ids=jnp.asarray(packed["segment_ids"]),
        positions=jnp.asarray(packed["positions"]),
        compute_dtype=jnp.float32,
        logits_dtype=jnp.float32,
    )
    packed_logits = np.asarray(packed_logits)

    # reconstruct per-example logits from the packed rows
    seg_cursor = {}
    for r in range(packed["input_ids"].shape[0]):
        seg = packed["segment_ids"][r]
        for sid in np.unique(seg[seg > 0]):
            idx = np.where(seg == sid)[0]
            seg_cursor[(r, sid)] = packed_logits[r, idx]

    # order of (row, sid) follows first-fit insertion order == example order
    flat_packed = []
    rows_used = packed["segment_ids"]
    order = []
    for r in range(rows_used.shape[0]):
        for sid in np.unique(rows_used[r][rows_used[r] > 0]):
            order.append((r, sid))
    # map each example to its (row, sid) by matching tokens
    for ex in examples:
        ln = ex.length
        ids = jnp.asarray(ex.input_ids[None, :ln])
        solo, _ = forward(
            params, ids, config, compute_dtype=jnp.float32, logits_dtype=jnp.float32
        )
        solo = np.asarray(solo)[0]
        # find the matching packed segment by token equality
        match = None
        for key, logits_seg in seg_cursor.items():
            r, sid = key
            idx = np.where(packed["segment_ids"][r] == sid)[0]
            if len(idx) == ln and (packed["input_ids"][r, idx] == ex.input_ids[:ln]).all():
                match = logits_seg
                break
        assert match is not None, "packed segment not found for example"
        np.testing.assert_allclose(match, solo, rtol=2e-4, atol=2e-4)


def test_packed_arrays_loss_mask_never_crosses_segments(tok):
    packed = build_packed_sft_arrays(_rows(12), tok, SEQ, system_prompt=SYS)
    seg = packed["segment_ids"]
    lm = packed["loss_mask"]
    # wherever a new segment starts (seg changes and is > 0), loss_mask is 0:
    # predicting a segment's first token from the previous segment is invalid
    starts = (seg[:, 1:] != seg[:, :-1]) & (seg[:, 1:] > 0)
    assert (lm[:, 1:][starts] == 0).all()


@pytest.mark.slow
def test_packed_training_with_seq_axis_matches_flat(tmp_path, eight_devices):
    """packing x sequence parallelism: a packed train step on
    a live seq axis (ring and ulysses) computes the SAME loss as the flat-mesh
    XLA-attention step — same data, same seed, same init."""
    from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    jsonl = tmp_path / "qa.jsonl"
    with open(jsonl, "w") as f:
        for i in range(64):
            f.write(json.dumps({
                "topic": "Knots",
                "question": f"question {i}?",
                "answer": f"answer {i}: " + "word " * (3 + i % 6),
            }) + "\n")
    convert_jsonl_to_parquet(str(jsonl), str(tmp_path / "qa_dataset.parquet"), verbose=False)

    def make(out, attention_impl, mesh):
        return TrainConfig(
            model_name="tiny-random",
            model_preset="tiny",
            tokenizer_path="byte-chatml",
            system_prompt=SYS,
            data_dir=str(tmp_path),
            dataset_file="qa_dataset.parquet",
            output_dir=str(out),
            packing=True,
            per_device_batch_size=2,
            gradient_accumulation_steps=2,
            max_seq_length=256,
            mesh=mesh,
            attention_impl=attention_impl,
            use_native_loader=False,
        )

    def one_step_loss(cfg):
        trainer = SFTTrainer(cfg)
        batch = next(iter(trainer.loader.epoch(0)))
        dev = trainer._device_batch(batch, trainer._batch_sharding, local_shards=True)
        _, metrics = trainer.train_step(trainer.state, dev)
        return float(metrics["loss"])

    ref = one_step_loss(
        make(tmp_path / "flat", "xla", MeshConfig(data=1, fsdp=2, tensor=1, seq=1))
    )
    from llm_fine_tune_distributed_tpu.parallel.diagnostics import assert_seq_parallel

    with assert_seq_parallel("ring"):
        ring = one_step_loss(
            make(tmp_path / "ring", "ring", MeshConfig(data=1, fsdp=2, tensor=1, seq=2))
        )
    with assert_seq_parallel("ulysses"):
        uly = one_step_loss(
            make(tmp_path / "uly", "ulysses", MeshConfig(data=1, fsdp=2, tensor=1, seq=2))
        )
    assert abs(ring - ref) < 2e-3, (ring, ref)
    assert abs(uly - ref) < 2e-3, (uly, ref)


@pytest.mark.slow
def test_packed_sft_end_to_end(tmp_path):
    from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    jsonl = tmp_path / "qa.jsonl"
    with open(jsonl, "w") as f:
        for i in range(96):
            f.write(json.dumps({
                "topic": "Knots",
                "question": f"question {i}?",
                "answer": f"answer {i}: " + "word " * (3 + i % 6),
            }) + "\n")
    convert_jsonl_to_parquet(str(jsonl), str(tmp_path / "qa_dataset.parquet"), verbose=False)

    def make(packing, out):
        return TrainConfig(
            model_name="tiny-random",
            model_preset="tiny",
            tokenizer_path="byte-chatml",
            system_prompt=SYS,
            data_dir=str(tmp_path),
            dataset_file="qa_dataset.parquet",
            output_dir=str(out),
            packing=packing,
            epochs=2,
            per_device_batch_size=2,
            gradient_accumulation_steps=2,
            learning_rate=2e-3,
            max_seq_length=256,
            eval_steps=4,
            logging_steps=2,
            save_steps=100,
            mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1),
            use_native_loader=False,
        )

    packed_trainer = SFTTrainer(make(True, tmp_path / "packed"))
    unpacked_steps = 96 * 9 // 10 // (2 * 2 * 2)  # examples / global batch
    assert packed_trainer.steps_per_epoch < unpacked_steps, (
        packed_trainer.steps_per_epoch, unpacked_steps
    )
    packed_trainer.train()
    losses = [h["loss"] for h in packed_trainer.metrics.history if "loss" in h]
    assert losses[-1] < losses[0], f"packed loss did not decrease: {losses}"
    evals = [h["eval_loss"] for h in packed_trainer.metrics.history if "eval_loss" in h]
    assert evals, "packed eval never ran"
    assert (tmp_path / "packed" / "best_model" / "model.safetensors").exists()
