"""DPO preference-pair path (BASELINE.json config #4 — the capability the
reference gets from TRL's DPOTrainer, first-party here).

Covers: loss formula against a hand computation from raw logits, chunked vs
full logprob parity, policy==reference init => loss == log 2, and a tiny
end-to-end DPOTrainer run (loss drops, reward accuracy rises, artifact
contract holds)."""

import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
from llm_fine_tune_distributed_tpu.data.preference import (
    build_dpo_arrays,
    load_preference_dataset,
    synthesize_preference_rows,
)
from llm_fine_tune_distributed_tpu.data.tokenizer import load_tokenizer
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import forward, init_params
from llm_fine_tune_distributed_tpu.train.dpo import make_dpo_loss_fn
from llm_fine_tune_distributed_tpu.utils.tree import merge_flat, split_by_mask


SEQ = 96
SYS = "You are a helpful expert."  # short prompt: completions fit in SEQ


def _rows(n=12):
    return [
        {
            "prompt": f"question {i}?",
            "chosen": f"the correct answer {i} with words",
            "rejected": f"wrong {i}",
        }
        for i in range(n)
    ]


@pytest.fixture(scope="module")
def setup():
    tok = load_tokenizer("byte-chatml")
    config = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    arrays = build_dpo_arrays(_rows(4), tok, SEQ, system_prompt=SYS)
    batch = {k: jnp.asarray(v) for k, v in arrays.items()}
    return tok, config, params, batch


def _split(params, config):
    from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask

    cfg = TrainConfig(model_preset="tiny", max_seq_length=SEQ)
    mask = trainable_mask(params, config, cfg)
    return split_by_mask(params, mask)


def _manual_dpo_loss(params, config, batch, beta, train_config):
    """Hand computation straight from full logits (no chunking, no helpers)."""
    def seq_logprob(ids, attn, mask):
        logits, _ = forward(
            params, ids, config,
            padding_mask=attn,
            compute_dtype=jnp.bfloat16,
        )
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(logp[:, :-1], ids[:, 1:, None], axis=-1)[..., 0]
        return (tgt * mask[:, 1:]).sum(-1)

    pi_c = seq_logprob(batch["chosen_input_ids"], batch["chosen_attention_mask"], batch["chosen_loss_mask"])
    pi_r = seq_logprob(batch["rejected_input_ids"], batch["rejected_attention_mask"], batch["rejected_loss_mask"])
    # policy == reference here (same params), so ref terms cancel:
    margin = (pi_c - pi_r) - (pi_c - pi_r)
    del margin
    return pi_c, pi_r


def test_dpo_loss_at_init_is_log2(setup):
    """With reference == policy the margin is 0 => loss = -log sigmoid(0) = log 2."""
    _, config, params, batch = setup
    trainable, frozen = _split(params, config)
    cfg = TrainConfig(model_preset="tiny", max_seq_length=SEQ, attention_impl="xla",
                      gradient_checkpointing=False)
    loss_fn = make_dpo_loss_fn(config, cfg)
    ref = {k: v.astype(jnp.bfloat16) for k, v in trainable.items()}
    frozen_bf16 = {k: v.astype(jnp.bfloat16) for k, v in frozen.items()}
    loss, aux = jax.jit(loss_fn)(
        {k: v for k, v in trainable.items()}, ref, frozen_bf16, batch
    )
    assert abs(float(loss) - math.log(2.0)) < 2e-2, float(loss)
    assert abs(float(aux["rewards_margin"])) < 1e-2


@pytest.mark.slow
def test_dpo_chunked_matches_full(setup):
    """loss_chunk_size path must agree with the single-unembed path."""
    _, config, params, batch = setup
    trainable, frozen = _split(params, config)
    frozen = {k: v.astype(jnp.bfloat16) for k, v in frozen.items()}
    # perturb the policy so the margin is nonzero (loss != log 2)
    pol = {k: v + 0.01 * (hash(k) % 7 - 3) for k, v in trainable.items()}
    ref = {k: v.astype(jnp.bfloat16) for k, v in trainable.items()}

    losses = {}
    for chunk in (None, 32):
        cfg = TrainConfig(model_preset="tiny", max_seq_length=SEQ, attention_impl="xla",
                          gradient_checkpointing=False, loss_chunk_size=chunk)
        loss, aux = jax.jit(make_dpo_loss_fn(config, cfg))(pol, ref, frozen, batch)
        losses[chunk] = (float(loss), float(aux["rewards_margin"]))
    assert losses[None][0] == pytest.approx(losses[32][0], abs=2e-3)
    assert losses[None][1] == pytest.approx(losses[32][1], abs=2e-2)


@pytest.mark.slow
def test_dpo_loss_matches_manual_logits(setup):
    """Framework sequence logprobs must match a from-scratch log_softmax gather."""
    _, config, params, batch = setup
    trainable, frozen = _split(params, config)
    frozen_b = {k: v.astype(jnp.bfloat16) for k, v in frozen.items()}
    cfg = TrainConfig(model_preset="tiny", max_seq_length=SEQ, attention_impl="xla",
                      gradient_checkpointing=False, dpo_beta=0.25)
    ref = {k: v.astype(jnp.bfloat16) for k, v in trainable.items()}
    pol = {k: v + 0.02 for k, v in trainable.items()}
    loss, aux = jax.jit(make_dpo_loss_fn(config, cfg))(pol, ref, frozen_b, batch)

    pi_c, pi_r = _manual_dpo_loss(merge_flat(pol, frozen), config, batch, 0.25, cfg)
    rf_c, rf_r = _manual_dpo_loss(
        merge_flat({k: v.astype(jnp.float32) for k, v in ref.items()}, frozen),
        config, batch, 0.25, cfg,
    )
    margin = (pi_c - pi_r) - (rf_c - rf_r)
    expected = float((-jax.nn.log_sigmoid(0.25 * margin)).mean())
    assert float(loss) == pytest.approx(expected, rel=0.05, abs=5e-3)


def test_preference_synthesis_and_loading(tmp_path):
    qa = [{"full-question": f"q{i}", "answer": f"a{i}"} for i in range(10)]
    rows = synthesize_preference_rows(qa, seed=3)
    assert len(rows) == 10
    assert all(r["chosen"] != r["rejected"] for r in rows)
    # jsonl round-trip with prompt/chosen/rejected schema
    p = tmp_path / "prefs.jsonl"
    with open(p, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    loaded = load_preference_dataset(str(p))
    assert loaded == rows


@pytest.mark.slow
def test_dpo_end_to_end(tmp_path):
    """Tiny DPOTrainer run on the 8-device mesh: loss below log2, accuracy
    above chance, SFT artifact contract preserved."""
    from llm_fine_tune_distributed_tpu.train.dpo import DPOTrainer

    rows = _rows(48)
    p = tmp_path / "prefs.jsonl"
    with open(p, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    out = tmp_path / "outputs"
    config = TrainConfig(
        model_name="tiny-random",
        model_preset="tiny",
        tokenizer_path="byte-chatml",
        data_dir=str(tmp_path),
        dataset_file="prefs.jsonl",
        output_dir=str(out),
        objective="dpo",
        system_prompt=SYS,
        dpo_beta=0.5,
        epochs=3,
        per_device_batch_size=2,
        gradient_accumulation_steps=2,
        learning_rate=2e-3,
        max_seq_length=SEQ,
        eval_steps=5,
        logging_steps=2,
        save_steps=100,
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1),
    )
    trainer = DPOTrainer(config)
    trainer.train()

    history = trainer.metrics.history
    losses = [h["loss"] for h in history if "loss" in h]
    accs = [h["rewards_accuracy"] for h in history if "rewards_accuracy" in h]
    assert losses[-1] < math.log(2.0), f"DPO loss never fell below log2: {losses}"
    assert losses[-1] < losses[0]
    assert accs[-1] > 0.6, f"reward accuracy stayed at chance: {accs}"
    evals = [h["eval_rewards_accuracy"] for h in history if "eval_rewards_accuracy" in h]
    assert evals, "eval accuracy never logged"

    assert (out / "best_model" / "model.safetensors").exists()
    assert (out / "training_summary.json").exists()


@pytest.mark.slow
def test_dpo_pipeline_end_to_end(tmp_path):
    """DPO x pipe: pipe=2 x fsdp=2 mesh runs the DPO
    objective as GPipe schedules (policy + reference), learns past log2,
    and first-step loss agrees with the flat mesh (same init, same data)."""
    from llm_fine_tune_distributed_tpu.train.dpo import DPOTrainer

    rows = _rows(48)
    p = tmp_path / "prefs.jsonl"
    with open(p, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    def cfg(out, mesh):
        return TrainConfig(
            model_name="tiny-random",
            model_preset="tiny",
            tokenizer_path="byte-chatml",
            data_dir=str(tmp_path),
            dataset_file="prefs.jsonl",
            output_dir=str(out),
            objective="dpo",
            system_prompt=SYS,
            dpo_beta=0.5,
            epochs=2,
            per_device_batch_size=2,
            gradient_accumulation_steps=2,
            learning_rate=2e-3,
            max_seq_length=SEQ,
            eval_steps=5,
            logging_steps=2,
            save_steps=100,
            mesh=mesh,
        )

    flat = DPOTrainer(cfg(tmp_path / "flat", MeshConfig(data=1, fsdp=2, tensor=1, seq=1)))
    flat.train()
    pipe = DPOTrainer(
        cfg(tmp_path / "pipe", MeshConfig(data=1, fsdp=2, tensor=1, seq=1, pipe=2))
    )
    pipe.train()

    flat_losses = [h["loss"] for h in flat.metrics.history if "loss" in h]
    pipe_losses = [h["loss"] for h in pipe.metrics.history if "loss" in h]
    # both start at ~log2 (identical-policy DPO) and learn below it
    assert pipe_losses[0] == pytest.approx(flat_losses[0], rel=2e-2)
    assert pipe_losses[-1] < math.log(2.0), f"pipe DPO never learned: {pipe_losses}"
    accs = [h["rewards_accuracy"] for h in pipe.metrics.history if "rewards_accuracy" in h]
    assert accs[-1] > 0.6
    evals = [
        h["eval_rewards_accuracy"] for h in pipe.metrics.history
        if "eval_rewards_accuracy" in h
    ]
    assert evals, "pipe DPO eval accuracy never logged"
    assert (tmp_path / "pipe" / "best_model" / "model.safetensors").exists()
