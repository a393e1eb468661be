"""End-to-end SFT integration test (SURVEY.md §4c): tiny model + synthetic QA
parquet -> loss decreases -> artifact contract holds (best_model/ safetensors,
training_history.json, training_summary.json — reference training.py:307-339).
Runs on the 8-device virtual CPU mesh with fsdp=2 to exercise sharding."""

import json
import os

import numpy as np
import pytest

import jax

from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet


@pytest.fixture(scope="module")
def qa_parquet(tmp_path_factory):
    """Synthetic QA jsonl -> parquet via the real converter."""
    tmp = tmp_path_factory.mktemp("data")
    jsonl = tmp / "qa.jsonl"
    rng = np.random.RandomState(0)
    topics = ["Knots", "First Aid", "Cooking"]
    with open(jsonl, "w") as f:
        for i in range(96):
            t = topics[i % 3]
            f.write(
                json.dumps(
                    {
                        "topic": t,
                        "question": f"question number {i} about {t.lower()}?",
                        "answer": f"answer {i}: " + " ".join(["word"] * int(rng.randint(3, 10))),
                    }
                )
                + "\n"
            )
    path = convert_jsonl_to_parquet(str(jsonl), str(tmp / "qa_dataset.parquet"), verbose=False)
    return tmp, os.path.basename(path)


def make_config(tmp_out, data_dir, dataset_file, **overrides):
    base = dict(
        model_name="tiny-random",  # not a dir -> random init
        model_preset="tiny",
        tokenizer_path="byte-chatml",
        data_dir=str(data_dir),
        dataset_file=dataset_file,
        output_dir=str(tmp_out),
        epochs=2,
        per_device_batch_size=2,
        gradient_accumulation_steps=2,
        learning_rate=2e-3,
        max_seq_length=128,
        eval_steps=5,
        logging_steps=2,
        save_steps=8,
        gradient_checkpointing=True,
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1),
    )
    base.update(overrides)
    return TrainConfig(**base)


@pytest.mark.slow
def test_sft_end_to_end(qa_parquet, tmp_path):
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    out = tmp_path / "outputs"
    config = make_config(out, data_dir, dataset_file)
    trainer = SFTTrainer(config)
    summary = trainer.train()

    # --- loss decreased
    history = trainer.metrics.history
    losses = [h["loss"] for h in history if "loss" in h]
    assert len(losses) >= 3
    assert losses[-1] < losses[0], f"loss did not decrease: {losses[0]} -> {losses[-1]}"

    # --- artifact contract (reference training.py:307-339)
    assert (out / "best_model" / "model.safetensors").exists()
    assert (out / "best_model" / "config.json").exists()
    assert (out / "training_history.json").exists()
    assert (out / "training_summary.json").exists()
    with open(out / "training_summary.json") as f:
        s = json.load(f)
    for key in (
        "model_name", "dataset_path", "epochs", "batch_size", "learning_rate",
        "trainable_params", "total_params", "training_samples",
        "validation_samples", "final_train_loss", "world_size",
        "distributed_training",
    ):
        assert key in s, f"summary missing reference key {key}"
    assert s["trainable_params"] < s["total_params"]  # freezing active
    assert summary["samples_per_second_per_chip"] > 0

    # --- checkpoints rotated and resumable
    ckpts = os.listdir(out / "checkpoints")
    assert len([c for c in ckpts if c.isdigit()]) <= 3


@pytest.mark.slow
def test_freezing_only_updates_last_layers(qa_parquet, tmp_path):
    """Frozen layer params must be bit-identical after training; unfrozen must move."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    config = make_config(tmp_path / "o2", data_dir, dataset_file, epochs=1, eval_steps=100, save_steps=100)
    trainer = SFTTrainer(config)
    frozen_keys = list(trainer.state.frozen)
    assert any("layers/0/" in k for k in frozen_keys)  # first layers frozen
    assert all("layers/3/" not in k for k in frozen_keys)  # last layer (idx 3) trainable
    before = {k: np.asarray(v).copy() for k, v in trainer.state.trainable.items()}
    trainer.train()
    moved = [
        k for k, v in trainer.state.trainable.items()
        if not np.allclose(np.asarray(v), before[k])
    ]
    assert moved, "no trainable parameter moved during training"


@pytest.mark.slow
def test_resume_from_checkpoint(qa_parquet, tmp_path):
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    out = tmp_path / "o3"
    config = make_config(out, data_dir, dataset_file, epochs=1, save_steps=4, eval_steps=100)
    t1 = SFTTrainer(config)
    t1.train()
    step_after = int(t1.state.step)
    assert step_after > 0

    config2 = make_config(out, data_dir, dataset_file, epochs=2, save_steps=4, eval_steps=100,
                          resume_from_checkpoint="latest")
    t2 = SFTTrainer(config2)
    t2.train()
    assert int(t2.state.step) > step_after


@pytest.mark.slow
def test_gemma2_family_sft_smoke(qa_parquet, tmp_path):
    """The full Gemma2 knob set survives the real trainer loop (freeze
    policy, sharding over the 4-norm layers, save) and the saved
    config.json round-trips every family knob through from_hf_config."""
    from llm_fine_tune_distributed_tpu.models.configs import from_hf_config
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    out = tmp_path / "outputs"
    config = make_config(
        out, data_dir, dataset_file, model_preset="tiny_gemma2", epochs=1
    )
    trainer = SFTTrainer(config)
    trainer.train()
    losses = [h["loss"] for h in trainer.metrics.history if "loss" in h]
    assert losses[-1] < losses[0]

    import types

    with open(out / "best_model" / "config.json") as f:
        saved = json.load(f)
    cfg = from_hf_config(types.SimpleNamespace(**saved))
    src = trainer.model_config
    for field in (
        "hidden_act", "sandwich_norms", "zero_centered_norm", "embed_scale",
        "attn_logit_softcap", "final_logit_softcap", "query_pre_attn_scalar",
        "alternating_sliding_window", "sliding_window",
    ):
        assert getattr(cfg, field) == getattr(src, field), field


def test_answer_only_eval_metric_and_eval_batch_size(qa_parquet, tmp_path):
    """(a) eval_loss_answer (completion-span CE) is computed
    from the same eval forward and logged beside the full-sequence eval_loss;
    with a long constant system prompt the two must differ. (b) eval_loss is
    a token-weighted sum, so a different eval_batch_size must reproduce it
    bit-closely while cutting the number of eval dispatches."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet

    def one_eval(out, **overrides):
        # short prompt: the default 1378-byte wilderness persona would
        # truncate every completion away at seq 128 (the r4 flagship's
        # silent data bug — case (d) pins that path)
        kw = dict(system_prompt="Be brief.", use_native_loader=False)
        kw.update(overrides)
        cfg = make_config(out, data_dir, dataset_file, epochs=1, **kw)
        trainer = SFTTrainer(cfg)
        loss = trainer.evaluate()
        return trainer, loss

    trainer, loss = one_eval(tmp_path / "a")
    assert "completion_mask" in trainer.val_arrays
    ans = trainer._last_eval_answer
    assert ans is not None and np.isfinite(ans)
    # the full-sequence loss averages prompt tokens too; the answer metric
    # is a different quantity (identical values would mean the mask did
    # nothing)
    assert abs(ans - loss) > 1e-6
    # answer mask is a non-empty strict subset of the full loss mask
    cm = trainer.val_arrays["completion_mask"]
    lm = trainer.val_arrays["loss_mask"]
    assert (cm <= lm).all() and 0 < cm.sum() < lm.sum()

    # (b) eval invariance to eval_batch_size
    _, loss_big = one_eval(tmp_path / "b", eval_batch_size=8)
    np.testing.assert_allclose(loss_big, loss, rtol=1e-5)

    # (c) the metric rides into the training logs
    cfg = make_config(tmp_path / "c", data_dir, dataset_file, epochs=1,
                      eval_steps=5, system_prompt="Be brief.",
                      use_native_loader=False)
    tr = SFTTrainer(cfg)
    tr.train()
    evals = [h for h in tr.metrics.history if "eval_loss" in h]
    assert evals and all("eval_loss_answer" in h for h in evals)

    # (d) fully-truncated completions (the r4 flagship data bug): metric
    # suppressed, not reported as a perfect 0.0
    tr2, _ = one_eval(tmp_path / "d", system_prompt=None)
    assert tr2.val_arrays["completion_mask"].sum() == 0
    assert tr2._last_eval_answer is None


def test_checkpoint_best_mode_warns_when_no_midrun_save_possible(
    qa_parquet, tmp_path, capsys
):
    """save_steps beyond total_steps in checkpoint-mode best tracking means
    only the end-of-train save ever exists: load_best_model_at_end silently
    degrades to final-weights-only. The trainer must say so up front."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    cfg = make_config(
        tmp_path, data_dir, dataset_file, epochs=1, save_steps=500,
        use_native_loader=False, best_model_tracking="checkpoint",
        load_best_model_at_end=True,
    )
    trainer = SFTTrainer(cfg)
    assert cfg.save_steps > trainer.total_steps  # the degenerate shape
    capsys.readouterr()
    assert trainer._resolve_best_mode() == "checkpoint"
    out = capsys.readouterr().out
    assert "final-weights-only" in out

    # aligned cadence below total_steps: no warning
    cfg2 = make_config(
        tmp_path / "ok", data_dir, dataset_file, epochs=1, save_steps=5,
        eval_steps=5, use_native_loader=False,
        best_model_tracking="checkpoint", load_best_model_at_end=True,
    )
    trainer2 = SFTTrainer(cfg2)
    assert cfg2.save_steps <= trainer2.total_steps
    capsys.readouterr()
    trainer2._resolve_best_mode()
    assert "final-weights-only" not in capsys.readouterr().out


def test_trainer_states_a_compiler_refusal_before_it_raises(
    qa_parquet, tmp_path, capsys
):
    """A step program the device cannot hold dies in the compiler with a page
    of buffer listings. The trainer says first what it asked for (mesh,
    microbatch, remat, loss chunk) and then lets the error through: the run
    still fails."""
    import jax

    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    cfg = make_config(
        tmp_path, data_dir, dataset_file, epochs=1, use_native_loader=False,
        remat_policy="dots_no_batch",
    )
    trainer = SFTTrainer(cfg)

    def refused(state, batch):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
            "memory in memory space hbm. Used 18.69G of 15.75G hbm.\nTotal hbm"
        )

    trainer.train_step = refused
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        trainer.train()
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if ln.startswith("[train] REFUSED"))
    assert "'fsdp': 2" in line and "per_device_batch_size=2" in line
    assert "remat_policy='dots_no_batch'" in line
    # rows of 128 tokens against a hidden size of 64: every block also keeps
    # the flash forward kernel's outputs (models/transformer._remat_policy)
    layers = trainer.model_config.num_layers
    # and no block of a dense model keeps an expert layer's routing (ops/moe.KEPT_ACROSS_REMAT)
    assert f"({layers} of {layers} layers also keep the flash kernel's outputs, 0 their experts' routing" in line
    assert "Used 18.69G of 15.75G" in line and "Total hbm" not in line
