"""Pre-staged real-weights path.

No-egress environments cannot fetch HF Hub weights, so the reference's end
oracle (real SmolLM3 answering the golden questions better after tuning)
runs here via PRE-STAGED weights: ``MODEL_NAME=/path/to/dir`` with real-format
HF files. Nothing previously proved that path end-to-end. This test stages a
tiny HF-layout checkpoint — safetensors weights, HF config.json, and a REAL
``tokenizers``-library BPE tokenizer (tokenizer.json + tokenizer_config.json
with a ChatML chat template, the exact file format a hub snapshot ships) —
then trains from it through the normal trainer (architecture resolved from
the dir's config.json via MODEL_PRESET=none) and runs the eval_golden CLI
against the produced best_model/, so the day egress exists the oracle runs
unchanged.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet

CHATML_TEMPLATE = (
    "{% for message in messages %}"
    "{{ '<|im_start|>' + message['role'] + '\n' + message['content'] + '<|im_end|>' + '\n' }}"
    "{% endfor %}"
    "{% if add_generation_prompt %}{{ '<|im_start|>assistant\n' }}{% endif %}"
)


def _build_real_hf_tokenizer(save_dir: str, corpus):
    """A genuine HF fast tokenizer built offline: ByteLevel BPE trained on
    the test corpus, ChatML specials, saved in the standard snapshot layout."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers
    from transformers import PreTrainedTokenizerFast

    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=384,
        special_tokens=["<|im_start|>", "<|im_end|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
    )
    tok.train_from_iterator(corpus, trainer)
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok,
        eos_token="<|im_end|>",
        pad_token="<|im_end|>",
        chat_template=CHATML_TEMPLATE,
    )
    fast.save_pretrained(save_dir)
    return fast


@pytest.mark.slow
def test_prestaged_hf_dir_trains_and_answers_golden_questions(tmp_path):
    from llm_fine_tune_distributed_tpu.config import ModelConfig
    from llm_fine_tune_distributed_tpu.models.configs import to_hf_dict
    from llm_fine_tune_distributed_tpu.models.hf_io import save_hf_checkpoint
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    # --- stage the "downloaded" checkpoint dir ---------------------------
    staged = tmp_path / "staged_model"
    staged.mkdir()
    rows = [
        {"topic": "Knots", "question": f"question {i}?",
         "answer": f"answer {i}: tie the loop and pull."}
        for i in range(48)
    ]
    corpus = [r["question"] + " " + r["answer"] for r in rows]
    tok = _build_real_hf_tokenizer(str(staged), corpus)
    assert (staged / "tokenizer.json").exists()  # the real HF file format
    assert (staged / "tokenizer_config.json").exists()

    mc = ModelConfig(
        name="llama",  # a real HF model_type: exercises the generic path
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        rope_theta=10_000.0,
        max_position_embeddings=512,
        tie_word_embeddings=True,
    )
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    save_hf_checkpoint(params, str(staged))
    with open(staged / "config.json", "w") as f:
        json.dump(to_hf_dict(mc), f)

    # --- dataset ----------------------------------------------------------
    jsonl = tmp_path / "qa.jsonl"
    with open(jsonl, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    convert_jsonl_to_parquet(str(jsonl), str(tmp_path / "qa_dataset.parquet"), verbose=False)

    # --- train FROM the staged dir (architecture from its config.json) ---
    out = tmp_path / "out"
    cfg = TrainConfig(
        model_name=str(staged),
        model_preset=None,          # MODEL_PRESET=none contract
        tokenizer_path=None,        # -> model_name dir (real HF files)
        system_prompt="Be brief.",
        data_dir=str(tmp_path),
        dataset_file="qa_dataset.parquet",
        output_dir=str(out),
        epochs=1,
        per_device_batch_size=2,
        gradient_accumulation_steps=2,
        learning_rate=1e-3,
        max_seq_length=96,
        eval_steps=5,
        save_steps=0,
        unfreeze_last_n_layers=1,
        use_native_loader=False,
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1),
    )
    trainer = SFTTrainer(cfg)
    # the staged REAL tokenizer is in play, not the byte fallback
    assert trainer.tokenizer.__class__.__name__ == "PreTrainedTokenizerFast"
    assert trainer.model_config.name == "llama"
    assert trainer.model_config.hidden_size == 64
    summary = trainer.train()
    assert np.isfinite(summary["final_train_loss"])

    best = out / "best_model"
    assert (best / "config.json").exists()
    assert (best / "tokenizer.json").exists()  # real tokenizer re-exported

    # --- the reference oracle runs unchanged against the artifact --------
    report = tmp_path / "golden.json"
    r = subprocess.run(
        [
            sys.executable, "eval_golden.py",
            "--tuned-dir", str(best),
            "--report", str(report),
            "--max-new-tokens", "8",
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert report.exists() or "How many cups" in r.stdout
