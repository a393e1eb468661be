"""Decode == train consistency.

A model whose teacher-forced train loss is ~0 on a memorized dataset MUST
greedily regurgitate the memorized answers through the production inference
path (infer/generate.py -> best_model artifact -> Generator.chat). The r4
flagship's eval_loss 0.0045 next to pure decode babble went unreconciled —
the cause was a data bug (every row truncated to the same prompt prefix, so
no answer token was ever trained; see trainer._attach_completion_mask), but
nothing PINNED the property that training and decode agree. This test pins
it forever: overfit tiny on 20 samples, assert near-exact greedy
regurgitation of the training answers end-to-end.
"""

import difflib
import json
import os

import numpy as np
import pytest

import jax

from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet

SYS = "Be brief."

_WORDS = [
    "river", "stone", "papaya", "gallon", "maple", "knot", "ember", "cliff",
    "lantern", "moss", "falcon", "cedar", "pearl", "quartz", "willow",
    "ridge", "fern", "slate", "harbor", "thistle",
]
# distinct, low-interference answers: one unique lead word per item
ANSWERS = [f"item {i} is {_WORDS[i]} {_WORDS[(i + 7) % 20]}." for i in range(20)]


@pytest.fixture(scope="module")
def memorize_setup(tmp_path_factory):
    """Overfit tiny on 20 distinct QA pairs until near-zero train loss,
    exporting best_model/ through the standard artifact contract."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    tmp = tmp_path_factory.mktemp("regurg")
    jsonl = tmp / "qa.jsonl"
    with open(jsonl, "w") as f:
        for i, a in enumerate(ANSWERS):
            f.write(json.dumps({
                "topic": "Memory",
                "question": f"what is item {i}?",
                "answer": a,
            }) + "\n")
    convert_jsonl_to_parquet(str(jsonl), str(tmp / "qa_dataset.parquet"), verbose=False)

    out = tmp / "out"
    cfg = TrainConfig(
        model_name="tiny-random",
        model_preset="tiny",
        tokenizer_path="byte-chatml",
        system_prompt=SYS,
        data_dir=str(tmp),
        dataset_file="qa_dataset.parquet",
        output_dir=str(out),
        epochs=150,
        per_device_batch_size=2,
        gradient_accumulation_steps=1,
        learning_rate=2e-3,
        lr_schedule="cosine",         # settles to 0 so memorization sticks
        warmup_ratio=0.02,
        # loss on answer bytes only: the full-sequence loss carries the
        # IRREDUCIBLE entropy of the item number inside the user prompt
        # (~0.04 here), which would mask whether the answers are memorized
        completion_only_loss=True,
        max_seq_length=160,
        freeze_strategy="none",       # memorization needs full capacity
        validation_fraction=0.1,      # 18 train / 2 val
        eval_steps=0,
        logging_steps=50,
        save_steps=0,
        gradient_checkpointing=False,
        use_native_loader=False,
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1),
    )
    trainer = SFTTrainer(cfg)
    summary = trainer.train()
    # the premise of the reconciliation: teacher-forced loss is ~0
    assert summary["final_train_loss"] < 0.02, summary["final_train_loss"]
    # regurgitation is a claim about TRAINING rows only: reproduce the 90/10
    # split and probe with the exact "For {topic}, {question}" prompt text
    # the trainer saw (data/convert.py concatenation)
    from llm_fine_tune_distributed_tpu.data.dataset import (
        load_qa_dataset,
        train_validation_split,
    )

    rows = load_qa_dataset(str(tmp / "qa_dataset.parquet"))
    tr_rows, _ = train_validation_split(
        rows, test_size=cfg.validation_fraction, seed=cfg.split_seed
    )
    train_rows = [{"q": r["full-question"], "a": r["answer"]} for r in tr_rows]
    return str(out / "best_model"), train_rows, summary


@pytest.mark.slow
def test_overfit_model_greedily_regurgitates_training_answers(memorize_setup):
    from llm_fine_tune_distributed_tpu.infer import (
        Generator,
        GenerationConfig,
        load_model_dir,
        load_tokenizer_dir,
    )

    best_dir, train_rows, summary = memorize_setup
    params, mc = load_model_dir(best_dir, dtype=np.float32)
    tok = load_tokenizer_dir(best_dir)
    gen = Generator(params, mc, tok, compute_dtype=np.float32)

    overlaps, exact = [], 0
    for row in train_rows[:10]:
        got = gen.chat(
            [
                {"role": "system", "content": SYS},
                {"role": "user", "content": row["q"]},
            ],
            GenerationConfig(max_new_tokens=len(row["a"]) + 24, do_sample=False),
        )
        ratio = difflib.SequenceMatcher(None, got, row["a"]).ratio()
        overlaps.append(ratio)
        exact += int(got.strip() == row["a"].strip())

    mean_overlap = float(np.mean(overlaps))
    # near-total byte overlap: loss ~0 must imply decode reproduces training
    # text; anything else is an inference-path (template/position/tokenizer)
    # mismatch — the failure mode this probe exists to make detectable
    assert mean_overlap > 0.9, (mean_overlap, overlaps)
    assert exact >= 7, (exact, overlaps)
