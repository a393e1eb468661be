#!/usr/bin/env python
"""Decode==train regurgitation probe on hardware.

A checkpoint whose teacher-forced loss is ~0 must greedily reproduce the
byte stream it memorized, through the production inference path. Two modes:

``--mode train-answers`` (the r5 flagship): greedy-decode N TRAINING
prompts (system + question through the chat template) and report byte
overlap with the training answers.

``--mode r4-prefix`` (the r4 reconciliation): the r4 flagship's data bug
truncated every row to the SAME 1024-byte prefix of the wilderness system
prompt (the 1378-byte persona exceeds seq 1024 under byte tokenization), so
the model memorized exactly one sequence — and the golden questions were
OUT-OF-DISTRIBUTION prompts, hence babble despite eval_loss 0.0045. The
in-distribution probe: feed the first K tokens of THE training sequence and
greedy-decode the continuation; near-total overlap proves decode==train on
hardware and fully reconciles the r4 artifacts.

Emits one JSON report (``--report``).
"""

import argparse
import difflib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model-dir", required=True)
    ap.add_argument("--mode", choices=["train-answers", "r4-prefix"], required=True)
    ap.add_argument("--n", type=int, default=10, help="training prompts to probe")
    ap.add_argument("--prompt-tokens", type=int, default=256, help="r4-prefix: context fed")
    ap.add_argument("--decode-tokens", type=int, default=256, help="r4-prefix: continuation len")
    ap.add_argument("--system-prompt", default=None,
                    help="train-answers: the system prompt the checkpoint trained with")
    ap.add_argument(
        "--dataset",
        default=os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "data", "qa_dataset.parquet",
        ),
        help="QA parquet (90/10 seed-42 split reproduced to pick TRAIN rows; "
        "pass the same file the checkpoint trained on)",
    )
    ap.add_argument("--report", default="regurgitation_report.json")
    args = ap.parse_args(argv)

    from llm_fine_tune_distributed_tpu.data.dataset import (
        WILDERNESS_EXPERT_SYSTEM_PROMPT,
        format_chat_example,
        load_qa_dataset,
        tokenize_example,
        train_validation_split,
    )
    from llm_fine_tune_distributed_tpu.infer import (
        GenerationConfig,
        Generator,
        load_model_dir,
        load_tokenizer_dir,
    )

    t0 = time.perf_counter()
    params, mc = load_model_dir(args.model_dir)
    tok = load_tokenizer_dir(args.model_dir)
    print(f"model loaded in {time.perf_counter() - t0:.0f}s")

    rows = load_qa_dataset(args.dataset)
    train_rows, _ = train_validation_split(rows)

    report = {"mode": args.mode, "model_dir": args.model_dir, "rows": []}

    if args.mode == "r4-prefix":
        # all r4 training rows share the same truncated prefix; reconstruct it
        msgs = format_chat_example(train_rows[0], WILDERNESS_EXPERT_SYSTEM_PROMPT)["messages"]
        ex = tokenize_example(msgs, tok, 1024)
        seq = [int(t) for t in ex.input_ids[: ex.length]]
        K, D = args.prompt_tokens, args.decode_tokens
        gen = Generator(params, mc, tok, eos_token_ids=[])
        got = gen.generate_ids(
            seq[:K], GenerationConfig(max_new_tokens=D, do_sample=False)
        )
        want = seq[K : K + D]
        exact = sum(int(a == b) for a, b in zip(got, want)) / max(len(want), 1)
        got_txt = tok.decode(list(got), skip_special_tokens=True)
        want_txt = tok.decode(want, skip_special_tokens=True)
        ratio = difflib.SequenceMatcher(None, got_txt, want_txt).ratio()
        report["rows"].append({
            "prompt_tokens": K,
            "decode_tokens": D,
            "token_exact_match": round(exact, 4),
            "byte_overlap": round(ratio, 4),
            "decoded_head": got_txt[:120],
            "expected_head": want_txt[:120],
        })
        report["summary"] = {
            "token_exact_match": round(exact, 4), "byte_overlap": round(ratio, 4)
        }
    else:
        gen = Generator(params, mc, tok)
        overlaps, exacts = [], 0
        # ONE GenerationConfig for every row: each distinct max_new_tokens
        # compiles a fresh decode program — eos stops short rows anyway. Sized in TOKENS of the
        # actual tokenizer (a byte tokenizer needs one token per UTF-8
        # byte, more than len() characters for non-ASCII answers).
        gcfg = GenerationConfig(
            max_new_tokens=max(
                len(tok.encode(r["answer"])) for r in train_rows[: args.n]
            ) + 48,
            do_sample=False,
        )
        for row in train_rows[: args.n]:
            msgs = [{"role": "user", "content": row["full-question"]}]
            if args.system_prompt:
                msgs.insert(0, {"role": "system", "content": args.system_prompt})
            t1 = time.perf_counter()
            got = gen.chat(msgs, gcfg)
            ratio = difflib.SequenceMatcher(None, got, row["answer"]).ratio()
            overlaps.append(ratio)
            exacts += int(got.strip() == row["answer"].strip())
            report["rows"].append({
                "question": row["full-question"][:80],
                "byte_overlap": round(ratio, 4),
                "exact": got.strip() == row["answer"].strip(),
                "decoded_head": got[:100],
                "expected_head": row["answer"][:100],
                "decode_seconds": round(time.perf_counter() - t1, 1),
            })
        report["summary"] = {
            "n": len(overlaps),
            "mean_byte_overlap": round(sum(overlaps) / max(len(overlaps), 1), 4),
            "exact_matches": exacts,
        }

    with open(args.report, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report["summary"], indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
