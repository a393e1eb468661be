"""Shared implementation of the two question-answering CLIs
(``ask_tuned_model.py`` / ``ask_original_model.py``): identical argparse
surface, load path, and sampling defaults (reference ``ask_tuned_model.py``
vs ``ask_original_model.py`` differ only in model source and the
``enable_thinking=False`` template flag)."""

from __future__ import annotations

import argparse
import os
from typing import Optional


def run_ask_cli(
    argv: Optional[list],
    *,
    description: str,
    default_model_dir: str,
    model_dir_env: str,
    missing_dir_help: str,
    template_kwargs: Optional[dict] = None,
) -> int:
    from llm_fine_tune_distributed_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    parser = argparse.ArgumentParser(description=description)
    parser.add_argument(
        "question", nargs="*", help="question for the model (omit with --serve)"
    )
    parser.add_argument(
        "--model-dir",
        default=os.environ.get(model_dir_env, default_model_dir),
        help="directory with config.json + model.safetensors (+ tokenizer)",
    )
    # sampling defaults = reference ask_tuned_model.py:56-65
    parser.add_argument("--max-new-tokens", type=int, default=3768)
    parser.add_argument("--temperature", type=float, default=0.6)
    parser.add_argument("--top-p", type=float, default=0.95)
    parser.add_argument("--top-k", type=int, default=40)
    parser.add_argument("--repetition-penalty", type=float, default=1.1)
    parser.add_argument("--greedy", action="store_true", help="disable sampling")
    parser.add_argument(
        "--speculative", type=int, default=0, metavar="K",
        help="speculative decoding with K drafts/step (greedy verifies by "
        "exact match; sampled by rejection sampling, keeping the output "
        "distribution). Drafts come from prompt-lookup (default — pays off "
        "when answers quote the context) or from a small draft MODEL when "
        "--draft-dir is set (pays off on any text)",
    )
    parser.add_argument(
        "--draft-dir", default=None, metavar="DIR",
        help="model directory of a SMALL same-vocab draft model for "
        "--speculative (e.g. a SmolLM2-135M beside a 3B target)",
    )
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--quantize",
        choices=["none", "int8"],
        default="none",
        help="weight-only inference quantization: int8 halves the HBM weight "
        "stream that bounds batch-1 decode (ops/int8.py)",
    )
    parser.add_argument(
        "--tp", type=int, default=1, metavar="N",
        help="tensor-parallel inference over N devices of the global pool "
        "(shards weights and KV cache so models beyond one chip's HBM are "
        "servable; under jax.distributed N may exceed the local device "
        "count — the mesh then spans hosts and --serve coordinates them)",
    )
    parser.add_argument(
        "--serve", action="store_true",
        help="run the HTTP server (infer/server.py) instead of answering once",
    )
    parser.add_argument("--host", default="0.0.0.0", help="--serve bind address")
    parser.add_argument("--port", type=int, default=8080, help="--serve port")
    args = parser.parse_args(argv)
    question = " ".join(args.question)
    if args.draft_dir and not args.speculative:
        # validate BEFORE the (multi-GB) target model load
        parser.error("--draft-dir requires --speculative K")
    if not args.model_dir or not os.path.isdir(args.model_dir):
        # reference exits with guidance when the artifact is missing
        # (ask_tuned_model.py:17-20)
        print(f"Error: model directory not found: {args.model_dir!r}")
        print(missing_dir_help)
        return 1

    if args.serve:
        # sampling knobs are per-REQUEST in server mode; refuse silently
        # ignored arguments instead of starting a misconfigured-looking server
        if question:
            parser.error("--serve takes no question (clients POST /v1/generate)")
        # --speculative is NOT in this list: with --serve it configures the
        # engine-level fused draft+verify tick (server.py speculative_k),
        # while requests still opt in per-call with 'speculative': K
        sampling_flags = (
            "max_new_tokens", "temperature", "top_p", "top_k",
            "repetition_penalty", "greedy", "seed",
        )
        ignored = [
            f"--{k.replace('_', '-')}" for k in sampling_flags
            if getattr(args, k) != parser.get_default(k)
        ]
        if ignored:
            parser.error(
                f"{' '.join(ignored)} have no effect with --serve — sampling "
                "options are per-request fields of POST /v1/generate"
            )
        from llm_fine_tune_distributed_tpu.infer.server import serve

        serve(
            args.model_dir, host=args.host, port=args.port,
            quantize=args.quantize, template_kwargs=template_kwargs,
            tp=args.tp, draft_dir=args.draft_dir,
            speculative_k=args.speculative,
        )
        return 0
    if not question:
        parser.error("a question is required (or pass --serve)")

    from llm_fine_tune_distributed_tpu.data.prompts import WILDERNESS_EXPERT_SYSTEM_PROMPT
    from llm_fine_tune_distributed_tpu.infer import (
        GenerationConfig,
        Generator,
        load_model_dir,
        load_tokenizer_dir,
    )

    print(f"Loading model from {args.model_dir} ...")
    params, model_config = load_model_dir(args.model_dir)
    from llm_fine_tune_distributed_tpu.ops.int8 import maybe_quantize

    params = maybe_quantize(params, args.quantize)
    tokenizer = load_tokenizer_dir(args.model_dir)
    mesh = None
    if args.tp > 1:
        from llm_fine_tune_distributed_tpu.infer.generate import make_tp_mesh

        mesh = make_tp_mesh(args.tp)
        print(f"Tensor-parallel decode over {args.tp} devices")
    draft_kwargs = {}
    if args.draft_dir:
        draft_params, draft_config = load_model_dir(args.draft_dir)
        draft_kwargs = {"draft_params": draft_params, "draft_config": draft_config}
        print(f"Draft model for speculation: {args.draft_dir}")
    generator = Generator(params, model_config, tokenizer, mesh=mesh, **draft_kwargs)

    gen = GenerationConfig(
        max_new_tokens=args.max_new_tokens,
        do_sample=not args.greedy,
        temperature=args.temperature,
        top_p=args.top_p,
        top_k=args.top_k,
        repetition_penalty=args.repetition_penalty,
        speculative_lookup=args.speculative,
    )
    messages = [
        {"role": "system", "content": WILDERNESS_EXPERT_SYSTEM_PROMPT},
        {"role": "user", "content": question},
    ]
    print(f"\nQuestion: {question}\n")
    answer = generator.chat(messages, gen, seed=args.seed, **(template_kwargs or {}))
    print(f"Answer: {answer}")
    if args.speculative and generator.last_acceptance_rate is not None:
        print(
            f"[speculative] {generator.last_spec_steps} sequential forwards, "
            f"draft acceptance {100 * generator.last_acceptance_rate:.0f}%"
        )
    return 0
