"""Package console entry points (``smollm3-train`` / ``smollm3-ask`` /
``smollm3-serve``, pyproject.toml [project.scripts]).

``train_main`` is the distributed SFT/DPO entry — the TPU-native equivalent
of the reference's ``training.py`` (same env-var contract: EPOCHS,
BATCH_SIZE, LEARNING_RATE, DATA_DIR, OUTPUT_DIR, AIM_REPO,
WORLD_SIZE/RANK/MASTER_ADDR/MASTER_PORT; reference ``training.py:19-23,54-60``),
with mesh shape via MESH_DATA/MESH_FSDP/MESH_TENSOR/MESH_SEQ/MESH_EXPERT.
The repo-root ``training.py`` / ``ask_tuned_model.py`` scripts are thin shims
over these functions, so ``python training.py`` and ``smollm3-train`` are the
same program.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Optional


def train_main(argv: Optional[list] = None) -> int:
    from llm_fine_tune_distributed_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", help="JSON/YAML TrainConfig file")
    parser.add_argument("--model-preset", help="model preset override")
    parser.add_argument(
        "--resume", nargs="?", const="latest", default=None,
        help="resume from checkpoint ('latest' or a step number)",
    )
    parser.add_argument(
        "--platform", default=None,
        help="force a jax platform ('cpu' for simulation runs on virtual "
             "devices; default: whatever JAX finds, the TPU where one is "
             "attached)",
    )
    parser.add_argument(
        "--virtual-devices", type=int, default=None,
        help="with --platform cpu: number of virtual host devices "
             "(XLA_FLAGS --xla_force_host_platform_device_count)",
    )
    parser.add_argument(
        "--train-port", type=int, default=None,
        help="serve the training control plane (/metrics, /v1/train/status, "
             "/v1/train/flight, POST /v1/train/profile) on this port from "
             "the primary host (0 = ephemeral)",
    )
    parser.add_argument(
        "--publish-require-clean", action="store_true", default=None,
        help="skip publishing checkpoints whose trailing anomaly window is "
             "dirty instead of stamping anomaly_clean=false",
    )
    args = parser.parse_args(argv)

    if args.virtual_devices:
        import re

        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            os.environ.get("XLA_FLAGS", ""),
        ).strip()
        # CPU-backend workaround (see tests/conftest.py): AllReducePromotion
        # check-fails on bf16 expert-axis all-reduces from pipe x EP backward
        if "xla_disable_hlo_passes" not in flags:
            flags = f"{flags} --xla_disable_hlo_passes=all-reduce-promotion".strip()
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.virtual_devices}"
        ).strip()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    # Multi-host bootstrap MUST run before any jax backend use
    # (reference analog: setup_distributed, training.py:16-42).
    from llm_fine_tune_distributed_tpu.runtime.distributed import (
        initialize_distributed,
        is_primary_host,
    )

    info = initialize_distributed()

    from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig

    config = TrainConfig.load(args.config) if args.config else TrainConfig()
    config.apply_env_overrides()
    if args.model_preset:
        config.model_preset = args.model_preset
    if args.resume is not None:
        config.resume_from_checkpoint = args.resume
    if args.train_port is not None:
        config.train_port = args.train_port
    if args.publish_require_clean:
        config.publish_require_clean = True
    mesh_env = {
        k: os.environ.get(f"MESH_{k.upper()}")
        for k in ("data", "fsdp", "tensor", "seq", "expert", "pipe")
    }
    if any(v is not None for v in mesh_env.values()):
        config.mesh = MeshConfig(
            **{k: int(v) for k, v in mesh_env.items() if v is not None}
        )

    if is_primary_host():
        print("=" * 60)
        print("TPU-native distributed SFT")
        print(f"  process {info.process_index}/{info.process_count}, "
              f"{info.global_device_count} devices "
              f"({info.platform}, {info.device_kind})")
        print(f"  epochs={config.epochs} batch={config.per_device_batch_size} "
              f"lr={config.learning_rate} accum={config.gradient_accumulation_steps}")
        print(f"  data={config.data_dir} output={config.output_dir}")
        print("=" * 60)

    if config.objective not in ("sft", "dpo"):
        raise SystemExit(
            f"unknown OBJECTIVE {config.objective!r}; expected 'sft' or 'dpo'"
        )
    if config.objective == "dpo":
        # preference-pair path (OBJECTIVE=dpo): BASELINE.json config #4
        from llm_fine_tune_distributed_tpu.train.dpo import DPOTrainer as Trainer
    else:
        from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer as Trainer

    trainer = Trainer(config)
    summary = trainer.train()

    if is_primary_host():
        print("\nDistributed Q&A fine-tuning completed successfully!")
        print(f"Training artifacts saved to {config.output_dir}/")
        steady = summary.get("samples_per_second_per_chip_steady")
        print(f"samples/sec/chip: {summary.get('samples_per_second_per_chip')}"
              + (f" (steady-state: {steady})" if steady else ""))
    return 0


def ask_main(argv: Optional[list] = None) -> int:
    """Ask the fine-tuned model a question (reference ``ask_tuned_model.py``)."""
    from llm_fine_tune_distributed_tpu.infer.cli import run_ask_cli

    return run_ask_cli(
        argv,
        description=ask_main.__doc__,
        default_model_dir="outputs/best_model",
        model_dir_env="MODEL_DIR",
        missing_dir_help="Run training first (smollm3-train) or pass --model-dir.",
    )


def serve_main(argv: Optional[list] = None) -> int:
    """Serve the tuned model over HTTP (infer/server.py)."""
    from llm_fine_tune_distributed_tpu.infer.server import main

    return main(argv)


if __name__ == "__main__":
    sys.exit(train_main())
