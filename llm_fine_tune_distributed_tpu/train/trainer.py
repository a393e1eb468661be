"""First-party SFT trainer — the replacement for the reference's entire
L1 delegation to TRL SFTTrainer + Accelerate (reference ``training.py:289-300``
and SURVEY.md §3.1 hot loop).

End-to-end responsibilities (reference parity points cited inline):
- model init or HF-checkpoint load, bf16 compute (``training.py:97-102``)
- freezing policy: last-2 blocks + lm_head (``training.py:113-149``)
- dataset: parquet -> 90/10 seed-42 split -> ChatML (``training.py:155-212``)
- jitted train loop: grad-accum 4, clip 1.0, lr x dp_size, linear decay
  (``training.py:258-287``), eval every 10 steps, log every 2 + first
  (``training.py:266-271``)
- best-eval-loss tracking + load-best-at-end (``training.py:273-275``)
- Orbax checkpoint rotation keep-3 (``training.py:268,276``) + explicit resume
  (absent in the reference, SURVEY.md §5.4)
- host-0 artifact contract: ``best_model/`` safetensors + tokenizer,
  ``training_history.json``, ``training_summary.json`` (``training.py:307-339``)
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from llm_fine_tune_distributed_tpu.config import ModelConfig, TrainConfig, str_to_dtype
from llm_fine_tune_distributed_tpu.data.dataset import (
    build_sft_arrays,
    load_qa_dataset,
    train_validation_split,
)
from llm_fine_tune_distributed_tpu.data.loader import SFTBatchLoader
from llm_fine_tune_distributed_tpu.data.tokenizer import load_tokenizer
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.hf_io import load_hf_checkpoint, save_hf_checkpoint
from llm_fine_tune_distributed_tpu.models.transformer import init_params, keeps_flash_outputs
from llm_fine_tune_distributed_tpu.observe.metrics import MetricLogger
from llm_fine_tune_distributed_tpu.observe.throughput import ThroughputMeter
from llm_fine_tune_distributed_tpu.observe.tracing import Histogram
from llm_fine_tune_distributed_tpu.observe.trainplane import (
    TrainControlPlane,
    TrainTelemetry,
)
from llm_fine_tune_distributed_tpu.observe.xla import CompileLedger, annotate, instrument
from llm_fine_tune_distributed_tpu.parallel.freeze import describe_trainable, trainable_mask
from llm_fine_tune_distributed_tpu.parallel.optimizer import (
    build_lr_schedule,
    build_optimizer,
    init_opt_state,
)
from llm_fine_tune_distributed_tpu.parallel.sharding import param_spec
from llm_fine_tune_distributed_tpu.runtime.distributed import (
    device_preflight,
    is_primary_host,
)
from llm_fine_tune_distributed_tpu.runtime.mesh import data_parallel_size, make_mesh
from llm_fine_tune_distributed_tpu.train.checkpoints import CheckpointManager
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.train.step import (
    build_eval_step,
    build_train_step,
    jit_train_step,
)
from llm_fine_tune_distributed_tpu.utils.tree import merge_flat, split_by_mask


class SFTTrainer:
    def __init__(
        self,
        config: TrainConfig,
        model_config: Optional[ModelConfig] = None,
        tokenizer=None,
        mesh=None,
        rng_seed: Optional[int] = None,
    ):
        self.config = config
        self.model_config = model_config or self._resolve_model_config(config)
        self.mesh = mesh if mesh is not None else make_mesh(config.mesh)
        self.dp_size = data_parallel_size(self.mesh)
        self.tokenizer = tokenizer or load_tokenizer(
            config.tokenizer_path or config.model_name
        )
        self.rng = jax.random.PRNGKey(config.seed if rng_seed is None else rng_seed)
        # preemption flag (SIGTERM / request_preemption): checked at the step
        # boundary in train(); set -> emergency checkpoint + clean exit so a
        # JobSet restart resumes instead of losing up to save_steps of work
        self._preempt = threading.Event()
        # live-deployment publisher (train/publish.py), built lazily at the
        # first save when config.publish_dir is set
        self._publisher = None
        # subclasses (DPO) stash extra eval-time scalars here; merged into the
        # metric sinks whenever an eval fires
        self.extra_eval_logs: Dict[str, float] = {}
        self.metrics = MetricLogger(
            config.output_dir,
            aim_repo=config.aim_repo,
            experiment=config.experiment_name,
        )
        # run-level hparams (Aim "color by run.hparams.*" / AimQL filters,
        # docs/aim-workflow.md): the full config + mesh shape
        hparams = {
            k: (v if isinstance(v, (int, float, str, bool, type(None))) else str(v))
            for k, v in config.to_dict().items()
        }
        hparams["mesh"] = {a: int(s) for a, s in self.mesh.shape.items()}
        self.metrics.set_params(hparams)
        # training control plane state (observe/trainplane.py): flight
        # recorder + anomaly sentinels + the status dict the HTTP server
        # reads. Always constructed (sentinels gate publish even with the
        # server off); fed only at log/eval/save boundaries.
        self.telemetry = TrainTelemetry(
            hparams=hparams,
            band_sigma=config.anomaly_band_sigma,
            anomaly_window_steps=config.anomaly_window_steps,
        )
        if is_primary_host():
            os.makedirs(os.path.join(config.output_dir, "best_model"), exist_ok=True)
        device_preflight()

        # start-up by phase (observe/xla.py spans; train() adds the restore and
        # the first step, prints them and hands them to /v1/train/status)
        with annotate("startup/data"):
            self._prepare_data()
        self._prepare_state()
        self._prepare_steps()

    @staticmethod
    def _resolve_model_config(config: TrainConfig) -> ModelConfig:
        """Architecture resolution: an explicit preset wins; with
        ``model_preset`` None or the literal string "none" (any surface:
        env MODEL_PRESET=none, --model-preset none, config file) the
        architecture comes from ``model_name``'s HF ``config.json`` — the
        pre-staged real-weights contract (reference
        ``AutoModelForCausalLM.from_pretrained`` flexibility,
        ``training.py:97-102``): point MODEL_NAME at any local HF checkpoint
        dir and train it unchanged."""
        preset = config.model_preset
        if isinstance(preset, str) and preset.lower() == "none":
            preset = None
        if preset:
            return get_preset(preset)
        from llm_fine_tune_distributed_tpu.models.configs import load_model_config

        try:
            return load_model_config(config.model_name or "")
        except FileNotFoundError as e:
            raise ValueError(
                "model_preset is None and model_name "
                f"({config.model_name!r}) is not a local HF checkpoint "
                "directory with a config.json — set MODEL_PRESET or stage "
                "the weights locally"
            ) from e

    # ------------------------------------------------------------------ data

    def _prompt_kwargs(self) -> Dict[str, Any]:
        """system_prompt override for the array builders (shared SFT/DPO)."""
        if self.config.system_prompt is not None:
            return {"system_prompt": self.config.system_prompt}
        return {}

    def _process_batch_rows(self) -> tuple:
        """(row_start, row_count): this process's contiguous row range of
        each microbatch's global batch, derived from the mesh.

        With the batch dim sharded over (data, fsdp) and the standard axis
        order, a process's devices cover a contiguous block of rows. When a
        seq (or tensor/pipe) axis spans processes, several processes map to
        the SAME rows — each loads the full rows and its devices take their
        sequence slices in _device_batch. This is what lets the seq axis
        cross host boundaries (long-context ring attention over DCN)."""
        B = self.config.per_device_batch_size * self.dp_size
        if jax.process_count() == 1:
            return 0, B
        sharding = NamedSharding(self.mesh, P(("data", "fsdp")))
        index_map = sharding.devices_indices_map((B,))
        pid = jax.process_index()
        blocks = sorted(
            {
                ((sl[0].start or 0), B if sl[0].stop is None else sl[0].stop)
                for d, sl in index_map.items()
                if d.process_index == pid
            }
        )
        lo, hi = blocks[0][0], blocks[-1][1]
        covered = 0
        for s, e in blocks:
            covered += e - s
        if covered != hi - lo:
            raise ValueError(
                f"process {pid}'s batch rows are not contiguous ({blocks}); "
                "reorder the mesh axes so data/fsdp are outermost"
            )
        return lo, hi - lo

    def _loader_kwargs(self) -> Dict[str, Any]:
        """Batch-loader kwargs (shared SFT/DPO so sharding semantics can't drift)."""
        cfg = self.config
        self._row_start, self._row_count = self._process_batch_rows()
        return dict(
            per_device_batch_size=cfg.per_device_batch_size,
            grad_accum_steps=cfg.gradient_accumulation_steps,
            data_parallel_size=self.dp_size,
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            seed=cfg.seed,
            drop_last=cfg.drop_last,
            row_start=self._row_start,
            row_count=self._row_count,
        )

    def _prepare_data(self) -> None:
        cfg = self.config
        dataset_path = os.path.join(cfg.data_dir, cfg.dataset_file)
        rows = load_qa_dataset(dataset_path)
        if is_primary_host():
            print(f"Total dataset size: {len(rows):,} Q&A pairs")
        train_rows, val_rows = train_validation_split(
            rows, test_size=cfg.validation_fraction, seed=cfg.split_seed
        )
        self.n_train, self.n_val = len(train_rows), len(val_rows)
        if is_primary_host():
            print(f"Training samples: {self.n_train:,}")
            print(f"Validation samples: {self.n_val:,}")

        prompt_kw = self._prompt_kwargs()
        if cfg.packing:
            # packing=True: multiple examples per fixed-length row with
            # segment ids / per-segment positions (data/packing.py). Rows
            # shrink, so steps_per_epoch and the sample counters reflect
            # PACKED rows, matching TRL's packing accounting.
            from llm_fine_tune_distributed_tpu.data.packing import (
                build_packed_sft_arrays,
                packing_efficiency,
            )

            self.train_arrays = build_packed_sft_arrays(
                train_rows, self.tokenizer, cfg.max_seq_length,
                cfg.completion_only_loss, **prompt_kw,
            )
            self.val_arrays = build_packed_sft_arrays(
                val_rows, self.tokenizer, cfg.max_seq_length,
                cfg.completion_only_loss, **prompt_kw,
            )
            self.n_train = self.train_arrays["input_ids"].shape[0]
            self.n_val = self.val_arrays["input_ids"].shape[0]
            if is_primary_host():
                print(
                    f"Packing: {len(train_rows):,} examples -> {self.n_train:,} "
                    f"rows ({100 * packing_efficiency(self.train_arrays):.1f}% "
                    f"token occupancy)"
                )
        else:
            self.train_arrays = build_sft_arrays(
                train_rows, self.tokenizer, cfg.max_seq_length, cfg.completion_only_loss,
                **prompt_kw,
            )
            self.val_arrays = build_sft_arrays(
                val_rows, self.tokenizer, cfg.max_seq_length, cfg.completion_only_loss,
                **prompt_kw,
            )
        self._attach_completion_mask(val_rows, prompt_kw)
        loader_kw = self._loader_kwargs()
        self.loader = None
        if cfg.use_native_loader:
            # C++ prefetch pipeline (native/loader.cc): batch assembly overlaps
            # device step time. Falls back to the Python loader without g++.
            # The two engines use different (each deterministic) permutations,
            # so the choice must be UNANIMOUS across hosts — a mixed fleet
            # would shard different epoch orders and silently desync the data.
            from llm_fine_tune_distributed_tpu.runtime import native

            use_native = native.available()
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                votes = np.asarray(
                    multihost_utils.process_allgather(
                        np.array([1 if use_native else 0], np.int32)
                    )
                ).reshape(-1)
                use_native = bool(votes.min())
            if use_native:
                from llm_fine_tune_distributed_tpu.data.native_loader import (
                    NativeBatchLoader,
                )

                self.loader = NativeBatchLoader(self.train_arrays, **loader_kw)
            elif is_primary_host():
                print(f"[data] native loader unavailable on >=1 host "
                      f"({native.build_error()}); all hosts using Python loader")
        if self.loader is None:
            self.loader = SFTBatchLoader(self.train_arrays, **loader_kw)
        if is_primary_host():
            print(f"[data] batches from {type(self.loader).__name__}")
        self.steps_per_epoch = self.loader.steps_per_epoch
        self.total_steps = self.steps_per_epoch * cfg.epochs

    def _attach_completion_mask(self, val_rows, prompt_kw) -> None:
        """Add a ``completion_mask`` to the validation arrays: the loss mask
        restricted to assistant-answer tokens. The full-sequence ``eval_loss``
        (reference parity, ``training.py:282`` semantics) is dominated by the
        constant system prompt — near-zero values mostly measure prompt
        memorization — so the trainer additionally logs ``eval_loss_answer``
        computed over this mask in the same eval forward.

        Tokenization is identical to the main build (same rows, same
        tokenizer, same truncation), so under packing the deterministic
        packer produces the same row layout and the masks align."""
        cfg = self.config
        if cfg.completion_only_loss:
            return  # loss_mask already IS the completion span
        pipe = "pipe" in self.mesh.axis_names and self.mesh.shape["pipe"] > 1
        if pipe:
            return  # the pipeline eval step aggregates a single CE sum
        if cfg.packing:
            from llm_fine_tune_distributed_tpu.data.packing import (
                build_packed_sft_arrays,
            )

            masked = build_packed_sft_arrays(
                val_rows, self.tokenizer, cfg.max_seq_length, True, **prompt_kw
            )
        else:
            masked = build_sft_arrays(
                val_rows, self.tokenizer, cfg.max_seq_length, True, **prompt_kw
            )
        if masked["input_ids"].shape != self.val_arrays["input_ids"].shape:
            # explicit (not assert): the layout invariant guards eval-metric
            # correctness and must survive `python -O`
            raise ValueError(
                "completion-mask build produced a different layout than the "
                f"validation arrays (mask {masked['input_ids'].shape} vs val "
                f"{self.val_arrays['input_ids'].shape}) — the mask pass must "
                "tokenize/pack identically to the eval pass"
            )
        self.val_arrays["completion_mask"] = masked["loss_mask"]
        if masked["loss_mask"].sum() == 0 and is_primary_host():
            # This is a DATA bug worth shouting about: with the byte-level
            # test tokenizer the 1378-byte wilderness prompt alone exceeds
            # seq 1024, so every row truncates to the same prompt prefix and
            # the model never sees a single answer token — training "loss"
            # then measures memorization of one constant sequence. Fail loud
            # at prep time, not after 3 epochs.
            print(
                "WARNING: every validation completion was truncated away "
                f"(max_seq_length={cfg.max_seq_length} too small for the "
                "prompt) — the model will never train on answer tokens. "
                "Raise MAX_SEQ_LENGTH or shorten the system prompt."
            )

    # ----------------------------------------------------------------- state

    def _load_or_init_params(self):
        cfg, mc = self.config, self.model_config
        compute_dtype = str_to_dtype(cfg.compute_dtype)
        source = cfg.model_name
        if source and (os.path.isdir(source) or source.endswith(".safetensors")):
            if is_primary_host():
                print(f"Loading model weights from: {source}")
            return load_hf_checkpoint(source, mc, dtype=np.float32)
        if is_primary_host():
            print(
                f"No local checkpoint at {source!r}; random-initializing "
                f"{mc.name} ({mc.num_params:,} params)"
            )
        # Init directly at the target dtype when no full-precision master is
        # kept anyway: a 3B fp32 init (12.3 GB) plus its bf16 casts overflows
        # a 16 GB chip, and dense() draws in f32 before casting per-leaf, so
        # the values are bit-identical either way. QLoRA keeps the f32 init —
        # NF4 quantizes from full precision (see _prepare_state).
        init_dtype = jnp.float32
        if cfg.freeze_strategy != "qlora" and str_to_dtype(
            cfg.param_dtype
        ) is str_to_dtype(cfg.compute_dtype):
            init_dtype = str_to_dtype(cfg.param_dtype)
        return init_params(self.rng, mc, dtype=init_dtype)

    def _prepare_state(self) -> None:
        cfg = self.config
        with annotate("startup/weights"):  # load or init, freeze split, casts, shard
            trainable, frozen = self._sharded_params()
        with annotate("startup/optimizer"):
            self.optimizer = build_optimizer(
                cfg, None, total_steps=self.total_steps, data_parallel_size=self.dp_size
            )
            # Adam moments on their params' shardings, scalar leaves (the step
            # count) replicated: the whole state shares the mesh's device set
            opt_state = init_opt_state(self.optimizer, trainable, self.mesh)
            self.state = TrainState(
                # replicated over the mesh so restore() places it consistently
                step=jax.device_put(
                    jnp.zeros((), jnp.int32), NamedSharding(self.mesh, P())
                ),
                trainable=trainable,
                frozen=frozen,
                opt_state=opt_state,
            )
        self.lr_schedule = build_lr_schedule(cfg, self.total_steps, self.dp_size)

    def _sharded_params(self):
        """(trainable, frozen): the weights loaded or initialised, split by
        the freeze policy, cast to their master dtypes and put on the mesh."""
        cfg, mc = self.config, self.model_config
        params = self._load_or_init_params()
        if cfg.freeze_strategy in ("lora", "qlora"):
            # Attach adapters (A kaiming, B zero: step-0 model == base model);
            # only lora_a/lora_b train (parallel/freeze.py), so optimizer
            # state shrinks to the adapter footprint.
            from llm_fine_tune_distributed_tpu.parallel.lora import add_lora_from_config

            params = add_lora_from_config(params, self.rng, cfg)
        mask = trainable_mask(params, mc, cfg)
        self.trainable_report = describe_trainable(params, mask)
        if is_primary_host():
            r = self.trainable_report
            print(
                f"Trainable: {r['trainable_parameters']:,}/{r['total_parameters']:,} "
                f"({r['trainable_percent']}%)"
            )

        self._pipe_size = (
            self.mesh.shape["pipe"] if "pipe" in self.mesh.axis_names else 1
        )
        if self._pipe_size > 1:
            self._validate_pipeline_config()

        trainable, frozen = split_by_mask(params, mask)
        from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

        # kept for cross-layout checkpoint resume (train/layout.py): the
        # per-leaf mask decides flat-layout trainable membership
        self._flat_mask = flatten_dict(mask)
        # Frozen-trunk fast path (frozen_compute="int8"): the trainable
        # boundary is the earliest layer with any trainable leaf; layers
        # below it run w8a8 (models/transformer forward). 0 = no trunk —
        # lora/qlora/full fine-tuning resolve to 0 and change nothing.
        self._frozen_boundary = 0
        if getattr(cfg, "frozen_compute", "bf16") == "int8":
            if cfg.objective != "sft":
                raise ValueError(
                    "frozen_compute='int8' supports objective='sft' only "
                    "(the DPO forwards do not thread the trunk boundary)"
                )
            from llm_fine_tune_distributed_tpu.parallel.freeze import (
                frozen_trunk_boundary,
            )

            self._frozen_boundary = frozen_trunk_boundary(
                self._flat_mask, mc.num_layers
            )
        elif getattr(cfg, "frozen_compute", "bf16") != "bf16":
            raise ValueError(
                f"unknown frozen_compute {cfg.frozen_compute!r} "
                "(expected 'bf16' or 'int8')"
            )
        if self._pipe_size > 1:
            # Pipeline state representation: per-layer block leaves stacked
            # [num_layers, ...] and sharded over `pipe` (parallel/pipeline.py),
            # with the freeze policy expressed as a per-layer gradient mask.
            from llm_fine_tune_distributed_tpu.parallel.pipeline import (
                build_pipeline_state_leaves,
            )

            trainable, frozen, self._layer_vec = build_pipeline_state_leaves(
                trainable, frozen, self._flat_mask, mc.num_layers
            )
        del params
        param_dtype = str_to_dtype(cfg.param_dtype)
        compute_dtype = str_to_dtype(cfg.compute_dtype)
        # Master copies: trainable in f32, frozen in compute dtype (bf16) —
        # frozen params carry no optimizer state and need no f32 master.
        trainable = {k: jnp.asarray(v, param_dtype) for k, v in trainable.items()}
        if cfg.freeze_strategy == "qlora":
            # NF4-quantize the frozen block linears (from full precision —
            # quantizing an already-bf16 cast would double the rounding).
            # MoE models included: stacked [E, h, f] expert weights quantize
            # per-expert (ops/nf4.quantize_nf4_stacked).
            from llm_fine_tune_distributed_tpu.parallel.qlora import (
                quantize_frozen,
                quantized_fraction,
            )

            frozen = quantize_frozen(
                frozen, cfg.quant_block_size, cfg.quant_double_quant
            )
            if is_primary_host():
                print(
                    f"QLoRA: {100 * quantized_fraction(frozen):.1f}% of frozen "
                    f"bytes in NF4 (block {cfg.quant_block_size}, "
                    f"double_quant={cfg.quant_double_quant})"
                )
        if self._frozen_boundary > 0:
            # w8a8 trunk: serving int8 sibling layout from FULL precision —
            # before the bf16 cast, like the QLoRA path (parallel/freeze.py
            # owns the which-leaves rule, shared with bench.py)
            from llm_fine_tune_distributed_tpu.parallel.freeze import (
                quantize_trunk_int8,
            )

            frozen, n_quant = quantize_trunk_int8(frozen, self._frozen_boundary)
            if is_primary_host():
                print(
                    f"Frozen trunk: layers [0, {self._frozen_boundary}) run "
                    f"w8a8 int8 ({n_quant} projections quantized)"
                )
        frozen = {
            k: jnp.asarray(v, compute_dtype)
            # scales stay f32; packed codes / int8 + NF4 absmax scales keep
            # their dtype (kernel_int8_scale must NOT round-trip through bf16)
            if jnp.issubdtype(v.dtype, jnp.floating)
            and "absmax" not in k
            and not k.endswith("int8_scale")
            else jnp.asarray(v)
            for k, v in frozen.items()
        }

        # Shard onto the mesh per path rules.
        def put(flat):
            return {
                k: jax.device_put(
                    v,
                    NamedSharding(
                        self.mesh, self._validated_spec(k, v)
                    ),
                )
                for k, v in flat.items()
            }

        return put(trainable), put(frozen)

    def _validated_spec(self, path: str, leaf) -> P:
        from llm_fine_tune_distributed_tpu.parallel.sharding import _validate_spec

        if getattr(self, "_pipe_size", 1) > 1:
            from llm_fine_tune_distributed_tpu.parallel.pipeline import (
                pipeline_param_spec,
            )

            spec = pipeline_param_spec(path, leaf, self.mesh)
            return _validate_spec(spec, leaf.shape, self.mesh)
        return _validate_spec(param_spec(path, leaf.ndim), leaf.shape, self.mesh)

    def _validate_pipeline_config(self) -> None:
        from llm_fine_tune_distributed_tpu.parallel.pipeline import bubble_fraction, layer_scan_problems

        cfg, mc = self.config, self.model_config
        problems = []
        if cfg.packing:
            problems.append("packing=True (the schedule has no segment support)")
        if cfg.attention_impl in ("ring", "ulysses"):
            # both sequence-parallel impls compose: the schedule goes manual
            # over seq and stages call the LOCAL kernel (ring_manual /
            # ulysses_manual)
            seq_size = max(self.mesh.shape.get("seq", 1), 1)
            if cfg.max_seq_length % seq_size:
                problems.append(
                    f"max_seq_length={cfg.max_seq_length} not divisible by "
                    f"the seq axis ({seq_size})"
                )
            if cfg.attention_impl == "ulysses" and mc.num_kv_heads % seq_size:
                problems.append(
                    f"ulysses needs kv heads ({mc.num_kv_heads}) divisible "
                    f"by the seq axis ({seq_size})"
                )
        if cfg.objective not in ("sft", "dpo"):
            problems.append(f"objective={cfg.objective!r}")
        # what the layer scan asks of the MODEL is said once; the checks here are about the run
        problems.extend(layer_scan_problems(mc, cfg.attention_impl in ("ring", "ulysses")))
        if cfg.loss_vocab_chunk is not None:
            # the schedule's last stage computes CE via loss_chunk_size only
            # (parallel/pipeline.py) — rejecting beats silently materializing
            # the f32 logits the flag promises to avoid
            problems.append("loss_vocab_chunk (pipeline CE streams by sequence; "
                            "use loss_chunk_size)")
        if getattr(cfg, "frozen_compute", "bf16") == "int8":
            # the layer-scan treats every layer identically (stacked leaves,
            # layer_idx as data) — a per-layer w8a8/bf16 split needs the
            # unstacked forward
            problems.append("frozen_compute='int8' (the pipeline layer-scan "
                            "has no per-layer trunk split)")
        if mc.num_layers % self._pipe_size:
            problems.append(
                f"{mc.num_layers} layers not divisible by pipe={self._pipe_size}"
            )
        accum = cfg.gradient_accumulation_steps
        if accum < self._pipe_size:
            # legal but mostly bubble: (S-1)/(M+S-1) of every step idle
            print(
                f"[pipeline] grad_accum={accum} < pipe={self._pipe_size}: "
                f"bubble fraction {bubble_fraction(accum, self._pipe_size):.0%}"
                " — raise gradient_accumulation_steps for efficiency"
            )
        if problems:
            raise ValueError(
                "pipe mesh axis does not compose with: " + "; ".join(problems)
            )

    # ----------------------------------------------------------------- steps

    def _make_shardings(self) -> NamedSharding:
        """Set batch/eval shardings; return the activation sharding.

        Sequence parallelism: when a seq axis is live and a sequence-parallel
        attention impl ("ring" or "ulysses") is selected, activations and
        batches shard the sequence dim too — the ring
        (parallel/ring_attention.py) rotates K/V over that axis; ulysses
        (parallel/ulysses.py) re-partitions heads with all_to_all.
        Shared by the SFT and DPO step builders so the rules can't drift.
        """
        seq_sharded = (
            self.config.attention_impl in ("ring", "ulysses")
            and self.mesh.shape["seq"] > 1
        )
        # The seq axis may span process boundaries: processes sharing batch
        # rows each load the full rows (_process_batch_rows) and their
        # devices take sequence slices in _device_batch — long-context ring
        # attention across hosts rides DCN collectives.
        seq_ax = "seq" if seq_sharded else None
        act = NamedSharding(self.mesh, P(("data", "fsdp"), seq_ax, None))
        self._batch_sharding = NamedSharding(self.mesh, P(None, ("data", "fsdp"), seq_ax))
        self._eval_sharding = NamedSharding(self.mesh, P(("data", "fsdp"), seq_ax))
        return act

    def _tokens_per_sample(self) -> int:
        """Data tokens one 'sample' consumes (DPO overrides: a pair is 2 seqs)."""
        return self.config.max_seq_length

    def _resolved_quant_impl(self) -> str:
        """NF4 matmuls take the XLA dequant path on every mesh (the fused
        Pallas kernel was retired after losing the v5e shootout —
        ops/nf4.nf4_matmul docstring; 4-bit at rest in HBM either way)."""
        return self.config.quant_matmul_impl

    def _rematted_layers(self) -> range:
        """The blocks whose ``jax.checkpoint`` has a policy
        (models/transformer._remat_policy). The pipeline schedule wraps its
        blocks without one and the int8 trunk is not rematerialized."""
        if not self.config.gradient_checkpointing or self._pipe_size > 1:
            return range(0)
        return range(self._frozen_boundary, self.model_config.num_layers)

    def _layers_keeping_flash_outputs(self) -> int:
        """How many blocks of the step keep the flash forward kernel's output
        and row statistics across their remat boundary: a static fact of the
        step, from its shapes."""
        mc, seq = self.model_config, self.config.max_seq_length
        return sum(keeps_flash_outputs(mc, seq, mc.layer(i).window) for i in self._rematted_layers())

    def _layers_keeping_routing(self) -> int:
        """How many keep an expert layer's routing and gathered rows
        (ops/moe.KEPT_ACROSS_REMAT): from the layers' kinds."""
        return sum(self.model_config.layer(i).feed_forward == "grouped_experts" for i in self._rematted_layers())

    def _prepare_steps(self) -> None:
        act = self._make_shardings()
        # Every jitted entry point registers with the compile ledger so a
        # shape drift mid-run (a loader emitting an off-bucket batch, an
        # eval slab reshaped) shows up as recompiles_after_warmup in the
        # step logs instead of an unexplained stall. aot=False: train_step
        # donates its state, so an AOT re-execute of the first call is
        # forbidden — first-call wall timing only.
        self.compile_ledger = CompileLedger()
        if self._pipe_size > 1:
            from llm_fine_tune_distributed_tpu.parallel.pipeline import (
                build_pipeline_eval_step,
                build_pipeline_train_step,
            )

            self.train_step = instrument(
                "train_step",
                jit_train_step(
                    build_pipeline_train_step(
                        self.model_config, self.config, self.optimizer,
                        self.mesh, self._layer_vec,
                    ),
                    mesh=self.mesh,
                ),
                self.compile_ledger,
                aot=False,
            )
            self._eval_step_fn = build_pipeline_eval_step(
                self.model_config, self.config, self.mesh
            )
        else:
            quant_impl = self._resolved_quant_impl()
            frozen_layers = getattr(self, "_frozen_boundary", 0)
            train_step = build_train_step(
                self.model_config, self.config, self.optimizer,
                activation_sharding=act, quant_impl=quant_impl,
                frozen_layers=frozen_layers,
            )
            self.train_step = instrument(
                "train_step", jit_train_step(train_step, mesh=self.mesh),
                self.compile_ledger, aot=False,
            )
            self._eval_step_fn = build_eval_step(
                self.model_config, self.config, activation_sharding=act,
                quant_impl=quant_impl, frozen_layers=frozen_layers,
            )
        self.eval_step = instrument(
            "eval_step", jax.jit(self._eval_step_fn),
            self.compile_ledger, aot=False,
        )

        def eval_all(state, staged):
            """Summed eval-step outputs over every staged eval batch in ONE
            XLA program: a lax.scan over [nb, bs, seq] slabs. One dispatch +
            one host sync per eval instead of one per batch; the per-batch
            compute is the same dp-sharded eval step. The tuple is
            (ce_sum, tokens) or (ce_sum, tokens, answer_ce_sum,
            answer_tokens) depending on whether the staged arrays carry a
            completion_mask (static per compile)."""
            def body(carry, batch):
                out = self._eval_step_fn(state, batch)
                return tuple(c + o for c, o in zip(carry, out)), None

            width = 4 if "completion_mask" in staged else 2
            init = tuple(jnp.float32(0.0) for _ in range(width))
            sums, _ = jax.lax.scan(body, init, staged)
            return sums

        self._eval_all = instrument(
            "eval_all", jax.jit(eval_all), self.compile_ledger, aot=False,
        )
        self._staged_eval = None

    def _device_batch(
        self, batch: Dict[str, np.ndarray], sharding, local_shards: bool = False
    ) -> Dict[str, jax.Array]:
        # "lengths" never reaches here: the loader strips it before yielding.
        #
        # Two multi-process cases:
        # - local_shards=True (training): each process holds the global batch
        #   ROWS its devices need (data/loader.py row_start/row_count —
        #   disjoint columns for plain dp meshes, shared rows when a seq axis
        #   spans processes), host-complete along the sequence. Each device's
        #   (row, seq) block is served from that local slab by callback.
        # - local_shards=False (eval): every process builds the identical full
        #   batch, and device_put's global semantics take each host's shard.
        if local_shards and jax.process_count() > 1:
            B = self.config.per_device_batch_size * self.dp_size
            row_lo = getattr(self, "_row_start", 0)

            def make(v):
                gshape = (v.shape[0], B, *v.shape[2:])

                def cb(index):
                    row_sl = index[1]
                    start = row_sl.start or 0
                    stop = B if row_sl.stop is None else row_sl.stop
                    if not (row_lo <= start and stop <= row_lo + v.shape[1]):
                        raise ValueError(
                            f"device requests batch rows [{start}, {stop}) but "
                            f"this process loaded [{row_lo}, {row_lo + v.shape[1]})"
                            " — mesh/loader row layout mismatch"
                        )
                    local = (index[0], slice(start - row_lo, stop - row_lo), *index[2:])
                    return v[local]

                return jax.make_array_from_callback(gshape, sharding, cb)

            return {k: make(v) for k, v in batch.items()}
        return {k: jax.device_put(v, sharding) for k, v in batch.items()}

    # ------------------------------------------------------------------ eval

    # keep eval slabs device-resident only up to this size; larger validation
    # sets stream batch-by-batch through eval_step instead
    _EVAL_STAGE_BYTES = 256 * 1024 * 1024

    @staticmethod
    def _pad_eval_rows(key: str, arr: np.ndarray, pad_rows: int) -> np.ndarray:
        """Append pad rows to one eval array. Padded rows carry zero
        loss_mask so they contribute no tokens to the token-weighted loss,
        but must not produce fully-masked attention rows: attention_mask is
        set real, and (packing) segment_ids nonzero so each pad token still
        attends to itself. Single source for the staged and streaming eval
        paths."""
        if pad_rows <= 0:
            return arr
        pad_block = np.zeros((pad_rows,) + arr.shape[1:], arr.dtype)
        if key in ("attention_mask", "segment_ids"):
            pad_block[:] = 1
        return np.concatenate([arr, pad_block])

    def _eval_global_batch_size(self) -> int:
        """Global eval batch: eval_batch_size (per device; forward-only eval
        fits far larger batches than training) or the
        training microbatch size, x the data-parallel degree."""
        cfg = self.config
        return (cfg.eval_batch_size or cfg.per_device_batch_size) * self.dp_size

    def _stage_eval_batches(self):
        """Pad + reshape the validation arrays into device-resident
        [nb, bs, seq] slabs, sharded like training batches (batch dim over
        data x fsdp). Built once; every eval after the first is a single
        dispatch with zero host-side array work."""
        bs = self._eval_global_batch_size()
        n = self.val_arrays["input_ids"].shape[0]
        nb = -(-n // bs)
        staged = {
            k: self._pad_eval_rows(k, v, nb * bs - n).reshape((nb, bs) + v.shape[1:])
            for k, v in self.val_arrays.items()
            if k != "lengths"
        }
        return {
            k: jax.device_put(v, self._batch_sharding) for k, v in staged.items()
        }

    def evaluate(self) -> float:
        """Token-weighted eval loss over the validation split
        (eval cadence contract: reference ``training.py:270-271``).

        Also computes the answer-only metric (``eval_loss_answer``) from the same forward when the validation arrays
        carry a completion_mask; it is stashed on ``self._last_eval_answer``
        and logged beside eval_loss — the RETURNED value stays the
        full-sequence loss (the reference-parity best-model metric).

        Distributed: the validation batch dim is sharded over the
        data-parallel axes exactly like a training batch, so per-device work
        is ~1/dp of the set (pinned by tests/test_distributed_eval.py), and
        XLA inserts the (ce_sum, token_count) psum. The whole sweep compiles
        to one scan program with a single host sync per eval."""
        bs = self._eval_global_batch_size()
        n = self.val_arrays["input_ids"].shape[0]
        self._last_eval_answer = None
        if n == 0:
            return float("nan")
        staged_bytes = sum(
            v.nbytes for k, v in self.val_arrays.items() if k != "lengths"
        )
        if staged_bytes <= self._EVAL_STAGE_BYTES:
            if self._staged_eval is None:
                self._staged_eval = self._stage_eval_batches()
            sums = [float(x) for x in self._eval_all(self.state, self._staged_eval)]
        else:
            # very large validation sets: stream host->device batch by batch
            sums = None
            for lo in range(0, n, bs):
                batch = {
                    k: v[lo : lo + bs]
                    for k, v in self.val_arrays.items()
                    if k != "lengths"
                }
                short = bs - batch["input_ids"].shape[0]
                if short > 0:
                    batch = {
                        k: self._pad_eval_rows(k, v, short) for k, v in batch.items()
                    }
                batch = self._device_batch(batch, self._eval_sharding)
                out = self.eval_step(self.state, batch)
                if sums is None:
                    sums = [0.0] * len(out)
                for i, x in enumerate(out):
                    sums[i] += float(x)
        if len(sums) == 4 and sums[3] > 0:
            # ans_tokens == 0 means every completion truncated away (see
            # _attach_completion_mask's warning) — a 0/1 "loss" would read
            # as perfect; suppress the metric instead
            self._last_eval_answer = sums[2] / sums[3]
        return sums[0] / max(sums[1], 1.0)

    # ------------------------------------------------------------------ train

    def _ckpt_save(self, ckpt: CheckpointManager, step: int, metrics) -> None:
        """One save-call shape for the loop and the final save: trainable-only
        payload + frozen fingerprint when configured, background snapshot
        save on single-process runs (the next train step
        must not block on the device->host checkpoint stream)."""
        fp = None
        if ckpt.trainable_only or self.config.publish_dir:
            if not hasattr(self, "_frozen_fp"):
                from llm_fine_tune_distributed_tpu.train.checkpoints import (
                    frozen_fingerprint,
                )

                self._frozen_fp = frozen_fingerprint(self.state.frozen)
            fp = self._frozen_fp
        ckpt.save(
            step,
            self.state,
            metrics=metrics,
            fingerprint=fp if ckpt.trainable_only else None,
            snapshot_async=self.config.checkpoint_async_snapshot,
        )
        self._publish(step, fp, metrics)

    def _publish(self, step: int, fp, metrics) -> None:
        """Live deployment (train/publish.py): drop the trainable weights +
        manifest into the publish dir a serving fleet hot-swaps from
        (infer/deploy.py). Process 0 only — one publisher per run, and the
        payload is the replicated trainable masters. Publish failures are
        logged, never fatal: deployment lag must not kill the fine-tune."""
        if not self.config.publish_dir or jax.process_index() != 0:
            return
        # anomaly gate: stamp (or enforce) trailing-window cleanliness so
        # the serving side never unknowingly promotes a checkpoint cut
        # mid-divergence (NaN loss, grad explosion)
        clean = self.telemetry.publish_clean(step)
        if not clean and self.config.publish_require_clean:
            self.telemetry.note_publish(step, clean=False, skipped=True)
            print(
                f"[train] skipping publish for step {step}: anomaly window "
                "dirty and publish_require_clean is set",
                flush=True,
            )
            return
        if self._publisher is None:
            from llm_fine_tune_distributed_tpu.train.publish import (
                CheckpointPublisher,
            )

            self._publisher = CheckpointPublisher(
                self.config.publish_dir,
                keep_last=self.config.publish_keep_last,
            )
        try:
            self._publisher.publish(
                step,
                self.state.trainable,
                frozen_fp=fp,
                metrics=metrics,
                run_id=self.telemetry.run_id,
                hparams_digest=self.telemetry.hparams_digest,
                anomaly_clean=clean,
            )
            self.telemetry.note_publish(step, clean=clean)
        except Exception as e:  # noqa: BLE001 — advisory side channel
            print(
                f"[train] checkpoint publish for step {step} failed: {e}",
                flush=True,
            )

    def _resolve_best_mode(self) -> str:
        cfg = self.config
        mode = cfg.best_model_tracking
        if mode == "auto":
            trainable_bytes = sum(v.nbytes for v in self.state.trainable.values())
            mode = "per_eval" if trainable_bytes < 512 * 1024**2 else "checkpoint"
        elif mode not in ("per_eval", "checkpoint"):
            raise ValueError(f"unknown best_model_tracking {mode!r}")
        if (
            mode == "checkpoint"
            and cfg.load_best_model_at_end
            and cfg.eval_steps
            and cfg.save_steps
            and cfg.save_steps % cfg.eval_steps != 0
            # only MID-RUN saves can carry a stale metric: the end-of-train
            # save runs right after the final eval (reference save_steps=500
            # with ~48 total steps was exactly this shape)
            and cfg.save_steps <= self.total_steps
        ):
            # checkpoint-mode best selection stamps each save with the LAST
            # eval's metric; an unaligned cadence would credit step-N weights
            # with an older eval and restore the wrong weights (HF requires
            # the same alignment for load_best_model_at_end). Fail at start,
            # not after the run.
            raise ValueError(
                f"best_model_tracking='checkpoint' needs save_steps "
                f"({cfg.save_steps}) to be a multiple of eval_steps "
                f"({cfg.eval_steps}) so every saved checkpoint carries a "
                "fresh metric — align the cadences or use "
                "best_model_tracking='per_eval'"
            )
        if (
            mode == "checkpoint"
            and cfg.load_best_model_at_end
            and cfg.save_steps
            and cfg.save_steps > self.total_steps
            and is_primary_host()
        ):
            # no mid-run checkpoint ever happens, so the only candidate for
            # "best" is the end-of-train save: selection silently degrades to
            # final weights. Legal, but say so up front.
            print(
                f"WARNING: best_model_tracking='checkpoint' with save_steps="
                f"{cfg.save_steps} > total_steps={self.total_steps}: only the "
                "end-of-training checkpoint will exist, so "
                "load_best_model_at_end degrades to final-weights-only — "
                "lower save_steps (or use best_model_tracking='per_eval') to "
                "track a real best"
            )
        return mode

    def request_preemption(self) -> None:
        """Ask the training loop to stop at the next step boundary, write an
        emergency checkpoint, and return cleanly (exit 0 for the CLI). The
        SIGTERM handler installed by ``train`` calls this; tests and
        embedding processes may call it directly from any thread."""
        if not self._preempt.is_set():
            self.telemetry.recorder.record("preemption_requested")
            self.telemetry.update(preempted=True)
        self._preempt.set()

    def train(self) -> Dict[str, Any]:
        cfg = self.config
        ckpt_dir = os.path.join(cfg.output_dir, "checkpoints")
        ckpt = CheckpointManager(
            ckpt_dir,
            max_to_keep=cfg.save_total_limit,
            metric_name=cfg.metric_for_best_model,
            greater_is_better=cfg.greater_is_better,
            trainable_only=cfg.checkpoint_trainable_only,
        )

        resumed_step = 0
        if cfg.resume_from_checkpoint:
            with annotate("startup/restore"):
                resumed_step = self._resume(ckpt)
        start_epoch = resumed_step // self.steps_per_epoch
        # Mid-epoch resume: skip the batches this epoch already consumed
        # (loader epochs are deterministic) so no sample trains twice and the
        # lr schedule ends exactly at total_steps.
        skip_batches = resumed_step % self.steps_per_epoch

        best_eval = float("inf") if not cfg.greater_is_better else -float("inf")
        best_trainable = None
        best_mode = self._resolve_best_mode()
        last_eval: Optional[float] = None
        meter = ThroughputMeter(
            n_chips=self.mesh.size, tokens_per_sample=self._tokens_per_sample()
        )
        samples_per_step = cfg.per_device_batch_size * cfg.gradient_accumulation_steps * self.dp_size

        if is_primary_host():
            print(
                f"Starting SFT: {cfg.epochs} epochs x {self.steps_per_epoch} steps, "
                f"effective batch {samples_per_step}, mesh {dict(self.mesh.shape)}"
            )

        # Failure detection (native/heartbeat.cc): auto-on for multi-host runs
        # so a wedged peer is detected instead of hanging in a collective.
        detector = None
        if cfg.heartbeat or jax.process_count() > 1:
            try:
                from llm_fine_tune_distributed_tpu.runtime.failure import FailureDetector

                coordinator = os.environ.get("MASTER_ADDR", "127.0.0.1")
                detector = FailureDetector(
                    rank=jax.process_index(),
                    world_size=jax.process_count(),
                    coordinator_host=coordinator,
                    port=cfg.heartbeat_port,
                    timeout_ms=cfg.heartbeat_timeout_ms,
                )
            except RuntimeError as e:
                if is_primary_host():
                    print(f"[runtime] heartbeat unavailable: {e}")
        from llm_fine_tune_distributed_tpu.observe.profiler import (
            StepProfiler,
            device_memory_report,
        )
        from llm_fine_tune_distributed_tpu.runtime.desync import DesyncMonitor

        desync = DesyncMonitor(cfg.desync_check_steps)
        profiler = StepProfiler(cfg.profile_dir, recorder=self.telemetry.recorder)
        # wedged-link detector (runtime/watchdog.py): a dead device link
        # under a single-process run otherwise hangs forever with a
        # healthy-looking process
        watchdog = None
        if cfg.watchdog_timeout_s > 0:
            from llm_fine_tune_distributed_tpu.runtime.watchdog import StepWatchdog

            # start_paused: the first arm happens at the first step's poke,
            # so resume fast-forward + first-step compile can't false-trip
            watchdog = StepWatchdog(
                cfg.watchdog_timeout_s,
                cfg.watchdog_action,
                start_paused=True,
                recorder=self.telemetry.recorder,
            )

        # Preemption safety (k8s node drain / spot reclaim): SIGTERM sets a
        # flag the loop checks at the step boundary — emergency checkpoint,
        # clean exit 0, and the JobSet restart resumes from it instead of
        # replaying up to save_steps of work. Signal handlers can only be
        # installed on the main thread; elsewhere (tests, embedding servers)
        # request_preemption() is the entry point.
        prev_sigterm = None
        if threading.current_thread() is threading.main_thread():
            def _on_sigterm(signum, frame):
                if not self._preempt.is_set() and is_primary_host():
                    print(
                        "[train] SIGTERM: checkpointing at the next step "
                        "boundary, then exiting for restart+resume",
                        flush=True,
                    )
                self.request_preemption()

            prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)

        t_start = time.perf_counter()
        step = int(self.state.step)
        final_loss = None
        preempted = False
        pending_samples, synced_step = 0, step
        pending_real_tokens = 0

        # Per-step phase timing into the serving stack's mergeable histogram
        # (observe/tracing.Histogram): where does a step's wall clock go —
        # waiting on the loader, the step itself, or checkpoint IO? Note the
        # step phase measures HOST-side dispatch under async dispatch; the
        # steps that land on a log/eval/save boundary include the
        # block_until_ready and so bound the true device time (the p99).
        phase_hist = {
            "data_wait": Histogram.exponential(),
            "step": Histogram.exponential(),
            "checkpoint": Histogram.exponential(),
        }

        # Training control plane (observe/trainplane.py): live /metrics +
        # /v1/train/status + flight recorder over this run's telemetry,
        # primary host only. The telemetry itself is fed strictly inside
        # the do_log/do_eval/do_save branches below (already synced) —
        # nothing extra rides the per-step path.
        self.telemetry.attach(
            phase_hist=phase_hist, compile_ledger=self.compile_ledger
        )
        self.telemetry.update(
            total_steps=self.total_steps,
            epochs=cfg.epochs,
            step=step,
            state="training",
        )
        plane = None
        if cfg.train_port is not None:
            plane = TrainControlPlane(
                self.telemetry, cfg.train_port, profile_dir=cfg.profile_dir
            )
            if plane.start():
                print(
                    f"[train] control plane listening on :{plane.port}",
                    flush=True,
                )
        self.train_plane = plane  # tests/benches read the bound port

        def _timed_batches(it):
            it = iter(it)
            while True:
                t0 = time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    return
                phase_hist["data_wait"].observe(time.perf_counter() - t0)
                yield batch

        # open until the first step's call has returned (first batch, the step
        # program's load, its first dispatch: the device's time is not waited
        # for); the steps after it get no span
        first_step = annotate("startup/first_step")
        first_step.__enter__()
        try:
            for epoch in range(start_epoch, cfg.epochs):
                batches = self.loader.epoch(epoch)
                if epoch == start_epoch and skip_batches:
                    import itertools

                    batches = itertools.islice(batches, skip_batches, None)
                for batch in _timed_batches(batches):
                    dev_batch = self._device_batch(
                        batch, self._batch_sharding, local_shards=True
                    )
                    t_step = time.perf_counter()
                    try:
                        self.state, metrics = self.train_step(self.state, dev_batch)
                    except jax.errors.JaxRuntimeError as e:
                        if "RESOURCE_EXHAUSTED" in str(e) and is_primary_host():
                            # the compiler's own report follows in the
                            # traceback; say first what it was asked for
                            print(
                                f"[train] REFUSED: the step program does not fit "
                                f"the device on mesh {dict(self.mesh.shape)} with "
                                f"per_device_batch_size={cfg.per_device_batch_size}, "
                                f"remat_policy={cfg.remat_policy!r} "
                                f"({self._layers_keeping_flash_outputs()} of "
                                f"{self.model_config.num_layers} layers also keep "
                                f"the flash kernel's outputs, "
                                f"{self._layers_keeping_routing()} their experts' "
                                f"routing and gathered rows), "
                                f"loss_chunk_size={cfg.loss_chunk_size}: "
                                f"{str(e).splitlines()[0][:300]}",
                                flush=True,
                            )
                        raise
                    step += 1
                    if step == resumed_step + 1:
                        # the first step is on its way: start-up has its phases
                        first_step.__exit__(None, None, None)
                        first_step = None
                        startup = self.compile_ledger.setup_phases()
                        self.telemetry.update(startup=startup)
                        if is_primary_host():
                            print(
                                "[train] start-up: "
                                + ", ".join(f"{k} {v:.1f} s" for k, v in startup["phases_s"].items())
                                + f"; {startup['since_process_start_s']:.1f} s since the process started; "
                                f"compile cache: {startup['cache_hits']} hits, {startup['cache_misses']} "
                                f"misses (JAX counts the entries it writes), {startup['compile_requests_use_cache']} requests",
                                flush=True,
                            )
                            # the step program is traced now: say which attention
                            # path it holds (a flash request that took XLA
                            # attention names its reason) and on what it runs
                            from llm_fine_tune_distributed_tpu.models import transformer
                            from llm_fine_tune_distributed_tpu.ops import gated_delta, moe, ssd
                            from llm_fine_tune_distributed_tpu.ops import eva_attention
                            from llm_fine_tune_distributed_tpu.ops import rope as rope_ops
                            from llm_fine_tune_distributed_tpu.ops.attention import (
                                dispatch_summary,
                            )

                            print(
                                f"[train] step program traced on "
                                f"{jax.default_backend()}; {dispatch_summary()}",
                                flush=True,
                            )
                            if moe.SUM_PROGRAMS:  # routed experts: kernel or loop, and why
                                print(f"[train] {moe.sum_programs_summary()}", flush=True)
                            if gated_delta.CALLS:  # linear-attention layers: the form their rule took
                                print(f"[train] {gated_delta.calls_summary()}", flush=True)
                            if ssd.CALLS:  # state-space layers: the sweeps or the XLA form, and why
                                print(f"[train] {ssd.calls_summary()}", flush=True)
                            if eva_attention.CALLS:  # EVA layers: the kernels or the XLA form, and why
                                print(f"[train] {eva_attention.calls_summary()}", flush=True)
                            if rope_ops.CALLS:  # layers of heads: the fused IN pass or the XLA form, and why
                                print(f"[train] {rope_ops.calls_summary()}", flush=True)
                            if transformer.REMAT_KEEPS:  # which named values each kind of block kept across its remat
                                print(f"[train] {transformer.remat_summary()}", flush=True)
                    pending_samples += samples_per_step
                    # real-token accounting for the throughput meter: a host
                    # numpy mean over the loader's (pre-device) mask — cheap
                    # next to the step, never touches device buffers, and
                    # scaling the mean to the GLOBAL token count keeps the
                    # figure right under multi-host local-shard loading
                    am = batch.get("attention_mask")
                    if am is not None and meter.tokens_per_sample:
                        pending_real_tokens += int(
                            float(np.mean(am))
                            * samples_per_step
                            * meter.tokens_per_sample
                        )
                    if watchdog is not None:
                        watchdog.poke(step)
                    if self._preempt.is_set():
                        # SIGTERM landed: stop HERE, at a step boundary, where
                        # the state is a consistent (params, opt, step) triple
                        preempted = True
                        break

                    do_log = (
                        (cfg.logging_first_step and step == 1)
                        or (cfg.logging_steps and step % cfg.logging_steps == 0)
                    )
                    do_eval = cfg.eval_steps and step % cfg.eval_steps == 0 and self.n_val > 0
                    do_save = cfg.save_steps and step % cfg.save_steps == 0

                    # Host sync only at meter/log boundaries: under async
                    # dispatch the step returns at ENQUEUE time, so stamping
                    # the meter needs a device sync — but syncing EVERY step
                    # stops the host from preparing the next batch while the
                    # device runs (ADVICE r1). The meter's window stores
                    # cumulative samples, so multi-step intervals measure
                    # correct rates.
                    if do_log or do_eval or do_save:
                        jax.block_until_ready(metrics["loss"])
                        meter.update(
                            pending_samples,
                            steps=step - synced_step,
                            real_tokens=pending_real_tokens,
                        )
                        pending_samples, synced_step = 0, step
                        pending_real_tokens = 0
                    phase_hist["step"].observe(time.perf_counter() - t_step)
                    profiler.step(step)

                    desync.maybe_check(step, self.state.trainable)
                    if detector is not None and not detector.all_alive():
                        dead = detector.dead_ranks()
                        # Fail fast so the job manager restarts the fleet and
                        # resumes from the last periodic checkpoint. No save
                        # here: a sharded Orbax save needs EVERY host to
                        # participate, and with a peer dead it would hang —
                        # the exact collective-timeout limbo this detector
                        # exists to avoid.
                        raise RuntimeError(
                            f"hosts {dead} stopped heartbeating at step {step}; "
                            "aborting for restart+resume"
                        )

                    if do_eval:
                        if watchdog is not None:
                            # an eval sweep has no loop pokes; a legitimately
                            # slow one must not abort a healthy run
                            watchdog.pause()
                        last_eval = self.evaluate()
                        improved = (
                            last_eval > best_eval if cfg.greater_is_better else last_eval < best_eval
                        )
                        if improved:
                            best_eval = last_eval
                            if cfg.load_best_model_at_end and best_mode == "per_eval":
                                # ON-DEVICE snapshot (device-side copy, no
                                # host sync and no host fetch at every
                                # eval improvement). HBM cost is one trainable copy; big
                                # trainable sets run best_mode="checkpoint"
                                # instead (see _resolve_best_mode), which the
                                # flagship needs: the extra 0.84 GB copy
                                # OOM'd a 16 GB chip mid-run.
                                best_trainable = jax.tree.map(
                                    jnp.copy, self.state.trainable
                                )

                    if do_log or do_eval:
                        final_loss = float(metrics["loss"])
                        logs = {
                            "loss": final_loss,
                            "learning_rate": float(self.lr_schedule(step - 1)),
                            **meter.snapshot(),
                        }
                        # every scalar the step emits (grad_norm always;
                        # rewards_* for DPO) rides into the metric sinks
                        for k, v in metrics.items():
                            if k != "loss" and getattr(v, "ndim", 0) == 0:
                                logs[k] = float(v)
                            elif getattr(v, "ndim", 0) == 1:  # expert_load: one entry a held expert
                                logs.update({f"{k}_{i}": float(x) for i, x in enumerate(v)})
                        if do_eval:
                            logs["eval_loss"] = last_eval
                            if getattr(self, "_last_eval_answer", None) is not None:
                                logs["eval_loss_answer"] = self._last_eval_answer
                            logs.update(self.extra_eval_logs)
                        # phase-timing percentiles into the three sinks —
                        # the per-step analog of /v1/stats histograms
                        for pname, ph in phase_hist.items():
                            psum = ph.summary()
                            if psum["count"]:
                                logs[f"phase_{pname}_p50_s"] = round(psum["p50"], 6)
                                logs[f"phase_{pname}_p99_s"] = round(psum["p99"], 6)
                        # compile ledger totals: total_compiles should go
                        # flat after the first eval boundary; a nonzero
                        # recompiles_after_warmup means a shape drifted
                        # mid-run (off-bucket batch, reshaped eval slab)
                        csnap = self.compile_ledger.snapshot()
                        logs["compile_total"] = csnap["total_compiles"]
                        logs["compile_s_total"] = csnap["total_compile_s"]
                        logs["recompiles_after_warmup"] = csnap[
                            "recompiles_after_warmup"
                        ]
                        if not self.compile_ledger.warmed and (
                            do_eval or not (cfg.eval_steps and self.n_val > 0)
                        ):
                            # warm boundary: the first eval has compiled the
                            # eval programs too (or no eval will ever run)
                            self.compile_ledger.mark_warm()
                        if is_primary_host():
                            mem = device_memory_report()
                            if mem:
                                # summed across local devices; empty on
                                # backends without memory_stats (CPU)
                                logs["hbm_bytes_in_use"] = sum(
                                    d["bytes_in_use"] or 0 for d in mem.values()
                                )
                                logs["hbm_peak_bytes_in_use"] = sum(
                                    d["peak_bytes_in_use"] or 0
                                    for d in mem.values()
                                )
                        self.metrics.log(step, step / self.steps_per_epoch, logs)
                        # control plane + sentinels consume the SAME
                        # already-synced host floats — no extra device sync
                        self.telemetry.on_step(step, logs)
                        self.telemetry.update(
                            epoch=round(step / self.steps_per_epoch, 4)
                        )
                        if do_eval and last_eval == best_eval:
                            self.telemetry.update(best_eval=best_eval)
                        if watchdog is not None:
                            self.telemetry.set_counter(
                                "watchdog_trips", watchdog.trips
                            )

                    if do_save:
                        if watchdog is not None:
                            # sync saves legitimately take minutes on slow
                            # links — IO progress, not a wedge; the NEXT
                            # step's poke re-arms
                            watchdog.pause()
                        t_ckpt = time.perf_counter()
                        self._ckpt_save(ckpt, step, {cfg.metric_for_best_model: last_eval} if last_eval is not None else None)
                        ckpt_s = time.perf_counter() - t_ckpt
                        phase_hist["checkpoint"].observe(ckpt_s)
                        self.telemetry.note_checkpoint(step, ckpt_s)
                    if do_eval or do_save:
                        # eval sweeps / checkpoint saves must not count
                        # against the NEXT steady-state interval (the
                        # cumulative rate still includes them)
                        meter.rebase()
                        # crash-safe history: atomic flush at every
                        # eval/checkpoint boundary so a preempted or killed
                        # run keeps everything up to here
                        self.metrics.save_history(
                            os.path.join(cfg.output_dir, "training_history.json")
                        )
                if preempted:
                    break
        finally:
            if first_step is not None:  # no step ran, or the first one raised
                first_step.__exit__(None, None, None)
            profiler.close()
            if detector is not None:
                detector.stop()
            if watchdog is not None:
                # end-of-run legs (final save, export) are long host-side IO
                # with no loop pokes — stop outright (also frees the thread;
                # repeated train() calls in one process must not accumulate
                # pollers)
                watchdog.stop()
            if prev_sigterm is not None:
                signal.signal(signal.SIGTERM, prev_sigterm)

        if preempted:
            # Emergency checkpoint, then get out: the periodic cadence may be
            # up to save_steps-1 steps stale, and the whole point of catching
            # SIGTERM is to resume from HERE. Skip final eval / best-model
            # restore / artifact export — the restarted run finishes those.
            if ckpt.latest_step != step:
                self._ckpt_save(
                    ckpt,
                    step,
                    {cfg.metric_for_best_model: last_eval}
                    if last_eval is not None
                    else None,
                )
            ckpt.wait()
            wall = time.perf_counter() - t_start
            if is_primary_host():
                print(
                    f"[train] preempted at step {step}: emergency checkpoint "
                    "saved; exiting cleanly for restart+resume",
                    flush=True,
                )
            self.telemetry.update(state="preempted", step=step)
            self.telemetry.recorder.record("emergency_checkpoint", step=step)
            self.metrics.save_history(
                os.path.join(cfg.output_dir, "training_history.json")
            )
            if plane is not None:
                plane.stop()
            ckpt.close()
            self.metrics.close()
            return {
                "preempted": True,
                "step": step,
                "final_train_loss": final_loss,
                "final_eval_loss": last_eval,
                "wall_clock_seconds": wall,
            }

        # end of training: final checkpoint + optional best-model restore.
        # Refresh the metric when the final step is not an eval boundary:
        # checkpoint-mode best selection stamps the final save with
        # last_eval, and a stale value would credit the final weights with
        # an OLDER eval (r5 review finding) — the same staleness the
        # mid-run cadence guard rules out.
        final_eval_stale = (
            cfg.load_best_model_at_end
            and best_mode == "checkpoint"
            and cfg.eval_steps
            and step % cfg.eval_steps != 0
        )
        if (last_eval is None or final_eval_stale) and self.n_val > 0:
            last_eval = self.evaluate()
            if cfg.load_best_model_at_end and (
                last_eval < best_eval if not cfg.greater_is_better else last_eval > best_eval
            ):
                best_eval = last_eval
                best_trainable = None  # current state IS best
        self._ckpt_save(ckpt, step, {cfg.metric_for_best_model: last_eval} if last_eval is not None else None)
        ckpt.wait()

        if cfg.load_best_model_at_end and best_trainable is not None:
            # reload best-eval weights (reference load_best_model_at_end,
            # training.py:273-275)
            self.state = self.state.replace(
                trainable={
                    k: jax.device_put(v, self.state.trainable[k].sharding)
                    for k, v in best_trainable.items()
                }
            )
        elif cfg.load_best_model_at_end and best_mode == "checkpoint":
            # best among SAVED checkpoints (HF's save-aligned semantics): a
            # disk restore only when the final step is not already the best,
            # so the common descending-loss run pays nothing
            bstep = ckpt.best_step
            if bstep is not None and bstep != step:
                abstract = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=x.sharding
                    ),
                    self.state,
                )
                if ckpt.trainable_only:
                    abstract = abstract.replace(frozen=self.state.frozen)
                best_state = ckpt.restore(bstep, abstract)
                self.state = self.state.replace(trainable=best_state.trainable)
                if is_primary_host():
                    print(
                        f"Restored best checkpoint step {bstep} "
                        f"({cfg.metric_for_best_model} tracking, "
                        "best_model_tracking=checkpoint)"
                    )

        if pending_samples:
            # steps since the last log boundary: the trailing steps may still
            # be in flight (the final ckpt.save enqueues an async copy), so
            # sync before stamping or the final interval reads short
            jax.block_until_ready(self.state.step)
            meter.update(pending_samples, steps=step - synced_step)
        wall = time.perf_counter() - t_start
        throughput = meter.snapshot()
        self.telemetry.update(state="completed", step=step)
        summary = self._save_artifacts(final_loss, last_eval, wall, throughput)
        if plane is not None:
            plane.stop()
        ckpt.close()
        self.metrics.close()
        return summary

    def _resume(self, ckpt: CheckpointManager) -> int:
        target = self.config.resume_from_checkpoint
        step = ckpt.latest_step if target in ("latest", "true", "1") else int(target)
        if step is None:
            if is_primary_host():
                print("No checkpoint found to resume from; starting fresh")
            return 0
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            self.state,
        )
        # Trainable-only restores re-derive the frozen params from the base
        # checkpoint/seed: _prepare_state already built them, so hand the
        # REAL frozen arrays through (verified against the saved fingerprint).
        partial_abstract = abstract.replace(frozen=self.state.frozen)
        from llm_fine_tune_distributed_tpu.train.checkpoints import (
            FingerprintMismatch,
        )

        try:
            if ckpt.trainable_only:
                try:
                    self.state = ckpt.restore(step, partial_abstract)
                except FingerprintMismatch:
                    # the base weights changed, NOT the payload layout —
                    # falling back would bury the real diagnosis
                    raise
                except Exception:
                    # the checkpoint on disk may predate trainable-only mode
                    # (a full payload) — accept it
                    self.state = ckpt.restore(step, abstract, trainable_only=False)
                    if is_primary_host():
                        print(
                            f"Resumed FULL checkpoint step {step} into a "
                            "trainable-only run (subsequent saves are lean)"
                        )
            else:
                try:
                    self.state = ckpt.restore(step, abstract)
                except Exception:
                    # inverse mismatch: lean checkpoint, full-mode run
                    self.state = ckpt.restore(
                        step, partial_abstract, trainable_only=True
                    )
                    if is_primary_host():
                        print(
                            f"Resumed trainable-only checkpoint step {step} "
                            "into a full-checkpoint run (frozen params "
                            "re-derived and fingerprint-verified)"
                        )
        except FingerprintMismatch:
            raise
        except Exception as e:
            # Tree mismatch usually means a mesh-layout change across resume:
            # pipe>1 checkpoints store layer params stacked under
            # model/layers/@stacked/ while flat meshes store per-layer keys.
            # Cross-layout resume (train/layout.py) restores the checkpoint
            # in ITS layout and transforms params + optimizer moments to the
            # current one — an exact elastic resize.
            from llm_fine_tune_distributed_tpu.train.layout import (
                adopt_layout,
                alternate_abstract_state,
            )

            cur = (
                "stacked (pipe>1)"
                if any("@stacked" in k for k in self.state.trainable)
                else "flat (pipe=1)"
            )
            try:
                alt = alternate_abstract_state(
                    self.state, self.optimizer, self._flat_mask,
                    self.model_config.num_layers, self.mesh,
                )
                restored = ckpt.restore(step, alt)
                self.state = adopt_layout(
                    restored, self.state, self._flat_mask,
                    self.model_config.num_layers,
                )
                if is_primary_host():
                    print(
                        f"Cross-layout resume: checkpoint step {step} "
                        f"restored from the alternate mesh layout into "
                        f"[{cur}, MESH_PIPE={getattr(self, '_pipe_size', 1)}] "
                        "(params + optimizer moments transformed exactly)"
                    )
            except Exception as e2:
                raise RuntimeError(
                    f"failed to restore checkpoint step {step} into the "
                    f"current state layout [{cur}, MESH_PIPE="
                    f"{getattr(self, '_pipe_size', 1)}] or its pipe/flat "
                    "alternate. If the checkpoint was written under a "
                    "different mesh family, resume with the original mesh, "
                    "or export final artifacts and start a new run from "
                    f"them. (direct restore: {e})"
                ) from e2
        resumed_step = int(self.state.step)
        self.telemetry.note_restore(resumed_step)
        if is_primary_host():
            print(f"Resumed from checkpoint step {resumed_step}")
        return resumed_step

    # -------------------------------------------------------------- artifacts

    def _host_fetch(self, flat: Dict[str, jax.Array]) -> Dict[str, np.ndarray]:
        """Flat param dict -> host numpy, correct under multi-process.

        Sharded leaves of a multi-process mesh are not host-fetchable
        directly; reshard them to fully-replicated first (an all-gather
        collective — so when process_count > 1 EVERY host must call this,
        see _save_artifacts).
        """
        from llm_fine_tune_distributed_tpu.utils.transfer import parallel_device_get

        if jax.process_count() == 1:
            # concurrent streams (utils/transfer.py): this is the
            # artifact-export leg, ~6 GB of device->host at end of run
            return parallel_device_get(flat)
        replicated = NamedSharding(self.mesh, P())
        out = {}
        primary = is_primary_host()
        staged = {}
        for k, v in flat.items():
            if not v.sharding.is_fully_replicated:
                v = jax.device_put(v, replicated)
            if primary:
                staged[k] = v
        if primary:
            # only the writing host pays the device->host transfer and host
            # RAM; the others just participated in the collective. NO leaf
            # splitting here: slicing a replicated-but-not-fully-addressable
            # global array is a cross-mesh computation one process cannot
            # issue alone — np.asarray on fully-replicated arrays is the one
            # fetch JAX allows, so parallelism stays at leaf granularity.
            out = parallel_device_get(staged, split_bytes=1 << 62)
        return out

    def _save_artifacts(
        self,
        final_loss: Optional[float],
        eval_loss: Optional[float],
        wall_seconds: float,
        throughput: Dict[str, float],
    ) -> Dict[str, Any]:
        """Artifact contract of reference ``training.py:307-339`` (host 0):
        best_model/ safetensors + tokenizer, training_history.json,
        training_summary.json with the same keys (+ TPU-native extras)."""
        cfg = self.config
        summary = {
            "model_name": cfg.model_name,
            "dataset_path": os.path.join(cfg.data_dir, cfg.dataset_file),
            "epochs": cfg.epochs,
            "batch_size": cfg.per_device_batch_size,
            "learning_rate": cfg.learning_rate,
            "trainable_params": self.trainable_report["trainable_parameters"],
            "total_params": self.trainable_report["total_parameters"],
            "training_samples": self.n_train,
            "validation_samples": self.n_val,
            "final_train_loss": final_loss,
            "world_size": self.dp_size,
            "distributed_training": self.dp_size > 1,
            # TPU-native extras (north-star instrumentation)
            "final_eval_loss": eval_loss,
            "wall_clock_seconds": round(wall_seconds, 2),
            "mesh": dict(self.mesh.shape),
            **{k: round(v, 4) for k, v in throughput.items()},
        }
        # Host fetch runs on EVERY host: resharding a multi-process array to
        # replicated is a collective, and a host-0-only collective deadlocks.
        frozen_flat = self._host_fetch(self.state.frozen)
        trainable_flat = self._host_fetch(self.state.trainable)
        if not is_primary_host():
            return summary

        if getattr(self, "_pipe_size", 1) > 1:
            # pipe-mode state stacks block leaves [L, ...]; the export
            # contract (plain per-layer safetensors) unstacks them so the
            # artifact is identical to a flat-mesh run's
            from llm_fine_tune_distributed_tpu.parallel.pipeline import (
                unstack_flat_layer_leaves,
            )

            trainable_flat = unstack_flat_layer_leaves(trainable_flat)
            frozen_flat = unstack_flat_layer_leaves(frozen_flat)

        best_dir = os.path.join(cfg.output_dir, "best_model")
        if cfg.freeze_strategy == "qlora":
            # Export contract is plain safetensors (reference training.py:310):
            # decode the NF4 base back to bf16 so the inference CLI / HF
            # loaders see ordinary kernels.
            from llm_fine_tune_distributed_tpu.parallel.qlora import dequantize_frozen

            frozen_flat = {
                k: np.asarray(v)
                for k, v in dequantize_frozen(frozen_flat, jnp.float32).items()
            }
        if getattr(self, "_frozen_boundary", 0) > 0:
            # same export contract for the int8 trunk: decode the w8a8
            # kernels back to plain bf16-exportable kernels
            from llm_fine_tune_distributed_tpu.ops.int8 import dequantize_int8

            decoded = {}
            for k, v in frozen_flat.items():
                if k.endswith("/kernel_int8"):
                    base = k[: -len("_int8")]
                    decoded[base] = np.asarray(
                        dequantize_int8(
                            {
                                "int8": jnp.asarray(v),
                                "int8_scale": jnp.asarray(
                                    frozen_flat[f"{k}_scale"]
                                ),
                            },
                            jnp.float32,
                        )
                    )
                elif not k.endswith("/kernel_int8_scale"):
                    decoded[k] = v
            frozen_flat = decoded
        params = merge_flat(trainable_flat, frozen_flat)
        if cfg.freeze_strategy in ("lora", "qlora"):
            # Export both forms: standalone PEFT adapter (small, composable)
            # and the merged model (what the serving path actually loads —
            # rank-16 side matmuls would waste MXU occupancy at inference).
            from llm_fine_tune_distributed_tpu.parallel.lora import (
                merge_lora,
                save_lora_adapter,
            )

            save_lora_adapter(params, os.path.join(cfg.output_dir, "adapter"), cfg)
            params = merge_lora(params)
        import ml_dtypes

        save_hf_checkpoint(
            params,
            best_dir,
            save_dtype=ml_dtypes.bfloat16,
            metadata={"framework": "llm_fine_tune_distributed_tpu"},
            config=self.model_config,
        )
        if hasattr(self.tokenizer, "save_pretrained"):
            self.tokenizer.save_pretrained(best_dir)
        self._save_model_config(best_dir)
        print(f"Best model saved to {best_dir}")

        self.metrics.save_history(os.path.join(cfg.output_dir, "training_history.json"))
        with open(os.path.join(cfg.output_dir, "training_summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
        return summary

    def _save_model_config(self, path: str) -> None:
        """Write a config.json so the inference CLI can rebuild the model."""
        from llm_fine_tune_distributed_tpu.models.configs import to_hf_dict

        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(to_hf_dict(self.model_config), f, indent=2)
