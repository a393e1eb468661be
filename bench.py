#!/usr/bin/env python
"""Throughput benchmark: SFT samples/sec/chip on the flagship SmolLM3-3B.

Prints ONE JSON line per arm: {"metric", "value", "unit", "vs_baseline", ...}.

Recipe matches the reference training step (reference training.py:258-287):
seq 1024, bf16 compute, grad-accum, global-norm clip 1.0, AdamW, last-2-layers
+ lm_head trainable (418.9M/3.075B, reference training.py:113-149), remat on,
chunked cross-entropy (the [b,s,128k]-logits HBM saver).

Baseline derivation (the reference never published absolute samples/sec —
SURVEY.md §6): per-sample FLOPs at seq 1024 are
  fwd 2*N*T + bwd 4*N_trainable*T  with N=3.075e9, N_trainable=418.9e6
  = (2*3.075e9 + 4*0.4189e9) * 1024 = 8.01e12 FLOPs/sample.
An L40S sustains ~30% MFU of its 181 TFLOPS dense-bf16 peak under the
reference's HF/TRL DDP stack (flash-attn-2, PCIe box) -> 54.3 TFLOP/s
-> 6.78 samples/sec per GPU. That per-GPU figure is the per-chip baseline
(the reference claims ~linear scaling to 4 GPUs, reference README.md:13).

Knobs (all env): BENCH_PRESET, BENCH_BATCH, BENCH_ACCUM, BENCH_SEQ,
BENCH_STEPS, BENCH_ATTENTION, BENCH_REMAT, BENCH_REMAT_POLICY,
BENCH_PARAM_DTYPE, BENCH_FREEZE, BENCH_LOSS_CHUNK, BENCH_LOSS_VOCAB_CHUNK,
BENCH_FROZEN_COMPUTE (bf16|int8 — the frozen-trunk w8a8 fast path), plus
TRUNK_MATMUL (xla|pallas|interpret) for the int8 arm's kernel choice.
Guard arms: BENCH_FROZEN_INT8_GUARD=1 (bf16 vs int8, exit 1 unless int8
wins >= BENCH_INT8_MIN_SPEEDUP at loss parity), BENCH_VOCAB_CHUNK_COMPARE=1
(full-vocab unembed vs vocab-chunked CE, measurement only — see
docs/architecture.md for the default-flip rule).

The device: this measures the chip. Finding only a CPU is an error and a
non-zero exit (runtime/device.py). With JAX_PLATFORMS=cpu — the caller
asking for the CPU by name — it rehearses the same code path on the tiny
preset instead; those lines say "platform": "cpu", are not rates, and the
int8 guard's speed gate reports its ratio without gating on it (XLA's CPU
backend has no int8 GEMM path; parity is still gated).

One process owns the chip: everything here runs in this process, and
nothing starts a child that would need the device.
"""

import json
import os
import sys
import time

BASELINE_SAMPLES_PER_SEC_PER_CHIP = 6.78


def build(model_preset, per_device_batch_size, grad_accum, seq_len, attention_impl,
          loss_chunk, frozen_compute=None, vocab_chunk="env"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.parallel.freeze import (
        frozen_trunk_boundary,
        quantize_trunk_int8,
        trainable_mask,
    )
    from llm_fine_tune_distributed_tpu.parallel.optimizer import (
        build_optimizer,
        init_opt_state,
    )
    from llm_fine_tune_distributed_tpu.parallel.sharding import _validate_spec, param_spec
    from llm_fine_tune_distributed_tpu.runtime.mesh import data_parallel_size, make_mesh
    from llm_fine_tune_distributed_tpu.train.state import TrainState
    from llm_fine_tune_distributed_tpu.train.step import build_train_step, jit_train_step
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict, split_by_mask

    model_config = get_preset(model_preset)
    param_dtype = os.environ.get("BENCH_PARAM_DTYPE", "bfloat16")
    if vocab_chunk == "env":
        raw_vc = os.environ.get("BENCH_LOSS_VOCAB_CHUNK", "none")
        vocab_chunk = None if raw_vc.lower() in ("", "none", "0") else int(raw_vc)
    if frozen_compute is None:
        frozen_compute = os.environ.get("BENCH_FROZEN_COMPUTE", "bf16")
    freeze_strategy = os.environ.get("BENCH_FREEZE", "last_n_and_head")
    train_config = TrainConfig(
        param_dtype=param_dtype,
        model_preset=model_preset,
        per_device_batch_size=per_device_batch_size,
        gradient_accumulation_steps=grad_accum,
        max_seq_length=seq_len,
        gradient_checkpointing=os.environ.get("BENCH_REMAT", "1") != "0",
        attention_impl=attention_impl,
        loss_chunk_size=loss_chunk,
        loss_vocab_chunk=vocab_chunk,
        remat_policy=os.environ.get("BENCH_REMAT_POLICY", "dots_no_batch") or None,
        freeze_strategy=freeze_strategy,
        frozen_compute=frozen_compute,
    )
    mesh = make_mesh(MeshConfig(data=1, fsdp=-1, tensor=1, seq=1))
    dp = data_parallel_size(mesh)

    # Init in bf16 (frozen stays bf16); the trainable subset is cast to
    # BENCH_PARAM_DTYPE (default bfloat16, matching the reference's torch
    # AdamW whose states live in the model's bf16; set float32 for f32
    # masters — a full-f32 init of 3B params would not fit 16GB HBM).
    params = init_params(jax.random.PRNGKey(0), model_config, dtype=jnp.bfloat16)
    if freeze_strategy in ("lora", "qlora"):
        from llm_fine_tune_distributed_tpu.parallel.lora import add_lora_from_config

        params = add_lora_from_config(params, jax.random.PRNGKey(1), train_config)
    mask = trainable_mask(params, model_config, train_config)
    # Frozen-trunk fast path: same boundary rule as the trainer
    # (_prepare_state) — earliest layer with any trainable leaf; 0 = no trunk
    frozen_layers = 0
    if frozen_compute == "int8":
        frozen_layers = frozen_trunk_boundary(
            flatten_dict(mask), model_config.num_layers
        )
    trainable, frozen = split_by_mask(params, mask)
    del params
    if freeze_strategy == "qlora":
        # NF4 base from the bf16 init (the trainer quantizes from f32; for a
        # throughput measurement the extra bf16 rounding is irrelevant and a
        # 3B f32 init would not fit the 16G chip alongside the batch)
        from llm_fine_tune_distributed_tpu.parallel.qlora import quantize_frozen

        frozen = quantize_frozen(frozen)
    if frozen_layers > 0:
        # w8a8 trunk from the bf16 init (same rounding caveat as qlora above)
        frozen, _ = quantize_trunk_int8(frozen, frozen_layers)
    from llm_fine_tune_distributed_tpu.config import str_to_dtype
    trainable = {k: v.astype(str_to_dtype(param_dtype)) for k, v in trainable.items()}

    def put(flat):
        return {
            k: jax.device_put(
                v, NamedSharding(mesh, _validate_spec(param_spec(k, v.ndim), v.shape, mesh))
            )
            for k, v in flat.items()
        }

    trainable, frozen = put(trainable), put(frozen)
    optimizer = build_optimizer(train_config, None, total_steps=1000, data_parallel_size=dp)
    opt_state = init_opt_state(optimizer, trainable, mesh)
    state = TrainState(
        step=jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P())),
        trainable=trainable,
        frozen=frozen,
        opt_state=opt_state,
    )

    act = NamedSharding(mesh, P(("data", "fsdp"), None, None))
    step_fn = jit_train_step(
        build_train_step(
            model_config, train_config, optimizer, activation_sharding=act,
            frozen_layers=frozen_layers,
        ),
        mesh=mesh,
    )

    batch_size = per_device_batch_size * dp
    rng = np.random.RandomState(0)
    batch_sharding = NamedSharding(mesh, P(None, ("data", "fsdp")))
    batch = {
        "input_ids": jax.device_put(
            rng.randint(0, model_config.vocab_size, (grad_accum, batch_size, seq_len)).astype(np.int32),
            batch_sharding,
        ),
        "loss_mask": jax.device_put(np.ones((grad_accum, batch_size, seq_len), np.float32), batch_sharding),
        "attention_mask": jax.device_put(np.ones((grad_accum, batch_size, seq_len), np.int32), batch_sharding),
    }
    info = {
        "frozen_compute": frozen_compute,
        "frozen_layers": frozen_layers,
        "loss_vocab_chunk": vocab_chunk,
    }
    return mesh, state, step_fn, batch, batch_size * grad_accum, info


def measure_arm(preset, bs, accum, seq, attention_impl, loss_chunk, warmup, timed,
                frozen_compute=None, vocab_chunk="env"):
    """Build + warm up + time one recipe. Returns the measured dict: the
    step is ledger-instrumented (observe/xla, AOT) so cost_analysis FLOPs
    feed an MFU gauge. Where the step's device time goes is measured, not
    estimated: the step's scopes in a chip trace (PERF.md section 5)."""
    import jax

    from llm_fine_tune_distributed_tpu.observe.xla import (
        CompileLedger,
        device_peak_specs,
        instrument,
        utilization_from_cost,
    )

    ledger = CompileLedger()
    mesh, state, step_fn, batch, samples_per_step, info = build(
        preset, bs, accum, seq, attention_impl, loss_chunk,
        frozen_compute=frozen_compute, vocab_chunk=vocab_chunk,
    )
    n_chips = mesh.size
    step_fn = instrument("train_step", step_fn, ledger)

    # compile + warmup
    for _ in range(warmup):
        state, metrics = step_fn(state, batch)
    jax.block_until_ready(metrics)
    ledger.mark_warm()

    # host sync EVERY step: the loss is fetched, so each step's device
    # work has ended before the next is timed
    t0 = time.perf_counter()
    for _ in range(timed):
        state, metrics = step_fn(state, batch)
        _ = float(metrics["loss"])
    elapsed = time.perf_counter() - t0
    step_s = elapsed / timed

    flops, bytes_acc = ledger.cost_for(("train_step",))
    peak_flops, peak_bw = device_peak_specs()
    mfu, _bw = utilization_from_cost(
        flops, bytes_acc, step_s, peak_flops * n_chips, peak_bw * n_chips
    )
    return {
        "samples_per_sec_per_chip": samples_per_step * timed / elapsed / n_chips,
        "step_seconds": step_s,
        "loss": float(metrics["loss"]),
        "effective_batch": samples_per_step,
        "n_chips": n_chips,
        "mfu": mfu,
        "frozen_compute": info["frozen_compute"],
        "frozen_layers": info["frozen_layers"],
        "loss_vocab_chunk": info["loss_vocab_chunk"],
        "recompiles_after_warmup": ledger.snapshot()["recompiles_after_warmup"],
    }


def _recipe():
    import jax

    from llm_fine_tune_distributed_tpu.runtime.device import on_accelerator

    platform = jax.devices()[0].platform
    accelerated = on_accelerator(platform)  # raises on a CPU nobody asked for
    preset = os.environ.get("BENCH_PRESET", "smollm3_3b" if accelerated else "tiny")
    if accelerated:
        # Best single-chip v5e recipe found by sweep: microbatch 2, bf16
        # masters/optimizer state (matching the reference, whose torch AdamW
        # states live in the model's bfloat16), matmul-saving remat, single
        # full-sequence unembed. The chip is compute-bound: cutting recompute
        # and optimizer-state HBM beats bigger microbatches under full remat.
        bs = int(os.environ.get("BENCH_BATCH", "2"))
        accum = int(os.environ.get("BENCH_ACCUM", "16"))
        seq = int(os.environ.get("BENCH_SEQ", "1024"))
        warmup, timed = 2, int(os.environ.get("BENCH_STEPS", "6"))
        raw_chunk = os.environ.get("BENCH_LOSS_CHUNK", "none")
        loss_chunk = None if raw_chunk.lower() in ("", "none", "0") else int(raw_chunk)
    else:  # JAX_PLATFORMS=cpu rehearsal: same path, tiny shapes, no rates
        bs, accum, seq, warmup, timed, loss_chunk = 2, 2, 128, 1, 2, 64
    attention_impl = os.environ.get("BENCH_ATTENTION", "flash")
    return platform, preset, bs, accum, seq, warmup, timed, loss_chunk, attention_impl


def main():
    from llm_fine_tune_distributed_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )
    from llm_fine_tune_distributed_tpu.runtime.device import NoAcceleratorError

    enable_compile_cache()
    try:
        (platform, preset, bs, accum, seq, warmup, timed, loss_chunk,
         attention_impl) = _recipe()
    except NoAcceleratorError as e:
        sys.exit(f"bench.py: {e}")

    if os.environ.get("BENCH_FROZEN_INT8_GUARD", "0") == "1":
        # Guard arm: the frozen-trunk w8a8 fast path must BEAT bf16 on the
        # same recipe at loss parity — else the int8 plumbing is dead weight.
        # platform == "cpu" here means JAX_PLATFORMS=cpu was set (_recipe
        # refuses any other CPU): a rehearsal, whose ratio is not a speed.
        min_speedup = float(os.environ.get("BENCH_INT8_MIN_SPEEDUP", "1.25"))
        loss_rtol = float(os.environ.get("BENCH_INT8_LOSS_RTOL", "0.02"))
        bf16 = measure_arm(preset, bs, accum, seq, attention_impl, loss_chunk,
                           warmup, timed, frozen_compute="bf16")
        int8 = measure_arm(preset, bs, accum, seq, attention_impl, loss_chunk,
                           warmup, timed, frozen_compute="int8")
        speedup = int8["samples_per_sec_per_chip"] / bf16["samples_per_sec_per_chip"]
        loss_rel = abs(int8["loss"] - bf16["loss"]) / max(abs(bf16["loss"]), 1e-9)
        parity = loss_rel <= loss_rtol
        trunk_live = int8["frozen_layers"] > 0
        ok = parity and trunk_live and (platform == "cpu" or speedup >= min_speedup)
        print(json.dumps({
            "metric": "train_frozen_int8_guard",
            "value": 1 if ok else 0,
            "unit": f"1 = int8 trunk >= {min_speedup}x bf16 samples/sec at "
                    f"loss parity (rtol {loss_rtol}; speedup informational on CPU)",
            "speedup": round(speedup, 3),
            "loss_bf16": round(bf16["loss"], 5),
            "loss_int8": round(int8["loss"], 5),
            "loss_rel_diff": round(loss_rel, 6),
            "samples_per_sec_per_chip_bf16": round(bf16["samples_per_sec_per_chip"], 3),
            "samples_per_sec_per_chip_int8": round(int8["samples_per_sec_per_chip"], 3),
            "frozen_layers": int8["frozen_layers"],
            "trunk_matmul": os.environ.get("TRUNK_MATMUL", "xla"),
            "model": preset,
            "platform": platform,
            "seq_len": seq,
        }), flush=True)
        if not ok:
            sys.exit(1)
        return

    if os.environ.get("BENCH_VOCAB_CHUNK_COMPARE", "0") == "1":
        # Compared arm: single full-sequence unembed (the default) vs the
        # vocab-chunked online-logsumexp CE at the SAME recipe. Measurement
        # only (exit 0 either way); the default-flip rule — flip
        # TrainConfig.loss_vocab_chunk if the chunked arm is >= 5% faster at
        # loss parity — is documented in docs/architecture.md.
        mc_vocab = 128256 if preset == "smollm3_3b" else None
        raw = os.environ.get("BENCH_LOSS_VOCAB_CHUNK", "none")
        chunk = (int(raw) if raw.lower() not in ("", "none", "0")
                 else (mc_vocab // 16 if mc_vocab else 128))
        base = measure_arm(preset, bs, accum, seq, attention_impl, loss_chunk,
                           warmup, timed, vocab_chunk=None)
        chunked = measure_arm(preset, bs, accum, seq, attention_impl, None,
                              warmup, timed, vocab_chunk=chunk)
        speedup = chunked["samples_per_sec_per_chip"] / base["samples_per_sec_per_chip"]
        loss_rel = abs(chunked["loss"] - base["loss"]) / max(abs(base["loss"]), 1e-9)
        print(json.dumps({
            "metric": "loss_vocab_chunk_compare",
            "value": round(speedup, 3),
            "unit": "chunked/full samples-per-sec ratio (>1 = chunked faster)",
            "vocab_chunk": chunk,
            "samples_per_sec_per_chip_full": round(base["samples_per_sec_per_chip"], 3),
            "samples_per_sec_per_chip_chunked": round(chunked["samples_per_sec_per_chip"], 3),
            "loss_full": round(base["loss"], 5),
            "loss_chunked": round(chunked["loss"], 5),
            "loss_rel_diff": round(loss_rel, 6),
            "default_flip_recommended": bool(speedup >= 1.05 and loss_rel <= 0.02),
            "model": preset,
            "platform": platform,
            "seq_len": seq,
        }), flush=True)
        return

    arm = measure_arm(preset, bs, accum, seq, attention_impl, loss_chunk, warmup, timed)
    sps_chip = arm["samples_per_sec_per_chip"]
    result = {
        "metric": "sft_samples_per_sec_per_chip",
        "value": round(sps_chip, 3),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps_chip / BASELINE_SAMPLES_PER_SEC_PER_CHIP, 3),
        "model": preset,
        "platform": platform,
        "n_chips": arm["n_chips"],
        "seq_len": seq,
        "effective_batch": arm["effective_batch"],
        "step_seconds": round(arm["step_seconds"], 3),
        "loss": round(arm["loss"], 4),
        "tokens_per_sec_per_chip": round(sps_chip * seq, 1),
        "mfu": round(arm["mfu"], 6),
        "frozen_compute": arm["frozen_compute"],
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
