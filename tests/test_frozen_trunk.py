"""Frozen-trunk fast path (TrainConfig.frozen_compute="int8"): the w8a8
op, the trainable-boundary rule, numeric parity against the bf16 default,
and — the guard the feature stands on — backward DCE: the trunk's backward
must be ABSENT from the compiled step. The compile-cost test below fails
if trunk backward/recompute ever reappears (a remat-scope regression, a
stop_gradient moved) and the lowered-text test fails if the trunk stops
lowering to int8 dot_generals (a dequant-then-bf16-matmul regression).

On-TPU speedup is gated by bench.py's BENCH_FROZEN_INT8_GUARD arm; here
(CPU tier-1) the gates are numeric parity (interpret == XLA bit-exact,
int8 trunk close to bf16) and program structure.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_fine_tune_distributed_tpu.config import TrainConfig
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import init_params
from llm_fine_tune_distributed_tpu.ops.int8 import quantize_int8
from llm_fine_tune_distributed_tpu.ops.int8_matmul import (
    int8_w8a8_matmul,
    quantize_rows_int8,
)
from llm_fine_tune_distributed_tpu.parallel.freeze import (
    frozen_trunk_boundary,
    quantize_trunk_int8,
    trainable_mask,
)
from llm_fine_tune_distributed_tpu.train.step import build_train_step, make_loss_fn
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

MC = get_preset("tiny")
SEQ, BATCH = 32, 4

# matches the int8 contraction in pre-optimization StableHLO ("dot_general
# ... tensor<...xi8>"); the compiled HLO is useless for this — CPU XLA
# rewrites s8 dots as convert+s32 and fuses the converts away
_I8_DOT_RE = re.compile(r"dot_general[^\n]*tensor<[0-9x]*xi8>")
# 7 projections per layer (q/k/v/o + gate/up/down)
_PROJECTIONS_PER_LAYER = 7


def _tiny_state(frozen_compute):
    """(trainable, frozen, train_config, frozen_layers) on the tiny preset,
    f32 params, default last_n_and_head freezing (trunk = 2 of 4 layers)."""
    tc = TrainConfig(
        model_preset="tiny",
        compute_dtype="float32",
        frozen_compute=frozen_compute,
        gradient_checkpointing=True,
        per_device_batch_size=BATCH,
        gradient_accumulation_steps=1,
        max_seq_length=SEQ,
    )
    params = init_params(jax.random.PRNGKey(0), MC, dtype=jnp.float32)
    mask = trainable_mask(params, MC, tc)
    flat_mask = flatten_dict(mask)
    boundary = 0
    flat = flatten_dict(params)
    trainable = {k: v for k, v in flat.items() if flat_mask[k]}
    frozen = {k: v for k, v in flat.items() if not flat_mask[k]}
    if frozen_compute == "int8":
        boundary = frozen_trunk_boundary(flat_mask, MC.num_layers)
        frozen, _ = quantize_trunk_int8(frozen, boundary)
    return trainable, frozen, tc, boundary


def _batch(accum=1):
    rng = np.random.RandomState(3)
    ids = rng.randint(0, MC.vocab_size, (accum, BATCH, SEQ)).astype(np.int32)
    return {
        "input_ids": jnp.asarray(ids),
        "loss_mask": jnp.ones((accum, BATCH, SEQ), jnp.float32),
        "attention_mask": jnp.ones((accum, BATCH, SEQ), jnp.int32),
    }


# ------------------------------------------------------------------ the op


def test_quantize_rows_int8():
    x = jnp.asarray(np.random.RandomState(0).randn(5, 64), jnp.float32)
    codes, scale = quantize_rows_int8(x)
    assert codes.dtype == jnp.int8 and scale.shape == (5,)
    # absmax-symmetric: dequant error bounded by half a quantization step
    deq = codes.astype(jnp.float32) * scale[:, None]
    assert float(jnp.max(jnp.abs(deq - x))) <= float(jnp.max(scale)) * 0.5 + 1e-6
    # all-zero rows: scale 1.0, zero codes, exact-zero dequant
    z_codes, z_scale = quantize_rows_int8(jnp.zeros((2, 8)))
    assert float(jnp.max(jnp.abs(z_codes))) == 0.0
    assert np.allclose(np.asarray(z_scale), 1.0 / 127.0)


def test_w8a8_interpret_matches_xla_bitwise():
    """The Pallas kernel (interpret mode on CPU) and the XLA dot_general
    compute the SAME int32 accumulation and f32 rescale — bit-identical,
    which is what lets the CPU tier run the kernel's math at all."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(16, 64), jnp.float32)
    q = quantize_int8(jnp.asarray(rng.randn(64, 48), jnp.float32))
    q = {"int8": q["int8"], "int8_scale": q["int8_scale"]}
    out_xla = int8_w8a8_matmul(x, q, jnp.float32, impl="xla")
    out_interp = int8_w8a8_matmul(x, q, jnp.float32, impl="interpret")
    assert np.array_equal(np.asarray(out_xla), np.asarray(out_interp))


def test_w8a8_close_to_f32_reference():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(8, 64), jnp.float32)
    w = jnp.asarray(rng.randn(64, 32), jnp.float32)
    ref = x @ w
    out = int8_w8a8_matmul(x, quantize_int8(w), jnp.float32, impl="xla")
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert rel < 0.05  # two 8-bit absmax roundings


def test_w8a8_rejects_unknown_impl():
    x = jnp.ones((2, 8))
    q = quantize_int8(jnp.ones((8, 4)))
    with pytest.raises(ValueError, match="unknown trunk matmul impl"):
        int8_w8a8_matmul(x, q, impl="cuda")


# ------------------------------------------------------------- the boundary


def test_boundary_last_n_and_head():
    # default unfreeze_last_n_layers=2 on the 4-layer tiny: trunk = [0, 2)
    _, _, _, boundary = _tiny_state("int8")
    assert boundary == MC.num_layers - 2


def test_boundary_lora_and_full_have_no_trunk():
    params = init_params(jax.random.PRNGKey(0), MC, dtype=jnp.float32)
    for strategy in ("lora", "none"):
        tc = TrainConfig(model_preset="tiny", freeze_strategy=strategy)
        p = params
        if strategy == "lora":
            from llm_fine_tune_distributed_tpu.parallel.lora import (
                add_lora_from_config,
            )

            p = add_lora_from_config(params, jax.random.PRNGKey(1), tc)
        flat_mask = flatten_dict(trainable_mask(p, MC, tc))
        assert frozen_trunk_boundary(flat_mask, MC.num_layers) == 0, strategy


def test_quantize_trunk_covers_exactly_the_trunk_projections():
    _, frozen, _, boundary = _tiny_state("int8")
    int8_keys = [k for k in frozen if k.endswith("/kernel_int8")]
    assert len(int8_keys) == boundary * _PROJECTIONS_PER_LAYER
    for k in int8_keys:
        layer = int(re.search(r"model/layers/(\d+)/", k).group(1))
        assert layer < boundary
        assert f"{k}_scale" in frozen  # per-channel scale sibling
    # norms stay full precision (plain weight leaves, never quantized)
    assert any(k.endswith("input_layernorm/weight") for k in frozen)


def test_make_loss_fn_rejects_unknown_frozen_compute():
    tc = TrainConfig(model_preset="tiny", frozen_compute="fp8")
    with pytest.raises(ValueError, match="unknown frozen_compute"):
        make_loss_fn(MC, tc)


# ----------------------------------------------------------------- parity


def _grad_fn(frozen_compute):
    trainable, frozen, tc, boundary = _tiny_state(frozen_compute)
    loss_fn = make_loss_fn(MC, tc, frozen_layers=boundary)
    gfn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
    b = _batch()
    batch = {k: v[0] for k, v in b.items()}
    return gfn, trainable, frozen, batch


def test_int8_loss_and_grads_parity_with_bf16_path():
    """int8 trunk ~ the full-precision path: loss within the 8-bit rounding
    band, gradients present for every trainable leaf and nonzero."""
    gfn_ref, trainable, frozen_ref, batch = _grad_fn("bf16")
    (loss_ref, _), _ = gfn_ref(trainable, frozen_ref, batch)
    gfn_i8, trainable, frozen_i8, batch = _grad_fn("int8")
    (loss_i8, _), grads = gfn_i8(trainable, frozen_i8, batch)
    assert abs(float(loss_i8) - float(loss_ref)) < 0.02 * float(loss_ref)
    for k, g in grads.items():
        assert float(jnp.max(jnp.abs(g))) > 0.0, f"dead gradient for {k}"


def test_int8_train_loss_curve_tracks_bf16():
    """5 optimizer steps on identical synthetic batches: the int8-trunk loss
    curve must track the full-precision curve within a tight relative band
    (the trunk only perturbs the forward; the trainable update rule is
    identical)."""
    from llm_fine_tune_distributed_tpu.parallel.optimizer import build_optimizer
    from llm_fine_tune_distributed_tpu.train.state import TrainState
    from llm_fine_tune_distributed_tpu.train.step import jit_train_step

    def run(frozen_compute):
        trainable, frozen, tc, boundary = _tiny_state(frozen_compute)
        opt = build_optimizer(tc, None, total_steps=5, data_parallel_size=1)
        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            trainable=trainable,
            frozen=frozen,
            opt_state=opt.init(trainable),
        )
        step_fn = jit_train_step(
            build_train_step(MC, tc, opt, frozen_layers=boundary)
        )
        batch = _batch(accum=1)
        losses = []
        for _ in range(5):
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
        return losses

    ref, i8 = run("bf16"), run("int8")
    assert ref[-1] < ref[0]  # both actually learn
    assert i8[-1] < i8[0]
    for a, b in zip(ref, i8):
        assert abs(a - b) < 0.02 * abs(a), (ref, i8)


# ------------------------------------------------------- backward-DCE guard


def _lower(frozen_compute):
    trainable, frozen, tc, boundary = _tiny_state(frozen_compute)
    loss_fn = make_loss_fn(MC, tc, frozen_layers=boundary)
    gfn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
    b = _batch()
    batch = {k: v[0] for k, v in b.items()}
    return gfn.lower(trainable, frozen, batch), boundary


def test_trunk_lowers_to_int8_dot_generals():
    """Exactly the frozen-block projections contract in int8 — counted in
    the pre-optimization StableHLO, where the i8 operand types survive."""
    lowered, boundary = _lower("int8")
    n_i8 = len(_I8_DOT_RE.findall(lowered.as_text()))
    assert n_i8 == boundary * _PROJECTIONS_PER_LAYER, n_i8
    lowered_ref, _ = _lower("bf16")
    assert not _I8_DOT_RE.findall(lowered_ref.as_text())


def test_backward_dce_compile_cost_guard():
    """THE guard: the int8-trunk grad program must cost meaningfully fewer
    FLOPs than the bf16 default, because the trunk pays forward-only (its
    backward + remat recompute are DCE'd past the boundary stop_gradient).
    Measured ratio on tiny is ~0.80; a ratio near 1.0 means trunk backward
    or recompute reappeared. cost_analysis comes from the REAL compiled
    step (the same signal CompileLedger records on TPU)."""

    def flops(frozen_compute):
        lowered, _ = _lower(frozen_compute)
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return float(ca["flops"])

    ratio = flops("int8") / flops("bf16")
    assert ratio < 0.9, f"trunk backward appears to be back: ratio={ratio:.3f}"


# ----------------------------------------------------- CPU bench smoke arm


def test_bench_smoke_int8_interpret(tmp_path):
    """bench.py end-to-end on the JAX_PLATFORMS=cpu rehearsal recipe with the int8 trunk
    on the INTERPRET path — tier-1 coverage of the Pallas kernel inside the
    real jitted train step, plus the bench JSON contract (mfu /
    frozen_compute fields)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        BENCH_FROZEN_COMPUTE="int8",
        TRUNK_MATMUL="interpret",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["metric"] == "sft_samples_per_sec_per_chip"
    assert result["frozen_compute"] == "int8"
    assert result["value"] > 0
    assert "mfu" in result  # 0.0 on CPU (no roofline), present by contract
