#!/usr/bin/env python
"""Measured op-by-op ledger of the flagship train step.

An earlier "~18% non-matmul tax" claim was cost_analysis() arithmetic;
this script replaces it with measurement: every constituent op of the
SmolLM3-3B train step is timed ON THE CHIP at the exact step shapes
(microbatch 2, seq 1024, bf16), fwd and fwd+bwd, then multiplied by its
per-step count (36 layers x accum 16 under remat policy dots_no_batch) and
compared against the measured whole-step time. The residual between the
sum of parts and the whole is XLA's fusion dividend (or overhead).

Usage (real TPU):
    python benchmarks/perf_ledger.py            # full ledger, one JSON line
    python benchmarks/perf_ledger.py --flash-only [BLOCK ...]   # the three flash kernels apart at both
        # cells' shapes, one JSON line for the kernel's own tile and one for each FLASH_BLOCK value given
Env: LEDGER_REPS (default 20), LEDGER_MB (microbatch, default 2).
"""

from __future__ import annotations

import functools
import sys
sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp


# flagship (SmolLM3-3B) step shapes at microbatch MB, seq 1024
MB = int(os.environ.get("LEDGER_MB", "2"))
S = 1024
H = 2048
HEADS, KV, D = 16, 4, 128
F = 11008
V = 128256
L = 36
ACCUM = 16


def _time(fn, *args, reps=None, warmup=3):
    reps = reps or int(os.environ.get("LEDGER_REPS", "20"))
    jfn = jax.jit(fn)
    for _ in range(warmup):
        out = jfn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jfn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _grad_time(fn, *args, reps=None):
    """fwd+bwd with a RANDOM cotangent.

    grad-of-sum would hand XLA an all-ones cotangent, which it simplifies
    (ones @ W^T becomes a reduction) — wrecking matmul backward times. A
    random cotangent forces the real dx/dw matmuls."""
    out = jax.eval_shape(fn, *args)
    cot = jnp.asarray(
        np.random.RandomState(7).randn(*out.shape), out.dtype
    )

    def fwd_bwd(cot_, *a):
        y, vjp = jax.vjp(fn, *a)
        return vjp(cot_)

    return _time(fwd_bwd, cot, *args, reps=reps)


# the attention call of one microbatch in each training cell of BENCHMARK.json:
# (rows, seq, q heads, kv heads, head size)
FLASH_SHAPES = {
    "smollm3-3b.sft-1k-full": (2, 1024, 16, 4, 128),
    "mistral-7b-d16.sft-2k-full": (1, 2048, 32, 8, 128),
}
FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")


def flash_kernels(reps=None):
    """Device time of the forward, dq and dk/dv kernels apart, read from a
    profiler trace of ``reps`` forward+backward calls by the kernels' own
    names (the reduction the benchmark uses, ``chipbench/trace.py``), and each
    kernel's share of the bfloat16 roofline by ``chipbench/flops.py``'s count:
    causal attention at half the square, recomputation not counted, so the
    backward's two halves (dO V^T and dS K in dq, P^T dO and dS^T q in dk/dv)
    each owe what the forward owes."""
    import tempfile

    from benchmarks.chipbench import flops, trace
    from llm_fine_tune_distributed_tpu.ops.flash_attention import (
        pallas_flash_attention,
    )

    reps = reps or int(os.environ.get("LEDGER_REPS", "20"))
    kind = jax.devices()[0].device_kind
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "chipbench", "peaks.json")) as f:
        peaks = json.load(f)[kind]  # a device without published peaks is an error
    rng = np.random.RandomState(0)
    out = {}
    for cell, (b, s, hq, hkv, d) in FLASH_SHAPES.items():
        q, k, v, cot = (
            jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16) for h in (hq, hkv, hkv, hq)
        )

        @jax.jit
        def fwd_bwd(q, k, v, cot):
            _, vjp = jax.vjp(pallas_flash_attention, q, k, v)
            return vjp(cot)

        jax.block_until_ready(fwd_bwd(q, k, v, cot))
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(reps):
                    grads = fwd_bwd(q, k, v, cot)
                jax.block_until_ready(grads)
            reduced = trace.reduce_dir(tmp)
        if reduced is None:
            raise SystemExit("perf_ledger: the trace holds no device plane (not a TPU?)")
        bound = flops.roofline_seconds(flops.flash_fwd_cost(b, s, hq, hkv, d), peaks)
        entry = {"shape": [b, s, hq, hkv, d], "roofline_bound": bound["bound"]}
        for name in FLASH_KERNELS:
            secs, calls = trace.kernel_seconds(reduced, name)
            entry[name] = {
                "ms": round(1e3 * secs / calls, 4),
                "calls": calls,
                "roofline_pct": round(100.0 * bound["seconds"] * calls / secs, 2),
            }
        out[cell] = entry
    return {"device_kind": kind, "flash_block": os.environ.get("FLASH_BLOCK", ""), "cells": out}


def main():
    if "--flash-only" in sys.argv[1:]:
        for block in [""] + [a for a in sys.argv[1:] if a.isdigit()]:
            os.environ["FLASH_BLOCK"] = block  # the kernel's sweep knob, read when it is traced
            print(json.dumps({"metric": "flash_kernels", **flash_kernels()}), flush=True)
        return
    from llm_fine_tune_distributed_tpu.ops.flash_attention import (
        pallas_flash_attention,
    )
    from llm_fine_tune_distributed_tpu.ops.norms import rms_norm
    from llm_fine_tune_distributed_tpu.ops.rope import apply_rope, rope_cos_sin

    rng = np.random.RandomState(0)
    bf = jnp.bfloat16

    def arr(*shape, dtype=bf):
        return jnp.asarray(rng.randn(*shape), dtype)

    x = arr(MB, S, H)
    w_norm = jnp.ones((H,), bf)
    q = arr(MB, S, HEADS, D)
    k = arr(MB, S, KV, D)
    v = arr(MB, S, KV, D)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (MB, S))
    cos, sin = rope_cos_sin(pos, D, 2e6)
    w_qkv = arr(H, HEADS * D)
    w_kv = arr(H, KV * D)
    w_gate = arr(H, F)
    w_down = arr(F, H)
    h_mlp = arr(MB, S, F)
    w_unembed = arr(H, V)
    ids = jnp.asarray(rng.randint(0, V, (MB, S)), jnp.int32)
    embed_tab = arr(V, H)

    ledger = {}

    def entry(name, fwd_s, bwd_s, count_fwd, count_bwd, remat_refwd=False):
        # remat_refwd: under remat policy dots_no_batch the op's forward is
        # NOT saved (only jnp.dot outputs are), so the backward pass
        # recomputes it once more — one extra fwd execution per bwd.
        refwd = count_bwd if remat_refwd else 0
        ledger[name] = {
            "fwd_ms": round(fwd_s * 1e3, 4),
            "fwdbwd_ms": round(bwd_s * 1e3, 4) if bwd_s is not None else None,
            # per-optimizer-step totals: counts already include accum/layers
            "step_ms": round(
                (
                    fwd_s * (count_fwd + refwd)
                    + (bwd_s - fwd_s if bwd_s else 0.0) * count_bwd
                )
                * 1e3,
                1,
            ),
            "count_fwd": count_fwd,
            "count_bwd": count_bwd,
            "remat_refwd": remat_refwd,
        }

    # Per-layer ops: fwd runs accum*L times. Matmul outputs are saved by
    # dots_no_batch so they pay no recompute; norms/rope/swiglu/flash are
    # recomputed in backward (remat_refwd=True).
    per_layer = ACCUM * L

    t = _time(lambda a, w: rms_norm(a, w), x, w_norm)
    tb = _grad_time(lambda a, w: rms_norm(a, w), x, w_norm)
    entry("rms_norm (x2/layer + final)", t, tb, per_layer * 2, per_layer * 2,
          remat_refwd=True)

    t = _time(lambda a, b_, c, d_: apply_rope(a, b_, c, d_)[0], q, k, cos, sin)
    tb = _grad_time(lambda a, b_, c, d_: apply_rope(a, b_, c, d_)[0], q, k, cos, sin)
    entry("rope", t, tb, per_layer, per_layer, remat_refwd=True)

    t = _time(lambda a, b_, c: pallas_flash_attention(a, b_, c), q, k, v)
    tb = _grad_time(lambda a, b_, c: pallas_flash_attention(a, b_, c), q, k, v)
    entry("flash_attention", t, tb, per_layer, per_layer, remat_refwd=True)
    ledger["flash_attention"]["kernels"] = flash_kernels()["cells"]

    t = _time(lambda a, w: a @ w, x, w_qkv)
    tb = _grad_time(lambda a, w: a @ w, x, w_qkv)
    entry("matmul q/o [h,h]", t, tb, per_layer * 2, per_layer * 2)

    t = _time(lambda a, w: a @ w, x, w_kv)
    tb = _grad_time(lambda a, w: a @ w, x, w_kv)
    entry("matmul k/v [h,kv]", t, tb, per_layer * 2, per_layer * 2)

    t = _time(lambda a, w: a @ w, x, w_gate)
    tb = _grad_time(lambda a, w: a @ w, x, w_gate)
    entry("matmul gate/up [h,f]", t, tb, per_layer * 2, per_layer * 2)

    t = _time(lambda a, w: a @ w, h_mlp, w_down)
    tb = _grad_time(lambda a, w: a @ w, h_mlp, w_down)
    entry("matmul down [f,h]", t, tb, per_layer, per_layer)

    t = _time(lambda g, u: jax.nn.silu(g.astype(jnp.float32)) * u, h_mlp, h_mlp)
    tb = _grad_time(
        lambda g, u: (jax.nn.silu(g.astype(jnp.float32)) * u).astype(bf), h_mlp, h_mlp
    )
    entry("swiglu elementwise", t, tb, per_layer, per_layer, remat_refwd=True)

    # once per microbatch (not per layer)
    t = _time(lambda tab, i: tab[i], embed_tab, ids)
    entry("embed lookup", t, None, ACCUM, 0)

    def unembed_loss(a, w):
        logits = (a @ w).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ids[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - gold)

    t = _time(unembed_loss, x, w_unembed)
    tb = _grad_time(unembed_loss, x, w_unembed)
    entry("unembed + CE [h,128k]", t, tb, ACCUM, ACCUM)

    parts_ms = sum(e["step_ms"] for e in ledger.values())

    # free the micro-bench operands (the [h,128k] unembed + embed tables are
    # ~1 GB) before the full model + optimizer state allocates
    del x, q, k, v, cos, sin, w_qkv, w_kv, w_gate, w_down, h_mlp
    del w_unembed, embed_tab, ids, w_norm, pos
    jax.clear_caches()

    # whole step, measured through the bench harness (same recipe). The
    # step is ledger-instrumented (observe/xla): AOT compile gives exact
    # compile seconds plus cost_analysis() FLOPs / bytes-accessed, which
    # the measured step time turns into roofline utilization gauges — the
    # measured counterpart of cost_analysis() arithmetic.
    import bench

    from llm_fine_tune_distributed_tpu.observe.xla import (
        CompileLedger,
        device_peak_specs,
        instrument,
        utilization_from_cost,
    )

    compile_ledger = CompileLedger()
    mesh, state, step_fn, batch, samples, build_info = bench.build(
        "smollm3_3b", MB, ACCUM, S, "flash", None
    )
    step_fn = instrument("train_step", step_fn, compile_ledger)
    for _ in range(2):
        state, metrics = step_fn(state, batch)
    _ = float(metrics["loss"])
    compile_ledger.mark_warm()
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        state, metrics = step_fn(state, batch)
        _ = float(metrics["loss"])
    step_s = (time.perf_counter() - t0) / reps

    comp = compile_ledger.snapshot()
    flops, bytes_acc = compile_ledger.cost_for(("train_step",))
    peak_flops, peak_bw = device_peak_specs()
    mfu, bw_util = utilization_from_cost(
        flops, bytes_acc, step_s, peak_flops, peak_bw
    )

    result = {
        "metric": "perf_ledger",
        "microbatch": MB,
        "accum": ACCUM,
        "step_ms_measured": round(step_s * 1e3, 1),
        "step_ms_sum_of_parts": round(parts_ms, 1),
        "fusion_dividend_ms": round(step_s * 1e3 - parts_ms, 1),
        "samples_per_sec_per_chip": round(samples / step_s, 3),
        "compiles_total": comp["total_compiles"],
        "compile_seconds_total": comp["total_compile_s"],
        "recompiles_after_warmup": comp["recompiles_after_warmup"],
        "model_flops_utilization": round(mfu, 6),
        "hbm_bandwidth_utilization": round(bw_util, 6),
        "frozen_compute": build_info["frozen_compute"],
        "frozen_layers": build_info["frozen_layers"],
        "ledger": ledger,
    }
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()
