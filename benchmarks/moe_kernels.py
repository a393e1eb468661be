#!/usr/bin/env python3
"""Kernels of the latent-attention, routed-experts path alone, on the chip, at
the shapes of ``moonlight-16b-a3b-ep8-d6.sft-4k-allparams``: which grouped
product (``jax.lax.ragged_dot`` or megablox ``gmm`` at several tilings) and
which flash layout (q/k padded to 256 lanes, or 192 as it lies) is faster.
Wall time of forward and forward+backward over ``--iters`` calls that end in
``block_until_ready``; a builder's tool, the benchmark never runs it.
``--sum`` times what sums a chunk's rows back into their tokens instead
(``ops/moe._sum_into_tokens``: the masked-gather loop against the kernel, at
both expert cells' shapes, float32 and bfloat16 rows) and holds the kernel to
the loop's bits on the chip.

    chiprun -- python benchmarks/moe_kernels.py [--sum]
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from llm_fine_tune_distributed_tpu.ops import flash_attention as fa
from llm_fine_tune_distributed_tpu.ops import moe
from llm_fine_tune_distributed_tpu.runtime.device import on_accelerator


def timed(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def grouped(rows, fill, iters):
    """One expert SwiGLU's three products over ``rows`` sorted pairs of which
    ``fill`` are real, 8 held experts of 2048 x 1408."""
    key = jax.random.key(0)
    x = jax.random.normal(key, (rows, 2048), jnp.bfloat16)
    w1, w3 = (jax.random.normal(jax.random.fold_in(key, i), (8, 2048, 1408), jnp.bfloat16) * 0.02 for i in (1, 2))
    w2 = jax.random.normal(jax.random.fold_in(key, 3), (8, 1408, 2048), jnp.bfloat16) * 0.02
    real = int(rows * fill)
    sizes = jnp.asarray(np.diff(np.linspace(0, real, 9).astype(np.int32)), jnp.int32)
    out = {}
    tilings = {"ragged_dot": None, "gmm_128": (128, 128, 128), "gmm_512_1024_1024": (512, 1024, 1024),
               "gmm_512_2048_1408": (512, 2048, 1408), "gmm_1024_1024_1408": (1024, 1024, 1408),
               "gmm_256_2048_1408": (256, 2048, 1408)}
    for name, tiling in tilings.items():
        if tiling is not None:
            moe.GMM_TILING = tiling
        impl = "ragged_dot" if tiling is None else "gmm"

        def swiglu(x, w1, w3, w2):
            act = jax.nn.silu(moe.grouped_matmul(x, w1, sizes, impl=impl)) * moe.grouped_matmul(x, w3, sizes, impl=impl)
            y = moe.grouped_matmul(act, w2, sizes, impl=impl)
            return jnp.where((jnp.arange(rows) < real)[:, None], y, 0)

        loss = lambda *a: swiglu(*a).astype(jnp.float32).sum()  # noqa: E731
        try:
            out[name] = {"fwd_ms": timed(jax.jit(swiglu), (x, w1, w3, w2), iters),
                         "fwd_bwd_ms": timed(jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))), (x, w1, w3, w2), iters)}
        except Exception as e:  # noqa: BLE001: a tiling Mosaic refuses is a finding, not a failure
            out[name] = {"error": str(e).splitlines()[0][:300]}
        print(json.dumps({"grouped": name, "rows": rows, "fill": fill, **out[name]}), flush=True)
    return out


def flash(batch, seq, iters):
    key = jax.random.key(1)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (batch, seq, 16, 192), jnp.bfloat16) for i in (1, 2))
    v = jax.random.normal(jax.random.fold_in(key, 3), (batch, seq, 16, 128), jnp.bfloat16)

    def native(q, k, v):  # 192 lanes as they lie: the wrapper's pad left out
        fn = fa._make_flash_fn(float(192 ** -0.5), fa._pick_block(seq), 1, False)
        o = fn(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), jnp.ones((batch, seq), jnp.int32))
        return o.transpose(0, 2, 1, 3)

    for name, fn in (("pad_256", fa.pallas_flash_attention), ("native_192", native)):
        loss = lambda q, k, v, fn=fn: fn(q, k, v).astype(jnp.float32).sum()  # noqa: E731
        try:
            row = {"fwd_ms": timed(jax.jit(fn), (q, k, v), iters),
                   "fwd_bwd_ms": timed(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (q, k, v), iters)}
        except Exception as e:  # noqa: BLE001
            row = {"error": str(e).splitlines()[0][:300]}
        print(json.dumps({"flash": name, "batch": batch, "seq": seq, **row}), flush=True)


def check_flash(seq=1024):
    """The kernels at 192/128 against XLA attention on the chip: largest
    error of o, dq, dk, dv against the largest value (chip_smoke's bound for
    the dense shapes is 2e-2)."""
    from llm_fine_tune_distributed_tpu.ops.attention import xla_attention

    key = jax.random.key(2)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (1, seq, 16, 192), jnp.bfloat16) for i in (1, 2))
    v, do = (jax.random.normal(jax.random.fold_in(key, i), (1, seq, 16, 128), jnp.bfloat16) for i in (3, 4))
    outs = {}
    for name, fn in (("flash", fa.pallas_flash_attention), ("xla", xla_attention)):
        o, vjp = jax.vjp(lambda q, k, v, fn=fn: fn(q, k, v), q, k, v)
        outs[name] = (o,) + vjp(do)
    errs = {n: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for n, a, b in zip(("o", "dq", "dk", "dv"), outs["flash"], outs["xla"])}
    print(json.dumps({"flash_vs_xla_rel_err": errs, "seq": seq}), flush=True)


# (rows of a chunk, hidden, tokens a microbatch, choices, routed, held): the first chunk of each expert cell
SUM_SHAPES = {"mellum2-12b-a2.5b-ep4-d4": (98304, 2304, 32768, 8, 64, 16), "moonlight-16b-a3b-ep8-d6": (16384, 2048, 16384, 6, 64, 8)}


def sum_into_tokens(iters, seed=0):
    """The loop and the kernel over one routing drawn as ``grouped_moe_mlp``
    sorts it (k of the routed experts a token, uniformly), ms a call; the
    kernel's least time is its bytes over the chip's 819 GB/s: the hits' rows
    read once, the tokens' sums written once."""
    rng = np.random.default_rng(seed)
    for cell, (c, h, t, k, routed, held) in SUM_SHAPES.items():
        top_i = np.argsort(rng.random((t, routed)), axis=1)[:, :k]
        local = np.where(top_i < held, top_i, held).reshape(-1)
        rank = np.argsort(np.argsort(local, kind="stable")).astype(np.int32).reshape(t, k)
        ends = np.clip(np.cumsum((local[:, None] == np.arange(held)).sum(0)), 0, c).astype(np.int32)
        rank, ends = jnp.asarray(rank), jnp.asarray(ends)
        for dtype in (jnp.float32, jnp.bfloat16):
            rows = jax.random.normal(jax.random.key(seed), (c, h), dtype)
            row = {"sum_into_tokens": cell, "rows": jnp.dtype(dtype).name, "pairs_a_token": round(int(ends[-1]) / t, 4),
                   "refused": moe.sum_kernel_refused(rows, rank, ends)}
            loop = jax.jit(functools.partial(moe._sum_into_tokens, impl="loop"))
            row["loop_ms"] = timed(loop, (rows, rank, ends), iters)
            if row["refused"] is None:
                kernel = functools.partial(moe._sum_into_tokens, impl="kernel")
                row["kernel_ms"] = timed(kernel, (rows, rank, ends), iters)
                plan = jax.jit(functools.partial(moe._sum_plan, tile=moe.SUM_TILE, block=moe._block_rows(dtype)))
                row["plan_ms"] = timed(plan, (rank, ends), iters)
                first, blocks, _ = plan(rank, ends)
                row["rows_fetched_a_row_used"] = round(int(blocks.sum()) * moe._block_rows(dtype) / int(ends[-1]), 3)
                least = (int(ends[-1]) * h * jnp.dtype(dtype).itemsize + t * h * 4) / 819e9 * 1e3
                row["least_ms"], row["share_of_least_pct"] = round(least, 3), round(100 * least / row["kernel_ms"], 1)
                same = jnp.array_equal(*(jax.lax.bitcast_convert_type(f(rows, rank, ends), jnp.int32) for f in (loop, kernel)))
                row["same_bits"] = bool(same)
            print(json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--only-check", action="store_true", help="the flash kernels against XLA attention, nothing timed")
    ap.add_argument("--sum", action="store_true", help="the expert layer's sum into tokens alone: loop against kernel")
    args = ap.parse_args()
    if not on_accelerator(jax.devices()[0].platform):
        print("moe_kernels: no accelerator; timings of a CPU are not rates", file=sys.stderr)
        return 2
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    if args.sum:
        sum_into_tokens(args.iters)
        return 0
    check_flash()
    if args.only_check:
        return 0
    for fill in (0.75, 1.0):
        grouped(args.rows, fill, args.iters)
    flash(2, 4096, args.iters)
    return 0


if __name__ == "__main__":
    sys.exit(main())
