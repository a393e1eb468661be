"""PR 41: the IN pass alone (``ops/rope.heads_in``: q/k norms, rope and the head-major layout as one Pallas kernel each
way) against the XLA form it replaces (``rms_norm`` + ``apply_rope`` + the three transposes to the layout the flash
kernels read, and their autodiff), at the shapes the cells run: ms a call forward and forward + backward, beside the
bytes' time at the HBM peak, and the distance between the two forms. First every shape at a tiny size under a
watchdog (a kernel that passes the interpreter and the deviceless compile can still never return on the chip). The
variants patch ``ops/rope`` in this process only.

    chiprun --timeout 900 -- python benchmarks/calls/pr41_pass.py [--variants] [shape ...]
"""
import faulthandler
import json
import os
import sys
import time

faulthandler.dump_traceback_later(90, exit=True)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from llm_fine_tune_distributed_tpu.ops import rope  # noqa: E402
from llm_fine_tune_distributed_tpu.ops.norms import rms_norm  # noqa: E402

HBM_BYTES_PER_S = 819e9
# a microbatch of each cell that runs _heads_qkv: rows, seq, q heads, kv heads, head, table width, gate, norm, zero-centred
SHAPES = {
    "trinity": (2, 8192, 32, 4, 128, 128, True, True, False),
    "trinity_nope": (2, 8192, 32, 4, 128, 128, True, True, False),
    "mellum": (4, 8192, 32, 4, 128, 128, False, False, False),
    "smollm3": (2, 1024, 16, 4, 128, 128, False, False, False),
    "mistral": (1, 2048, 32, 8, 128, 128, False, False, False),
    "qwen3next": (2, 8192, 16, 2, 256, 64, True, True, True),
}
LIBRARY = {name: getattr(rope, name) for name in ("ROWS", "_STEP_BYTES", "_MAX_HEADS_A_STEP")}
VARIANTS = {
    "library": {},
    "rows128": {"ROWS": 128},
    "rows512": {"ROWS": 512},
    "step1m": {"_STEP_BYTES": 1 << 20},
    "step4m": {"_STEP_BYTES": 4 << 20},
    "heads4": {"_MAX_HEADS_A_STEP": 4},
    "heads2": {"_MAX_HEADS_A_STEP": 2},
}


def xla_form(xq, xk, xv, cos, sin, wq, wk, *, heads, kv, gated, zc, do_rope):
    """What ``models/transformer._heads_qkv`` and ``pallas_flash_attention`` did between them before the pass."""
    b, s, _ = xk.shape
    d = xk.shape[2] // kv
    gate = None
    if gated:
        q = xq.reshape(b, s, heads, 2 * d)
        q, gate = q[..., :d], q[..., d:].reshape(b, s, heads * d)
    else:
        q = xq.reshape(b, s, heads, d)
    k, v = xk.reshape(b, s, kv, d), xv.reshape(b, s, kv, d)
    if wq is not None:
        q, k = rms_norm(q, wq, 1e-6, zero_centered=zc), rms_norm(k, wk, 1e-6, zero_centered=zc)
    if do_rope:
        q, k = rope.apply_rope(q, k, cos, sin)
    return q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), gate


def fused_form(xq, xk, xv, cos, sin, wq, wk, *, heads, kv, gated, zc, do_rope):
    """The pass over flat q, k, v. ``xq`` is q alone: a gated layer's gate is a product of its own in the program
    (``models/transformer._heads_qkv_head_major`` cuts the leaf) and never meets the pass."""
    mult = lambda w: None if w is None else (1.0 + w if zc else w)  # noqa: E731
    return (*rope.heads_in(xq, xk, xv, cos, sin, heads=heads, kv_heads=kv, q_weight=mult(wq), k_weight=mult(wk),
                           rope=do_rope), None)


def q_columns(x, heads):
    """A gated layer's ``[q | gate]`` by head -> q's columns."""
    b, s, _ = x.shape
    return x.reshape(b, s, heads, 2, -1)[:, :, :, 0].reshape(b, s, -1)


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / iters * 1e3, 4)


def inputs(name, seq=None):
    b, s, heads, kv, d, width, gated, norm, zc = SHAPES[name]
    s = seq or s
    ks = jax.random.split(jax.random.key(41), 8)
    xq = jax.random.normal(ks[0], (b, s, heads * d * (2 if gated else 1)), jnp.bfloat16)
    xk, xv = (jax.random.normal(k, (b, s, kv * d), jnp.bfloat16) for k in ks[1:3])
    cos, sin = rope.rope_cos_sin(jnp.broadcast_to(jnp.arange(s)[None], (b, s)), width, 10000.0)
    wq = wk = None
    if norm:
        wq, wk = (0.1 * jax.random.normal(k, (d,), jnp.float32) + (0.0 if zc else 1.0) for k in ks[3:5])
    static = dict(heads=heads, kv=kv, gated=gated, zc=zc, do_rope=not name.endswith("_nope"))
    cts = [jax.random.normal(ks[5], (b, heads, s, d), jnp.bfloat16), jax.random.normal(ks[6], (b, kv, s, d), jnp.bfloat16),
           jax.random.normal(ks[7], (b, kv, s, d), jnp.bfloat16)] + ([jax.random.normal(ks[5], (b, s, heads * d), jnp.bfloat16)] if gated else [None])
    return (xq, xk, xv, cos, sin, wq, wk), tuple(cts), static


def programs(form, static):
    def fwd(*args):
        return form(*args, **static)[:3]  # (the XLA form's gate is a view of xq, the pass never sees one)

    def both(args, cts):
        out, vjp = jax.vjp(lambda xq, xk, xv, wq, wk: form(xq, xk, xv, args[3], args[4], wq, wk, **static), *args[:3], *args[5:])
        return out[:3], vjp(cts)

    return jax.jit(fwd), jax.jit(both)


def distance(a, b):
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        worst = max(worst, float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-9)))
    return worst


def one(name, variant, iters=20, seq=None):
    for key, value in {**LIBRARY, **VARIANTS[variant]}.items():
        setattr(rope, key, value)
    jax.clear_caches()
    args, cts, static = inputs(name, seq)
    b, s, heads, kv, d, *_ = SHAPES[name]
    s = seq or s
    moved = 2 * 2 * b * s * (heads + 2 * kv) * d                       # q, k, v read and written once, bfloat16
    line = {"shape": name, "variant": variant, "rows": b, "seq": s, "fwd_bound_ms": round(moved / HBM_BYTES_PER_S * 1e3, 4)}
    try:
        outs = {}
        gated = static["gated"]
        for label, form in (("xla", xla_form), ("fused", fused_form)):
            if label == "fused" and gated:
                args, cts = (q_columns(args[0], heads), *args[1:]), (*cts[:3], None)
            fwd, both = programs(form, static)
            line[f"{label}_fwd_ms"] = timed(fwd, args, iters)
            line[f"{label}_fwd_bwd_ms"] = timed(both, (args, cts), iters)
            outs[label] = both(args, cts)
        if gated:  # the XLA form's dxq holds the gate's cotangent beside dq
            y, (dxq, *rest) = outs["xla"]
            outs["xla"] = (y, (q_columns(dxq, heads), *rest))
        line["distance"] = distance(outs["fused"], outs["xla"])
        line["fwd_of_bound_pct"] = round(100 * line["fwd_bound_ms"] / line["fused_fwd_ms"], 1)
    except Exception as e:  # noqa: BLE001 — a refusal is the reading
        line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    print(json.dumps(line), flush=True)


def main(argv) -> int:
    variants = ["library"]
    if "--variants" in argv:
        argv.remove("--variants")
        variants = list(VARIANTS)
    names = argv or list(SHAPES)
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for name in names:                                   # tiny first, under the 90 s watchdog
        one(name, "library", iters=2, seq=256)
    faulthandler.cancel_dump_traceback_later()
    faulthandler.dump_traceback_later(800, exit=True)
    for name in names:
        for variant in variants:
            one(name, variant)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
