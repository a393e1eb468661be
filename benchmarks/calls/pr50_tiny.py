"""PR 50: the state-space scan's two sweeps on the chip before the cell leans on them, under a watchdog (a kernel that
passes the interpreter and the deviceless compile can still never return: PERF.md, PR 31). At a small size and at the
cell's (1 row of 8192, 64 heads of 64, a state of 128): the sweeps against the XLA form, output and every cotangent
(dt's and ``a``'s are the ones this PR's sweeps write), and a fast head beside a slow one in one lane block at dt near 1
(the running sum through the MXU must stay float32: the interpreter on a CPU cannot show a bfloat16 pass). Then ms a
call, the two custom calls alone and the scan through ``ssd_scan`` forward and forward + backward beside the XLA form:
a smoke test and a first look, NOT the verdict (PERF.md, PR 49's review round: a scan's form is judged in the cell).
``chiprun --timeout 900 -- python benchmarks/calls/pr50_tiny.py`` (``benchmarks/calls/pr50_first.sh`` runs it in front of the cell)"""
import faulthandler
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
faulthandler.dump_traceback_later(300, exit=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from llm_fine_tune_distributed_tpu.ops import ssd  # noqa: E402

print(jax.devices(), flush=True)
rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32)) / jnp.linalg.norm(b.astype(jnp.float32)))  # noqa: E731
NAMES = ("x", "dt", "a", "b", "c", "d")
FAR = []  # comparisons that stand further apart than bfloat16 products explain: the script exits 1 and the cell is not run


def operands(b, s, heads, p, n, seed=0, a=None, dt_near_one=False):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x, w = (jax.random.normal(k[i], (b, s, heads, p), jnp.float32).astype(jnp.bfloat16) for i in (0, 6))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, heads), jnp.float32) - 3.0)
    if dt_near_one:
        dt = 1.0 + 0.1 * jax.random.uniform(k[1], (b, s, heads), jnp.float32, -1.0, 1.0)
    a = -jnp.arange(1, heads + 1, dtype=jnp.float32) if a is None else jnp.asarray(a, jnp.float32)
    bm, cm = (jax.random.normal(k[i], (b, s, 1, n), jnp.float32).astype(jnp.bfloat16) for i in (3, 4))
    return x, dt, a, bm, cm, jnp.ones((heads,), jnp.float32), w


def run(impl):
    def fn(x, dt, a, bm, cm, d, w):
        def loss(x, dt, a, bm, cm, d):
            y = ssd.ssd_scan(x, dt, a, bm, cm, d, impl=impl)
            return jnp.sum(y.astype(jnp.float32) * w.astype(jnp.float32)), y
        (_, y), grads = jax.value_and_grad(loss, argnums=range(6), has_aux=True)(x, dt, a, bm, cm, d)
        return y, grads
    return jax.jit(fn)


kern, xla = run("kernels"), run("xla")


def both(tag, args):
    t0 = time.time()
    (y_k, g_k), (y_x, g_x) = jax.block_until_ready(kern(*args)), jax.block_until_ready(xla(*args))
    finite = bool(jnp.isfinite(y_k.astype(jnp.float32)).all()) and all(bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in g_k)
    y_rel, grads_rel = rel(y_k, y_x), dict(zip(NAMES, (rel(p, q) for p, q in zip(g_k, g_x))))
    if not (finite and y_rel < 1e-3 and max(grads_rel.values()) < 1e-2):  # (PR 49 read 9e-6 and up to 6e-4 on the chip)
        FAR.append(tag)
    print(json.dumps({tag: list(args[0].shape), "seconds": round(time.time() - t0, 1), "finite": finite, "y_rel": y_rel, "grads_rel": grads_rel}), flush=True)


both("small", operands(2, 2048, 4, 64, 128))
both("one token over a step", operands(1, 1025, 4, 64, 128, seed=2))
both("a fast head beside a slow one, dt near 1", operands(1, 2048, 2, 64, 128, seed=3, a=[-64.0, -1.0], dt_near_one=True))
both("heads of 128", operands(1, 2048, 2, 128, 128, seed=4))
faulthandler.cancel_dump_traceback_later()
faulthandler.dump_traceback_later(500, exit=True)

args = operands(1, 8192, 64, 64, 128, seed=1)
both("cell_shape", args)


def timed(fn, *z, n=10):
    jax.block_until_ready(fn(*z))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*z)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


x, dt, a, bm, cm, d, w = args
flat = lambda z: z.reshape(1, 8192, -1)  # noqa: E731
own = (flat(x), dt, a.reshape(1, 64), flat(bm), flat(cm), jnp.ones((32, 1, 128), jnp.float32))
fwd = lambda *z: ssd.ssd_scan_fwd(*z, p=64, state_dtype=jnp.dtype("float32"), interpret=False)  # noqa: E731
y, states = fwd(*own)
bwd = lambda *z: ssd.ssd_scan_bwd(*z, p=64, interpret=False)  # noqa: E731
forward = {impl: jax.jit(lambda *z, impl=impl: ssd.ssd_scan(*z[:6], impl=impl)) for impl in ("kernels", "xla")}
print(json.dumps({"cell_shape_ms": {"ssd_scan_fwd alone": timed(fwd, *own), "ssd_scan_bwd alone": timed(bwd, *own, flat(w), states),
                                    "fwd": {k: timed(f, *args) for k, f in forward.items()},
                                    "fwd_bwd": {"kernels": timed(kern, *args), "xla": timed(xla, *args)}}}), flush=True)
faulthandler.cancel_dump_traceback_later()
print(json.dumps({"far": FAR}), flush=True)
sys.exit(1 if FAR else 0)
