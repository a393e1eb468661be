"""PR 49: the state-space scan's two sweeps and the flash kernels at heads of 64 on the chip before anything leans on
them. At a small size under a watchdog (a kernel that passes the interpreter and the deviceless compile can still never
return: PERF.md, PR 31): the sweeps against the XLA form, output and every cotangent; the flash kernels at 32 / 8 heads
of 64 against XLA attention. Then at the cell's shape (1 row of 8192, 64 heads of 64, a state of 128): ms a call forward
and forward + backward, kernels beside the XLA form.
``chiprun --timeout 900 -- python benchmarks/calls/pr49_tiny.py``"""
import faulthandler
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
faulthandler.dump_traceback_later(240, exit=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from llm_fine_tune_distributed_tpu.ops import ssd  # noqa: E402
from llm_fine_tune_distributed_tpu.ops.attention import xla_attention  # noqa: E402
from llm_fine_tune_distributed_tpu.ops.flash_attention import pallas_flash_attention  # noqa: E402

print(jax.devices(), flush=True)
rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32)) / jnp.linalg.norm(b.astype(jnp.float32)))  # noqa: E731


def operands(b, s, heads, p, n, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x, w = (jax.random.normal(k[i], (b, s, heads, p), jnp.float32).astype(jnp.bfloat16) for i in (0, 6))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, heads), jnp.float32) - 3.0)
    a = -jnp.arange(1, heads + 1, dtype=jnp.float32)
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
args = operands(2, 2048, 4, 64, 128)
t0 = time.time()
(y_k, g_k), (y_x, g_x) = jax.block_until_ready(kern(*args)), jax.block_until_ready(xla(*args))
print(json.dumps({"small": [2, 2048, 4, 64, 128], "seconds": round(time.time() - t0, 1), "y_rel": rel(y_k, y_x),
                  "grads_rel": dict(zip(("x", "dt", "a", "b", "c", "d"), (rel(p, q) for p, q in zip(g_k, g_x))))}), flush=True)


def attention_both(b, s, hq, hkv, d):
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    q, kk, v, w = (jax.random.normal(k[i], (b, s, h, d), jnp.float32).astype(jnp.bfloat16) for i, h in ((0, hq), (1, hkv), (2, hkv), (3, hq)))
    def both(fn):
        def loss(q, kk, v):
            o = fn(q, kk, v)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, kk, v)
    (_, o_f), g_f = both(pallas_flash_attention)
    (_, o_x), g_x = both(lambda q, kk, v: xla_attention(q, kk, v, causal=True))
    return {"o_rel": rel(o_f, o_x), "grads_rel": [rel(p, q) for p, q in zip(g_f, g_x)]}


print(json.dumps({"flash at heads of 64, resident [1, 2048, 32 / 8]": attention_both(1, 2048, 32, 8, 64)}), flush=True)
print(json.dumps({"flash at heads of 64, streamed [1, 8192, 8 / 2]": attention_both(1, 8192, 8, 2, 64)}), flush=True)
faulthandler.cancel_dump_traceback_later()
faulthandler.dump_traceback_later(500, exit=True)

args = operands(1, 8192, 64, 64, 128, seed=1)


def timed(fn, n=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


forward = {impl: jax.jit(lambda *a, impl=impl: ssd.ssd_scan(*a[:6], impl=impl)) for impl in ("kernels", "xla")}
(y_k, g_k), (y_x, g_x) = kern(*args), xla(*args)
print(json.dumps({"cell_shape": [1, 8192, 64, 64, 128], "y_rel": rel(y_k, y_x),
                  "grads_rel": dict(zip(("x", "dt", "a", "b", "c", "d"), (rel(p, q) for p, q in zip(g_k, g_x)))),
                  "fwd_ms": {k: timed(f) for k, f in forward.items()}, "fwd_bwd_ms": {"kernels": timed(kern), "xla": timed(xla)}}), flush=True)
