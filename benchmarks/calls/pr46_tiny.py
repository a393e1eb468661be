"""PR 46: EVA attention's kernels on the chip before anything leans on them. At a small size under a watchdog (a
kernel that passes the interpreter and the deviceless compile can still never return: PERF.md, PR 31): the kernels
against the XLA form, output and every cotangent. Then at the cell's shape (1 row of 32,768, 32 heads of 128, windows of
2048, chunks of 16): ms a call forward and forward + backward, kernels alone (the XLA form does not fit there), beside
the roofline's time for the pairs the masks keep (benchmarks/chipbench/flops_eva.py).
``chiprun --timeout 900 -- python benchmarks/calls/pr46_tiny.py``"""
import faulthandler
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
faulthandler.dump_traceback_later(240, exit=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chipbench import flops, flops_eva  # noqa: E402
from llm_fine_tune_distributed_tpu.ops import eva_attention as eva  # noqa: E402

print(jax.devices(), flush=True)


def operands(b, h, t, d, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, w = (jax.random.normal(keys[i], (b, h, t, d), jnp.float32).astype(jnp.bfloat16) for i in (0, 1, 2, 5))
    phi, mu = (jnp.clip(jax.random.normal(keys[i], (h, d), jnp.float32), -1, 1) * d ** -0.5 for i in (3, 4))
    return q, k, v, phi, mu, w


def both(window, chunk, d):
    scale = d ** -0.5

    def run(form):
        def fn(q, k, v, phi, mu, w):
            def loss(q, k, v, phi, mu):
                ks, vs = eva.pool(k, v, phi, mu, chunk=chunk, scale=scale)
                if form == "kernels":
                    o = eva._make_aggregate(window, chunk, scale, False)(q, k, v, ks, vs)
                else:
                    o = eva._aggregate_xla(q, k, v, ks, vs, window=window, chunk=chunk, scale=scale)
                return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o
            (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(q, k, v, phi, mu)
            return o, grads
        return jax.jit(fn)

    return run("kernels"), run("xla")


kern, xla = both(2048, 16, 128)
args = operands(1, 2, 8192, 128)
t0 = time.time()
(o_k, g_k), (o_x, g_x) = jax.block_until_ready(kern(*args)), jax.block_until_ready(xla(*args))
rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32)) / jnp.linalg.norm(b.astype(jnp.float32)))  # noqa: E731
print(json.dumps({"small": [1, 2, 8192, 128], "seconds": round(time.time() - t0, 1), "o_rel": rel(o_k, o_x),
                  "grads_rel": dict(zip(("q", "k", "v", "phi", "mu"), (rel(a, b) for a, b in zip(g_k, g_x))))}), flush=True)
faulthandler.cancel_dump_traceback_later()
faulthandler.dump_traceback_later(500, exit=True)

cfg = dict(num_attention_heads=32, head_dim=128, window_size=2048, chunk_size=16)
args = operands(1, 32, 32768, 128, seed=1)
scale = 128 ** -0.5


@jax.jit
def forward(q, k, v, phi, mu, w):
    ks, vs = eva.pool(k, v, phi, mu, chunk=16, scale=scale)
    return eva._make_aggregate(2048, 16, scale, False)(q, k, v, ks, vs)


def timed(fn, n=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


peaks = json.load(open("benchmarks/chipbench/peaks.json"))[jax.devices()[0].device_kind]
need = flops.roofline_seconds(flops_eva.eva_agg_fwd_cost(1, 32768, cfg), peaks)
print(json.dumps({"cell_shape": [1, 32, 32768, 128], "fwd_ms": timed(forward), "fwd_bwd_ms": timed(kern),
                  "roofline_fwd_ms": 1e3 * need["seconds"], "bound": need["bound"]}), flush=True)
