"""PR 42: the delta rule with a decay a channel alone on the chip, at the Kimi cell's shapes (2 rows of 8192, 32 heads
of 128): forward and forward + backward, ms a call, and XLA's triangular inverse alone at one row's batch. A builder's
tool, nothing runs it. (My chip run, PR 42: the inverse 3.98 / 5.70 ms, the rule 36.35 / 152.82 ms; the same inverse
by levels of float32 products at HIGHEST, the kernels' way, read 7.44 / 22.50 and the rule with it 44.86 / 214.17: the
solve stays.)

    python benchmarks/calls/pr42_rule.py
"""
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from llm_fine_tune_distributed_tpu.ops import gated_delta as gd  # noqa: E402


def timed(fn, args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / n


b, s, h, d = 2, 8192, 32, 128
ks = jax.random.split(jax.random.PRNGKey(0), 6)
q = gd.l2_norm(jax.random.normal(ks[0], (b, s, h, d))).astype(jnp.bfloat16)
k = gd.l2_norm(jax.random.normal(ks[1], (b, s, h, d))).astype(jnp.bfloat16)
v = jax.random.normal(ks[2], (b, s, h, d)).astype(jnp.bfloat16)
g = -jnp.exp(jax.random.uniform(ks[3], (b, s, h, d), minval=jnp.log(1e-3), maxval=jnp.log(1.6)))
beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
a = jnp.tril(0.3 * jax.random.normal(ks[5], (128, h, 64, 64)), -1)
print(jax.devices()[0].device_kind, flush=True)
alone = jax.jit(gd.unit_lower_inverse)
alone_both = jax.jit(jax.grad(lambda x: jnp.sum(jnp.sin(gd.unit_lower_inverse(x)))))
fwd = jax.jit(lambda *x: gd.gated_delta_rule(*x))
both = jax.jit(jax.grad(lambda *x: jnp.sum(jnp.sin(gd.gated_delta_rule(*x).astype(jnp.float32))), argnums=(0, 1, 2, 3, 4)))
print("inverse alone [128, 32, 64, 64] fwd %.2f ms, fwd+bwd %.2f ms; rule fwd %.2f ms, fwd+bwd %.2f ms" % (
    timed(alone, (a,)), timed(alone_both, (a,)), timed(fwd, (q, k, v, g, beta)), timed(both, (q, k, v, g, beta))), flush=True)
