"""PR 43: the by-channel sweeps compiled on the chip at a tiny size first (1 row of 1100 tokens, a key head serving 2
value heads of 128), under a watchdog: a kernel that passes the interpreter and the deviceless compile can still never
return on the chip (PR 31). Output and every cotangent against the XLA form, float32 and bfloat16 operands.

    python benchmarks/calls/pr43_tiny.py
"""
import faulthandler
import os
import sys

sys.path.insert(0, os.getcwd())
faulthandler.dump_traceback_later(240, exit=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.gdn_kernels import kda_inputs, rel  # noqa: E402
from llm_fine_tune_distributed_tpu.ops import gated_delta as gd  # noqa: E402

print(jax.devices()[0].device_kind, flush=True)
for dtype in (jnp.float32, jnp.bfloat16):
    q, k, v, g, beta = kda_inputs(1, 1100, 2, 128, dtype)
    x = (q[:, :, :1], k[:, :, :1], v, g, beta)
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        both = lambda impl: jax.jit(jax.value_and_grad(  # noqa: E731
            lambda *a: jnp.sum(jnp.sin(gd.gated_delta_rule(*a, impl=impl).astype(jnp.float32))), argnums=(0, 1, 2, 3, 4)))
        (o, grads), (o_want, want) = both("kernels")(*x), both("xla")(*x)
    print(jnp.dtype(dtype).name, "loss", float(o), float(o_want),
          {n: rel(a, b) for n, a, b in zip("q k v g beta".split(), grads, want)}, flush=True)
