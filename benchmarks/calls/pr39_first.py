"""PR 39: the four new kernels' FIRST run on the chip, at a tiny size, under a watchdog (PR 31 lost two calls to a kernel
that passed the interpreter and the deviceless compile and never returned): each pass compiled by Mosaic against its
XLA form, output and every cotangent, in bfloat16 and in float32; rows of one token block, of three (the halo crosses
a block both ways) and of no whole block.

    chiprun -- python benchmarks/calls/pr39_first.py
"""
import faulthandler
import json
import os
import sys

faulthandler.dump_traceback_later(90, exit=True)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from llm_fine_tune_distributed_tpu.ops import gated_delta as gd  # noqa: E402


def rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def main() -> int:
    for rows, seq, hk, r, dtype in ((1, 512, 1, 2, jnp.bfloat16), (2, 1536, 2, 2, jnp.bfloat16), (2, 700, 1, 1, jnp.float32), (2, 4096, 2, 2, jnp.float32)):
        faulthandler.dump_traceback_later(90, exit=True)
        d = 128
        ks = jax.random.split(jax.random.key(seq), 8)
        act = lambda key, width: jax.random.normal(key, (rows, seq, width), jnp.float32).astype(dtype)  # noqa: E731,B023
        x_in = (act(ks[0], hk * d), act(ks[1], hk * d), act(ks[2], hk * r * d), (jax.random.normal(ks[3], (4, (2 + r) * hk * d)) * 0.5).astype(dtype))
        x_out = (act(ks[4], hk * r * d), act(ks[5], hk * r * d), (1 + 0.3 * jax.random.normal(ks[6], (d,))).astype(dtype))
        line = {"device": jax.devices()[0].device_kind, "rows": rows, "seq": seq, "hk": hk, "r": r, "dtype": jnp.dtype(dtype).name}
        for name, fn, args in (("in", lambda impl: (lambda *a: gd.mixer_in(*a, hk, impl=impl)), x_in),  # noqa: B023
                               ("out", lambda impl: (lambda *a: gd.gated_norm(*a, 1e-6, impl=impl)), x_out)):
            loss = lambda impl: (lambda *a: sum(jnp.sum(jnp.sin(y.astype(jnp.float32))) for y in jax.tree.leaves(fn(impl)(*a))))  # noqa: E731,B023
            got = jax.jit(fn("kernels"))(*args), jax.jit(jax.grad(loss("kernels"), argnums=tuple(range(len(args)))))(*args)
            want = jax.jit(fn("xla"))(*args), jax.jit(jax.grad(loss("xla"), argnums=tuple(range(len(args)))))(*args)
            line[name] = [round(rel(a, b), 8) for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        print(json.dumps(line), flush=True)
    print(gd.calls_summary())
    return 0


if __name__ == "__main__":
    sys.exit(main())
