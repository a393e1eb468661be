"""PR 39: the four kernels alone at the cell's shapes (2 rows of 8192, 16 key and 32 value heads of 128, bfloat16), ms a
call, by what a builder can turn: the rows a trip of the inner loop holds, the token block, how the taps shift, how
the sigmoid is made. Each variant patches ``ops/gated_delta`` in this process only; a refusal is the reading. (The
library held 32 rows a trip when ``chiprun_out/pr39_sweep1.jsonl`` was taken: its ``library`` row is today's ``rows32``,
its ``rows256`` today's ``library``.)

    chiprun -- python benchmarks/calls/pr39_sweep.py [variant ...]
"""
import faulthandler
import json
import os
import sys
import time

faulthandler.dump_traceback_later(600, exit=True)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from llm_fine_tune_distributed_tpu.ops import gated_delta as gd  # noqa: E402

LIBRARY = {name: getattr(gd, name) for name in ("ROWS", "_token_block", "_shifted", "_sigmoid")}


def by_tanh(x):
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def by_reciprocal(x):
    d = 1.0 + jnp.exp(-x)
    r = pl.reciprocal(d, approx=True)
    return r * (2.0 - d * r)


VARIANTS = {
    "library": {},
    "rows32": {"ROWS": 32},
    **{f"rows{n}": {"ROWS": n} for n in (64, 128, 512)},
    **{f"rows128_block{t}": {"ROWS": 128, "_token_block": (lambda s, t=t: t)} for t in (512, 1024, 4096)},
    "rows128_slices": {"ROWS": 128, "_shifted": lambda ext, first, rows: ext[first:first + rows]},
    "rows128_tanh": {"ROWS": 128, "_sigmoid": by_tanh},
    "rows128_reciprocal": {"ROWS": 128, "_sigmoid": by_reciprocal},
    "rows256_tanh": {"ROWS": 256, "_sigmoid": by_tanh},
}


def timed(fn, args, iters=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / iters * 1e3, 3)


def main(argv) -> int:
    rows, seq, hk, hv, d = 2, 8192, 16, 32, 128
    ks = jax.random.split(jax.random.key(0), 10)
    act = lambda key, width: jax.random.normal(key, (rows, seq, width), jnp.float32).astype(jnp.bfloat16)  # noqa: E731
    xq, xk, xv, dq, dk, dv, o, z, dy = (act(k, w) for k, w in zip(ks, (hk * d, hk * d, hv * d) * 2 + (hv * d,) * 3))
    w = (0.3 * jax.random.normal(ks[9], (4, (2 * hk + hv) * d))).astype(jnp.bfloat16)
    nw = jnp.ones((d,), jnp.bfloat16)
    for name in argv or VARIANTS:
        for attr, value in {**LIBRARY, **VARIANTS[name]}.items():
            setattr(gd, attr, value)
        jax.clear_caches()
        line = {"device": jax.devices()[0].device_kind, "variant": name}
        for kernel, call in (("in_fwd", lambda: timed(lambda *a: gd.gdn_in_fwd(*a, hk=hk, interpret=False), (xq, xk, xv, w))),
                             ("in_bwd", lambda: timed(lambda *a: gd.gdn_in_bwd(*a, hk=hk, interpret=False), (xq, xk, xv, w, dq, dk, dv))),
                             ("out_fwd", lambda: timed(lambda *a: gd.gdn_out_fwd(*a, eps=1e-6, interpret=False), (o, z, nw))),
                             ("out_bwd", lambda: timed(lambda *a: gd.gdn_out_bwd(*a, eps=1e-6, interpret=False), (o, z, nw, dy)))):
            try:
                line[kernel + "_ms"] = call()
            except Exception as e:  # noqa: BLE001
                line[kernel + "_refused"] = str(e).split("\n")[0][:200]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
