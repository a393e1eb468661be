"""PR 50: the forward sweep alone at the cell's shape (1 row of 8192, 64 heads of 64, a state of 128) under other ways of
making the per-token scalars' rows at a step's first block of heads, monkeypatched over ``ops/ssd._make_rows`` (an
experiment: the program has ONE way, ``landed``): ``logstep``, the running sum as a log-step shifted add over the
tokens and ONE ``[C, C]`` transpose of ``[dt | G]``; ``default_precision`` (WRONG: a bfloat16 ``G``) and ``nothing``
(WRONG: no rows made), which only price the ``HIGHEST`` passes and the whole of what ``block == 0`` takes on. ms a call
of ``ssd_scan_fwd`` and, for the two sound forms, the output against the XLA form's. Standalone readings: a first look,
not the verdict (PERF.md, PR 49's review round).
``chiprun --timeout 600 -- python benchmarks/calls/pr50_forms.py``"""
import faulthandler
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
faulthandler.dump_traceback_later(400, exit=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from llm_fine_tune_distributed_tpu.ops import ssd  # noqa: E402

C = ssd.CHUNK


def logstep(dt_ref, a_ref, dtr_ref, g_ref):
    """A log-step shifted add over the tokens (sublanes), then ONE [C, C] transpose of [dt | G] (64 heads: 2 x 64 lanes)."""
    heads = dt_ref.shape[2]
    ti = jax.lax.broadcasted_iota(jnp.int32, (C, heads), 0)
    def one(c, _):
        dt = dt_ref[0, ssd._rows(c), :]
        g = dt * a_ref[...]
        k = 1
        while k < C:
            g = g + jnp.where(ti >= k, pltpu.roll(g, k, axis=0), 0.0)
            k *= 2
        both = jnp.concatenate([dt, g], axis=1)
        if both.shape[1] < C:
            both = jnp.concatenate([both, jnp.zeros((C, C - both.shape[1]), jnp.float32)], axis=1)
        t = both.T
        dtr_ref[c] = t[:heads].reshape(dtr_ref.shape[1:])
        g_ref[c] = t[heads:2 * heads].reshape(g_ref.shape[1:])
        return 0
    jax.lax.fori_loop(0, ssd.STEP_CHUNKS, one, 0)


def default_precision(dt_ref, a_ref, dtr_ref, g_ref):
    """The products at the MXU's default precision: WRONG (bfloat16 G), only to price the HIGHEST passes."""
    upper, same = ssd._zero_ones()
    def one(c, _):
        dt = dt_ref[0, ssd._rows(c), :]
        dtr_ref[c] = ssd._dot_tn(dt, same).reshape(dtr_ref.shape[1:])
        g_ref[c] = ssd._dot_tn(dt * a_ref[...], upper).reshape(g_ref.shape[1:])
        return 0
    jax.lax.fori_loop(0, ssd.STEP_CHUNKS, one, 0)


def nothing(dt_ref, a_ref, dtr_ref, g_ref):
    """No rows made (garbage in scratch): WRONG, only to price what block == 0 takes on."""
    dtr_ref[...] = jnp.full(dtr_ref.shape, 0.01, jnp.float32)
    g_ref[...] = jnp.full(g_ref.shape, -0.01, jnp.float32)

VARIANTS = {"landed": ssd._make_rows, "logstep": logstep, "default_precision": default_precision, "nothing": nothing}


def use(name):
    ssd._make_rows = VARIANTS[name]
    ssd._flat_scan.cache_clear()
    ssd.ssd_scan_fwd.clear_cache()
    ssd.ssd_scan_bwd.clear_cache()


def main():
    print(jax.devices(), flush=True)
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(k[0], (1, 8192, 64, 64), jnp.float32).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, 8192, 64), jnp.float32) - 3.0)
    a = -jnp.arange(1, 65, dtype=jnp.float32)
    bm, cm = (jax.random.normal(k[i], (1, 8192, 1, 128), jnp.float32).astype(jnp.bfloat16) for i in (2, 3))
    d = jnp.ones((64,), jnp.float32)
    want = jax.jit(lambda *z: ssd.ssd_scan(*z, impl="xla"))(x, dt, a, bm, cm, d).astype(jnp.float32)
    own = (x.reshape(1, 8192, -1), dt, a.reshape(1, 64), bm.reshape(1, 8192, -1), cm.reshape(1, 8192, -1), jnp.ones((32, 1, 128), jnp.float32))
    for name in sys.argv[1:] or [*VARIANTS, "landed"]:  # (the first form timed read 19 ms in the first call, the same kernel 1.15 in pr50_tiny.py: once more, last)
        use(name)
        fwd = lambda *z: ssd.ssd_scan_fwd(*z, p=64, state_dtype=jnp.dtype("float32"), interpret=False)  # noqa: E731
        y = jax.block_until_ready(fwd(*own))[0]
        t0 = time.perf_counter()
        for _ in range(20):
            out = fwd(*own)
        jax.block_until_ready(out)
        gap = float(jnp.linalg.norm(y.astype(jnp.float32).reshape(want.shape) - want) / jnp.linalg.norm(want))
        print(json.dumps({"form": name, "ssd_scan_fwd_ms": 1e3 * (time.perf_counter() - t0) / 20, "y_rel_to_xla": gap}), flush=True)


if __name__ == "__main__":
    main()
