"""PR 47: the forms of EVA attention's remote forward kernel that were timed against each other, and the timing.

``FORMS`` holds every body tried, each a drop-in for ``ops/eva_attention._remote_fwd_kernel`` (same operands, same
aliases, same grid): ``parent`` (PR 46's: the online softmax continued a tile of 128 summaries at a time, ``m`` and ``l``
``[rows, 1]`` columns); ``tree`` (what the operator runs: the online softmax with ``m`` and ``l`` lane-dense, 4 windows'
summaries behind each rescale and what is left a tile at a time); ``online_8`` / ``online_2`` / ``online_1`` (the
same with 8, 2 or 1 window a trip; ``online_1`` is PR 46's trips without the columns) and ``online_8421`` /
``online_421`` (trips of 8, 4, 2 and 1 in turn); ``two_sweeps`` (the form ISSUE 47 expected: no online state, the
maximum first, then one plain sweep), ``two_sweeps_scaled_max`` (as this PR first wrote it) and ``two_sweeps_rows_2``
/ ``_4`` (each product cut by rows into products side by side). Only ``tree`` is reachable from the operator; the
others exist here. The first call's log (``chiprun_out/pr47_forms.log``) names the forms as they stood then: its
``two_sweeps`` is ``two_sweeps`` here, its ``online_4`` is ``tree``.

On the chip, under a watchdog (a kernel that passes the interpreter and the deviceless compile can still never return:
PERF.md, PR 31): each form against the XLA form at ``[1, 2, 8192, 128]`` (output; the tree's form every cotangent too),
then at the cell's shape (1 row of 32,768, 32 heads of 128, windows of 2048, chunks of 16) ms a call of
``eva_remote_fwd`` alone from a profiler trace by the kernel's name, and of pooling + aggregate forward by the host's
clock (PR 46 read 31.05 ms there).
``chiprun --timeout 1500 -- python benchmarks/calls/pr47_forms.py [FORM ...]``; ``--rehearse`` runs the forms under
the Pallas interpreter on a CPU at a tiny size against the XLA form and times nothing."""
import faulthandler
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from llm_fine_tune_distributed_tpu.ops import eva_attention as eva  # noqa: E402
from llm_fine_tune_distributed_tpu.ops.flash_attention import _operand  # noqa: E402

_NEG_INF = eva._NEG_INF


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), preferred_element_type=jnp.float32)


def parent(q_ref, ks_ref, vs_ref, o_in_ref, lse_in_ref, o_ref, lse_ref, *, scale, per, blocks_a_window):
    """PR 46's body, verbatim."""
    w = eva._windows_seen(pl.program_id(2) // blocks_a_window)
    q = _operand(q_ref[0, 0])

    def tile(c, carry):
        m, l, acc = carry
        keys = pl.ds(pl.multiple_of(c * per, per), per)
        v_blk = _operand(vs_ref[0, 0, keys, :])
        s = _dot(q, _operand(ks_ref[0, 0, keys, :]), ((1,), (1,))) * scale
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
        acc = acc * alpha + _dot(p.astype(v_blk.dtype), v_blk, ((1,), (0,)))
        return m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True), acc

    start = (lse_in_ref[0, 0], jnp.ones(lse_in_ref.shape[2:], jnp.float32), o_in_ref[0, 0].astype(jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, w, tile, start)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def two_sweeps(chains=1, scaled_max=False):
    """The form ISSUE 47 expected: no online state, the largest score first (lane-dense, elementwise), then one plain
    sweep of sums under ``m = max(lse_local, that)``; a score tile is computed in both sweeps. ``scaled_max``: the
    first sweep scales its scores before the maximum is taken (one multiply of the tile a trip more; the same bits).
    ``chains``: each product written as that many products of a strip of the rows, side by side in the program's
    text (does one product stay on one MXU?)."""

    def body(q_ref, ks_ref, vs_ref, o_in_ref, lse_in_ref, o_ref, lse_ref, *, scale, per, blocks_a_window):
        w = eva._windows_seen(pl.program_id(2) // blocks_a_window)
        q = _operand(q_ref[0, 0])
        wide, strip = (q.shape[0], per), q.shape[0] // chains

        def strips(x, weights, contract):
            return jnp.concatenate([_dot(x[r * strip:(r + 1) * strip], weights, contract) for r in range(chains)], axis=0)

        def products(c):
            keys = pl.ds(pl.multiple_of(c * per, per), per)
            return keys, strips(q, _operand(ks_ref[0, 0, keys, :]), ((1,), (1,)))

        first = (lambda c, mx: jnp.maximum(mx, products(c)[1] * scale)) if scaled_max else (lambda c, mx: jnp.maximum(mx, products(c)[1]))
        highest = jnp.max(jax.lax.fori_loop(0, w, first, jnp.full(wide, _NEG_INF, jnp.float32)), axis=1, keepdims=True)
        lse_local = lse_in_ref[0, 0]
        # (the largest product IS the largest score: a scale above 0 keeps the order, rounded or not)
        m = jnp.maximum(lse_local, highest if scaled_max else highest * scale)
        alpha = jnp.exp(lse_local - m)
        m_wide = jnp.broadcast_to(m, wide)

        def tile(c, carry):
            sums, acc = carry
            keys, s = products(c)
            p = jnp.exp(s * scale - m_wide)
            return sums + p, acc + strips(p, _operand(vs_ref[0, 0, keys, :]), ((1,), (0,)))

        sums, acc = jax.lax.fori_loop(0, w, tile, (jnp.zeros(wide, jnp.float32), o_in_ref[0, 0].astype(jnp.float32) * alpha))
        l = alpha + jnp.sum(sums, axis=1, keepdims=True)
        o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m + jnp.log(l)

    return body


def online(*groups):
    """The other form: ONE sweep, the online softmax kept, ``groups[0]`` windows' summaries behind each rescale, then
    what is left in trips of ``groups[1]``, ... and at last a tile at a time (each size a copy of the loop's body).
    ``m`` is ``[rows, per]`` with a row's maximum in every lane, ``l`` ``[rows, per]`` sums by lane; heads as wide as a
    tile of summaries (128)."""

    def body(q_ref, ks_ref, vs_ref, o_in_ref, lse_in_ref, o_ref, lse_ref, *, scale, per, blocks_a_window):
        w = eva._windows_seen(pl.program_id(2) // blocks_a_window)
        q = _operand(q_ref[0, 0])
        wide = (q.shape[0], per)
        assert q.shape[1] == per, "acc * alpha is taken lane for lane"

        def trips(n, first, count, carry):
            def tile(c, carry):
                m, l, acc = carry
                keys = pl.ds(pl.multiple_of((first + c * n) * per, per), n * per)
                v_blk = _operand(vs_ref[0, 0, keys, :])
                s = _dot(q, _operand(ks_ref[0, 0, keys, :]), ((1,), (1,))) * scale
                parts = [s[:, g * per:(g + 1) * per] for g in range(n)]
                top = parts[0]
                for part in parts[1:]:
                    top = jnp.maximum(top, part)
                m_new = jnp.maximum(m, jnp.broadcast_to(jnp.max(top, axis=1, keepdims=True), wide))
                alpha = jnp.exp(m - m_new)
                ps = [jnp.exp(part - m_new) for part in parts]
                l = l * alpha
                for p in ps:
                    l = l + p
                p = ps[0] if n == 1 else jnp.concatenate(ps, axis=1)
                return m_new, l, acc * alpha + _dot(p.astype(v_blk.dtype), v_blk, ((1,), (0,)))

            return jax.lax.fori_loop(0, count, tile, carry)

        lse_local = lse_in_ref[0, 0]
        carry = (jnp.broadcast_to(lse_local, wide), jnp.zeros(wide, jnp.float32), o_in_ref[0, 0].astype(jnp.float32))
        done = 0
        for group in groups:
            count = (w - done) // group
            carry = trips(group, done, count, carry)
            done = done + count * group
        m, l, acc = carry
        m = m[:, :1]
        # the local source's l = 1 has been rescaled with everything else: exp(lse_local - m) of it is left
        l = jnp.exp(lse_local - m) + jnp.sum(l, axis=1, keepdims=True)
        o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m + jnp.log(l)

    return body


FORMS = {"parent": parent, "tree": eva._remote_fwd_kernel, "two_sweeps": two_sweeps(),
         "two_sweeps_scaled_max": two_sweeps(scaled_max=True), "two_sweeps_rows_2": two_sweeps(2), "two_sweeps_rows_4": two_sweeps(4),
         "online_8": online(8, 1), "online_2": online(2, 1), "online_1": online(1), "online_8421": online(8, 4, 2, 1),
         "online_421": online(4, 2, 1)}


def install(name):
    """Make the operator's kernels form run ``FORMS[name]`` as its remote forward, in this process."""
    eva._remote_fwd_kernel = FORMS[name]
    eva._make_aggregate.cache_clear()


def operands(b, h, t, d, seed=0, dtype=jnp.bfloat16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, w = (jax.random.normal(keys[i], (b, h, t, d), jnp.float32).astype(dtype) for i in (0, 1, 2, 5))
    phi, mu = (jnp.clip(jax.random.normal(keys[i], (h, d), jnp.float32), -1, 1) * d ** -0.5 for i in (3, 4))
    return q, k, v, phi, mu, w


def value_and_grads(form, window, chunk, d, interpret=False):
    scale = d ** -0.5

    def fn(q, k, v, phi, mu, w):
        def loss(q, k, v, phi, mu):
            ks, vs = eva.pool(k, v, phi, mu, chunk=chunk, scale=scale)
            if form == "kernels":
                o = eva._make_aggregate(window, chunk, scale, interpret)(q, k, v, ks, vs)
            else:
                o = eva._aggregate_xla(q, k, v, ks, vs, window=window, chunk=chunk, scale=scale)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o
        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(q, k, v, phi, mu)
        return o, grads
    return jax.jit(fn)


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def against_xla(shape, window, chunk, dtype, interpret=False):
    """Every form's output, and the tree's form's cotangents, as a share of the XLA form's norm."""
    b, h, t, d = shape
    args = operands(b, h, t, d, dtype=dtype)
    o_x, g_x = jax.block_until_ready(value_and_grads("xla", window, chunk, d)(*args))
    for name in FORMS:
        install(name)
        t0 = time.time()
        o_k, g_k = jax.block_until_ready(value_and_grads("kernels", window, chunk, d, interpret)(*args))
        line = {"form": name, "shape": list(shape), "seconds": round(time.time() - t0, 1), "o_rel": rel(o_k, o_x)}
        if name == "tree":
            line["grads_rel"] = dict(zip(("q", "k", "v", "phi", "mu"), (rel(a, b) for a, b in zip(g_k, g_x))))
        print(json.dumps(line), flush=True)


def main():
    named = [a for a in sys.argv[1:] if not a.startswith("--")]
    for name in [n for n in FORMS if named and n not in named]:  # (forms named on the command line: those alone)
        del FORMS[name]
    if "--rehearse" in sys.argv:
        against_xla((1, 1, 2560, 128), 256, 2, jnp.float32, interpret=True)  # ten windows: w = 0 .. 9, a trip of 8 and a rest
        return
    from benchmarks.chipbench import flops, flops_eva, trace

    faulthandler.dump_traceback_later(300, exit=True)
    print(jax.devices(), flush=True)
    against_xla((1, 2, 8192, 128), 2048, 16, jnp.bfloat16)
    faulthandler.cancel_dump_traceback_later()

    cfg = dict(num_attention_heads=32, head_dim=128, window_size=2048, chunk_size=16)
    args = operands(1, 32, 32768, 128, seed=1)
    scale = 128 ** -0.5
    peaks = json.load(open("benchmarks/chipbench/peaks.json"))[jax.devices()[0].device_kind]
    need = flops.roofline_seconds(flops_eva.eva_agg_fwd_cost(1, 32768, cfg), peaks)
    for name in FORMS:
        faulthandler.dump_traceback_later(240, exit=True)
        install(name)

        @jax.jit
        def forward(q, k, v, phi, mu, w):
            ks, vs = eva.pool(k, v, phi, mu, chunk=16, scale=scale)
            return eva._make_aggregate(2048, 16, scale, False)(q, k, v, ks, vs)

        jax.block_until_ready(forward(*args))
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            out = forward(*args)
        jax.block_until_ready(out)
        clock_ms = 1e3 * (time.perf_counter() - t0) / reps
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(reps):
                    out = forward(*args)
                jax.block_until_ready(out)
            reduced = trace.reduce_dir(tmp)
        by_kernel = {}
        for kernel in ("eva_remote_fwd", "flash_attention_fwd"):
            secs, calls = trace.kernel_seconds(reduced, kernel)
            by_kernel[kernel] = {"ms": round(1e3 * secs / max(calls, 1), 4), "calls": calls}
        print(json.dumps({"form": name, "cell_shape": [1, 32, 32768, 128], "pool_and_aggregate_fwd_ms": round(clock_ms, 3),
                          **by_kernel, "roofline_fwd_ms": round(1e3 * need["seconds"], 3), "bound": need["bound"]}), flush=True)
        faulthandler.cancel_dump_traceback_later()


if __name__ == "__main__":
    main()
