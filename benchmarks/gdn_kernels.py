#!/usr/bin/env python3
"""A linear-attention mixer's own operations alone, on the chip, at the shapes
of ``qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams`` (2 rows of 8192,
hidden 2048, 16 key and 32 value heads of 128, bfloat16); a builder's tool,
the benchmark never runs it. Wall time over ``--iters`` calls that end in
``block_until_ready``.

``--only rule``: the gated delta rule (``ops/gated_delta.gated_delta_rule``),
forward and forward+backward, by chunk (64 or 128) and by the way to the
triangular inverse: ``solve`` (the library's ``unit_lower_inverse``: XLA's
triangular solve against the identity) or doublings ``(I - A)(I + A^2)(I +
A^4)...`` at ``HIGHEST``, ``HIGH`` or default precision (defined here: the
library keeps ONE way, the one this tool found ahead; PERF.md, PR 32), with
each variant's distance from the token-by-token recurrence in float32 at 512
tokens; last the library's Pallas kernels (``inverse`` reads ``kernels``; chunks of 64, heads of 128), with the
seconds their forward + backward takes to trace and lower, each kernel's serialized Mosaic module in bytes
(``observe/xla.mosaic_programs``: what a warm start loads) and their distance from the XLA form on the timed inputs,
output and every gradient. ``--inverse NAME ...`` keeps those variants.

``--only inverse``: the kernels' triangular inverse alone against float64, by the precision of its products.

``--only mixer``: the mixer's two elementwise passes alone (``ops/gated_delta.mixer_in``: convolution, silu, l2
norms, scale; ``gated_norm``: the norm gated by ``silu(z)``), forward and forward+backward, as XLA operations and as
the Pallas kernels, each beside the time its bytes take at the HBM peak (every array read once and written once) and
the kernels' distance from the XLA form; then the whole mixer (``models/transformer._linear_mixer``: projections, both
passes, the rule), forward+backward to its input and every leaf, with the passes in either form (the rule in the form
the backend gives it).

``--only kda``: the rule with a decay a CHANNEL (Kimi Delta Attention) at the Kimi cell's shapes (2 rows of 8192, 32
heads of 128), the XLA form (``_rule_xla_by_channel``) beside the two Pallas sweeps (``kda_rule_fwd``,
``kda_rule_bwd``) and the choices their builder weighed (``kda_variants``); then (``--only sweeps``: that alone) the two sweeps ALONE, each called
by itself in the layouts ``_rule_kernels`` hands it (``kda_sweeps``: ms a call, and what the forward sweep keeps for the
backward one in MiB), and with ``--parent DIR`` the same of another checkout's ``ops/gated_delta.py`` beside the
largest difference of the output and of each cotangent from that checkout's (0.0: equal bit for bit).

    chiprun -- python benchmarks/gdn_kernels.py [--only rule|kda|sweeps|mixer|inverse] [--inverse solve kernels] [--parent _parent]
"""
import argparse
import functools
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.chipbench import reference_gdn_moe
from llm_fine_tune_distributed_tpu.observe.xla import mosaic_programs
from llm_fine_tune_distributed_tpu.ops import gated_delta as gd
from llm_fine_tune_distributed_tpu.runtime.device import on_accelerator


def timed(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def inputs(rows, seq, hk, hv, d, dtype):
    ks = jax.random.split(jax.random.key(0), 6)
    q = (gd.l2_norm(jax.random.normal(ks[0], (rows, seq, hk, d))) * d ** -0.5).astype(dtype)
    k = gd.l2_norm(jax.random.normal(ks[1], (rows, seq, hk, d))).astype(dtype)
    v = jax.random.normal(ks[2], (rows, seq, hv, d)).astype(dtype)
    a = jax.random.uniform(ks[3], (hv,), minval=0.02, maxval=2.0)
    g = -a * jax.nn.softplus(jax.random.normal(ks[4], (rows, seq, hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (rows, seq, hv)))
    return q, k, v, g, beta


def by_doublings(precision):
    """``(I + a)^-1`` as ``(I - a)(I + a^2)(I + a^4)...`` up to the power C / 2 (a is nilpotent), its
    derivative written out (``-T^T dT T^T``: autodiff of the doublings would hold every power of a)."""
    mm = functools.partial(jnp.matmul, precision=precision)

    @jax.custom_vjp
    def inverse(a):
        n = a.shape[-1]
        out = jnp.eye(n, dtype=a.dtype) - a
        power, reach = mm(a, a), 2  # a^reach; out covers the powers below reach
        while reach < n:
            out = out + mm(out, power)
            reach *= 2
            if reach < n:
                power = mm(power, power)
        return out

    def fwd(a):
        t = inverse(a)
        return t, t

    def bwd(t, dt):
        tt = jnp.swapaxes(t, -1, -2)
        return (-mm(mm(tt, dt), tt),)

    inverse.defvjp(fwd, bwd)
    return inverse


def rule_variants(args, small):
    rows, seq, hk, hv, d = (1, 256, 2, 4, 32) if small else (args.rows, args.seq, 16, 32, 128)
    solve = gd.unit_lower_inverse
    check = inputs(1, 256 if small else 512, hk, hv, d, jnp.float32)
    r = hv // hk
    want = reference_gdn_moe._highest(reference_gdn_moe.delta_rule)(
        jnp.repeat(check[0], r, axis=2), jnp.repeat(check[1], r, axis=2), *check[2:])
    x = inputs(rows, seq, hk, hv, d, jnp.bfloat16)
    # the XLA form by chunk and by the way to the inverse, then the library's kernels (chunks of 64, d = 128 only)
    variants = [(chunk, name, "xla") for chunk in (64, 128) for name in ("solve", "HIGHEST", "HIGH", "DEFAULT")]
    if not small:
        variants.append((64, "kernels", "kernels"))
    for chunk, name, impl in variants:
        if args.inverse and name not in args.inverse:
            continue
        gd.unit_lower_inverse = solve if name in ("solve", "kernels") else by_doublings(getattr(jax.lax.Precision, name))
        rule = lambda *a: gd.gated_delta_rule(*a, chunk=chunk, impl=impl)  # noqa: E731,B023
        fwd = jax.jit(rule)
        both = jax.jit(jax.grad(lambda *a: jnp.sum(rule(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2, 3, 4)))  # noqa: B023
        line = {"device": jax.devices()[0].device_kind, "rows": rows, "seq": seq, "chunk": chunk, "inverse": name}
        try:
            got = fwd(*check)
            line["rel_err_f32_512"] = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
            line["fwd_ms"] = round(timed(fwd, x, args.iters), 3)
            line["fwd_bwd_ms"] = round(timed(both, x, args.iters), 3)
            if impl == "kernels":  # what a warm start loads of them (PERF.md, PR 37), then held to the XLA form on the timed inputs
                jax.clear_caches()
                t0 = time.perf_counter()
                lowered = both.lower(*x)
                line["trace_lower_s"] = round(time.perf_counter() - t0, 2)
                line["programs"] = mosaic_programs(lowered.as_text())
                xla = lambda *a: gd.gated_delta_rule(*a, impl="xla")  # noqa: E731
                line["rel_to_xla_fwd"] = rel(fwd(*x), jax.jit(xla)(*x))
                grads = jax.jit(jax.grad(lambda *a: jnp.sum(xla(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2, 3, 4)))(*x)
                line["rel_to_xla_grads"] = {n: rel(a, b) for n, a, b in zip("q k v g beta".split(), both(*x), grads)}
        except Exception as e:  # a refusal (memory, a shape) is the reading
            line["refused"] = str(e).split("\n")[0][:300]
        print(json.dumps(line), flush=True)
        jax.clear_caches()
    gd.unit_lower_inverse = solve


def kda_inputs(rows, seq, h, d, dtype):
    """The rule's inputs with a decay a CHANNEL, as the Kimi cell draws it (as many key heads as value heads)."""
    q, k, v, _, beta = inputs(rows, seq, h, h, d, dtype)
    g = -jnp.exp(jax.random.uniform(jax.random.key(7), (rows, seq, h, d), minval=jnp.log(1e-3), maxval=jnp.log(1.6)))
    return q, k, v, g, beta


def kda_variants(args, small):
    """The rule with a decay a channel at the Kimi cell's shapes (2 rows of 8192, 32 heads of 128): the XLA form
    (``_rule_xla_by_channel``) beside the two sweeps, and the sweeps by what the builder weighed (PR 43): chunks of a
    group side by side (``GROUP`` 4, the landed one, or 2), g summed from each sub-block's start inside the kernels
    (landed) or by XLA outside them. Each with its distance from the token-by-token rule in float32 at 512 tokens and,
    on the timed inputs, from the XLA form in output and every cotangent."""
    from benchmarks.chipbench import reference_kda_moe

    rows, seq, h, d = (1, 256, 2, 16) if small else (args.rows, args.seq, 32, 128)
    check = kda_inputs(1, 256 if small else 512, 2 if small else 4, d, jnp.float32)
    want = reference_gdn_moe._highest(reference_kda_moe.delta_rule)(*check)
    x = kda_inputs(rows, seq, h, d, jnp.bfloat16)
    inside, group = gd._from_sub_block_start, gd.GROUP

    def summed_outside(q, k, v, g, beta, impl):
        b, s = g.shape[:2]
        (g,), s = gd._padded_rows((g,), gd.SUB)
        local = jnp.cumsum(g.reshape(b, -1, gd.SUB, *g.shape[2:]), axis=2).reshape(g.shape)[:, :s]
        return gd.gated_delta_rule(q, k, v, local, beta, impl=impl)

    xla = lambda *a: gd.gated_delta_rule(*a, impl="xla")  # noqa: E731
    loss = lambda rule: jax.jit(jax.grad(lambda *a: jnp.sum(rule(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2, 3, 4)))  # noqa: E731
    held_to = None
    variants = [("xla", group, inside)] + ([] if small else [("kernels", 4, inside), ("kernels", 2, inside), ("kernels, local summed outside", 4, None)])
    for name, chunks_side_by_side, sums in variants:
        gd.GROUP, gd._from_sub_block_start = chunks_side_by_side, sums or (lambda g, against_time=False: g)
        impl = name.split(",")[0]
        rule = (lambda *a: gd.gated_delta_rule(*a, impl=impl)) if sums else (lambda *a: summed_outside(*a, impl))  # noqa: E731,B023
        fwd, both = jax.jit(rule), loss(rule)
        line = {"device": jax.devices()[0].device_kind, "rows": rows, "seq": seq, "heads": h, "rule": "a decay a channel", "form": name,
                "group": gd.GROUP}
        try:
            got = fwd(*check)
            line["rel_err_f32_512"] = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
            line["fwd_ms"] = round(timed(fwd, x, args.iters), 3)
            line["fwd_bwd_ms"] = round(timed(both, x, args.iters), 3)
            if impl == "xla":
                held_to = (fwd(*x), both(*x))
            else:
                jax.clear_caches()
                t0 = time.perf_counter()
                lowered = both.lower(*x)
                line["trace_lower_s"] = round(time.perf_counter() - t0, 2)
                line["programs"] = mosaic_programs(lowered.as_text())
                line["rel_to_xla_fwd"] = rel(fwd(*x), held_to[0])
                line["rel_to_xla_grads"] = {n: rel(a, b) for n, a, b in zip("q k v g beta".split(), both(*x), held_to[1])}
        except Exception as e:  # a refusal (memory, a shape) is the reading
            line["refused"] = str(e).split("\n")[0][:300]
        print(json.dumps(line), flush=True)
        jax.clear_caches()
        gd._flat_rule.cache_clear()
    gd._from_sub_block_start, gd.GROUP = inside, group


def kda_sweeps(args, small):
    """``kda_rule_fwd`` and ``kda_rule_bwd`` each ALONE at the Kimi cell's shapes, on the operands ``_rule_kernels`` hands
    them and a drawn ``do``: ms a call, the MiB the forward sweep returns beyond ``o`` (what is held from a microbatch's
    forward pass to its backward pass). With ``--parent DIR`` the sweeps of that checkout's ``ops/gated_delta.py`` too,
    whatever they keep (a sweep's residuals are whatever its forward returns beyond ``o``), and the largest absolute
    difference of ``o`` and of each cotangent between the two."""
    rows, seq, h, d = (1, 512, 2, 128) if small else (args.rows, args.seq, 32, 128)
    q, k, v, g, beta = kda_inputs(rows, seq, h, d, jnp.bfloat16)
    do = jax.random.normal(jax.random.key(11), (rows, seq, h * d)).astype(jnp.bfloat16)
    operands = gd._laid_out(q, k, v, g, beta)[2]  # seq is whole steps: nothing is padded, and the parent lays them out the same

    def sweeps(module, tree):
        fwd = functools.partial(module.kda_rule_fwd, hk=h, state_dtype=jnp.float32, interpret=small)
        bwd = functools.partial(module.kda_rule_bwd, hk=h, interpret=small)
        o, *kept = fwd(*operands)
        back = operands + (do,) + tuple(kept)
        line = {"device": jax.devices()[0].device_kind, "rows": rows, "seq": seq, "heads": h, "rule": "a decay a channel", "form": "the sweeps alone",
                "tree": tree, "fwd_ms": round(timed(fwd, operands, args.iters), 3), "bwd_ms": round(timed(bwd, back, args.iters), 3),
                "kept_mib": {f"{list(x.shape)} {x.dtype}": round(x.nbytes / 2**20, 1) for x in kept}}
        return line, (o, *bwd(*back))

    line, got = sweeps(gd, "this")
    if args.parent:
        spec = importlib.util.spec_from_file_location("parent_gated_delta", os.path.join(args.parent, "llm_fine_tune_distributed_tpu/ops/gated_delta.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        theirs, want = sweeps(parent, args.parent)
        print(json.dumps(theirs), flush=True)
        line["max_abs_diff_from_parent"] = {n: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
                                            for n, a, b in zip("o dq dk dv dg dbeta".split(), got, want)}
    print(json.dumps(line), flush=True)


def rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def inverse_alone(args, small):
    """The kernels' triangular inverse alone (``gd._inverse_in_vmem`` on one ``[128, 128]`` matrix of two diagonal
    blocks), against numpy's inverse in float64: what the products' precision leaves, as the library does it (levels
    at one bfloat16 pass, ``gd.NEWTON_STEPS`` Newton steps with the residual at HIGHEST), with fewer and more steps,
    and with every level at HIGHEST and no step. Entries of A are ``beta decay k.k'``, so within (-1, 1): all of one
    sign up to 1.0 is every key alike; both signs let T's entries grow."""
    import numpy as np
    from jax.experimental import pallas as pl

    def body(a_ref, t_ref):
        t_ref[...] = gd._in_step([gd._inverse_in_vmem(a_ref[...], *gd._iotas(128))])[0]

    ri, ci = np.indices((128, 128))
    library = gd.NEWTON_STEPS, gd._one_pass
    for name, steps, product in [(f"one pass, {n} Newton steps", n, gd._one_pass) for n in (0, 1, 2, 3)] + [("HIGHEST, no step", 0, gd._mm32)]:
        gd.NEWTON_STEPS, gd._one_pass = steps, product
        for low, high in ((0, 0.1), (0, 0.5), (0, 1.0), (-0.3, 0.3), (-0.6, 0.6)):
            a = np.where((ri // 64 == ci // 64) & (ri > ci), np.random.RandomState(0).uniform(low, high, (128, 128)), 0).astype(np.float32)
            t = jax.jit(pl.pallas_call(body, out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32), interpret=small))(jnp.asarray(a))
            want = np.linalg.inv(np.eye(128) + a.astype(np.float64))
            print(json.dumps({"device": jax.devices()[0].device_kind, "inverse": name + (" (the library)" if (steps, product) == library else ""),
                              "entries": [low, high], "rel_err": float(np.linalg.norm(np.asarray(t, np.float64) - want) / np.linalg.norm(want)),
                              "largest_entry": float(np.abs(want).max())}), flush=True)
        jax.clear_caches()
    gd.NEWTON_STEPS, gd._one_pass = library


HBM_BYTES_PER_S = 819e9  # benchmarks/chipbench/peaks.json, "TPU v5 lite"


def _timed_pass(line, name, fn, args, iters, arrays_moved, both_arrays_moved, nbytes):
    """``fn`` forward and forward + backward (to every argument) beside the time its bytes take at the HBM peak."""
    # (sin, not a square: q and k are l2-normed, the square's gradient would be rounding alone)
    both = jax.jit(jax.grad(lambda *a: sum(jnp.sum(jnp.sin(y.astype(jnp.float32))) for y in jax.tree.leaves(fn(*a))),
                            argnums=tuple(range(len(args)))))
    line[f"{name}_fwd_ms"] = round(timed(jax.jit(fn), args, iters), 3)
    line[f"{name}_fwd_bwd_ms"] = round(timed(both, args, iters), 3)
    line[f"{name}_fwd_bound_ms"] = round(1e3 * arrays_moved * nbytes / HBM_BYTES_PER_S, 3)
    line[f"{name}_fwd_bwd_bound_ms"] = round(1e3 * (arrays_moved + both_arrays_moved) * nbytes / HBM_BYTES_PER_S, 3)
    return jax.jit(fn)(*args), both(*args)


def mixer_variants(args, small):
    from llm_fine_tune_distributed_tpu.models import transformer
    from llm_fine_tune_distributed_tpu.models.configs import get_preset

    mc = get_preset("qwen3_next_80b_a3b")
    rows, seq, hk, hv = (1, 512, 2, 4) if small else (args.rows, args.seq, mc.linear_num_key_heads, mc.linear_num_value_heads)
    d = mc.linear_key_head_dim
    kernels = "kernels_interpret" if small else "kernels"
    ks = jax.random.split(jax.random.key(3), 8)
    act = lambda key, width: jax.random.normal(key, (rows, seq, width), jnp.float32).astype(jnp.bfloat16)  # noqa: E731
    x_in = (act(ks[0], hk * d), act(ks[1], hk * d), act(ks[2], hv * d),
            (jax.random.normal(ks[3], (mc.linear_conv_kernel_dim, (2 * hk + hv) * d)) * 0.3).astype(jnp.bfloat16))
    x_out = (act(ks[4], hv * d), act(ks[5], hv * d), (1 + 0.1 * jax.random.normal(ks[6], (d,))).astype(jnp.bfloat16))
    lines, kept = [], {}
    for impl in ("xla", kernels):
        line = {"device": jax.devices()[0].device_kind, "rows": rows, "seq": seq, "passes": impl}
        try:
            # in: the q | k | v columns read and q, k, v written (2 arrays of [rows, seq, channels]); backward: the columns and
            # dq, dk, dv read, the columns' cotangent written (3). out: o, z read, y written (3 of [rows, seq, hv d]); 5 back.
            kept[impl] = (
                _timed_pass(line, "in", lambda *a: gd.mixer_in(*a, hk, impl=impl), x_in, args.iters, 2, 3, 2 * rows * seq * (2 * hk + hv) * d),  # noqa: B023
                _timed_pass(line, "out", lambda *a: gd.gated_norm(*a, mc.rms_norm_eps, impl=impl), x_out, args.iters, 3, 5, 2 * rows * seq * hv * d),  # noqa: B023
            )
            if impl != "xla":
                line["programs"] = mosaic_programs(jax.jit(jax.grad(lambda *a: sum(  # noqa: B023
                    jnp.sum(jnp.sin(y.astype(jnp.float32))) for y in gd.mixer_in(*a, hk, impl=impl)), argnums=(0, 1, 2, 3))).lower(*x_in).as_text())  # noqa: B023
                for name, got, want in zip(("in", "out"), kept[impl], kept["xla"]):
                    line[f"{name}_rel_to_xla"] = [rel(a, b) for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        except Exception as e:  # a refusal is the reading
            line["refused"] = str(e).split("\n")[0][:300]
        print(json.dumps(line), flush=True)
        lines.append(line)
        jax.clear_caches()

    mc = mc if not small else mc.replace(linear_num_key_heads=hk, linear_num_value_heads=hv, hidden_size=256)
    keys = iter(jax.random.split(jax.random.key(1), 8))
    dense = lambda key, shape: (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(jnp.bfloat16)  # noqa: E731
    leaves = transformer._init_linear_attention(keys, mc, dense, jnp.bfloat16)
    hid = jax.random.normal(jax.random.key(2), (rows, seq, mc.hidden_size), jnp.bfloat16)
    lin = lambda x, p: x @ p["kernel"]  # noqa: E731

    def loss(leaves, hid):
        out, _ = transformer._linear_mixer(leaves, hid, None, None, config=mc, lin=lin, segment_ids=None, cache_entry=None)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    library = gd.mixer_in, gd.gated_norm
    for impl in ("xla", kernels):
        gd.mixer_in, gd.gated_norm = (functools.partial(f, impl=impl) for f in library)
        line = {"device": jax.devices()[0].device_kind, "rows": rows, "seq": seq, "mixer": "fwd+bwd", "passes": impl}
        try:
            line["fwd_bwd_ms"] = round(timed(jax.jit(jax.grad(loss, argnums=(0, 1))), (leaves, hid), args.iters), 3)
        except Exception as e:
            line["refused"] = str(e).split("\n")[0][:300]
        print(json.dumps(line), flush=True)
        jax.clear_caches()
    gd.mixer_in, gd.gated_norm = library


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--only", choices=("rule", "kda", "sweeps", "mixer", "inverse"))
    ap.add_argument("--inverse", nargs="*", help="of the rule's variants, only these (solve HIGHEST HIGH DEFAULT kernels)")
    ap.add_argument("--parent", help="--only kda: another checkout of this repo whose two sweeps are priced and compared too")
    args = ap.parse_args(argv)
    small = not on_accelerator(jax.devices()[0].platform)  # a CPU rehearsal of the control flow: its numbers are not rates
    if args.only == "inverse":
        inverse_alone(args, small)
    if args.only in (None, "rule"):
        rule_variants(args, small)
    if args.only in (None, "kda"):
        kda_variants(args, small)
    if args.only in (None, "kda", "sweeps"):
        kda_sweeps(args, small)
    if args.only in (None, "mixer"):
        mixer_variants(args, small)
    return 0


if __name__ == "__main__":
    sys.exit(main())
