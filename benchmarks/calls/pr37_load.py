"""What a program costs to LOAD (PR 37, step 0): seconds in ``lower()``, seconds in ``.compile()`` and the bytes of
its entry in the compile cache, of the tree this is run FROM (``cd _parent && python ../benchmarks/calls/pr37_load.py
step``). Run it twice against one ``JAX_COMPILATION_CACHE_DIR``: the first process compiles, the second loads.

``step``: the train step of ``qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams`` over abstract state, built as
``tests/test_tpu_compile.py::test_step_with_linear_and_full_layers_compiles_for_v5e`` builds it, for the chip that is
there. ``kernels``: the rule alone at the cell's shapes, forward at one call site, forward at three, forward + backward.

``--group N`` sets ``ops/gated_delta.GROUP`` (the chunks side by side in the kernels' text) before anything is traced;
``--bwd xla`` puts autodiff of the XLA form behind the forward kernel. Both are this tool's, not options of the program.
"""
import argparse
import os
import re
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp

from llm_fine_tune_distributed_tpu.ops import gated_delta as gd


def mosaic_programs(text):
    """``{(kernel name, serialized body): call sites}`` of the Mosaic calls in a lowered module's text (its own copy of
    ``observe/xla.mosaic_programs``' pattern: the parent's and PR 36's trees, which this runs from, have none)."""
    found = {}
    for body, name in re.findall(r'\\22body\\22: \\22([^\\]*)\\22.*?kernel_name = "([^"]*)"', text):
        found[name, body] = found.get((name, body), 0) + 1
    return found


def entries(cache_dir, before):
    """The cache's files this process wrote, or (a warm run writes none) its largest three: ``[(name, bytes)]``."""
    now = {f: os.path.getsize(os.path.join(cache_dir, f)) for f in os.listdir(cache_dir)} if os.path.isdir(cache_dir) else {}
    new = {f: n for f, n in now.items() if f not in before and not f.endswith("-atime")}
    shown = new or dict(sorted(now.items(), key=lambda x: -x[1])[:3])
    return [(f[:40], n) for f, n in sorted(shown.items(), key=lambda x: -x[1])]


def measure(tag, jitted, args, cache_dir):
    before = set(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else set()
    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    programs = mosaic_programs(lowered.as_text())
    rule = {k: n for k, n in programs.items() if k[0].startswith("gdn_rule")}
    print({"what": tag, "device": jax.devices()[0].device_kind, "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
           "mosaic_programs": len(programs), "rule_programs": len(rule), "rule_call_sites": sum(rule.values()),
           "rule_text_bytes": sum(len(body) for _, body in rule), "cache": entries(cache_dir, before)}, flush=True)
    return compiled


def xla_backward():
    """The forward kernel with ``jax.vjp`` of the XLA form behind it (the floor of ISSUE 37's ladder)."""
    kernels = gd.gated_delta_rule

    def rule(q, k, v, g, beta, **kw):
        @jax.custom_vjp
        def f(*a):
            return kernels(*a, **kw)

        f.defvjp(lambda *a: (kernels(*a, **kw), a), lambda kept, do: jax.vjp(lambda *a: gd._rule_xla(*a), *kept)[1](do))
        return f(q, k, v, g, beta)

    gd.gated_delta_rule = rule


def step_setup(tiny):
    import dataclasses

    from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

    recipe = dict(freeze_strategy="none", remat_policy="full", loss_chunk_size=1024)
    if tiny:  # a CPU rehearsal of the control flow
        setup = abstract_train_setup({"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, "tiny_qwen3_next", devices=jax.devices()[:1],
                                     accum=2, seq=128, per_dp_batch=2, param_dtype="bfloat16", train_kwargs=recipe)
    else:
        setup = abstract_train_setup(
            {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, "qwen3_next_80b_a3b",
            devices=jax.devices()[:1], accum=2, seq=8192, per_dp_batch=2, param_dtype="bfloat16",
            train_kwargs=dict(recipe, attention_impl="flash"),
            model_overrides=dict(num_layers=4, vocab_size=18992, held_experts=tuple(range(32))),
        )
    state = setup.state.replace(opt_state=jax.tree.map(  # Adam's moments float32, as the cell holds them
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=x.sharding) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        setup.state.opt_state))
    return dataclasses.replace(setup, state=state)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("step", "kernels"))
    ap.add_argument("--group", type=int)
    ap.add_argument("--bwd", choices=("xla",))
    ap.add_argument("--tag", default="")
    ap.add_argument("--tiny", action="store_true", help="a CPU rehearsal: the tiny preset, short rows")
    args = ap.parse_args()
    cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.group:
        gd.GROUP = args.group
    if args.bwd:
        xla_backward()
    if args.what == "step":
        setup = step_setup(args.tiny)
        compiled = measure(f"step {args.tag}", setup.step, (setup.state, setup.batch), cache_dir)
        try:
            print({"what": f"step {args.tag}", "peak_memory_gib": round(compiled.memory_analysis().peak_memory_in_bytes / 2**30, 3)})
        except Exception as e:  # an executable read back from the cache may not say
            print("no memory analysis:", str(e)[:100])
        print(gd.calls_summary())
        return
    like = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    seq = 512 if args.tiny else 8192
    x = (like(2, seq, 16, 128), like(2, seq, 16, 128), like(2, seq, 32, 128),
         jax.ShapeDtypeStruct((2, seq, 32), jnp.float32), jax.ShapeDtypeStruct((2, seq, 32), jnp.float32))

    def pr37_fwd(*a):
        return gd.gated_delta_rule(*a)

    def pr37_fwd_x3(q, k, v, g, beta):
        for _ in range(3):
            v = gd.gated_delta_rule(q, k, v, g, beta)
        return v

    def pr37_grad(*a):
        return jax.grad(lambda *b: jnp.sum(gd.gated_delta_rule(*b).astype(jnp.float32) ** 2), argnums=(0, 1, 2, 3, 4))(*a)

    for fn in (pr37_fwd, pr37_fwd_x3, pr37_grad):
        measure(f"{fn.__name__} {args.tag}", jax.jit(fn), x, cache_dir)


if __name__ == "__main__":
    main()
