"""The rule's kernels alone on the chip, of the tree this is run FROM (PR 37). ``tiny``: a new kernel runs first at a
tiny size under faulthandler (PERF.md, PR 31): 1 row of 512 tokens, one key head, two value heads of 128, output and
gradients against the XLA form. ``speed``: 2 rows of 8192, 16 key and 32 value heads of 128: forward ms, forward +
backward ms, distance from the XLA form. ``--group N`` sets ``ops/gated_delta.GROUP`` before anything is traced (this
tool's handle, not an option of the program)."""
import argparse
import faulthandler
import os
import sys

sys.path.insert(0, os.getcwd())

ap = argparse.ArgumentParser()
ap.add_argument("what", choices=("tiny", "speed"))
ap.add_argument("--group", type=int)
args = ap.parse_args()
faulthandler.dump_traceback_later(90 if args.what == "tiny" else 300, exit=True)

import jax
import jax.numpy as jnp

from benchmarks import gdn_kernels as tool
from llm_fine_tune_distributed_tpu.ops import gated_delta as gd

if args.group:
    gd.GROUP = args.group
rule = lambda impl: (lambda *a: gd.gated_delta_rule(*a, impl=impl))  # noqa: E731
both = lambda impl: jax.jit(jax.grad(lambda *a: jnp.sum(rule(impl)(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2, 3, 4)))  # noqa: E731
if args.what == "tiny":
    x = tool.inputs(1, 512, 1, 2, 128, jnp.bfloat16)
    gap = tool.rel(jax.jit(rule(None))(*x), jax.jit(rule("xla"))(*x))
    print("tiny:", gd.calls_summary(), "group", getattr(gd, "GROUP", None), "rel to xla", gap, flush=True)
    print("tiny grads rel to xla:", [round(tool.rel(a, b), 5) for a, b in zip(both(None)(*x), both("xla")(*x))], flush=True)
else:
    x = tool.inputs(2, 8192, 16, 32, 128, jnp.bfloat16)
    fwd = jax.jit(rule(None))
    line = {"device": jax.devices()[0].device_kind, "group": getattr(gd, "GROUP", None), "fwd_ms": round(tool.timed(fwd, x, 5), 3),
            "fwd_bwd_ms": round(tool.timed(both(None), x, 5), 3), "rel_to_xla_fwd": tool.rel(fwd(*x), jax.jit(rule("xla"))(*x))}
    line["rel_to_xla_grads"] = [round(tool.rel(a, b), 5) for a, b in zip(both(None)(*x), both("xla")(*x))]
    print(line, flush=True)
