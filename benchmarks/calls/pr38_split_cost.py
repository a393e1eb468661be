"""PR 38: is ``fn.trace(...)`` followed by ``traced.lower()`` dearer than ``fn.lower(...)``? Call D read the SmolLM3
step's trace + lowering at 11.6 s held apart and at 4.8 s as one call. The cell's step over abstract state, for the
device that is there, one way a process; ``--profile`` prints the lowering's fifteen dearest functions by own time.

    python benchmarks/calls/pr38_split_cost.py whole|split [--profile]
"""
import cProfile
import io
import json
import os
import pstats
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402

from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup  # noqa: E402

mode, profiled = sys.argv[1], "--profile" in sys.argv
tiny = jax.devices()[0].platform == "cpu"
setup = abstract_train_setup(
    {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, "tiny" if tiny else "smollm3_3b", devices=jax.devices()[:1],
    accum=16, seq=128 if tiny else 1024, per_dp_batch=2, param_dtype="bfloat16",
    train_kwargs=dict(freeze_strategy="last_n_and_head", unfreeze_last_n_layers=2,
                      attention_impl="xla" if tiny else "flash", remat_policy="dots_no_batch"),
)
profile = cProfile.Profile()
t0 = time.perf_counter()
if mode == "split":
    traced = setup.step.trace(setup.state, setup.batch)
    t1 = time.perf_counter()
    lowered = profile.runcall(traced.lower) if profiled else traced.lower()
else:
    t1 = t0
    lowered = profile.runcall(setup.step.lower, setup.state, setup.batch) if profiled else setup.step.lower(setup.state, setup.batch)
t2 = time.perf_counter()
print(json.dumps({"mode": mode, "profiled": profiled, "device": jax.devices()[0].device_kind, "trace_s": round(t1 - t0, 3),
                  "lower_s": round(t2 - t1, 3), "module_bytes": len(lowered.as_text())}), flush=True)
if profiled:
    out = io.StringIO()
    pstats.Stats(profile, stream=out).sort_stats("tottime").print_stats(15)
    print("\n".join(line[:180] for line in out.getvalue().splitlines() if line.strip())[:5000], flush=True)
