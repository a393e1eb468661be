"""PR 38: what the recorder costs the step's trace and lowering, apart from everything else a run does. The
Qwen3-Next cell's step over abstract state (as ``benchmarks/step_memory.py`` builds it) is traced and lowered
once in this process, for the device that is there, in one of three ways:

    plain   ``step.trace(...)`` then ``.lower()``, nothing listening (what the parent's process does)
    listen  the same with ``observe/xla.install_compile_listeners()`` called first (every jitted function's
            trace reported to the recorder)
    spans   the same under ``train_step/load`` > ``train_step/trace`` and ``train_step/lower``, as
            ``_InstrumentedProgram._first_call`` holds them

    python benchmarks/calls/pr38_listen_ab.py plain|listen|spans
"""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402

from llm_fine_tune_distributed_tpu.observe import xla  # noqa: E402
from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup  # noqa: E402

mode = sys.argv[1]
tiny = jax.devices()[0].platform == "cpu"  # a rehearsal of the control flow
setup = abstract_train_setup(
    {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, "tiny_qwen3_next" if tiny else "qwen3_next_80b_a3b",
    devices=jax.devices()[:1], accum=2, seq=256 if tiny else 8192, per_dp_batch=2, param_dtype="bfloat16",
    train_kwargs=dict(freeze_strategy="none", attention_impl="xla" if tiny else "flash", remat_policy="full",
                      loss_chunk_size=128 if tiny else 1024),
    model_overrides={} if tiny else dict(num_layers=4, vocab_size=18992, held_experts=tuple(range(32))),
)
if mode != "plain":
    xla.install_compile_listeners()
t0 = time.perf_counter()
if mode == "spans":
    with xla.annotate("train_step/load", program="train_step"):
        with xla.annotate("train_step/trace", program="train_step"):
            traced = setup.step.trace(setup.state, setup.batch)
        t1 = time.perf_counter()
        with xla.annotate("train_step/lower", program="train_step"):
            lowered = traced.lower()
else:
    traced = setup.step.trace(setup.state, setup.batch)
    t1 = time.perf_counter()
    lowered = traced.lower()
t2 = time.perf_counter()
counters = xla._RECORDER.counters
print(json.dumps({"mode": mode, "device": jax.devices()[0].device_kind, "trace_s": round(t1 - t0, 3),
                  "lower_s": round(t2 - t1, 3), "spans": counters["spans"], "kept": len(xla._RECORDER._spans),
                  "module_bytes": len(lowered.as_text())}), flush=True)
