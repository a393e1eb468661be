"""PR 38: the proof that no program changed. For the four tiny presets' train steps under
``full`` and ``dots_no_batch``, the sha256 of the lowered StableHLO two ways: ``step.lower(...)``
(what ``observe/xla._InstrumentedProgram._first_call`` calls, at the parent and now) and
``step.trace(...).lower()`` (what this PR's first version called, a span around each stage: the
same text, and seconds dearer to lower on the chip, PERF.md section 6). Run in both trees
(``JAX_PLATFORMS=cpu python benchmarks/calls/pr38_lowered_sha.py`` here and from ``_parent/``):
sixteen equal digests. Counts and digests of a CPU lowering, never a rate. On the chip a kernel's
serialized body carries the line numbers of the call stack, ``observe/xla.py``'s among them, so
the two sides of a comparison still keep separate entries in the compile cache (PERF.md)."""
import hashlib
import json
import os
import sys

sys.path.insert(0, os.getcwd())

from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup  # noqa: E402

for preset in ("tiny", "tiny_mla_moe", "tiny_mellum", "tiny_qwen3_next"):
    for remat in ("full", "dots_no_batch"):
        setup = abstract_train_setup(
            {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, preset, accum=2, seq=128, per_dp_batch=2,
            param_dtype="bfloat16", train_kwargs=dict(remat_policy=remat, freeze_strategy="none"),
        )
        one = setup.step.lower(setup.state, setup.batch).as_text()
        two = setup.step.trace(setup.state, setup.batch).lower().as_text()
        print(json.dumps({"preset": preset, "remat_policy": remat, "bytes": len(one),
                          "lower": hashlib.sha256(one.encode()).hexdigest()[:16],
                          "trace_then_lower": hashlib.sha256(two.encode()).hexdigest()[:16]}), flush=True)
