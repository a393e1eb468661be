"""PR 46: the proof that no accepted cell's step changed. Each of the seven cells' steps as ``benchmarks/step_memory.py``
states it, lowered for a described v5e with the dispatch as a TPU makes it, and the sha256 of the StableHLO two ways:
whole, and with every Mosaic kernel's serialized body masked (a body carries the line numbers of its call stack, and
``ops/flash_attention.py`` grew by a keyword above the calls: on the chip the two trees keep separate cache entries for
that reason alone). Run in both trees (``JAX_PLATFORMS=cpu python benchmarks/calls/pr46_lowered.py`` here and from a
checkout of the parent): the masked digests are equal, and the kernels' names, counts and bytes are. A CPU lowering:
counts and digests, never a rate."""
import dataclasses
import hashlib
import json
import os
import re
import sys

sys.path.insert(0, os.getcwd())
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402

from benchmarks.step_memory import STEPS  # noqa: E402
from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup  # noqa: E402
from llm_fine_tune_distributed_tpu.observe.xla import mosaic_programs  # noqa: E402

CELLS = [
    "smollm3-3b.sft-1k-full", "mistral-7b-d16.sft-2k-full", "moonlight-16b-a3b-ep8-d6.sft-4k-allparams",
    "mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams", "qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams",
    "trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams", "kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams",
]
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
jax.default_backend = lambda: "tpu"
for name in sys.argv[1:] or CELLS:
    preset, overrides, rows, accum, seq, recipe = STEPS[name]
    setup = abstract_train_setup(
        {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, preset, devices=topo.devices[:1], accum=accum, seq=seq,
        per_dp_batch=rows, param_dtype="bfloat16", train_kwargs=recipe, model_overrides=overrides)
    with jax._src.config.include_full_tracebacks_in_locations(False):
        text = dataclasses.replace(setup).lower().as_text()
    masked = re.sub(r'\\22body\\22: \\22[^\\]*\\22', r'\\22body\\22: \\22...\\22', text)
    print(json.dumps({"step": name, "bytes": len(text), "whole": hashlib.sha256(text.encode()).hexdigest()[:16],
                      "bodies_masked": hashlib.sha256(masked.encode()).hexdigest()[:16],
                      "kernels": {k: (v["programs"], v["bytes"], v["call_sites"]) for k, v in sorted(mosaic_programs(text).items())}}), flush=True)
