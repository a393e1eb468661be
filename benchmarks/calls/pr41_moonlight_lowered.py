"""PR 41: the proof that the Moonlight cell's step did not change. Its step as ``benchmarks/step_memory.py`` states it
(latent attention: ``_latent_qkv``, which the IN pass does not touch), lowered for a described v5e with the dispatch as a
TPU makes it, and the sha256 of the StableHLO two ways: whole, and with every Mosaic kernel's serialized body masked (a
body carries the line numbers of its call stack, and ``ops/flash_attention.py`` grew by a docstring above the call: on
the chip the two trees keep separate cache entries for that reason alone). Run in both trees (``JAX_PLATFORMS=cpu python
benchmarks/calls/pr41_moonlight_lowered.py`` here and from ``_parent/``): the masked digests are equal, and the
kernels' names, counts and bytes are. A CPU lowering: counts and digests, never a rate."""
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

jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
jax.default_backend = lambda: "tpu"
for name in sys.argv[1:] or ["moonlight-16b-a3b-ep8-d6.sft-4k-allparams"]:
    preset, overrides, rows, accum, seq, recipe = STEPS[name]
    setup = abstract_train_setup(
        {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, preset, devices=topo.devices[:1], accum=accum, seq=seq,
        per_dp_batch=rows, param_dtype="bfloat16", train_kwargs=recipe, model_overrides=overrides)
    text = dataclasses.replace(setup).lower().as_text()
    masked = re.sub(r'\\22body\\22: \\22[^\\]*\\22', r'\\22body\\22: \\22...\\22', text)
    print(json.dumps({"step": name, "bytes": len(text), "whole": hashlib.sha256(text.encode()).hexdigest()[:16],
                      "bodies_masked": hashlib.sha256(masked.encode()).hexdigest()[:16], "masked_bytes": len(masked),
                      "kernels": {k: (v["programs"], v["bytes"], v["call_sites"]) for k, v in sorted(mosaic_programs(text).items())}}), flush=True)
