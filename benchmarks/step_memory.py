#!/usr/bin/env python
"""What the TPU's compiler says of a train step's memory, with no chip: the
step of a benchmark cell (or a neighbour of one) is compiled for a described
v5e through ``observe/scaling.abstract_train_setup`` and one JSON line a step
gives ``peak_memory_in_bytes`` (what has to fit; arguments + temporaries
counts the donated state twice), the Mosaic calls by flash kernel (the
resident ones and, by their own names, the streamed ones of window and global
layers) and those of the expert layer's sum of rows into tokens, whether any ``[seq, seq]`` scores are in the program, or the
compiler's refusal. Counts and the compiler's word, never a rate.

The state is the cells': bfloat16 masters, Adam's moments float32 (optax
keeps them so from the first update on, PERF.md section 6).

Usage: JAX_PLATFORMS=cpu python benchmarks/step_memory.py [STEP ...]
(all of ``STEPS`` when none is named; about a minute each, one after another:
only one process at a time may load the TPU's library).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

_DENSE = dict(freeze_strategy="last_n_and_head", unfreeze_last_n_layers=2, attention_impl="flash")
_ALL = dict(freeze_strategy="none", attention_impl="flash", remat_policy="full", loss_chunk_size=1024)
_MELLUM = ("mellum2_12b_a2_5b", dict(num_layers=4, vocab_size=24576, held_experts=tuple(range(16))))
_QWEN3_NEXT = ("qwen3_next_80b_a3b", dict(num_layers=4, vocab_size=18992, held_experts=tuple(range(32))))
_TRINITY = ("trinity_mini", dict(
    num_layers=5, first_k_dense_replace=1, vocab_size=25024, held_experts=tuple(range(16)),
    layer_types=("sliding_attention",) * 3 + ("full_attention", "sliding_attention"), no_rope_layers=(1, 1, 1, 0, 1),
))
_KIMI = ("kimi_linear_48b_a3b", dict(num_layers=5, vocab_size=20480, held_experts=tuple(range(8))))
_EVA_RECIPE = dict(_DENSE, remat_policy="full", loss_chunk_size=1024)
# name -> (preset, model overrides, rows, accumulation, sequence, recipe)
STEPS = {
    # the cells of BENCHMARK.json, as their traffic files state them
    "smollm3-3b.sft-1k-full": ("smollm3_3b", {}, 2, 16, 1024, dict(_DENSE, remat_policy="dots_no_batch")),
    "mistral-7b-d16.sft-2k-full": ("mistral_7b", dict(num_layers=16), 1, 16, 2048, dict(_DENSE, remat_policy="dots_no_batch")),
    "moonlight-16b-a3b-ep8-d6.sft-4k-allparams": (
        "moonlight_16b_a3b", dict(num_layers=6, vocab_size=20480, held_experts=tuple(range(8))), 4, 2, 4096, _ALL,
    ),
    "mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams": (*_MELLUM, 4, 1, 8192, _ALL),
    # the same at 2 rows and at 1 (of 4, 2, 1 rows the cell takes the most that fits: 4)
    "mellum2-12b-a2.5b-ep4-d4.8k-2rows": (*_MELLUM, 2, 2, 8192, _ALL),
    "mellum2-12b-a2.5b-ep4-d4.8k-1row": (*_MELLUM, 1, 4, 8192, _ALL),
    "qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams": (*_QWEN3_NEXT, 2, 2, 8192, _ALL),
    # the same at 4 rows and at 1 (the cell was sized at 2 when 4 were refused, 17.89 G of 15.75 G; since PR 39 4 fit, 14.13 GiB)
    "qwen3-next-80b-a3b-ep16-d4.8k-4rows": (*_QWEN3_NEXT, 4, 1, 8192, _ALL),
    "qwen3-next-80b-a3b-ep16-d4.8k-1row": (*_QWEN3_NEXT, 1, 4, 8192, _ALL),
    "trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams": (*_TRINITY, 2, 2, 8192, _ALL),
    # the same at 1 row and at 4 (2 x 2 if the compiler's count stays under 15.0 GiB, else 1 x 4: ISSUE 40)
    "trinity-mini-26b-a3b-ep8-d5.8k-1row": (*_TRINITY, 1, 4, 8192, _ALL),
    "trinity-mini-26b-a3b-ep8-d5.8k-4rows": (*_TRINITY, 4, 1, 8192, _ALL),
    "kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams": (*_KIMI, 2, 2, 8192, _ALL),
    # the same at 1 row (2 x 2 if the compiler's count stays under 15.0 GiB, else 1 x 4: ISSUE 42) and at 4 (ISSUE 43:
    # for the benchmark issue that may change the cell's microbatch now that the rule's kernels hold no chunk in HBM)
    "kimi-linear-48b-a3b-ep32-d5.8k-1row": (*_KIMI, 1, 4, 8192, _ALL),
    "kimi-linear-48b-a3b-ep32-d5.8k-4rows": (*_KIMI, 4, 1, 8192, _ALL),
    # the last pipeline stage of a last-two-layers job on EvaByte: 8 frozen + 2 trained layers, one row of 32,768 bytes
    "evabyte-6.5b-d10.sft-32k-eva-last2": ("evabyte_6_5b", dict(num_layers=10), 1, 1, 32768, _EVA_RECIPE),
    # its neighbours: two more frozen layers, and the same tokens as two rows of 16,384 (ISSUE 46)
    "evabyte-6.5b-d12.32k-1row": ("evabyte_6_5b", dict(num_layers=12), 1, 1, 32768, _EVA_RECIPE),
    "evabyte-6.5b-d10.16k-2rows": ("evabyte_6_5b", dict(num_layers=10), 2, 1, 16384, _EVA_RECIPE),
    # Granite 4.0-H Micro whole (36 Mamba-2 layers and 4 GQA layers at heads of 64), the last 2 layers and the tied table
    # trained: one row of 8192 x 2 (the backward pass crosses all 40 layers on its way to the table's lookup)
    "granite-4.0-h-micro.sft-8k-ssd-tied-last2": ("granite_4_0_h_micro", {}, 1, 2, 8192, _EVA_RECIPE),
    # its neighbours: the same tokens a step as two rows a microbatch and as one row of 16,384 (ISSUE 49)
    "granite-4.0-h-micro.8k-2rows": ("granite_4_0_h_micro", {}, 2, 1, 8192, _EVA_RECIPE),
    "granite-4.0-h-micro.16k-1row": ("granite_4_0_h_micro", {}, 1, 1, 16384, _EVA_RECIPE),
    # the long-row neighbour no cell measures: benchmarks/long_context.py at 4096
    "smollm3-3b.4k-mlp-ce512": ("smollm3_3b", {}, 1, 8, 4096, dict(_DENSE, remat_policy="mlp", loss_chunk_size=512)),
    "smollm3-3b.4k-mlp": ("smollm3_3b", {}, 1, 8, 4096, dict(_DENSE, remat_policy="mlp")),
}


def main(names) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

    # a deviceless executable cannot be read back from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # or the flash dispatch takes its CPU branch

    def float32(leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        return jax.ShapeDtypeStruct(leaf.shape, jnp.float32, sharding=leaf.sharding)

    for name in names:
        preset, overrides, rows, accum, seq, recipe = STEPS[name]
        started = time.time()
        setup = abstract_train_setup(
            {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, preset, devices=topo.devices[:1],
            accum=accum, seq=seq, per_dp_batch=rows, param_dtype="bfloat16",
            train_kwargs=recipe, model_overrides=overrides,
        )
        state = setup.state.replace(opt_state=jax.tree.map(float32, setup.state.opt_state))
        line = {"step": name}
        try:
            compiled = dataclasses.replace(setup, state=state).compile()
        except jax.errors.JaxRuntimeError as e:  # the refusal is the reading
            line["refused"] = str(e).split("\n\n")[0]
        else:
            memory, text = compiled.memory_analysis(), compiled.as_text().splitlines()
            gib = lambda n: round(n / 2**30, 3)  # noqa: E731
            line.update(
                peak_bytes=memory.peak_memory_in_bytes,
                peak_gib=gib(memory.peak_memory_in_bytes),
                arguments_gib=gib(memory.argument_size_in_bytes),
                temporaries_gib=gib(memory.temp_size_in_bytes),
                mosaic_calls=sum("tpu_custom_call" in ln for ln in text),
                **{
                    kernel: sum("tpu_custom_call" in ln and f"/{kernel}/" in ln for ln in text)
                    for kernel in ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")
                },
                streamed={
                    kernel: n
                    for kernel in (f"flash_attention_{kind}_{k}" for kind in ("window", "causal") for k in ("fwd", "dq", "dkv"))
                    if (n := sum("tpu_custom_call" in ln and f"/{kernel}/" in ln for ln in text))
                },
                # EVA attention's remote kernels (ops/eva_attention.py; its local ones are the resident flash kernels above)
                eva={
                    kernel: n
                    for kernel in ("eva_remote_fwd", "eva_remote_dq", "eva_remote_dkv")
                    if (n := sum("tpu_custom_call" in ln and f"/{kernel}/" in ln for ln in text))
                },
                # the state-space scan's two sweeps (ops/ssd.py)
                ssd={
                    kernel: n
                    for kernel in ("ssd_scan_fwd", "ssd_scan_bwd")
                    if (n := sum("tpu_custom_call" in ln and f"/{kernel}/" in ln for ln in text))
                },
                # the expert layer's sum of rows into tokens (ops/moe._sum_held_rows): 4 an expert layer, 2 of them behind the overflow cond
                sum_held_rows=sum("tpu_custom_call" in ln and "/sum_held_rows/" in ln for ln in text),
                seq_by_seq_buffers=sum(f",{seq},{seq}]" in ln.split(" = ", 1)[-1].split("(", 1)[0] for ln in text),
                seq_by_chunks_buffers=sum(f",{seq},{seq // 16}]" in ln.split(" = ", 1)[-1].split("(", 1)[0] for ln in text),
            )
        line["compile_s"] = round(time.time() - started, 1)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or list(STEPS)))
