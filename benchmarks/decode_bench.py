#!/usr/bin/env python
"""Decode (inference) throughput: tokens/sec for the flagship model, bf16 vs
NF4-quantized base.

Autoregressive decode is weight-bandwidth-bound at batch 1 — each token reads
every matmul weight once — so the NF4 path (4.5 bits/param at rest) trades a
~3.5x smaller HBM weight stream against dequantization cost. The NF4 matmuls
run through the default XLA dequant path (``nf4_matmul(impl="auto")``
resolves to ``"xla"`` — measured fastest on v5e; the fused Pallas VMEM-decode
Pallas kernel was retired after the v5e shootout — ops/nf4.py). This harness
measures both variants on the same chip and prints one JSON line per variant.

The reference has no decode benchmark (its inference is an interactive CLI);
this quantifies the serving-side half of the framework.

Usage: python benchmarks/decode_bench.py  (env: DECODE_PRESET, DECODE_NEW,
DECODE_PROMPT, DECODE_VARIANTS=bf16,nf4)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_fine_tune_distributed_tpu.data.tokenizer import load_tokenizer
    from llm_fine_tune_distributed_tpu.infer.generate import GenerationConfig, Generator
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.parallel.qlora import quantize_frozen
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict, unflatten_dict

    from llm_fine_tune_distributed_tpu.runtime.device import on_accelerator as _on_acc

    # raises on a CPU nobody asked for; JAX_PLATFORMS=cpu rehearses on tiny
    on_accelerator = _on_acc(jax.devices()[0].platform)
    preset = os.environ.get(
        "DECODE_PRESET", "smollm3_3b" if on_accelerator else "tiny"
    )
    max_new = int(os.environ.get("DECODE_NEW", "128" if on_accelerator else "16"))
    prompt_len = int(os.environ.get("DECODE_PROMPT", "64"))
    variants = os.environ.get("DECODE_VARIANTS", "bf16,int8,nf4").split(",")

    mc = get_preset(preset)
    tok = load_tokenizer("byte-chatml")
    params_bf16 = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, min(mc.vocab_size, 256), (prompt_len,)).tolist()
    gen = GenerationConfig(max_new_tokens=max_new, do_sample=False)

    def measure(params, label):
        g = Generator(params, mc, tok, eos_token_ids=[])  # no early stop
        t0 = time.perf_counter()
        out = g.generate_ids(prompt, gen)  # compile + first run
        compile_and_first = time.perf_counter() - t0
        n_runs = 3
        t0 = time.perf_counter()
        for s in range(n_runs):
            out = g.generate_ids(prompt, gen, seed=s)
        dt = (time.perf_counter() - t0) / n_runs
        tps = len(out) / dt if out else max_new / dt
        print(json.dumps({
            "metric": f"decode_tokens_per_sec_{label}",
            "value": round(tps, 2),
            "unit": "tokens/sec",
            "model": preset,
            "platform": jax.devices()[0].platform,
            "max_new_tokens": max_new,
            "prompt_len": prompt_len,
            "first_call_seconds": round(compile_and_first, 2),
        }))
        return tps

    # Measure one variant at a time, freeing each quantized copy before the
    # next is built — three resident 3B copies would exceed 16GB HBM.
    import gc

    results = {}
    if "bf16" in variants:
        results["bf16"] = measure(params_bf16, "bf16")
    if "int8" in variants:
        from llm_fine_tune_distributed_tpu.ops.int8 import quantize_params_int8

        # weight-only int8: half the HBM weight stream, dequant fused into
        # the matmul read (ops/int8.py) — the decode-side sweet spot
        params_int8 = quantize_params_int8(params_bf16)
        results["int8"] = measure(params_int8, "int8")
        del params_int8
        gc.collect()
    if "nf4" in variants:
        # leaves passed as-is: quantize_frozen's large-leaf path quantizes
        # on-device, so no host round-trip of the full weight set
        qflat = quantize_frozen(dict(flatten_dict(params_bf16)))
        # non-quantized leaves back to bf16 compute dtype (no-op copies for
        # already-bf16 leaves, so embeddings/norms stay SHARED with
        # params_bf16 — which can then be dropped before the measure)
        qflat = {
            k: (jnp.asarray(v, jnp.bfloat16)
                if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating) and "absmax" not in k
                else jnp.asarray(v))
            for k, v in qflat.items()
        }
        del params_bf16
        gc.collect()
        results["nf4"] = measure(unflatten_dict(qflat), "nf4")
    if "spec" in variants:
        # prompt-lookup speculation on the bf16 weights: pays off exactly
        # when the OUTPUT repeats n-grams (greedy decode of an un-tuned
        # model loops readily, making this the favorable case; the
        # acceptance rate in the output line says how favorable it was)
        if "bf16" not in results or "nf4" in variants:
            # the nf4 branch frees params_bf16 to fit HBM — rebuild
            params_bf16 = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.bfloat16)
        g = Generator(params_bf16, mc, tok, eos_token_ids=[])
        spec_gen = GenerationConfig(
            max_new_tokens=max_new, do_sample=False,
            speculative_lookup=int(os.environ.get("DECODE_SPEC_K", "8")),
        )
        t0 = time.perf_counter()
        out = g.generate_ids(prompt, spec_gen)
        first = time.perf_counter() - t0
        n_runs = 3
        t0 = time.perf_counter()
        for s in range(n_runs):
            out = g.generate_ids(prompt, spec_gen, seed=s)
        dt = (time.perf_counter() - t0) / n_runs
        tps = (len(out) or max_new) / dt
        results["spec"] = tps
        print(json.dumps({
            "metric": "decode_tokens_per_sec_spec_lookup",
            "value": round(tps, 2),
            "unit": "tokens/sec",
            "model": preset,
            "platform": jax.devices()[0].platform,
            "speculative_lookup": spec_gen.speculative_lookup,
            "acceptance_rate": round(g.last_acceptance_rate or 0.0, 3),
            "sequential_forwards": g.last_spec_steps,
            "first_call_seconds": round(first, 2),
        }))

    if "bf16" in results:
        for name, tps in results.items():
            if name == "bf16":
                continue
            print(json.dumps({
                "metric": f"decode_{name}_speedup_vs_bf16",
                "value": round(tps / results["bf16"], 3),
                "unit": "x",
            }))


if __name__ == "__main__":
    main()
