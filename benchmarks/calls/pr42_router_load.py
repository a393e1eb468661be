"""PR 42: ``benchmarks/chipbench/tools/router_load.py``'s count for the kimi_linear configuration (that tool names the
Qwen3-Next reference inside): whether a seed's random sigmoid router spreads its choices over ALL 256 experts at the
real widths, on a CPU, by the reference's own selections of one row of 1024 tokens, a line a seed, ``embed_std`` and
expert layer. A count, never a rate.

    JAX_PLATFORMS=cpu python benchmarks/calls/pr42_router_load.py [embed_std ...]   (seeds 1, 2, 3)
"""
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402

from benchmarks.chipbench import reference_kda_moe as ref, traffic, weights_kda_moe  # noqa: E402

TOKENS = 1024
with open("benchmarks/chipbench/configs/kimi-linear-48b-a3b-ep32-d5.json") as f:
    CFG = json.load(f)
held = len(CFG["held_experts"])
for embed_std in map(float, sys.argv[1:] or [CFG["embed_std"]]):
    cfg = dict(CFG, embed_std=embed_std)
    for seed in (1, 2, 3):
        flat = weights_kda_moe.make_flat(seed, cfg)
        ids = traffic.sft_batch({"accum": 1, "microbatch": 1, "seq_len": TOKENS}, cfg["vocab_size"], seed, 0)["input_ids"][0]
        for layer, chosen in sorted(ref.selections(flat, cfg, ids).items()):
            load = np.asarray(chosen).sum((0, 1))  # [router's width]
            print(json.dumps({
                "embed_std": embed_std, "seed": seed, "layer": layer,
                "pairs_a_token_held": float(load[:held].sum() / TOKENS),
                "held_max_over_mean": float(load[:held].max() / load[:held].mean()),
                "all_max_over_mean": float(load.max() / load.mean()), "experts_with_no_token": int((load == 0).sum()),
                "fullest_chosen_by_pct_of_tokens": float(100 * load.max() / TOKENS),
            }), flush=True)
