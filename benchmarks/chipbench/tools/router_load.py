"""Whether a seed's random router spreads its choices over ALL its experts at a
configuration's real widths, on a CPU: the reference's own selections
(``reference_gdn_moe.selections``) of one row of 1024 tokens, a line a seed and
layer (the pairs a token this chip's share draws, its fullest expert over the
mean, the same over all the router's columns, experts no token chose).

    JAX_PLATFORMS=cpu python benchmarks/chipbench/tools/router_load.py [config name] [seed ...]

A builder's tool, nothing runs it (PERF.md section 4, PR 32: the count that
``embed_std`` 1.0 and the zero-sum router columns are held against).
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.chipbench import reference_gdn_moe as ref, traffic, weights_gdn_moe  # noqa: E402

TOKENS = 1024


def main(argv) -> int:
    name = argv[0] if argv else "qwen3-next-80b-a3b-ep16-d4"
    with open(os.path.join(ROOT, "benchmarks/chipbench/configs", name + ".json")) as f:
        cfg = json.load(f)
    held = len(cfg["held_experts"])
    for seed in map(int, argv[1:] or (1, 2, 3)):
        flat = weights_gdn_moe.make_flat(seed, cfg)
        ids = traffic.sft_batch({"accum": 1, "microbatch": 1, "seq_len": TOKENS}, cfg["vocab_size"], seed, 0)["input_ids"][0]
        for layer, chosen in sorted(ref.selections(flat, cfg, ids).items()):
            load = np.asarray(chosen).sum((0, 1))  # [router's width]
            print(json.dumps({
                "seed": seed, "layer": layer, "pairs_a_token_held": float(load[:held].sum() / TOKENS),
                "held_max_over_mean": float(load[:held].max() / load[:held].mean()),
                "all_max_over_mean": float(load.max() / load.mean()), "experts_with_no_token": int((load == 0).sum()),
                "fullest_chosen_by_pct_of_tokens": float(100 * load.max() / TOKENS),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
