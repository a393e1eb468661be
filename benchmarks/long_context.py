#!/usr/bin/env python
"""Long-context single-chip sweep: train-step throughput vs sequence length.

Runs bench.py once per sequence length with the measured-best single-chip
recipe for that cell (found by earlier rounds; their record was deleted in
PR 21 and the rates are not re-measured) and
prints one JSON line per point plus a summary table. The recipes encode the
HBM findings from the round-4 sweep on the 16G v5e chip (SmolLM3-3B):

  seq 1024  mb2 accum16  dots_no_batch remat, full-sequence unembed
  seq 2048  mb1 accum16  dots_no_batch remat, seq-chunked CE 512
  seq 4096  mb1 accum8   mlp remat (dots_no_batch OOMs: 19.4G), CE 512
  seq 8192  mb1 accum4   QLoRA (NF4 base) — full-SFT does not fit a single
                         16G chip at 8k even under full remat (16.9G);
                         beyond that the supported path is the seq axis
                         (ring/ulysses) across chips.

Usage: python benchmarks/long_context.py [--seqs 1024,2048,4096,8192]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# seq -> env recipe (measured-best on one v5e; see module docstring)
RECIPES = {
    1024: {"BENCH_BATCH": "2", "BENCH_ACCUM": "16"},
    2048: {
        "BENCH_BATCH": "1",
        "BENCH_ACCUM": "16",
        "BENCH_LOSS_CHUNK": "512",
    },
    4096: {
        "BENCH_BATCH": "1",
        "BENCH_ACCUM": "8",
        "BENCH_LOSS_CHUNK": "512",
        "BENCH_REMAT_POLICY": "mlp",
    },
    8192: {
        "BENCH_BATCH": "1",
        "BENCH_ACCUM": "4",
        "BENCH_LOSS_CHUNK": "512",
        "BENCH_REMAT_POLICY": "full",
        "BENCH_FREEZE": "qlora",
    },
}


def run_point(seq: int, steps: int) -> dict:
    """One bench.py child per point (this parent never imports JAX, so each
    child in turn owns the chip). A point that fails fails the sweep."""
    env = dict(os.environ)
    env.update(RECIPES[seq])
    env["BENCH_SEQ"] = str(seq)
    env["BENCH_STEPS"] = str(steps)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    if proc.returncode == 0:
        for line in proc.stdout.splitlines():
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
    tail = "\n  ".join(proc.stderr.strip().splitlines()[-5:])
    raise RuntimeError(
        f"seq {seq}: bench.py failed rc={proc.returncode}\n  {tail}"
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="1024,2048,4096,8192")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    seqs = [int(s) for s in args.seqs.split(",")]
    unknown = [s for s in seqs if s not in RECIPES]
    if unknown:
        raise SystemExit(f"no recipe for seq {unknown} (known: {sorted(RECIPES)})")
    rows = []
    for seq in seqs:
        res = run_point(seq, args.steps)
        res["recipe"] = dict(RECIPES[seq])
        rows.append(res)
        print(json.dumps(res), flush=True)

    print(f"\n{'seq':>6} {'samples/s/chip':>15} {'tokens/s/chip':>14} {'step_s':>7}")
    for r in rows:
        print(
            f"{r['seq_len']:>6} {r['value']:>15.3f} "
            f"{r['tokens_per_sec_per_chip']:>14.1f} {r['step_seconds']:>7.2f}"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
