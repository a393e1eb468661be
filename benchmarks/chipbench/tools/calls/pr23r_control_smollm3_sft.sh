#!/bin/bash
# PR 23, review round, chip call 15 (one chip):
#   chiprun --chips 1 --timeout 600 -- bash benchmarks/chipbench/tools/calls/pr23r_control_smollm3_sft.sh
# The control (int8 frozen trunk) alone on call 14's three control seeds, with the scale that
# gradient clipping puts on every leaf fitted out: what the lower precision alone does to the
# first gradient, leaf by leaf. Rows are appended to chiprun_out/calibrate_smollm3-3b.sft-1k-full.jsonl.
mkdir -p chiprun_out
python3 benchmarks/chipbench/tools/calibrate_sft.py --workload smollm3-3b.sft-1k-full --steps 1 --control 3 \
  --only-control 1 --seeds 401,402,2147484403 > chiprun_out/r2_control.out 2> chiprun_out/r2_control.err
echo "control rc=$?"
tail -n 3 chiprun_out/calibrate_smollm3-3b.sft-1k-full.jsonl | cut -c1-2500
tail -n 5 chiprun_out/r2_control.err
