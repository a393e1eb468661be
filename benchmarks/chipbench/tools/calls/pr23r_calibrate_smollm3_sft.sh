#!/bin/bash
# PR 23, review round, chip call 14 (one chip):
#   chiprun --chips 1 --timeout 1380 -- bash benchmarks/chipbench/tools/calls/pr23r_calibrate_smollm3_sft.sh
# Reads the first gradient's error leaf by leaf on SmolLM3-3B sft: twelve sound seeds, the first
# three also with the control (int8 frozen trunk). Rows go to
# chiprun_out/calibrate_smollm3-3b.sft-1k-full.jsonl as they are read.
mkdir -p chiprun_out
python3 benchmarks/chipbench/tools/calibrate_sft.py --workload smollm3-3b.sft-1k-full --steps 1 --control 3 \
  --seeds 401,402,2147484403,404,405,406,407,408,409,410,2147484411,412 \
  > chiprun_out/r2_calib.out 2> chiprun_out/r2_calib.err
echo "calibrate rc=$?"
grep -c . chiprun_out/calibrate_smollm3-3b.sft-1k-full.jsonl
grep "first_grad_worst_leaf_rel_err\|^---\|set-up" chiprun_out/r2_calib.out | tail -n 60
