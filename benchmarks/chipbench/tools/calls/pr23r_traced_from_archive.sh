#!/bin/bash
# PR 23, review round, chip call 17 (one chip), after call 16 showed train_mfu_pct reading the
# trace's write-out as step time. Before the call, here:
#   git add -A && rm -rf _checkout && mkdir _checkout && git archive $(git write-tree) | tar -x -C _checkout
#   chiprun --chips 1 --timeout 330 -- bash benchmarks/chipbench/tools/calls/pr23r_traced_from_archive.sh
# One traced run of SmolLM3 sft at the driver's own arguments, from the committed files alone.
mkdir -p chiprun_out
cd _checkout || exit 1
python3 benchmarks/chipbench/run.py --workload smollm3-3b.sft-1k-full --seed 2147485503 --seconds 30 --trace 1 \
  > ../chiprun_out/r2_proof_c.out 2> ../chiprun_out/r2_proof_c.err
echo "proof c rc=$?"
grep '^set-up\|^reference' ../chiprun_out/r2_proof_c.out; tail -n 1 ../chiprun_out/r2_proof_c.out | cut -c1-1600
