#!/bin/bash
# PR 23, review round, chip call 16 (one chip). Before the call, here:
#   git add -A && rm -rf _checkout && mkdir _checkout && git archive $(git write-tree) | tar -x -C _checkout
#   chiprun --chips 1 --timeout 780 -- bash benchmarks/chipbench/tools/calls/pr23r_proof_from_archive.sh
# Runs a cell from the committed files alone, in _checkout/ (ignored): SmolLM3 sft with the new
# limit, end-to-end metrics; then, if the first run left the time, the same cell traced.
mkdir -p chiprun_out
cd _checkout || exit 1
t0=$(date +%s)
python3 benchmarks/chipbench/run.py --workload smollm3-3b.sft-1k-full --seed 2147485501 --seconds 30 --trace 0 \
  > ../chiprun_out/r2_proof_a.out 2> ../chiprun_out/r2_proof_a.err
echo "proof a rc=$? after $(( $(date +%s) - t0 )) s"
grep -v '^{' ../chiprun_out/r2_proof_a.out; tail -n 1 ../chiprun_out/r2_proof_a.out | cut -c1-700
if [ $(( $(date +%s) - t0 )) -lt 470 ]; then
  python3 benchmarks/chipbench/run.py --workload smollm3-3b.sft-1k-full --seed 5502 --seconds 30 --trace 1 \
    > ../chiprun_out/r2_proof_b.out 2> ../chiprun_out/r2_proof_b.err
  echo "proof b rc=$? after $(( $(date +%s) - t0 )) s"
  grep '^set-up\|^reference' ../chiprun_out/r2_proof_b.out; tail -n 1 ../chiprun_out/r2_proof_b.out | cut -c1-2500
fi
