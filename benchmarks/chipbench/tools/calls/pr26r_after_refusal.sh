# mkdir -p _parent _step1 _checkout && git archive <parent commit> | tar -x -C _parent && git archive <parent commit> | tar -x -C _step1
# git archive $(git write-tree) | tar -x -C _checkout
# cp _checkout/BENCHMARK.json _step1/ && cp -r _checkout/benchmarks/chipbench/. _step1/benchmarks/chipbench/
# chiprun --timeout 2400 -- bash benchmarks/chipbench/tools/calls/pr26r_after_refusal.sh
# PR 26, after the benchmark check refused the edit to README.md: what the driver does with a new cell, on the
# committed files alone. The parent (_parent) and the parent under this PR's benchmark files (_step1) asked for
# the new cell: both have to fail at once. An accepted cell traced on _step1: the new readers must leave their
# metrics off the line and raise nothing. The new cell from _checkout: one traced run, two untraced, new seeds.
mkdir -p chiprun_out
C=moonlight-16b-a3b-ep8-d6.sft-4k-allparams
run() { # dir cell seed trace tag
  local t0=$SECONDS
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4) > chiprun_out/pr26r_$5.log 2>&1
  echo "rc=$? $5 $((SECONDS - t0)) s"
}
run _parent $C 2147483801 0 parent_newcell; tail -1 chiprun_out/pr26r_parent_newcell.log | cut -c1-300
run _step1 $C 2147483801 0 overlay_newcell; tail -1 chiprun_out/pr26r_overlay_newcell.log | cut -c1-300
run _checkout $C 2147483803 1 newcell_traced
run _checkout $C 3000000207 0 newcell_a
run _checkout $C 2147483809 0 newcell_b
run _step1 smollm3-3b.sft-1k-full 2147483811 1 overlay_smol_traced
grep -h "^check\|^{\|set-up\|reference:\|attention paths" chiprun_out/pr26r_*.log | cut -c1-2600
