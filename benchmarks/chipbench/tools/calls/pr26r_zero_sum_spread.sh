# git archive $(git write-tree) | tar -x -C _checkout
# chiprun --timeout 1000 -- bash benchmarks/chipbench/tools/calls/pr26r_zero_sum_spread.sh
# PR 26, after the refusal: the new cell from the committed files with the router's columns of each share summing
# to zero (weights_mla_moe.zero_sum_by_share): four new seeds for the spread, one traced run for the counters.
mkdir -p chiprun_out
C=moonlight-16b-a3b-ep8-d6.sft-4k-allparams
run() { # seed trace tag
  local t0=$SECONDS
  (cd _checkout && python benchmarks/chipbench/run.py --workload $C --seed $1 --seconds 30 --trace $2) > chiprun_out/pr26z_$3.log 2>&1
  echo "rc=$? $3 $((SECONDS - t0)) s"
}
run 2147483901 0 a
run 3000000311 0 b
run 907 0 c
run 2147483913 0 d
run 3000000317 1 traced
grep -h "^check\|^{\|set-up\|reference:" chiprun_out/pr26z_*.log | cut -c1-1800
