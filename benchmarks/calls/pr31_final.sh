# rm -rf _step1 && mkdir -p _step1 && git archive $(git write-tree) | tar -x -C _step1
# chiprun --timeout 3000 -- bash benchmarks/calls/pr31_final.sh
# PR 31, the final tree: the committed files alone (git archive of the final tree, in _step1/) against the parent
# (_parent/, git archive of 9b5aecd). The kernel alone; the claimed cell traced on both sides (one seed) and read by
# operation and by scope; three more untraced pairs of it and two of Moonlight, a seed a pair, alternating which side
# goes first.
mkdir -p chiprun_out
M=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
L=moonlight-16b-a3b-ep8-d6.sft-4k-allparams
ROOT=$PWD
run() {  # side, cell, seed, trace, tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr31f_$5.log 2>&1; echo "rc=$? $5")
  grep -h "^window" chiprun_out/pr31f_$5.log; grep -h "^{" chiprun_out/pr31f_$5.log | cut -c1-200
}
(cd _step1 && python benchmarks/moe_kernels.py --sum --iters 10 2>&1 | grep "^{" | tee $ROOT/chiprun_out/pr31f_sum_alone.jsonl)
for side in _parent _step1; do
  run $side $M 2147485117 1 m_${side}_traced
  python benchmarks/dispatch_by_op.py $side/.chipbench_trace/$M auto 12 > chiprun_out/pr31f_m_${side}_by_op.txt 2>&1
  python benchmarks/chipbench/tools/scope_table.py $side/.chipbench_trace/$M 4 4 12 > chiprun_out/pr31f_m_${side}_by_scope.txt 2>&1
  grep -h "^{" chiprun_out/pr31f_m_${side}_traced.log | cut -c1-2600
  grep -v "Warn\|warn" chiprun_out/pr31f_m_${side}_by_op.txt | head -34
done
run _step1 $M 3000000821 0 m_change_3
run _parent $M 3000000821 0 m_parent_3
run _parent $M 2147484827 0 m_parent_4
run _step1 $M 2147484827 0 m_change_4
run _step1 $M 3000000833 0 m_change_5
run _parent $M 3000000833 0 m_parent_5
run _parent $L 3000000839 0 l_parent_1
run _step1 $L 3000000839 0 l_change_1
run _step1 $L 2147484841 0 l_change_2
run _parent $L 2147484841 0 l_parent_2
grep -h "^check" chiprun_out/pr31f_m_*_[345].log | sort | uniq -c | sort -rn | head -40 | cut -c1-200
grep -ih "error\|exhaust\|Traceback" chiprun_out/pr31f_*.log | head -5 | cut -c1-300
