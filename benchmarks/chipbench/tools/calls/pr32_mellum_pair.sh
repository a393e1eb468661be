# (with _parent/ = git archive of the parent commit and _step1/ = git archive $(git write-tree), both ignored by git)
# chiprun --timeout 800 -- bash benchmarks/chipbench/tools/calls/pr32_mellum_pair.sh
# PR 32, second session: the Mellum cell once more, change then parent, same seed and chip: the first pair
# (pr32_cells_before_after.sh) read -1.05% through one stalled step of the change's run at equal median steps.
mkdir -p chiprun_out
E=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
for side in _step1 _parent; do
  (cd $side && python benchmarks/chipbench/run.py --workload $E --seed ${SEED:-3000000653} --seconds 30 --trace 0) > chiprun_out/pr32h_mellum$side.log 2>&1; echo "rc=$? $side"
  grep -h "^window" chiprun_out/pr32h_mellum$side.log; grep -h "^{" chiprun_out/pr32h_mellum$side.log | cut -c1-260
done
