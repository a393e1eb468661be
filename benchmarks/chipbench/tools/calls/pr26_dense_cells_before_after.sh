# mkdir -p _parent && git archive <parent commit> | tar -x -C _parent
# chiprun --timeout 3400 -- bash benchmarks/chipbench/tools/calls/pr26_dense_cells_before_after.sh
# PR 26: both accepted cells, parent (in _parent/, ignored by git) and change, same chip, same seeds; and the
# parent asked for the new cell, which it has to refuse at once.
mkdir -p chiprun_out
run() { # dir cell seed trace tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4) > chiprun_out/pr26_$5.log 2>&1; echo "rc=$? $5"
}
S=smollm3-3b.sft-1k-full; M=mistral-7b-d16.sft-2k-full
run _parent $S 501 0 smol_parent_a
run .       $S 501 0 smol_change_a
run .       $S 502 1 smol_change_traced
run _parent $S 502 0 smol_parent_b
run _parent $M 601 0 mistral_parent_a
run .       $M 601 0 mistral_change_a
run .       $M 602 1 mistral_change_traced
run _parent $M 602 0 mistral_parent_b
(cd _parent && python benchmarks/chipbench/run.py --workload moonlight-16b-a3b-ep8-d6.sft-4k-allparams --seed 1 --seconds 30 --trace 0) > chiprun_out/pr26_parent_newcell.log 2>&1
echo "rc=$? parent on the new cell"; tail -2 chiprun_out/pr26_parent_newcell.log | cut -c1-300
grep -h "^{" chiprun_out/pr26_*.log | cut -c1-900
