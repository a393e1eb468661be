# rm -rf _step1 && mkdir -p _step1 && git archive $(git write-tree) | tar -x -C _step1
# chiprun --timeout 2400 -- bash benchmarks/chipbench/tools/calls/pr30_proof_from_archive.sh
# PR 30: the committed files alone (git archive of the final tree, in _step1/, ignored by git) run the new cell:
# untraced on three seeds (or those of $SEEDS), traced with the table by scope behind the traced line's shares, and the control.
mkdir -p chiprun_out
C=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
cd _step1
for seed in ${SEEDS:-3000000401 2147484403 3000000407}; do
  python benchmarks/chipbench/run.py --workload $C --seed $seed --seconds 30 --trace 0 > ../chiprun_out/pr30_final_$seed.log 2>&1; echo "rc=$? final $seed"
done
python benchmarks/chipbench/run.py --workload $C --seed 2147484409 --seconds 30 --trace 1 > ../chiprun_out/pr30_final_traced.log 2>&1; echo "rc=$? final traced"
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$C 4 4 > ../chiprun_out/pr30_final_scope_table.txt 2>&1; echo "rc=$? scope table"
python benchmarks/chipbench/tools/control.py --workload $C --seed 3000000411 --seconds 30 --trace 0 > ../chiprun_out/pr30_final_control.log 2>&1; echo "rc=$? final control"
cd ..
grep -h "^check" chiprun_out/pr30_final_*.log | cut -c1-200
grep -h "^{" chiprun_out/pr30_final_*.log | cut -c1-2400
head -60 chiprun_out/pr30_final_scope_table.txt

