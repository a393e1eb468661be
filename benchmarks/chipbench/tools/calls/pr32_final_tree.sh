# rm -rf _step1 && mkdir -p _step1 && git archive $(git write-tree) | tar -x -C _step1
# chiprun --timeout 3400 -- bash benchmarks/chipbench/tools/calls/pr32_final_tree.sh
# PR 32: the committed files alone (git archive of the final tree, in _step1/, ignored by git) run the new cell: six
# seeds untraced, one traced with its tables (by scope, the linear layers by part, router and experts by operation), the
# mix's control (router in float8_e4m3fn, the rule's carried state in bfloat16) and the two planted faults: not correct.
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
cd _step1
for seed in ${SEEDS:-3000000401 2147484403 3000000407 2147484409 3000000413 2147484419}; do
  python benchmarks/chipbench/run.py --workload $C --seed $seed --seconds 30 --trace 0 > ../chiprun_out/pr32f_$seed.log 2>&1; echo "rc=$? $seed"
  grep -h "^window\|^reference" ../chiprun_out/pr32f_$seed.log; grep -h "^{" ../chiprun_out/pr32f_$seed.log | cut -c1-260
done
python benchmarks/chipbench/run.py --workload $C --seed 2147484421 --seconds 30 --trace 1 > ../chiprun_out/pr32f_traced.log 2>&1; echo "rc=$? traced"
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$C 4 4 12 > ../chiprun_out/pr32f_scope_table.txt 2>&1
python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$C auto 40 > ../chiprun_out/pr32f_gdn_by_op.txt 2>&1
python benchmarks/dispatch_by_op.py .chipbench_trace/$C auto 12 > ../chiprun_out/pr32f_dispatch_by_op.txt 2>&1
python benchmarks/chipbench/tools/control.py --workload $C --seed 3000000427 --seconds 5 --trace 0 > ../chiprun_out/pr32f_control.log 2>&1; echo "rc=$? control"
python benchmarks/chipbench/tools/fault.py --fault half_batch --workload $C --seed 2147484429 --seconds 5 --trace 0 > ../chiprun_out/pr32f_half_batch.log 2>&1; echo "rc=$? half_batch"
python benchmarks/chipbench/tools/fault.py --fault unchanged_state --workload $C --seed 3000000431 --seconds 5 --trace 0 > ../chiprun_out/pr32f_unchanged_state.log 2>&1; echo "rc=$? unchanged_state"
cd ..
grep -h "^check" chiprun_out/pr32f_[0-9]*.log chiprun_out/pr32f_traced.log | awk '{print $2, $3}' | sort -k1,1 -k2,2g | awk '{last[$1]=$2} END {for (k in last) print "sound largest", k, last[k]}'
for f in control half_batch unchanged_state; do echo "== $f"; grep -h "^check" chiprun_out/pr32f_$f.log | cut -c1-200; grep -h "^{" chiprun_out/pr32f_$f.log | cut -c1-40; done
grep -h "^set-up\|^window\|^reference\|attention paths\|gated delta\|summed into" chiprun_out/pr32f_traced.log | cut -c1-240
grep -h "^{" chiprun_out/pr32f_traced.log | cut -c1-3200
head -14 chiprun_out/pr32f_gdn_by_op.txt | tail -11 | cut -c1-160
sed -n 3,16p chiprun_out/pr32f_scope_table.txt | cut -c1-200
sed -n 3,22p chiprun_out/pr32f_dispatch_by_op.txt | cut -c1-160
grep -ih "error\|exhaust" chiprun_out/pr32f_*.log | head -5 | cut -c1-300
