# rm -rf _parent && mkdir -p _parent && git archive <parent commit> | tar -x -C _parent && cp BENCHMARK.json _parent/ && cp -r benchmarks/chipbench/. _parent/benchmarks/chipbench/
# chiprun --timeout 3400 -- bash benchmarks/chipbench/tools/calls/pr32_first_look.sh
# PR 32: the parent (this PR's benchmark files laid over it, in _parent/, ignored by git) asked for the new cell,
# which it has to refuse at once; the gated delta rule alone (chunk and inverse: benchmarks/gdn_kernels.py); then
# the new cell traced and untraced, and the traced step by scope and by operation.
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
t0=$(date +%s)
(cd _parent && timeout 600 python benchmarks/chipbench/run.py --workload $C --seed 1 --seconds 30 --trace 0) > chiprun_out/pr32_parent_newcell.log 2>&1
echo "rc=$? parent on the new cell, $(( $(date +%s) - t0 )) s"; tail -2 chiprun_out/pr32_parent_newcell.log | cut -c1-300
python benchmarks/gdn_kernels.py > chiprun_out/pr32_gdn_kernels.log 2>&1; echo "rc=$? gdn_kernels"; grep "^{" chiprun_out/pr32_gdn_kernels.log
run() { # seed trace tag
  python benchmarks/chipbench/run.py --workload $C --seed $1 --seconds 30 --trace $2 > chiprun_out/pr32_$3.log 2>&1; echo "rc=$? $3"
}
run 2147484101 1 new_traced
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$C 4 4 12 > chiprun_out/pr32_scope_table.txt 2>&1
python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$C auto 30 > chiprun_out/pr32_gdn_by_op.txt 2>&1
python benchmarks/dispatch_by_op.py .chipbench_trace/$C auto 20 > chiprun_out/pr32_dispatch_by_op.txt 2>&1
run 3000000103 0 new_a
grep -h "^check\|^set-up\|^reference\|attention paths\|gated delta\|^window\|summed into" chiprun_out/pr32_new_*.log | cut -c1-260
grep -ih "error\|exhaust" chiprun_out/pr32_new_*.log | head -5 | cut -c1-400
grep -h "^{" chiprun_out/pr32_new_*.log | cut -c1-3000
head -40 chiprun_out/pr32_gdn_by_op.txt | cut -c1-200
head -16 chiprun_out/pr32_scope_table.txt | cut -c1-220
head -24 chiprun_out/pr32_dispatch_by_op.txt | cut -c1-200
