# chiprun --timeout 1500 -- bash benchmarks/calls/pr31_first.sh
# PR 31: the kernel alone (the committed tool), then the Mellum cell traced on the parent (_parent/, git archive of 9b5aecd)
# and on the change, one seed, each trace read by operation under mlp/router and mlp/experts.
mkdir -p chiprun_out
C=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
SEED=${SEED:-2147485013}
python benchmarks/moe_kernels.py --sum --iters 10 2>&1 | grep "^{" | tee chiprun_out/pr31_sum_alone.jsonl
(cd _parent && python benchmarks/chipbench/run.py --workload $C --seed $SEED --seconds 30 --trace 1 > ../chiprun_out/pr31_parent_traced.log 2>&1; echo "rc=$? parent traced")
python benchmarks/dispatch_by_op.py _parent/.chipbench_trace/$C auto 30 > chiprun_out/pr31_parent_by_op.txt 2>&1
python benchmarks/chipbench/run.py --workload $C --seed $SEED --seconds 30 --trace 1 > chiprun_out/pr31_change_traced.log 2>&1; echo "rc=$? change traced"
python benchmarks/dispatch_by_op.py .chipbench_trace/$C auto 30 > chiprun_out/pr31_change_by_op.txt 2>&1
for side in parent change; do
  echo "== $side"; grep -h "^window\|^attention\|^expert rows" chiprun_out/pr31_${side}_traced.log; grep -h "^{" chiprun_out/pr31_${side}_traced.log | cut -c1-3000
  head -24 chiprun_out/pr31_${side}_by_op.txt
done
grep -ih "error\|exhaust\|Traceback" chiprun_out/pr31_*_traced.log | head -5 | cut -c1-300
