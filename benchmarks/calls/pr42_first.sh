# chiprun --timeout 3000 -- bash benchmarks/calls/pr42_first.sh
# PR 42, first look at the new cell from the working tree: one untraced run (cold: everything compiles), one traced.
mkdir -p chiprun_out
CELL=kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^gated|unknown workload|Error|Traceback|RESOURCE'
python benchmarks/chipbench/run.py --workload $CELL --seed 3000004201 --seconds 30 --trace 0 > chiprun_out/pr42a_cold.log 2>&1; echo "cold exit $?"
grep -E "$KEEP" chiprun_out/pr42a_cold.log | cut -c1-2500
python benchmarks/chipbench/run.py --workload $CELL --seed 2147486203 --seconds 30 --trace 1 > chiprun_out/pr42a_traced.log 2>&1; echo "traced exit $?"
grep -E "$KEEP" chiprun_out/pr42a_traced.log | cut -c1-6000
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 5 0 > chiprun_out/pr42a_scope_table.txt 2>&1; tail -60 chiprun_out/pr42a_scope_table.txt | cut -c1-200
python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$CELL auto > chiprun_out/pr42a_gdn_by_op.txt 2>&1; tail -50 chiprun_out/pr42a_gdn_by_op.txt | cut -c1-220
