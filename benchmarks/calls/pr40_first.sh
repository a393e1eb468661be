# (_parent/: git archive 59472a7)
# chiprun --timeout 1700 -- bash benchmarks/calls/pr40_first.sh
# PR 40, the first look at the new cell on the chip: the PARENT as it stands exits at once on the cell's name (unknown
# workload); the change's first run (cold: every program compiles), then a traced run. Seeds 3000001601, 3000001603.
mkdir -p chiprun_out
CELL=trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|flash|unknown workload|Error|Traceback|sum of rows|summed'
(cd _parent && time python benchmarks/chipbench/run.py --workload $CELL --seed 3000001601 --seconds 30 --trace 0; echo "parent exit $?") 2>&1 | tail -6
python benchmarks/chipbench/run.py --workload $CELL --seed 3000001601 --seconds 30 --trace 0 > chiprun_out/pr40a_cold.log 2>&1; echo "cold exit $?"
grep -E "$KEEP" chiprun_out/pr40a_cold.log | cut -c1-1500
python benchmarks/chipbench/run.py --workload $CELL --seed 3000001603 --seconds 30 --trace 1 > chiprun_out/pr40a_traced.log 2>&1; echo "traced exit $?"
grep -E "$KEEP" chiprun_out/pr40a_traced.log | cut -c1-6000
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 5 5 > chiprun_out/pr40a_scope_table.txt 2>&1; tail -60 chiprun_out/pr40a_scope_table.txt | cut -c1-220
python benchmarks/dispatch_by_op.py .chipbench_trace/$CELL auto > chiprun_out/pr40a_dispatch.txt 2>&1; tail -25 chiprun_out/pr40a_dispatch.txt | cut -c1-220
cp .chipbench_trace/$CELL/setup_spans.json chiprun_out/pr40a_setup_spans.json 2>/dev/null
python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$CELL 2>&1 | tail -30 | cut -c1-220
