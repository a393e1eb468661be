#!/bin/bash
# PR 43, first look at the Kimi cell with the by-channel sweeps, from the working tree: one untraced run (cold: the step
# compiles), one traced with its tables, one untraced warm (its setup_s is the one to read).
#   chiprun --timeout 2400 -- bash benchmarks/calls/pr43_cell_first.sh
mkdir -p chiprun_out
CELL=kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^gated|unknown workload|Error|Traceback|RESOURCE'
python benchmarks/chipbench/run.py --workload $CELL --seed 3000004301 --seconds 30 --trace 0 > chiprun_out/pr43b_cold.log 2>&1; echo "cold exit $?"
grep -E "$KEEP" chiprun_out/pr43b_cold.log | cut -c1-2500
python benchmarks/chipbench/run.py --workload $CELL --seed 2147486303 --seconds 30 --trace 1 > chiprun_out/pr43b_traced.log 2>&1; echo "traced exit $?"
grep -E "$KEEP" chiprun_out/pr43b_traced.log | cut -c1-7000
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 5 0 > chiprun_out/pr43b_scope_table.txt 2>&1
python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$CELL auto 40 > chiprun_out/pr43b_gdn_by_op.txt 2>&1; tail -45 chiprun_out/pr43b_gdn_by_op.txt | cut -c1-230
python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$CELL > chiprun_out/pr43b_setup_table.txt 2>&1
python benchmarks/chipbench/run.py --workload $CELL --seed 3000004307 --seconds 30 --trace 0 > chiprun_out/pr43b_warm.log 2>&1; echo "warm exit $?"
grep -E "$KEEP" chiprun_out/pr43b_warm.log | cut -c1-2500
