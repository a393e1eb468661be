# git add -A && rm -rf _checkout && mkdir _checkout && git archive $(git write-tree) | tar -x -C _checkout
# chiprun --timeout 600 -- bash benchmarks/calls/pr49_final2.sh
# PR 49, review round, the last call (10.8 chip-minutes were left): the cell from the committed files alone as it lands
# (the scan's sweeps, the weights made a layer a program, nothing of a scan kept, the final limits), once, traced, on a
# seed of its own and on the machine's own compile cache.
mkdir -p chiprun_out
ROOT=$PWD
CELL=granite-4.0-h-micro.sft-8k-ssd-tied-last2
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^state-space|^q/k|^a remat|Error|Traceback|RESOURCE'
(cd _checkout && python benchmarks/chipbench/run.py --workload $CELL --seed 3000005021 --seconds 30 --trace 1 > $ROOT/chiprun_out/pr49g_traced.log 2>&1); echo "traced exit $? after $SECONDS s"
grep -E "$KEEP" chiprun_out/pr49g_traced.log | cut -c1-7000
(cd _checkout && timeout 60 python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 40 2 20) > chiprun_out/pr49g_scope_table.txt 2>&1; sed -n 1,12p chiprun_out/pr49g_scope_table.txt | cut -c1-200
