# git add -A && rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout
# && git archive 891ebcdeb16a2324daa7ece2ae5477e474b04847 | tar -x -C _parent
# chiprun --timeout 3500 -- env PART=pair bash benchmarks/calls/pr47_cell.sh   (then PART=more, PART=faults, PART=final)
# PR 47, the EvaByte cell, parent against change from the committed files alone (_parent/, _checkout/) on one machine.
# PART=pair: untraced parent, change, change, parent (two pairs, each pair one seed), then traced once a side with the
# by-scope table of each. PART=more: three more pairs on three more seeds, the order alternating. PART=faults: the
# change's control (the int8 frozen trunk) and the three planted faults, `correct` false by the cell's limits.
# PART=final: one more pair, from the write-tree as it is handed in.
mkdir -p chiprun_out
ROOT=$PWD
CELL=evabyte-6.5b-d10.sft-32k-eva-last2
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^eva|Error|Traceback|RESOURCE'
run() {  # tree seed trace tag [entry and its options]
  TREE=$1; SEED=$2; TRACE=$3; TAG=$4; shift 4
  (cd $TREE && python ${@:-benchmarks/chipbench/run.py} --workload $CELL --seed $SEED --seconds 30 --trace $TRACE > $ROOT/chiprun_out/pr47_$TAG.log 2>&1; echo "$TAG $TREE $SEED exit $?")
  grep -E "$KEEP" chiprun_out/pr47_$TAG.log | cut -c1-${WIDE:-700}
}
case "${PART:-pair}" in
pair)
  run _parent 3000004711 0 pair1_parent
  run _checkout 3000004711 0 pair1_change
  run _checkout 2147486717 0 pair2_change
  run _parent 2147486717 0 pair2_parent
  for SIDE in parent change; do
    TREE=_parent; [ $SIDE = change ] && TREE=_checkout
    WIDE=7000 run $TREE 3000004723 1 traced_$SIDE
    (cd $TREE && python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 10 2 20) > chiprun_out/pr47_scope_table_$SIDE.txt 2>&1
    tail -45 chiprun_out/pr47_scope_table_$SIDE.txt | cut -c1-200
    (cd $TREE && python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$CELL) > chiprun_out/pr47_setup_table_$SIDE.txt 2>&1
    cp $TREE/.chipbench_trace/$CELL/setup_spans.json chiprun_out/pr47_setup_spans_$SIDE.json 2>/dev/null
  done ;;
more)
  run _checkout 3000004729 0 pair3_change
  run _parent 3000004729 0 pair3_parent
  run _parent 2147486731 0 pair4_parent
  run _checkout 2147486731 0 pair4_change
  run _checkout 3000004733 0 pair5_change
  run _parent 3000004733 0 pair5_parent ;;
final)  # one pair from the final write-tree
  run _checkout 2147486749 0 final_change
  run _parent 2147486749 0 final_parent ;;
faults)
  run _checkout 2147486741 0 control benchmarks/chipbench/tools/control.py
  for FAULT in no_summaries own_window_summaries first_head_only; do
    run _checkout 3000004743 0 $FAULT benchmarks/chipbench/tools/fault_eva.py --fault $FAULT
  done ;;
esac
