#!/bin/bash
# git add -A && rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout && git archive a242456 | tar -x -C _parent
# rm -rf _step1 && cp -r _checkout _step1 && (the one edit of benchmarks/calls/pr48_order.sh's header in _step1/.../ops/gated_delta.py)
# chiprun --timeout 3500 -- bash benchmarks/calls/pr48_final.sh            (PART=kimi, the default, runs sweeps after it; then PART=qwen, PART=again)
# PR 48, from the committed files alone (_checkout/) against the parent (_parent/), through run.py itself, a seed a pair,
# the order parent, change, change, parent. kimi: the claimed cell, two pairs untraced and one traced run a side with its
# tables (no benchmark file differs between the trees). qwen: the control, the cell that shares _flat_rule,
# _rule_kernels and keeps_scan_output and runs the scalar rule's untouched kernels: one pair and a traced run a side.
# sweeps: the rule's two sweeps alone on both trees and the choices of kda_variants, from the committed tree; then the
# committed tree's sweeps beside _step1/'s (benchmarks/calls/pr48_order.sh: the backward sweep's forward half in the other order).
mkdir -p chiprun_out
ROOT=$PWD
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^gated|^a rematerialized|Error|Traceback|RESOURCE'
KIMI=kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams
QWEN=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
run() {  # tree cell seed trace tag [columns]
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr48f_$5.log 2>&1; echo "$5 exit $?")
  grep -E "$KEEP" chiprun_out/pr48f_$5.log | cut -c1-${6:-420}
}
tables() {  # tree cell tag layers
  (cd $1 && python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$2 $4 0 > $ROOT/chiprun_out/pr48f_$3_scope_table.txt 2>&1
   python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$2 auto 40 > $ROOT/chiprun_out/pr48f_$3_gdn_by_op.txt 2>&1
   python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$2 > $ROOT/chiprun_out/pr48f_$3_setup_table.txt 2>&1
   python benchmarks/dispatch_by_op.py .chipbench_trace/$2 auto 30 > $ROOT/chiprun_out/pr48f_$3_dispatch_by_op.txt 2>&1)
  tail -40 chiprun_out/pr48f_$3_gdn_by_op.txt | cut -c1-230
}
case "${PART:-kimi}" in
kimi)
  run _parent $KIMI 3000004811 0 kimi_parent_1
  run _checkout $KIMI 3000004811 0 kimi_change_1
  run _checkout $KIMI 2147486813 0 kimi_change_2
  run _parent $KIMI 2147486813 0 kimi_parent_2
  run _checkout $KIMI 3000004817 1 kimi_change_traced 7000
  tables _checkout $KIMI kimi_change 5
  run _parent $KIMI 3000004817 1 kimi_parent_traced 7000
  tables _parent $KIMI kimi_parent 5
  PART=sweeps bash $0
  ;;
qwen)
  run _parent $QWEN 3000004821 0 qwen_parent_1
  run _checkout $QWEN 3000004821 0 qwen_change_1
  run _checkout $QWEN 3000004827 1 qwen_change_traced 7000
  run _parent $QWEN 3000004827 1 qwen_parent_traced 7000
  ;;
again)  # kimi_parent_2 stalled (one step of 4,170 ms among 25: the host, PR 42's story): its seed once more on the parent, and a third pair
  run _parent $KIMI 2147486813 0 kimi_parent_2_again
  run _parent $KIMI 3000004831 0 kimi_parent_3
  run _checkout $KIMI 3000004831 0 kimi_change_3
  ;;
sweeps)
  (cd _checkout && python benchmarks/gdn_kernels.py --only kda --parent ../_parent --iters 10 > $ROOT/chiprun_out/pr48f_sweeps.log 2>&1; echo "sweeps exit $?")
  grep -E '^\{|Error|Traceback' chiprun_out/pr48f_sweeps.log | cut -c1-1500
  (cd _checkout && python benchmarks/gdn_kernels.py --only sweeps --parent ../_step1 --iters 20 > $ROOT/chiprun_out/pr48f_order.log 2>&1; echo "order exit $?")
  grep -E '^\{|Error|Traceback' chiprun_out/pr48f_order.log | cut -c1-900
  ;;
esac
