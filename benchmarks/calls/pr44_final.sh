#!/bin/bash
# git add -A && rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout && git archive a19485c | tar -x -C _parent
# chiprun --timeout 3500 -- bash benchmarks/calls/pr44_final.sh            (PART=kimi, the default; then PART=qwen, PART=others, PART=again, PART=dispatch)
# PR 44, from the committed files alone (_checkout/) against the parent (_parent/), through run.py itself, a seed a pair,
# the order parent, change, change, parent. kimi and qwen: the two claimed cells, two pairs untraced and one traced run a
# side with its tables (no benchmark file differs between the trees). others: a cell without a linear layer whose
# blocks pass through the changed _remat_policy (Trinity: window and global layers, routed experts), one pair. again:
# the Kimi cell's first pair's seed once more on each side: whether a tree's loss and gradient norm repeat to the last
# digit from run to run (they differ between the trees in the seventh digit: the compile's doing, or the chip's?).
mkdir -p chiprun_out
ROOT=$PWD
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^gated|Error|Traceback|RESOURCE'
KIMI=kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams
QWEN=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
TRINITY=trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams
run() {  # tree cell seed trace tag [columns]
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr44f_$5.log 2>&1; echo "$5 exit $?")
  grep -E "$KEEP" chiprun_out/pr44f_$5.log | cut -c1-${6:-420}
}
tables() {  # tree cell tag layers
  (cd $1 && python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$2 $4 0 > $ROOT/chiprun_out/pr44f_$3_scope_table.txt 2>&1
   python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$2 auto 40 > $ROOT/chiprun_out/pr44f_$3_gdn_by_op.txt 2>&1
   python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$2 > $ROOT/chiprun_out/pr44f_$3_setup_table.txt 2>&1)
  tail -40 chiprun_out/pr44f_$3_gdn_by_op.txt | cut -c1-230
}
claimed() {  # cell tag layers seeds: pair 1, pair 2, traced
  run _parent $1 $4 0 $2_parent_1
  run _checkout $1 $4 0 $2_change_1
  run _checkout $1 $5 0 $2_change_2
  run _parent $1 $5 0 $2_parent_2
  run _checkout $1 $6 1 $2_change_traced 7000
  tables _checkout $1 $2_change $3
  run _parent $1 $6 1 $2_parent_traced 7000
  tables _parent $1 $2_parent $3
}
case "${PART:-kimi}" in
kimi) claimed $KIMI kimi 5 3000004411 2147486413 3000004417 ;;
qwen) claimed $QWEN qwen 4 3000004421 2147486423 3000004427 ;;
others)
  run _parent $TRINITY 3000004431 0 trinity_parent_1
  run _checkout $TRINITY 3000004431 0 trinity_change_1
  ;;
dispatch)  # what under mlp/router and mlp/experts moved (+3.5 ms a step on Qwen3-Next, +5.6 on Kimi): both sides by operation
  for SIDE in checkout parent; do
    run _$SIDE $QWEN 3000004441 1 qwen_${SIDE}_dispatch 300
    (cd _$SIDE && python benchmarks/dispatch_by_op.py .chipbench_trace/$QWEN auto 30 > $ROOT/chiprun_out/pr44f_qwen_${SIDE}_dispatch_by_op.txt 2>&1
     python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$QWEN auto 400 > $ROOT/chiprun_out/pr44f_qwen_${SIDE}_gdn_by_op_400.txt 2>&1)
    grep -v "^ \|Warning\|warn" chiprun_out/pr44f_qwen_${SIDE}_dispatch_by_op.txt | cut -c1-200
  done
  ;;
again)
  run _checkout $KIMI 3000004411 0 kimi_change_1_again
  run _parent $KIMI 3000004411 0 kimi_parent_1_again
  ;;
esac
