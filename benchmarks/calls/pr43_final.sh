#!/bin/bash
# git add -A && rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout && git archive b62c0fa | tar -x -C _parent
# chiprun --timeout 3500 -- bash benchmarks/calls/pr43_final.sh            (PART=kimi, the default; then PART=others, PART=controls)
# PR 43, from the committed files alone (_checkout/) against the parent (_parent/), through run.py itself, a seed a pair,
# the order parent, change, change, parent. kimi: the claimed cell, two pairs untraced and one traced run a side (the
# parent's with this PR's benchmark files laid over it, as the driver's traced runs are). others: the Qwen3-Next cell
# (the control that the shared helpers were not bent), a pair and a traced run a side. controls: the Kimi cell's planted
# faults on the change (tools/fault_kda.py: the decay as one scalar a head; tools/control.py), and more seeds of the change.
mkdir -p chiprun_out
ROOT=$PWD
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^gated|Error|Traceback'
KIMI=kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams
QWEN=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
run() {  # tree cell seed trace tag [columns]
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr43f_$5.log 2>&1; echo "$5 exit $?")
  grep -E "$KEEP" chiprun_out/pr43f_$5.log | cut -c1-${6:-420}
}
tables() {  # tree cell tag
  (cd $1 && python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$2 ${4:-5} 0 > $ROOT/chiprun_out/pr43f_$3_scope_table.txt 2>&1
   python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$2 auto 40 > $ROOT/chiprun_out/pr43f_$3_gdn_by_op.txt 2>&1
   python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$2 > $ROOT/chiprun_out/pr43f_$3_setup_table.txt 2>&1
   cp .chipbench_trace/$2/setup_spans.json $ROOT/chiprun_out/pr43f_$3_setup_spans.json 2>/dev/null)
  tail -40 chiprun_out/pr43f_$3_gdn_by_op.txt | cut -c1-230
}
case "${PART:-kimi}" in
kimi)
  run _parent $KIMI 3000004311 0 kimi_parent_1
  run _checkout $KIMI 3000004311 0 kimi_change_1
  run _checkout $KIMI 2147486313 0 kimi_change_2
  run _parent $KIMI 2147486313 0 kimi_parent_2
  run _checkout $KIMI 3000004317 1 kimi_change_traced 7000
  tables _checkout $KIMI kimi_change
  cp BENCHMARK.json _parent/ && cp -r benchmarks/chipbench/. _parent/benchmarks/chipbench/
  run _parent $KIMI 3000004317 1 kimi_parent_traced 7000
  ;;
others)
  run _parent $QWEN 3000004321 0 qwen_parent_1
  run _checkout $QWEN 3000004321 0 qwen_change_1
  run _checkout $QWEN 2147486323 0 qwen_change_2
  run _parent $QWEN 2147486323 0 qwen_parent_2
  run _checkout $QWEN 3000004327 1 qwen_change_traced 7000
  run _parent $QWEN 3000004327 1 qwen_parent_traced 7000
  ;;
controls)
  (cd _checkout && python benchmarks/chipbench/tools/fault_kda.py --workload $KIMI --seed 3000004331 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr43f_fault_kda.log 2>&1; echo "fault_kda exit $?")
  grep -E "$KEEP" chiprun_out/pr43f_fault_kda.log | cut -c1-900
  (cd _checkout && python benchmarks/chipbench/tools/control.py --workload $KIMI --seed 2147486333 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr43f_control.log 2>&1; echo "control exit $?")
  grep -E "$KEEP" chiprun_out/pr43f_control.log | cut -c1-900
  for SEED in 3000004337 2147486339 3000004341 2147486343; do run _checkout $KIMI $SEED 0 kimi_sound_$SEED; done
  ;;
esac
