#!/bin/bash
# PR 44, first look at both linear cells with the rule's o and states kept across the block's remat, from the working
# tree: a traced run a cell with its tables (whether a forward sweep is left under rematted_computation, the sweeps'
# calls a step, remat and gdn_scan shares, held GiB), then an untraced one (the first run of a cell is cold: the step
# compiles; the second run's setup_s is the one to read).
#   chiprun --timeout 2400 -- bash benchmarks/calls/pr44_first.sh
mkdir -p chiprun_out
KIMI=kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams
QWEN=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^gated|unknown workload|Error|Traceback|RESOURCE'
look() {  # cell tag layers seed-traced seed-untraced
  python benchmarks/chipbench/run.py --workload $1 --seed $4 --seconds 30 --trace 1 > chiprun_out/pr44a_$2_traced.log 2>&1; echo "$2 traced exit $?"
  grep -E "$KEEP" chiprun_out/pr44a_$2_traced.log | cut -c1-7000
  python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$1 $3 0 > chiprun_out/pr44a_$2_scope_table.txt 2>&1
  python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$1 auto 40 > chiprun_out/pr44a_$2_gdn_by_op.txt 2>&1; tail -45 chiprun_out/pr44a_$2_gdn_by_op.txt | cut -c1-230
  python benchmarks/chipbench/run.py --workload $1 --seed $5 --seconds 30 --trace 0 > chiprun_out/pr44a_$2_warm.log 2>&1; echo "$2 warm exit $?"
  grep -E "$KEEP" chiprun_out/pr44a_$2_warm.log | cut -c1-2500
}
look $KIMI kimi 5 3000004401 2147486403
look $QWEN qwen 4 3000004407 2147486409
