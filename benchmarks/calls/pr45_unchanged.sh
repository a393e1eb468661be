#!/bin/bash
# git add -A && rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout && git archive d7eecd1 | tar -x -C _parent
# chiprun --timeout 2400 -- bash benchmarks/calls/pr45_unchanged.sh
# PR 45 changes tests and documents only: no file of the program or of the benchmark differs between the trees
# (diff -r below says so on the machine itself). One pair a seed in ONE cell all the same, from the committed files alone
# (_checkout/) against the parent (_parent/), through run.py itself, the order parent, change, change, parent: that the
# committed tree builds and runs the benchmark, and what two identical programs read apart on this chip.
mkdir -p chiprun_out
ROOT=$PWD
CELL=${CELL:-smollm3-3b.sft-1k-full}
diff -r _parent/llm_fine_tune_distributed_tpu _checkout/llm_fine_tune_distributed_tpu && diff -r _parent/benchmarks/chipbench _checkout/benchmarks/chipbench \
  && diff _parent/BENCHMARK.json _checkout/BENCHMARK.json && echo "program and benchmark: identical in both trees"
run() {  # tree seed tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $CELL --seed $2 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr45_$3.log 2>&1; echo "$3 exit $?")
  grep -E '^check|^\{|Error|Traceback|RESOURCE' chiprun_out/pr45_$3.log | cut -c1-600
}
run _parent 3000004511 parent_1
run _checkout 3000004511 change_1
run _checkout 2147486513 change_2
run _parent 2147486513 parent_2
