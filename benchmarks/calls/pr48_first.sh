#!/bin/bash
# PR 48, first look from the working tree: the rule's two sweeps with a decay a channel ALONE at the Kimi cell's shapes,
# this tree's beside the parent's (_parent/: git archive of a242456), ms a call and the largest difference of every
# cotangent; then the Kimi cell, a traced run with its tables (kda_rule_bwd and kda_rule_fwd a call, whether a forward
# sweep is left under rematted_computation, held GiB, the expert layers) and an untraced one.
#   rm -rf _parent && mkdir _parent && git archive a242456 | tar -x -C _parent
#   chiprun --timeout 2400 -- bash benchmarks/calls/pr48_first.sh
mkdir -p chiprun_out
KIMI=kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^gated|^a rematerialized|unknown workload|Error|Traceback|RESOURCE'
python benchmarks/gdn_kernels.py --only sweeps --parent _parent --iters 10 > chiprun_out/pr48a_sweeps.log 2>&1; echo "sweeps exit $?"
grep -E '^\{|Error|Traceback' chiprun_out/pr48a_sweeps.log | cut -c1-1200
python benchmarks/chipbench/run.py --workload $KIMI --seed 3000004801 --seconds 30 --trace 1 > chiprun_out/pr48a_kimi_traced.log 2>&1; echo "kimi traced exit $?"
grep -E "$KEEP" chiprun_out/pr48a_kimi_traced.log | cut -c1-7000
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$KIMI 5 0 > chiprun_out/pr48a_kimi_scope_table.txt 2>&1
python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$KIMI auto 40 > chiprun_out/pr48a_kimi_gdn_by_op.txt 2>&1; tail -45 chiprun_out/pr48a_kimi_gdn_by_op.txt | cut -c1-230
python benchmarks/chipbench/run.py --workload $KIMI --seed 2147486803 --seconds 30 --trace 0 > chiprun_out/pr48a_kimi_warm.log 2>&1; echo "kimi warm exit $?"
grep -E "$KEEP" chiprun_out/pr48a_kimi_warm.log | cut -c1-2500
