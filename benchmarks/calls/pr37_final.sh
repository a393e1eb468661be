# rm -rf _checkout _scratch_calls/group2 && mkdir -p _checkout _scratch_calls/group2 && git archive $(git write-tree) | tar -x -C _checkout
# git archive $(git write-tree) | tar -x -C _scratch_calls/group2 && sed -i 's/^GROUP = 4$/GROUP = 2/' _scratch_calls/group2/llm_fine_tune_distributed_tpu/ops/gated_delta.py
# chiprun --timeout 3500 -- bash benchmarks/calls/pr37_final.sh
# PR 37, the final tree: the committed files alone (git archive of the final tree, in _checkout/) against the parent
# (_parent/, git archive of 7d120b3). The rule alone (XLA solve beside the kernels, the builder's tool); the cell once
# untimed for set-up (the archive's paths are new to the machine's compile cache), then traced and read by part and by
# scope, its control, and three more pairs on seeds of their own, which side goes first alternating; last PR 36's tree and
# a group of 2 through the same run.py.
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
ROOT=$PWD
run() {  # directory, seed, trace, tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $C --seed $2 --seconds 30 --trace $3 > $ROOT/chiprun_out/pr37f_$4.log 2>&1; echo "rc=$? $4")
  grep -h "^set-up: state\|^window\|^gated delta" chiprun_out/pr37f_$4.log | cut -c1-220; grep -h "^{" chiprun_out/pr37f_$4.log | cut -c1-${5:-230}
}
(cd _checkout && python benchmarks/gdn_kernels.py --only rule --inverse solve kernels 2>&1 | grep "^{" | tee $ROOT/chiprun_out/pr37f_rule.jsonl)
run _checkout 3000000739 0 change_first
run _checkout 2147484743 1 change_traced 4000
python benchmarks/chipbench/tools/gdn_by_op.py _checkout/.chipbench_trace/$C auto 14 > chiprun_out/pr37f_gdn_by_op.txt 2>&1
python benchmarks/chipbench/tools/scope_table.py _checkout/.chipbench_trace/$C 4 4 12 > chiprun_out/pr37f_scope_table.txt 2>&1
grep -v "Warn\|warn" chiprun_out/pr37f_gdn_by_op.txt | head -30 | cut -c1-230
(cd _checkout && python benchmarks/chipbench/tools/control.py --workload $C --seed 3000000751 --seconds 5 --trace 0 > $ROOT/chiprun_out/pr37f_control.log 2>&1; echo "rc=$? control")
grep -h "^check" chiprun_out/pr37f_control.log | cut -c1-200; grep -h "^{" chiprun_out/pr37f_control.log | cut -c1-200
# the gate as ISSUE 37 words it, through run.py itself (pr37_gate.sh went through pr37_cell.py on both sides): three pairs
run _parent 2147484757 0 parent_4
run _checkout 2147484757 0 change_4
run _checkout 3000000761 0 change_5
run _parent 3000000761 0 parent_5
run _parent 2147484767 0 parent_6
run _checkout 2147484767 0 change_6
# PR 36's tree through run.py itself, as its own calls and the driver ran it (they read +11 s of set-up; under
# pr37_cell.py it read +1): twice, the second surely out of the cache
run _step1 3000000773 0 pr36_plain_1
run _step1 2147484779 0 pr36_plain_2
# the same final tree with 2 chunks side by side (GROUP = 2; _scratch_calls/group2/, made by sed from the archive): its
# first run compiles, the second is read
run _scratch_calls/group2 3000000787 0 group2_first
run _scratch_calls/group2 2147484791 0 group2_second
grep -h "^check" chiprun_out/pr37f_change_*.log | sort | uniq -c | sort -rn | head -40 | cut -c1-200
grep -ih "error\|exhaust\|Traceback" chiprun_out/pr37f_*.log | head -5 | cut -c1-300
