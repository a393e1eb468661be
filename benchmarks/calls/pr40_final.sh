# git add -A && rm -rf _checkout && mkdir _checkout && git archive $(git write-tree) | tar -x -C _checkout
# chiprun --timeout 2400 -- bash benchmarks/calls/pr40_final.sh
# PR 40, the final tree: the committed files alone (_checkout/), the limits as committed. A traced run of the new cell, two
# untraced on seeds not used before, the control; and the Qwen3-Next cell traced on the change (attn_gate_time_pct.train
# is read there too).
mkdir -p chiprun_out
ROOT=$PWD
CELL=trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams
QWEN=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
KEEP='^check|^\{|^window|^chipbench|Error|Traceback'
one() {  # entry point, cell, seed, trace, tag
  (cd _checkout && python $1 --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr40f_$5.log 2>&1; echo "$5 exit $?")
  grep -E "$KEEP" chiprun_out/pr40f_$5.log | cut -c1-2600
}
one benchmarks/chipbench/run.py $CELL 3000001651 1 traced
one benchmarks/chipbench/run.py $CELL 2147485653 0 sound1
one benchmarks/chipbench/run.py $CELL 3000001657 0 sound2
one benchmarks/chipbench/tools/control.py $CELL 2147485659 0 control
one benchmarks/chipbench/run.py $QWEN 3000001661 1 qwen_traced
