# (_checkout/, _overlay/: as pr40_cell.sh makes them; _parent/: git archive 59472a7)
# chiprun --timeout 3400 -- bash benchmarks/calls/pr40_others.sh
# PR 40, the cells that share the changed code. An OLD cell traced on the parent with this PR's benchmark files laid over it
# (_overlay/: what the driver's traced runs do; Qwen3-Next, whose full layer has the gate: attn_gate_time_pct.train has to
# read there); then pairs parent against change through run.py itself, a seed a pair: Mellum (the streamed window and causal
# kernels, the remat rule, the dispatch), Qwen3-Next (_heads_qkv's q/k norms and the gate under their scopes).
mkdir -p chiprun_out
ROOT=$PWD
MELLUM=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
QWEN=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
KEEP='^check|^\{|^window|^chipbench|Error|Traceback'
one() {  # directory, cell, seed, trace, tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr40c_$5.log 2>&1; echo "$5 exit $?")
  grep -E "$KEEP" chiprun_out/pr40c_$5.log | grep -v '^check' | cut -c1-2600
  grep -c "^check.* ok" chiprun_out/pr40c_$5.log
}
one _overlay $QWEN 3000001641 1 overlay_qwen_traced
one _parent $MELLUM 3000001643 0 mellum_parent
one _checkout $MELLUM 3000001643 0 mellum_change
one _checkout $QWEN 3000001647 0 qwen_change
one _parent $QWEN 3000001647 0 qwen_parent
one _checkout $MELLUM 3000001649 0 mellum_change2
one _parent $MELLUM 3000001649 0 mellum_parent2
