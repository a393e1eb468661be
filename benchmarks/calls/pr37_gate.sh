# chiprun --timeout 3500 -- bash benchmarks/calls/pr37_gate.sh
# PR 37, the gate before submission (ISSUE 37, step 2) and the split inside the cell's own process: the cell through
# benchmarks/chipbench/run.py, parent (_parent/, git archive of 7d120b3) and change (this tree), each side's first run
# left out (it compiles), then three pairs on a seed each, which side goes first alternating; last PR 36's tree
# (_step1/) twice, to read ITS load where the driver read it. Every run goes through pr37_cell.py, which prints the
# step's compile() seconds, the ledger's lower().compile() seconds and the garbage collector's share beside the run.
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
ROOT=$PWD
run() {  # directory, seed, tag
  (cd $1 && python $ROOT/benchmarks/calls/pr37_cell.py --workload $C --seed $2 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr37g_$3.log 2>&1; echo "rc=$? $3")
  grep -h "^pr37\|^set-up: state\|^window\|^gated delta" chiprun_out/pr37g_$3.log | cut -c1-220; grep -h "^{" chiprun_out/pr37g_$3.log | cut -c1-230
}
run _parent 3000000701 parent_first
run . 3000000701 change_first
run _parent 2147484707 parent_1
run . 2147484707 change_1
run . 3000000713 change_2
run _parent 3000000713 parent_2
run _parent 2147484721 parent_3
run . 2147484721 change_3
run _step1 3000000727 pr36_first
run _step1 2147484733 pr36_second
grep -ih "error\|exhaust\|Traceback" chiprun_out/pr37g_*.log | head -5 | cut -c1-300
