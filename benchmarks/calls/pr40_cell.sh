# git add -A && rm -rf _checkout _overlay && mkdir _checkout _overlay && git archive $(git write-tree) | tar -x -C _checkout
# && git archive 59472a7 | tar -x -C _overlay && cp BENCHMARK.json _overlay/ && cp -r benchmarks/chipbench/. _overlay/benchmarks/chipbench/
# chiprun --timeout 3400 -- bash benchmarks/calls/pr40_cell.sh
# PR 40, the new cell from the committed files alone (_checkout/): the parent with this PR's benchmark files laid over it
# (_overlay/) fails cleanly on the cell's name; six untraced runs of the change on seeds not used while writing, the mix's
# control (router in float8_e4m3fn) and a planted fault (half of each microbatch's rows out of the program's loss).
mkdir -p chiprun_out
ROOT=$PWD
CELL=trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|unknown workload|Error|Traceback'
(cd _overlay && time python benchmarks/chipbench/run.py --workload $CELL --seed 3000001611 --seconds 30 --trace 0; echo "overlay exit $?") 2>&1 | grep -v Warning | tail -8 | cut -c1-400
for SEED in 3000001613 2147485617 3000001619 2147485621 3000001627 2147485629; do
  (cd _checkout && python benchmarks/chipbench/run.py --workload $CELL --seed $SEED --seconds 30 --trace 0 > $ROOT/chiprun_out/pr40b_sound_$SEED.log 2>&1; echo "sound $SEED exit $?")
  grep -E "$KEEP" chiprun_out/pr40b_sound_$SEED.log | cut -c1-420
done
(cd _checkout && python benchmarks/chipbench/tools/control.py --workload $CELL --seed 3000001631 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr40b_control.log 2>&1; echo "control exit $?")
grep -E "$KEEP" chiprun_out/pr40b_control.log | cut -c1-420
(cd _checkout && python benchmarks/chipbench/tools/fault.py --fault half_batch --workload $CELL --seed 3000001637 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr40b_fault.log 2>&1; echo "fault exit $?")
grep -E "$KEEP" chiprun_out/pr40b_fault.log | cut -c1-420
