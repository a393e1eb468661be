# git add -A && rm -rf _checkout _overlay && mkdir _checkout _overlay && git archive $(git write-tree) | tar -x -C _checkout
# && git archive bcea84d | tar -x -C _overlay && cp BENCHMARK.json _overlay/ && cp -r benchmarks/chipbench/. _overlay/benchmarks/chipbench/
# chiprun --timeout 3400 -- bash benchmarks/calls/pr42_controls.sh
# PR 42, what the new cell's limits are held against, from the committed files alone (_checkout/): the parent with this
# PR's benchmark files laid over it (_overlay/) fails cleanly on the cell's name; one sound run; the mix's control (router in
# float8_e4m3fn, the rule's state in bfloat16); the planted faults (half of each microbatch's rows out of the program's
# loss; a learning rate of zero; the decay as one scalar a head).
mkdir -p chiprun_out
ROOT=$PWD
CELL=kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^gated|unknown workload|Error|Traceback'
(cd _overlay && time python benchmarks/chipbench/run.py --workload $CELL --seed 3000004211 --seconds 30 --trace 0; echo "overlay exit $?") 2>&1 | grep -v Warning | tail -8 | cut -c1-400
(cd _checkout && python benchmarks/chipbench/run.py --workload $CELL --seed 3000004213 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr42b_sound.log 2>&1; echo "sound exit $?")
grep -E "$KEEP" chiprun_out/pr42b_sound.log | cut -c1-700
(cd _checkout && python benchmarks/chipbench/tools/control.py --workload $CELL --seed 3000004217 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr42b_control.log 2>&1; echo "control exit $?")
grep -E "$KEEP" chiprun_out/pr42b_control.log | cut -c1-700
for FAULT in half_batch unchanged_state; do
  (cd _checkout && python benchmarks/chipbench/tools/fault.py --fault $FAULT --workload $CELL --seed 2147486219 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr42b_$FAULT.log 2>&1; echo "$FAULT exit $?")
  grep -E "$KEEP" chiprun_out/pr42b_$FAULT.log | cut -c1-700
done
(cd _checkout && python benchmarks/chipbench/tools/fault_kda.py --workload $CELL --seed 3000004223 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr42b_scalar_decay.log 2>&1; echo "scalar decay exit $?")
grep -E "$KEEP" chiprun_out/pr42b_scalar_decay.log | cut -c1-700
