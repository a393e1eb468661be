# git add -A && rm -rf _checkout && mkdir _checkout && git archive $(git write-tree) | tar -x -C _checkout
# chiprun --timeout 3400 -- bash benchmarks/calls/pr42_cell.sh
# PR 42, the new cell from the committed files alone (_checkout/): six untraced runs on seeds not used while writing, one traced.
mkdir -p chiprun_out
ROOT=$PWD
CELL=kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^gated|unknown workload|Error|Traceback'
for SEED in 3000004231 2147486233 3000004237 2147486239 3000004241 2147486243; do
  (cd _checkout && python benchmarks/chipbench/run.py --workload $CELL --seed $SEED --seconds 30 --trace 0 > $ROOT/chiprun_out/pr42c_sound_$SEED.log 2>&1; echo "sound $SEED exit $?")
  grep -E "$KEEP" chiprun_out/pr42c_sound_$SEED.log | cut -c1-420
done
(cd _checkout && python benchmarks/chipbench/run.py --workload $CELL --seed 3000004247 --seconds 30 --trace 1 > $ROOT/chiprun_out/pr42c_traced.log 2>&1; echo "traced exit $?")
grep -E "$KEEP" chiprun_out/pr42c_traced.log | cut -c1-6000
(cd _checkout && python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 5 0 > $ROOT/chiprun_out/pr42c_scope_table.txt 2>&1; python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$CELL auto 40 > $ROOT/chiprun_out/pr42c_gdn_by_op.txt 2>&1; python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$CELL > $ROOT/chiprun_out/pr42c_setup_table.txt 2>&1)
tail -45 chiprun_out/pr42c_gdn_by_op.txt | cut -c1-230
