# git add -A && rm -rf _checkout _overlay && mkdir _checkout _overlay && git archive $(git write-tree) | tar -x -C _checkout
# && git archive bcea84d | tar -x -C _overlay && cp BENCHMARK.json _overlay/ && cp -r benchmarks/chipbench/. _overlay/benchmarks/chipbench/
# chiprun --timeout 2400 -- bash benchmarks/calls/pr42_final.sh
# PR 42, the final tree: an OLD cell traced on the parent with this PR's benchmark files laid over it (what the driver's traced
# runs of the accepted cells do), then the new cell from the committed files alone on two more seeds.
mkdir -p chiprun_out
ROOT=$PWD
CELL=kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|unknown workload|Error|Traceback'
(cd _overlay && python benchmarks/chipbench/run.py --workload qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams --seed 3000004271 --seconds 30 --trace 1 > $ROOT/chiprun_out/pr42e_overlay_qwen_traced.log 2>&1; echo "overlay old cell traced exit $?")
grep -E '^\{' chiprun_out/pr42e_overlay_qwen_traced.log | cut -c1-2500
for SEED in 2147486273 3000004277; do
  (cd _checkout && python benchmarks/chipbench/run.py --workload $CELL --seed $SEED --seconds 30 --trace 0 > $ROOT/chiprun_out/pr42e_sound_$SEED.log 2>&1; echo "sound $SEED exit $?")
  grep -E "$KEEP" chiprun_out/pr42e_sound_$SEED.log | cut -c1-420
done
