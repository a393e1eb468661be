# rm -rf _parent && mkdir _parent && git archive f6d8de5 | tar -x -C _parent   (then this PR's BENCHMARK.json and its new
# files under benchmarks/chipbench/ copied over it, as the driver lays them over the parent: no program file of it changes)
# _step1/ is PR 36's tree (git archive of its commit, PR 37 made it) with this PR's observe/xla.py, runtime/compile_cache.py
# and the same benchmark files over it: the spans on the tree that read +11 s of set-up.
# chiprun --timeout 3400 -- bash benchmarks/calls/pr38_a.sh
# PR 38, first call. (1) a capture over a set-up against the recorder's spans; (2) the Qwen3-Next cell: a run a side to
# fill the cache, then three pairs parent / change on a seed each, which side first alternating, then the change traced
# and its set-up table; (3) the parent with this PR's benchmark files over it, traced: the six metrics left out, nothing
# raised; (4) PR 36's tree with the recorder, as the script and under runpy.
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
ROOT=$PWD
run() {  # directory, cell, seed, trace, tag
  (cd $1 && timeout 600 python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr38a_$5.log 2>&1; echo "rc=$? $5")
  grep -h "^set-up: state" chiprun_out/pr38a_$5.log | cut -c1-200; grep -h "^{" chiprun_out/pr38a_$5.log | cut -c1-${6:-260}
}
python benchmarks/calls/pr38_capture.py smollm3-3b.sft-1k-full 2147485001 2>&1 | grep "^{\|^loss\|Error\|error" | tee chiprun_out/pr38a_capture.jsonl | cut -c1-300
run . $C 3000000801 0 change_0
run _parent $C 3000000803 0 parent_0
run _parent $C 2147485007 0 parent_1
run . $C 2147485007 0 change_1
run . $C 3000000811 0 change_2
run _parent $C 3000000811 0 parent_2
run _parent $C 2147485019 0 parent_3
run . $C 2147485019 0 change_3
run . $C 3000000823 1 change_traced 6000
python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$C 12 > chiprun_out/pr38a_setup_table_qwen3next.txt 2>&1
cp .chipbench_trace/$C/setup_spans.json chiprun_out/pr38a_setup_spans_qwen3next.json
cat chiprun_out/pr38a_setup_table_qwen3next.txt | cut -c1-200
run _parent $C 2147485031 1 parent_traced 6000
for seed in 3000000841 2147485043; do
  for how in script runpy; do
    tag=pr36_${how}_$seed
    if [ $SECONDS -gt 2900 ]; then echo "skipped $tag at $SECONDS s"; continue; fi
    if [ $how = script ]; then
      (cd _step1 && timeout 600 python benchmarks/chipbench/run.py --workload $C --seed $seed --seconds 30 --trace 1 > $ROOT/chiprun_out/pr38a_$tag.log 2>&1; echo "rc=$? $tag")
    else
      (cd _step1 && timeout 600 python $ROOT/benchmarks/calls/pr37_bisect.py -- --workload $C --seed $seed --seconds 30 --trace 1 > $ROOT/chiprun_out/pr38a_$tag.log 2>&1; echo "rc=$? $tag")
    fi
    grep -h "^set-up: state" chiprun_out/pr38a_$tag.log | cut -c1-200
    python - chiprun_out/pr38a_$tag.log <<'PY'
import json, sys
line = json.loads([l for l in open(sys.argv[1]) if l.startswith("{")][-1])
print({k: round(v["value"], 3) for k, v in line["metrics"].items() if k.startswith(("train_step_", "setup_"))}, line["correct"])
PY
    python benchmarks/chipbench/tools/setup_table.py _step1/.chipbench_trace/$C 6 > chiprun_out/pr38a_setup_table_$tag.txt 2>&1
  done
done
grep -ih "Traceback\|exhaust" chiprun_out/pr38a_*.log | head -5 | cut -c1-300
