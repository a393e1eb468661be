# rm -rf _checkout && mkdir _checkout && git archive $(git write-tree) | tar -x -C _checkout     (_parent/ as in pr38_a.sh)
# chiprun --timeout 3500 -- bash benchmarks/calls/pr38_b.sh
# PR 38, second call: the committed files alone (git archive of the final tree, in _checkout/) against the parent.
# (1) the Mistral cell: the change traced on paths new to the machine's cache (a cold run: setup_cache_misses above 0),
# a run of the parent, three pairs on a seed each, which side first alternating, the change traced warm and its table;
# (2) the other three cells, each traced twice: the first run cold (its paths are new), the second warm, with its table.
mkdir -p chiprun_out
ROOT=$PWD
run() {  # directory, cell, seed, trace, tag
  if [ $SECONDS -gt ${LIMIT:-2950} ]; then echo "skipped $5 at $SECONDS s"; return; fi
  (cd $1 && timeout 700 python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr38b_$5.log 2>&1; echo "rc=$? $5 at $SECONDS s")
  grep -h "^set-up: state" chiprun_out/pr38b_$5.log | cut -c1-200; grep -h "^{" chiprun_out/pr38b_$5.log | cut -c1-${6:-260}
  if [ $4 = 1 ]; then
    python benchmarks/chipbench/tools/setup_table.py $1/.chipbench_trace/$2 12 > chiprun_out/pr38b_setup_table_$5.txt 2>&1
    cp $1/.chipbench_trace/$2/setup_spans.json chiprun_out/pr38b_setup_spans_$5.json
    python - chiprun_out/pr38b_$5.log <<'PY'
import json, sys
line = json.loads([l for l in open(sys.argv[1]) if l.startswith("{")][-1])
print({k: round(v["value"], 3) for k, v in line["metrics"].items() if k.startswith(("train_step_", "setup_", "recompiles"))}, line["correct"])
PY
  fi
}
M=mistral-7b-d16.sft-2k-full
run _checkout $M 3000000901 1 mistral_change_cold
run _parent $M 3000000907 0 mistral_parent_0
run _parent $M 2147485103 0 mistral_parent_1
run _checkout $M 2147485103 0 mistral_change_1
run _checkout $M 3000000919 0 mistral_change_2
run _parent $M 3000000919 0 mistral_parent_2
run _parent $M 2147485111 0 mistral_parent_3
run _checkout $M 2147485111 0 mistral_change_3
run _checkout $M 3000000929 1 mistral_change_traced
# PR 36's tree (_step1/, with this PR's observe/xla.py over it as in pr38_a.sh, where the list of spans overflowed and
# took the step's own spans with it) as the script and under runpy: in which stage its eleven seconds sit
Q=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
run _step1 $Q 3000000941 1 pr36_script
if [ $SECONDS -le ${LIMIT:-2950} ]; then
  (cd _step1 && timeout 700 python $ROOT/benchmarks/calls/pr37_bisect.py -- --workload $Q --seed 2147485147 --seconds 30 --trace 1 > $ROOT/chiprun_out/pr38b_pr36_runpy.log 2>&1; echo "rc=$? pr36_runpy at $SECONDS s")
  grep -h "^set-up: state" chiprun_out/pr38b_pr36_runpy.log | cut -c1-200
  python benchmarks/chipbench/tools/setup_table.py _step1/.chipbench_trace/$Q 12 > chiprun_out/pr38b_setup_table_pr36_runpy.txt 2>&1
  head -20 chiprun_out/pr38b_setup_table_pr36_runpy.txt | cut -c1-160
fi
head -20 chiprun_out/pr38b_setup_table_pr36_script.txt | cut -c1-160
seed=2147485200
for C in mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams smollm3-3b.sft-1k-full moonlight-16b-a3b-ep8-d6.sft-4k-allparams; do
  short=${C%%-*}
  seed=$((seed + 11)); run _checkout $C $seed 1 ${short}_cold
  seed=$((seed + 11)); run _checkout $C $seed 1 ${short}_traced
done
grep -ih "Traceback\|exhaust" chiprun_out/pr38b_*.log | head -5 | cut -c1-300
