# rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout
# git archive f6d8de5 | tar -x -C _parent && cp BENCHMARK.json _parent/ && cp -r benchmarks/chipbench/. _parent/benchmarks/chipbench/
# chiprun --timeout 2280 -- bash benchmarks/calls/pr38_g.sh
# PR 38, review round (40 chip-minutes): the FINAL tree (the committed files, _checkout/), whose _first_call makes one
# fn.lower(...) call, whose stages are JAX's own jit/trace and jit/lower under train_step/load, and which records nothing
# after mark_warm(). Its paths' line numbers are new to the cache, so each cell's first run is cold (and is the cold row);
# then pairs parent / change on a seed each, which side first alternating, every run traced (setup_s is read before the
# trace starts; a traced run costs 5 s more and prints the set-up metrics, the parent's train_step_load_s among them; the
# change's side of a pair is the warm row of section 5's table). Mistral and Qwen3-Next in turn; a pair is skipped when
# the time is short.
mkdir -p chiprun_out
M=mistral-7b-d16.sft-2k-full
Q=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
ROOT=$PWD
LIMIT=${LIMIT:-2150}
run() {  # directory, cell, seed, trace, tag, seconds after which the run is killed
  (cd $1 && timeout $6 python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr38g_$5.log 2>&1; echo "rc=$? $5 at $SECONDS s")
  grep -h "^set-up: state" chiprun_out/pr38g_$5.log | cut -c1-200
  python - chiprun_out/pr38g_$5.log <<'PY'
import json, sys
lines = [l for l in open(sys.argv[1]) if l.startswith("{")]
if lines:
    line = json.loads(lines[-1])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    keep = ("train_tokens_per_s", "setup_s", "recompiles_in_window.train", "train_step_load_s", "train_step_trace_s", "train_step_lower_s",
            "train_step_compile_s", "setup_jit_s", "setup_cache_misses", "setup_spanned_pct", "device_idle_pct.train", "memory_peak_bytes")
    print({k: round(m[k], 3) for k in keep if k in m}, "correct", line["correct"], "failed", line["failed"], line.get("device"))
PY
  if [ $4 = 1 ] && [ -f $1/.chipbench_trace/$2/setup_spans.json ]; then
    python benchmarks/chipbench/tools/setup_table.py $1/.chipbench_trace/$2 10 > chiprun_out/pr38g_setup_table_$5.txt 2>&1
    cp $1/.chipbench_trace/$2/setup_spans.json chiprun_out/pr38g_setup_spans_$5.json
    sed -n 2,18p chiprun_out/pr38g_setup_table_$5.txt | cut -c1-150
  fi
}
pair() {  # cell, seed, tag, first side, second side, seconds a warm run of the cell takes
  if [ $((SECONDS + 2 * $6)) -gt $LIMIT ]; then echo "skipped pair $3 at $SECONDS s"; return; fi
  for side in $4 $5; do
    if [ $side = parent ]; then run _parent $1 $2 1 ${3}_parent 480; else run _checkout $1 $2 1 ${3}_change 480; fi
  done
}
run _checkout $M 3000001311 1 mistral_change_cold 480
run _checkout $Q 3000001313 1 qwen3next_change_cold 480
pair $M 3000001319 mistral_1 parent change 150
pair $Q 3000001321 qwen3next_1 parent change 200
pair $M 3000001327 mistral_2 change parent 150
pair $Q 3000001331 qwen3next_2 change parent 200
pair $M 3000001337 mistral_3 parent change 150
pair $Q 3000001339 qwen3next_3 parent change 200
grep -ih "Traceback\|exhaust" chiprun_out/pr38g_*.log | head -5 | cut -c1-300
echo "ended at $SECONDS s"
