# chiprun --timeout 280 -- bash benchmarks/calls/pr38_h.sh
# PR 38, last call (5.0 chip-minutes): call G read the Mistral step's trace 0.6 s dearer on the final tree than at the
# parent, warm, all of it inside `backward`'s first kernel wrapper (0.34 s -> 0.73, 0.84), and nothing of the kind in the
# Qwen3-Next cell. A pause of that size in one place looks like a full collection of the garbage collector. One traced
# warm run a side with benchmarks/calls/pr38_gcprobe on PYTHONPATH (run.py started as ever): every generation-2
# collection and every compile stage of 50 ms or more, on one clock.
mkdir -p chiprun_out
M=mistral-7b-d16.sft-2k-full
ROOT=$PWD
for side in change parent; do
  dir=_checkout; [ $side = parent ] && dir=_parent
  (cd $dir && GCPROBE_OUT=$ROOT/chiprun_out/pr38h_gc_$side.jsonl PYTHONPATH=$ROOT/benchmarks/calls/pr38_gcprobe timeout 138 python benchmarks/chipbench/run.py --workload $M --seed 3000001351 --seconds 30 --trace 1 > $ROOT/chiprun_out/pr38h_mistral_$side.log 2>&1; echo "rc=$? $side at $SECONDS s")
  grep -h "^set-up: state" chiprun_out/pr38h_mistral_$side.log | cut -c1-160
  grep -c '"gc"' chiprun_out/pr38h_gc_$side.jsonl
  [ -f $dir/.chipbench_trace/$M/setup_spans.json ] && cp $dir/.chipbench_trace/$M/setup_spans.json chiprun_out/pr38h_setup_spans_$side.json
done
python - <<'PY'
import json
for side in ("change", "parent"):
    try:
        line = json.loads([l for l in open(f"chiprun_out/pr38h_mistral_{side}.log") if l.startswith("{")][-1])
        print(side, {k: round(v["value"], 3) for k, v in line["metrics"].items() if k.startswith(("train_step_", "setup_"))}, line["correct"])
    except Exception as e:
        print(side, "no line", e)
PY
