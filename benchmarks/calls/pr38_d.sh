# chiprun --timeout 1500 -- bash benchmarks/calls/pr38_d.sh
# PR 38, fourth call: calls A and B read the step's load 1.7 to 2.2 s over the parent's, and call C showed that neither the
# listeners nor the spans cost the trace and lowering that much by themselves. One ingredient at a time, in place in
# _checkout/ (pr38_variant.py), the SmolLM3 cell traced (its load reads train_step_load_s at the parent too).
mkdir -p chiprun_out
C=${CELL:-smollm3-3b.sft-1k-full}
ROOT=$PWD
seed=3000001100
for variant in ${VARIANTS:-parent change nosplit nolisten parent}; do
  seed=$((seed + 17))
  if [ $SECONDS -gt ${LIMIT:-1150} ]; then echo "skipped $variant at $SECONDS s"; continue; fi
  python benchmarks/calls/pr38_variant.py _checkout $variant
  (cd _checkout && timeout 600 python benchmarks/chipbench/run.py --workload $C --seed $seed --seconds 30 --trace 1 > $ROOT/chiprun_out/pr38d_${variant}_$seed.log 2>&1; echo "rc=$? $variant at $SECONDS s")
  grep -h "^set-up: state" chiprun_out/pr38d_${variant}_$seed.log | cut -c1-200
  python - chiprun_out/pr38d_${variant}_$seed.log <<'PY'
import json, sys
line = json.loads([l for l in open(sys.argv[1]) if l.startswith("{")][-1])
print({k: round(v["value"], 3) for k, v in line["metrics"].items() if k.startswith(("train_step_", "setup_"))}, line["correct"])
PY
done
python benchmarks/calls/pr38_variant.py _checkout change
