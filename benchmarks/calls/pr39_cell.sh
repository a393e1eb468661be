# git add -A && rm -rf _checkout && mkdir _checkout && git archive $(git write-tree) | tar -x -C _checkout
# (_parent/: git archive 2fb8c47, made for pr39_step0.sh)
# chiprun --timeout 3000 -- bash benchmarks/calls/pr39_cell.sh
# PR 39: the change in the claimed cell, the committed files (_checkout/) against the parent (_parent/), through run.py itself
# as the driver starts it: the change once untimed (its programs are new to the machine's cache: a cold set-up), then traced
# and read by part, by scope and by what under linear_attn is no product; pairs on a seed each, which side first
# alternating; the mix's control (router float8_e5m2, the rule's state bfloat16), which has to fail.
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
ROOT=$PWD
TAG=${TAG:-pr39c}
S=${SEED:-3000001409}   # every run a seed of its own; the two sides of a pair share one
run() {  # directory, seed, trace, tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $C --seed $2 --seconds 30 --trace $3 > $ROOT/chiprun_out/${TAG}_$4.log 2>&1; echo "rc=$? $4 at $SECONDS s")
  grep -h "^set-up: state\|^window\|^gated delta" chiprun_out/${TAG}_$4.log | cut -c1-260
  python - chiprun_out/${TAG}_$4.log <<'PY'
import json, sys
lines = [l for l in open(sys.argv[1]) if l.startswith("{")]
if lines:
    line = json.loads(lines[-1])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    print({k: round(v, 4) for k, v in m.items()}, "correct", line["correct"], "failed", line["failed"], line.get("device"))
    print({c["name"]: float(f"{c['value']:.3g}") for c in line.get("checks", [])})
    print(line.get("breakdown", {}).get("device_ops"))
PY
}
run _checkout $S 0 change_first
run _checkout $((S + 2)) 1 change_traced
python benchmarks/chipbench/tools/gdn_by_op.py _checkout/.chipbench_trace/$C auto 30 2>&1 | grep -v -i warn > chiprun_out/${TAG}_gdn_by_op.txt
python benchmarks/chipbench/tools/scope_table.py _checkout/.chipbench_trace/$C 4 4 12 2>&1 | grep -v -i warn > chiprun_out/${TAG}_scope_table.txt
python benchmarks/calls/pr39_not_products.py _checkout/.chipbench_trace/$C 30 2>&1 | grep -v -i warn > chiprun_out/${TAG}_not_products.txt
python benchmarks/chipbench/tools/setup_table.py _checkout/.chipbench_trace/$C 10 2>&1 | grep -v -i warn > chiprun_out/${TAG}_setup_table.txt
head -40 chiprun_out/${TAG}_gdn_by_op.txt | cut -c1-230; head -12 chiprun_out/${TAG}_scope_table.txt | cut -c1-200; head -16 chiprun_out/${TAG}_not_products.txt | cut -c1-220
run _parent $((S + 14)) 0 parent_1
run _checkout $((S + 14)) 0 change_1
run _checkout $((S + 18)) 0 change_2
run _parent $((S + 18)) 0 parent_2
run _parent $((S + 20)) 0 parent_3
run _checkout $((S + 20)) 0 change_3
(cd _checkout && python benchmarks/chipbench/tools/control.py --workload $C --seed $((S + 24)) --seconds 5 --trace 0 > $ROOT/chiprun_out/${TAG}_control.log 2>&1; echo "rc=$? control at $SECONDS s")
grep -h "^check" chiprun_out/${TAG}_control.log | cut -c1-200; grep -h "^{" chiprun_out/${TAG}_control.log | cut -c1-300
if [ -n "$OTHER" ]; then  # a cell that runs none of the changed code, as a control: the change's first run is cold (new paths), then a pair
  C=$OTHER
  run _checkout $((S + 30)) 0 other_change_first
  run _parent $((S + 32)) 0 other_parent
  run _checkout $((S + 32)) 0 other_change
fi
grep -ih "Traceback\|exhaust" chiprun_out/${TAG}_*.log | head -5 | cut -c1-300
echo "ended at $SECONDS s"
