# git add -A && rm -rf _checkout _parent _overlay && mkdir _checkout _parent _overlay && git archive $(git write-tree) | tar -x -C _checkout
#   && git archive 590c4b8 | tar -x -C _parent && git archive 590c4b8 | tar -x -C _overlay && cp -r _checkout/BENCHMARK.json _overlay/ && cp -r _checkout/benchmarks/chipbench/. _overlay/benchmarks/chipbench/
# chiprun --timeout 3400 -- env CELLS="<cell> ..." bash benchmarks/calls/pr41_cell.sh
# PR 41: the change in a cell, the committed files (_checkout/) against the parent (_parent/), through run.py itself as the
# driver starts it: the change once untimed (its programs are new to the machine's cache: a cold set-up), then traced and
# read by scope and by operation under attn; the PARENT traced with this PR's benchmark files laid over it (_overlay/: what
# the driver's traced runs do; TRACE_PARENT=1); pairs on a seed each, which side first alternating (PAIRS, default 2).
mkdir -p chiprun_out
ROOT=$PWD
TAG=${TAG:-pr41c}
S=${SEED:-3000001711}   # every run a seed of its own; the two sides of a pair share one
run() {  # directory, cell, seed, trace, tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/${TAG}_$5.log 2>&1; echo "rc=$? $5 at $SECONDS s")
  grep -h "^window\|q/k norms, rope" chiprun_out/${TAG}_$5.log | cut -c1-300
  python - chiprun_out/${TAG}_$5.log <<'PY'
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
n=0
for C in $CELLS; do
  L=$(python -c "import json; b = json.load(open('BENCHMARK.json')); c = {w['name']: w['config'] for w in b['workloads']}['$C']; print(json.load(open({x['name']: x['file'] for x in b['configs']}[c]))['num_hidden_layers'])")
  short=${C%%-*}
  [ -n "$FAST" ] || run _checkout $C $((S + n)) 0 ${short}_change_first
  traced() { case " $NOTRACE " in *" $short "*) ;; *)
  run _checkout $C $((S + n + 2)) 1 ${short}_change_traced
  python benchmarks/chipbench/tools/scope_table.py _checkout/.chipbench_trace/$C $L $L 12 2>&1 | grep -v -i warn > chiprun_out/${TAG}_${short}_scope_table.txt
  python benchmarks/calls/pr41_attn_by_op.py _checkout/.chipbench_trace/$C 2>&1 | grep -v -i warn > chiprun_out/${TAG}_${short}_attn_by_op.txt
  python benchmarks/chipbench/tools/setup_table.py _checkout/.chipbench_trace/$C 8 2>&1 | grep -v -i warn > chiprun_out/${TAG}_${short}_setup_table.txt
  head -14 chiprun_out/${TAG}_${short}_scope_table.txt | cut -c1-200; head -20 chiprun_out/${TAG}_${short}_attn_by_op.txt | cut -c1-230
  ;; esac; }
  [ -n "$FAST" ] || traced
  if [ -n "$TRACE_PARENT" ]; then
    run _overlay $C $((S + n + 4)) 1 ${short}_parent_traced
    python benchmarks/calls/pr41_attn_by_op.py _overlay/.chipbench_trace/$C 2>&1 | grep -v -i warn > chiprun_out/${TAG}_${short}_parent_attn_by_op.txt
    cat chiprun_out/${TAG}_${short}_parent_attn_by_op.txt | cut -c1-230
  fi
  for p in $(seq 1 ${PAIRS:-2}); do
    if [ $((p % 2)) = 1 ]; then order="_parent _checkout"; else order="_checkout _parent"; fi
    for side in $order; do run $side $C $((S + n + 4 + 2 * p)) 0 ${short}_pair${p}${side}; done
  done
  [ -z "$FAST" ] || traced   # FAST=1: no run of its own to warm the cache; the pair's change side is the cold one, the traced run warm
  if [ -n "$CONTROL" ]; then  # the mix's lower-precision control: correct has to come out false
    (cd _checkout && python benchmarks/chipbench/tools/control.py --workload $C --seed $((S + n + 16)) --seconds 5 --trace 0 > $ROOT/chiprun_out/${TAG}_${short}_control.log 2>&1; echo "rc=$? ${short}_control at $SECONDS s")
    grep -h "^check" chiprun_out/${TAG}_${short}_control.log | cut -c1-200; grep -h "^{" chiprun_out/${TAG}_${short}_control.log | cut -c1-200
  fi
  n=$((n + 20))
done
grep -ih "Traceback\|exhaust" chiprun_out/${TAG}_*.log | head -5 | cut -c1-300
echo "ended at $SECONDS s"
