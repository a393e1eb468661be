#!/bin/bash
# PR 25, after the forward and backward went behind a jit of their own (the warm start's 77 s of Python lowering):
# set-up and throughput again, from the committed files, one cell a call (one chip; _checkout and _parent made as in
# pr25_proof_from_archive.sh). 1 the change traced (compiles its step); 2 the change warm; 3 and 4 the parent, on
# run 2's seed (the second is warm whatever the machine's cache still held).
#   chiprun --chips 1 --timeout 2400 -- bash benchmarks/calls/pr25_setup_proof.sh smollm3-3b.sft-1k-full 2147495001
CELL=$1; SEED=$2; RUNS=${3:-"change:1:1 change:2:0 parent:2:0 parent:2:0"}
mkdir -p chiprun_out
OUT=$PWD/chiprun_out; TAG=pr25_setup_${CELL%%.*}
n=0
for run in $RUNS; do
  n=$((n + 1)); IFS=: read side seed trace <<< "$run"
  (cd _$([ $side = change ] && echo checkout || echo parent) && python3 benchmarks/chipbench/run.py --workload $CELL \
     --seed $((SEED + seed)) --seconds 30 --trace $trace) > $OUT/${TAG}_${n}_$side.out 2> $OUT/${TAG}_${n}_$side.err
  echo "== $n $side (seed $((SEED + seed)), trace $trace) rc=$?"; grep '^set-up\|first_grad_worst_leaf_rel_err\|param_change' $OUT/${TAG}_${n}_$side.out
  tail -n 1 $OUT/${TAG}_${n}_$side.out | python3 -c "
import json, sys
line = json.loads(sys.stdin.read()); print(line['correct'], json.dumps({k: round(v['value'], 4) for k, v in line['metrics'].items()}))"
done
