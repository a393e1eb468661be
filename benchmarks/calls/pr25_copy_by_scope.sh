#!/bin/bash
# PR 25 (one chip): name the step's `copy` operations (HLO category "data formatting") by scope. One traced run of
# _checkout/ in the cell given, then the category's device time grouped by the scope path of each operation with the
# layer index taken out, through the benchmark's own readers (trace.py, xplane_meta.py).
#   chiprun --chips 1 --timeout 1200 -- bash benchmarks/calls/pr25_copy_by_scope.sh smollm3-3b.sft-1k-full 2147496001
CELL=$1; SEED=$2
mkdir -p chiprun_out
(cd _checkout && python3 benchmarks/chipbench/run.py --workload $CELL --seed $SEED --seconds 30 --trace 1) \
  > chiprun_out/pr25_copy_run.out 2> chiprun_out/pr25_copy_run.err
echo "rc=$?"; grep '^set-up' chiprun_out/pr25_copy_run.out
tail -n 1 chiprun_out/pr25_copy_run.out | python3 -c "
import json, sys
line = json.loads(sys.stdin.read()); print(line['correct'], json.dumps({k: round(v['value'], 4) for k, v in line['metrics'].items()}))"
cd _checkout && python3 - $CELL <<'PY' | tee ../chiprun_out/pr25_copy_by_scope.txt
import re, sys
sys.path.insert(0, ".")
from benchmarks.chipbench import trace, xplane_meta
path = trace.find_xplane(f".chipbench_trace/{sys.argv[1]}")
red = trace.reduce_planes(trace.read_planes(path), chips=1)
meta = xplane_meta.read(path)
groups, total = {}, 0.0
for name, secs in red["op_seconds"].items():
    m = meta.get(name, {})
    if m.get("hlo_category") != "data formatting":
        continue
    total += secs
    key = re.sub(r"layer\d+", "layer<i>", m.get("tf_op", "(no tf_op)"))
    shape = name.split(" = ", 1)[-1].split(" ", 1)[0][:60]
    groups.setdefault((key, shape), [0.0, 0])
    groups[(key, shape)][0] += secs
    groups[(key, shape)][1] += 1
print(f"data formatting: {total:.4f} s of {red['busy_s']:.4f} s busy ({100 * total / red['busy_s']:.2f}%)")
for (key, shape), (secs, n) in sorted(groups.items(), key=lambda kv: -kv[1][0])[:14]:
    print(f"{secs:8.4f} s {100 * secs / red['busy_s']:5.2f}%  x{n:<4d} {shape:40s} {key}")
PY
