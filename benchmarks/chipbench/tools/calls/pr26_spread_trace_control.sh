# chiprun --timeout 3400 -- bash benchmarks/chipbench/tools/calls/pr26_spread_trace_control.sh
# PR 26: the new cell as the driver runs it: two sets of three seeds (spread of train_tokens_per_s, the
# readings the limits were set from), one traced run with the table behind section 5, and the control.
C=moonlight-16b-a3b-ep8-d6.sft-4k-allparams
mkdir -p chiprun_out
for seed in 101 2147483749 3000000103 104 2147483752 3000000106; do
  python benchmarks/chipbench/run.py --workload $C --seed $seed --seconds 30 --trace 0 > chiprun_out/pr26_seed$seed.log 2>&1; echo rc=$?
done
python benchmarks/chipbench/run.py --workload $C --seed 107 --seconds 30 --trace 1 > chiprun_out/pr26_traced107.log 2>&1; echo rc=$?
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$C 6 6 15 > chiprun_out/pr26_scope_table.txt 2>&1
python - > chiprun_out/pr26_expert_parts.json <<'PY'
# seconds and calls by part of the expert layers (readers/moe.py), and the traced busy time
import json, sys
sys.path.insert(0, ".")
from benchmarks.chipbench import trace, xplane_meta
from benchmarks.chipbench.readers import moe
d = ".chipbench_trace/moonlight-16b-a3b-ep8-d6.sft-4k-allparams"
red = trace.reduce_dir(d)
parts = moe.seconds_by_part(red["op_seconds"], red["op_counts"], xplane_meta.read(trace.find_xplane(d)), ("gmm", "ragged_dot"))
print(json.dumps({"busy_s": red["busy_s"], "parts": parts}))
PY
python benchmarks/chipbench/tools/control.py --workload $C --seed 101 --seconds 10 --trace 0 > chiprun_out/pr26_control101.log 2>&1; echo rc=$?
grep -h "^check\|^{\|set-up\|reference:\|attention paths" chiprun_out/pr26_*.log | cut -c1-500
