# chiprun --timeout 3000 -- bash benchmarks/chipbench/tools/calls/pr32_mixer_and_trace.sh
# PR 32, after the first look: the whole mixer alone by the convolution's form (benchmarks/gdn_kernels.py --only
# mixer), then the cell traced with the inverse by triangular solve and the column splits, its tables, and how the
# rule's scan shows in the trace (the paths that hold a while under gdn_scan).
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
python benchmarks/gdn_kernels.py --only mixer > chiprun_out/pr32_mixer.log 2>&1; echo "rc=$? mixer"; grep "^{" chiprun_out/pr32_mixer.log
python benchmarks/chipbench/run.py --workload $C --seed 2147484211 --seconds 30 --trace 1 > chiprun_out/pr32_new_traced2.log 2>&1; echo "rc=$? traced2"
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$C 4 4 12 > chiprun_out/pr32_scope_table2.txt 2>&1
python benchmarks/chipbench/tools/gdn_by_op.py .chipbench_trace/$C auto 70 > chiprun_out/pr32_gdn_by_op2.txt 2>&1
python benchmarks/dispatch_by_op.py .chipbench_trace/$C auto 10 > chiprun_out/pr32_dispatch_by_op2.txt 2>&1
python - > chiprun_out/pr32_whiles.txt 2>&1 <<'PY'
import sys
sys.path.insert(0, ".")
from benchmarks.chipbench import trace, xplane_meta
path = trace.find_xplane(".chipbench_trace/qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams")
red = trace.reduce_planes(trace.read_planes(path), chips=1)
meta = xplane_meta.read(path)
rows = []
for name, secs in red["op_seconds"].items():
    tf_op = meta.get(name, {}).get("tf_op", "")
    short = trace.short_name(name)
    if "while" in short or ("gdn_scan" in tf_op and "/body/" not in tf_op and "while" in tf_op):
        rows.append((red["op_counts"][name], secs, short, meta.get(name, {}).get("hlo_category", ""), tf_op[-160:]))
for r in sorted(rows, key=lambda r: -r[1])[:40]:
    print(r)
copies = sorted(((secs, red["op_counts"][n], trace.short_name(n), meta.get(n, {}).get("tf_op", "")[-150:]) for n, secs in red["op_seconds"].items()
                 if trace.short_name(n).startswith(("copy", "reshape", "broadcast", "transpose"))), reverse=True)[:40]
print("largest data-formatting operations:")
for r in copies:
    print(r)
PY
grep -h "^check\|^set-up\|^reference\|^window" chiprun_out/pr32_new_traced2.log | cut -c1-200
grep -h "^{" chiprun_out/pr32_new_traced2.log | cut -c1-2600
head -12 chiprun_out/pr32_gdn_by_op2.txt | cut -c1-160
sed -n 12,80p chiprun_out/pr32_gdn_by_op2.txt | grep -v "linear_attn/dot_general" | cut -c1-230 | head -45
cat chiprun_out/pr32_whiles.txt | cut -c1-330
