# PR 47: the seven other cells' steps lowered for a described v5e in both trees (no chip: a CPU lowering, digests and
# counts, never a rate). Equal `bodies_masked` digests say that no program of theirs changed.
# rm -rf /root/scratch/parent && mkdir -p /root/scratch/parent && git archive 891ebcdeb16a2324daa7ece2ae5477e474b04847 | tar -x -C /root/scratch/parent
# bash benchmarks/calls/pr47_lowered.sh /root/scratch/parent
mkdir -p chiprun_out
JAX_PLATFORMS=cpu python benchmarks/calls/pr46_lowered.py 2>/dev/null | grep '^{' > chiprun_out/pr47_lowered_change.jsonl
(cd ${1:?the parent tree} && JAX_PLATFORMS=cpu python benchmarks/calls/pr46_lowered.py 2>/dev/null | grep '^{') > chiprun_out/pr47_lowered_parent.jsonl
python - <<'PY'
import json
sides = [[json.loads(x) for x in open(f"chiprun_out/pr47_lowered_{s}.jsonl")] for s in ("parent", "change")]
for p, c in zip(*sides):
    # (a body carries its call stack's file paths, so its bytes move with the tree's directory: programs and call sites)
    counts = lambda x: {k: (v[0], v[2]) for k, v in x["kernels"].items()}
    print(p["step"], p["bodies_masked"], c["bodies_masked"], "equal" if p["bodies_masked"] == c["bodies_masked"] and counts(p) == counts(c) else "DIFFERENT")
PY
