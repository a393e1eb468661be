# PR 49: the eight accepted cells' steps lowered for a described v5e in both trees (no chip: a CPU lowering, digests
# and counts, never a rate). Equal `bodies_masked` digests say that no program of theirs changed.
# rm -rf /root/scratch/parent && mkdir -p /root/scratch/parent && git archive 7a0ee9582fe9b552c2fee116dbb7421c4b61c9d5 | tar -x -C /root/scratch/parent
# bash benchmarks/calls/pr49_lowered.sh /root/scratch/parent
mkdir -p chiprun_out
CELLS="smollm3-3b.sft-1k-full mistral-7b-d16.sft-2k-full moonlight-16b-a3b-ep8-d6.sft-4k-allparams mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams evabyte-6.5b-d10.sft-32k-eva-last2"
JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python benchmarks/calls/pr46_lowered.py $CELLS 2>/dev/null | grep '^{' > chiprun_out/pr49_lowered_change.jsonl
(cd ${1:?the parent tree} && JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python benchmarks/calls/pr46_lowered.py $CELLS 2>/dev/null | grep '^{') > chiprun_out/pr49_lowered_parent.jsonl
python - <<'PY'
import json
sides = [[json.loads(x) for x in open(f"chiprun_out/pr49_lowered_{s}.jsonl")] for s in ("parent", "change")]
for p, c in zip(*sides):
    # (a body carries its call stack's file paths, so its bytes move with the tree's directory: programs and call sites)
    counts = lambda x: {k: (v[0], v[2]) for k, v in x["kernels"].items()}
    print(p["step"], p["bodies_masked"], c["bodies_masked"], "equal" if p["bodies_masked"] == c["bodies_masked"] and counts(p) == counts(c) else "DIFFERENT")
PY
