# PR 50: the eight other cells' steps lowered for a described v5e in both trees (no chip: a CPU lowering, digests and
# counts, never a rate). Equal `bodies_masked` digests say that no program of theirs changed: none has an "ssd" layer,
# and ops/gated_delta.py (whose _padded_rows and causal_conv ops/ssd.py imports) is not edited.
# rm -rf /root/scratch/parent && mkdir -p /root/scratch/parent && git archive 320e0e1d3cb4fb71e0c1bc9d508f92c34aaa457c | tar -x -C /root/scratch/parent
# bash benchmarks/calls/pr50_lowered.sh /root/scratch/parent
mkdir -p chiprun_out
CELLS="smollm3-3b.sft-1k-full mistral-7b-d16.sft-2k-full moonlight-16b-a3b-ep8-d6.sft-4k-allparams mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams evabyte-6.5b-d10.sft-32k-eva-last2"
JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python benchmarks/calls/pr46_lowered.py $CELLS 2>/dev/null | grep '^{' > chiprun_out/pr50_lowered_change.jsonl
(cd ${1:?the parent tree} && JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python benchmarks/calls/pr46_lowered.py $CELLS 2>/dev/null | grep '^{') > chiprun_out/pr50_lowered_parent.jsonl
python - <<'PY'
import json
sides = [[json.loads(x) for x in open(f"chiprun_out/pr50_lowered_{s}.jsonl")] for s in ("parent", "change")]
assert len(sides[0]) == len(sides[1]) == 8, [len(s) for s in sides]
for p, c in zip(*sides):
    # (a body carries its call stack's file paths, so its bytes move with the tree's directory: programs and call sites)
    counts = lambda x: {k: (v[0], v[2]) for k, v in x["kernels"].items()}
    print(p["step"], p["bodies_masked"], c["bodies_masked"], "equal" if p["bodies_masked"] == c["bodies_masked"] and counts(p) == counts(c) else "DIFFERENT")
PY
