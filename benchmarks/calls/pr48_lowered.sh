# PR 48: the seven other cells' steps (EvaByte's too) lowered for a described v5e in both trees (no chip: a CPU lowering,
# digests and counts, never a rate). Equal `bodies_masked` digests and equal kernels' names, programs and call sites say
# that no program of theirs changed; the Kimi cell's, which did, is the eighth line.
# rm -rf /root/scratch/parent && mkdir -p /root/scratch/parent && git archive a242456 | tar -x -C /root/scratch/parent
# bash benchmarks/calls/pr48_lowered.sh /root/scratch/parent
mkdir -p chiprun_out
CELLS="smollm3-3b.sft-1k-full mistral-7b-d16.sft-2k-full moonlight-16b-a3b-ep8-d6.sft-4k-allparams mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams evabyte-6.5b-d10.sft-32k-eva-last2 kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams"
JAX_PLATFORMS=cpu python benchmarks/calls/pr46_lowered.py $CELLS 2>/dev/null | grep '^{' > chiprun_out/pr48_lowered_change.jsonl
(cd ${1:?the parent tree} && JAX_PLATFORMS=cpu python benchmarks/calls/pr46_lowered.py $CELLS 2>/dev/null | grep '^{') > chiprun_out/pr48_lowered_parent.jsonl
python - <<'PY'
import json
sides = [[json.loads(x) for x in open(f"chiprun_out/pr48_lowered_{s}.jsonl")] for s in ("parent", "change")]
for p, c in zip(*sides):
    counts = lambda x: {k: (v[0], v[2]) for k, v in x["kernels"].items()}
    print(p["step"], p["bodies_masked"], c["bodies_masked"], "equal" if p["bodies_masked"] == c["bodies_masked"] and counts(p) == counts(c) else "DIFFERENT")
PY
