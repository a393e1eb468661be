# git add -A && rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout && git archive 7a0ee9582fe9b552c2fee116dbb7421c4b61c9d5 | tar -x -C _parent
# chiprun --timeout 3500 -- env PART=a bash benchmarks/calls/pr49_others.sh   (then PART=b)
# PR 49, the eight accepted cells, which share code this PR touched (models/transformer._block, _heads_qkv, unembed,
# train/step's losses, ops/flash_attention.flash_unsupported_reason, ops/rope.why_not_fused): one pair each, parent
# against change on one seed, from the committed files alone (_parent/, _checkout/). PART=b ends with the new cell from
# the committed files.
mkdir -p chiprun_out
ROOT=$PWD
KEEP='^\{|^set-up|^window|^chipbench|Error|Traceback'
run() {  # tree cell seed trace tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr49c_$5.log 2>&1; echo "$5 exit $?")
  grep -E "$KEEP" chiprun_out/pr49c_$5.log | cut -c1-${6:-330}
}
pair() {  # cell seed tag
  run _parent $1 $2 0 $3_parent
  run _checkout $1 $2 0 $3_change
}
if [ "${PART:-a}" = a ]; then
pair smollm3-3b.sft-1k-full 3000004951 smol
pair mistral-7b-d16.sft-2k-full 2147486953 mistral
pair moonlight-16b-a3b-ep8-d6.sft-4k-allparams 3000004957 moon
pair mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams 2147486959 mellum
else
pair qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams 3000004961 qwen
pair trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams 2147486963 trinity
pair kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams 3000004967 kimi
pair evabyte-6.5b-d10.sft-32k-eva-last2 2147486969 eva
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^state-space|Error|Traceback'
run _checkout granite-4.0-h-micro.sft-8k-ssd-tied-last2 3000004973 0 granite_committed 700
fi
