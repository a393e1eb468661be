# git add -A && rm -rf _checkout _parent _overlay && mkdir _overlay && git archive 3b0b41ed1b083871ba17a9974ffdb1b026624d79 | tar -x -C _overlay && cp BENCHMARK.json _overlay/ && cp -r benchmarks/chipbench/. _overlay/benchmarks/chipbench/ && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout && git archive 3b0b41ed1b083871ba17a9974ffdb1b026624d79 | tar -x -C _parent
# chiprun --timeout 3500 -- env PART=a bash benchmarks/calls/pr46_others.sh   (then PART=b)
# PR 46, the seven accepted cells, which share code this PR touched (ops/flash_attention.py's resident entry points,
# models/transformer._block, train/step.chunked_ce_sum): one pair each, parent against change on one seed, from the
# committed files alone (_parent/, _checkout/). PART=b ends with the new cell from the committed files: two more seeds.
mkdir -p chiprun_out
ROOT=$PWD
KEEP='^\{|^set-up|^window|^chipbench|Error|Traceback'
run() {  # tree cell seed trace tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr46c_$5.log 2>&1; echo "$5 exit $?")
  grep -E "$KEEP" chiprun_out/pr46c_$5.log | cut -c1-${6:-330}
}
pair() {  # cell seed tag
  run _parent $1 $2 0 $3_parent
  run _checkout $1 $2 0 $3_change
}
if [ "${PART:-a}" = a ]; then
# (the parent with this PR's benchmark files laid over it fails cleanly on the new cell's name, at once)
S=$(date +%s)
(cd _overlay && python benchmarks/chipbench/run.py --workload evabyte-6.5b-d10.sft-32k-eva-last2 --seed 3000004611 --seconds 30 --trace 0; echo "overlay exit $? after $(( $(date +%s) - S )) s") > chiprun_out/pr46c_overlay.log 2>&1
grep -E "chipbench:|overlay exit" chiprun_out/pr46c_overlay.log | cut -c1-400
EVAKEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^eva|Error|Traceback'
KEEPWAS=$KEEP; KEEP=$EVAKEEP
run _checkout evabyte-6.5b-d10.sft-32k-eva-last2 2147486669 0 eva_committed_1 700
KEEP=$KEEPWAS
pair smollm3-3b.sft-1k-full 3000004651 smol
pair mistral-7b-d16.sft-2k-full 2147486653 mistral
pair moonlight-16b-a3b-ep8-d6.sft-4k-allparams 3000004657 moon
pair mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams 2147486659 mellum
else
pair kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams 3000004667 kimi
pair qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams 3000004661 qwen
pair trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams 2147486663 trinity
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^eva|Error|Traceback'
run _checkout evabyte-6.5b-d10.sft-32k-eva-last2 3000004673 0 eva_committed_2 700
KEEP='^\{|^set-up|^window|^chipbench|Error|Traceback'
# (PART=a's Moonlight pair read -0.63%, inside the bound and about that cell's spread: once more, the other way round)
run _checkout moonlight-16b-a3b-ep8-d6.sft-4k-allparams 2147486679 0 moon2_change
run _parent moonlight-16b-a3b-ep8-d6.sft-4k-allparams 2147486679 0 moon2_parent
fi
