# git add -A && rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout && git archive bcea84d | tar -x -C _parent
# chiprun --timeout 3400 -- bash benchmarks/calls/pr42_others.sh   (then again with TRACED=1 in front of bash: env TRACED=1 bash ...)
# PR 42, the two accepted cells whose code this PR touches (ops/gated_delta.py's passes and _latent_qkv): a pair each, parent
# against change on one seed, the order parent, change, change, parent; then one traced run a side for the step's trace and lowering.
mkdir -p chiprun_out
ROOT=$PWD
KEEP='^\{|^set-up|^window|^chipbench|Error|Traceback'
run() {  # tree cell seed trace tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr42d_$5.log 2>&1; echo "$5 exit $?")
  grep -E "$KEEP" chiprun_out/pr42d_$5.log | cut -c1-${6:-330}
}
MOON=moonlight-16b-a3b-ep8-d6.sft-4k-allparams
QWEN=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
if [ "${TRACED:-0}" = 0 ]; then
run _parent $QWEN 3000004251 0 qwen_parent_1
run _checkout $QWEN 3000004251 0 qwen_change_1
run _checkout $QWEN 2147486253 0 qwen_change_2
run _parent $QWEN 2147486253 0 qwen_parent_2
run _parent $MOON 3000004257 0 moon_parent_1
run _checkout $MOON 3000004257 0 moon_change_1
run _checkout $MOON 2147486259 0 moon_change_2
run _parent $MOON 2147486259 0 moon_parent_2
else
run _parent $QWEN 3000004261 1 qwen_parent_traced 3000
run _checkout $QWEN 3000004261 1 qwen_change_traced 3000
run _parent $MOON 3000004263 1 moon_parent_traced 3000
run _checkout $MOON 3000004263 1 moon_change_traced 3000
fi
