# git add -A && rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout && git archive 7a0ee9582fe9b552c2fee116dbb7421c4b61c9d5 | tar -x -C _parent
# chiprun --timeout 3300 -- bash benchmarks/calls/pr49_final.sh
# PR 49, from the committed files alone (_checkout/, the parent in _parent/): the new cell on a seed of its own and
# the fault that stands in for sqrt_scale (unit_residual), under a cache of this call's own (pr49_cell.sh says why);
# then two accepted cells, one pair each on the machine's cache as the driver's runs find it: SmolLM3 (the one other
# cell whose backward crosses a frozen trunk to a tied table, through _block, unembed and chunked_ce_sum) and
# Qwen3-Next (_by_columns, causal_conv, _remat_policy, _whole_rows_only). The eight cells' lowered steps are equal in
# both trees (pr49_lowered.sh), so the other six are left to the driver.
mkdir -p chiprun_out
ROOT=$PWD
CELL=granite-4.0-h-micro.sft-8k-ssd-tied-last2
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^state-space|Error|Traceback|RESOURCE'
(cd _checkout && JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1 python benchmarks/chipbench/run.py --workload $CELL --seed 3000004973 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr49d_committed.log 2>&1; echo "committed exit $?")
grep -E "$KEEP" chiprun_out/pr49d_committed.log | cut -c1-700
(cd _checkout && JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1 python benchmarks/chipbench/tools/fault_ssd.py --fault unit_residual --workload $CELL --seed 3000004943 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr49d_unit_residual.log 2>&1; echo "unit_residual exit $?")
grep -E "$KEEP" chiprun_out/pr49d_unit_residual.log | cut -c1-400
KEEP='^\{|^set-up|^window|^chipbench|Error|Traceback'
run() {  # tree cell seed tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr49d_$4.log 2>&1; echo "$4 exit $?")
  grep -E "$KEEP" chiprun_out/pr49d_$4.log | cut -c1-330
}
run _parent smollm3-3b.sft-1k-full 3000004951 smol_parent
run _checkout smollm3-3b.sft-1k-full 3000004951 smol_change
run _parent qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams 3000004961 qwen_parent
run _checkout qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams 3000004961 qwen_change
