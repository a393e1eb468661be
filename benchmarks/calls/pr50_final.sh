# git add -A && rm -rf _checkout _parent && mkdir _checkout _parent && git archive $(git write-tree) | tar -x -C _checkout && git archive 320e0e1d3cb4fb71e0c1bc9d508f92c34aaa457c | tar -x -C _parent
# chiprun --timeout 3500 -- bash benchmarks/calls/pr50_final.sh
# PR 50, from the committed files alone (_checkout/) against the parent (_parent/), through run.py itself; no benchmark
# file differs between the trees. Chips were scarce, so ONE call for the claim: first the sweeps' smoke test under its watchdog
# (benchmarks/calls/pr50_tiny.py), and only if it passes (finite, and the XLA form's within what bfloat16 products
# explain) the claimed cell: three pairs untraced, a seed a pair, in the order parent, change, change, parent, parent,
# change; then one traced run a side with the scan's scope by operation (pr50_scan_by_op.py); last the forward sweep
# alone under the other ways of making the rows (pr50_forms.py). A cache of this call's own without a size limit
# (pr49_cell.sh says why: the machine's 192 MiB does not hold this cell's step), shared by both trees, so `setup_s` is
# warm from each tree's second run on and compares there. The eight other cells run none of the changed code
# (benchmarks/calls/pr50_lowered.sh: their lowered steps are equal in both trees): they are left to the driver.
# PART=look was the second call (chiprun --timeout 2700 -- env PART=look bash benchmarks/calls/pr50_final.sh: the tool
# does not carry the shell's environment): one traced run a side once more (what grew under `ssd_in`, by operation on
# both sides, and the calls `ssd_scan_fwd_roofline_pct`'s reader counts, layer by layer), the heads-major experiment in
# _step1/, the forms again. PART=smol (one pair of SmolLM3) and PART=forms were not run.
mkdir -p chiprun_out
ROOT=$PWD
CELL=granite-4.0-h-micro.sft-8k-ssd-tied-last2
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^state-space|Error|Traceback|RESOURCE'
run() {  # tree cell seed trace tag [columns]
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $ROOT/chiprun_out/pr50f_$5.log 2>&1; echo "$5 seed $3 exit $? at $SECONDS s")
  grep -E "$KEEP" chiprun_out/pr50f_$5.log | cut -c1-${6:-420}
}
tables() {  # tree tag
  (cd $1 && timeout 120 python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 40 2 20 > $ROOT/chiprun_out/pr50f_$2_scope_table.txt 2>&1
   timeout 120 python $ROOT/_checkout/benchmarks/calls/pr50_scan_by_op.py .chipbench_trace/$CELL 25 > $ROOT/chiprun_out/pr50f_$2_scan_by_op.txt 2>&1
   timeout 120 python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$CELL > $ROOT/chiprun_out/pr50f_$2_setup_table.txt 2>&1
   cp .chipbench_trace/$CELL/setup_spans.json $ROOT/chiprun_out/pr50f_$2_setup_spans.json 2>/dev/null)
  cut -c1-230 chiprun_out/pr50f_$2_scan_by_op.txt | head -24
}
forms() {
  (cd _checkout && python benchmarks/calls/pr50_forms.py > $ROOT/chiprun_out/pr50f_forms.log 2>&1; echo "forms exit $? at $SECONDS s")
  grep -E '^\{|Error|Traceback|Timeout' chiprun_out/pr50f_forms.log | cut -c1-300
}
case "${PART:-cell}" in
cell)
  (cd _checkout && python benchmarks/calls/pr50_tiny.py > $ROOT/chiprun_out/pr50f_tiny.log 2>&1); CODE=$?
  grep -E '^\{|Error|Traceback|Timeout' chiprun_out/pr50f_tiny.log | cut -c1-900
  echo "tiny exit $CODE at $SECONDS s"
  [ $CODE = 0 ] || exit $CODE
  export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1
  run _parent $CELL 3000005123 0 parent_1
  run _checkout $CELL 3000005123 0 change_1
  run _checkout $CELL 2147487017 0 change_2
  run _parent $CELL 2147487017 0 parent_2
  run _parent $CELL 3000005131 0 parent_3
  run _checkout $CELL 3000005131 0 change_3
  run _checkout $CELL 3000005137 1 change_traced 7000
  tables _checkout change
  run _parent $CELL 3000005137 1 parent_traced 7000
  tables _parent parent
  unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
  forms
  ;;
forms) forms ;;
look)
  export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1
  for SIDE in change parent; do
    TREE=_checkout; [ $SIDE = parent ] && TREE=_parent
    run $TREE $CELL 3000005153 1 ${SIDE}_traced_2 7000
    (cd $TREE && timeout 120 python $ROOT/_checkout/benchmarks/calls/pr50_scan_by_op.py .chipbench_trace/$CELL 12 > $ROOT/chiprun_out/pr50f_${SIDE}_scan_by_op_2.txt 2>&1
     timeout 120 python $ROOT/_checkout/benchmarks/calls/pr50_scan_by_op.py .chipbench_trace/$CELL 30 ssd_in > $ROOT/chiprun_out/pr50f_${SIDE}_in_by_op.txt 2>&1)
    grep -A40 "^readers/gdn" chiprun_out/pr50f_${SIDE}_scan_by_op_2.txt | cut -c1-400 | head -60
    head -36 chiprun_out/pr50f_${SIDE}_in_by_op.txt | cut -c1-230
  done
  # NOT the program: the sweeps reading dt heads-major (benchmarks/calls/pr50_heads_major.patch over the committed
  # ops/ssd.py, in _step1/: rm -rf _step1 && cp -r _checkout _step1 && patch -d _step1 -p0 < benchmarks/calls/pr50_heads_major.patch),
  # once untraced and once traced, for PERF.md section 7's first item
  if [ -d _step1 ]; then
    run _step1 $CELL 3000005161 0 heads_major_1
    run _step1 $CELL 3000005167 1 heads_major_traced 7000
    (cd _step1 && timeout 120 python $ROOT/_checkout/benchmarks/calls/pr50_scan_by_op.py .chipbench_trace/$CELL 12 > $ROOT/chiprun_out/pr50f_heads_major_scan_by_op.txt 2>&1
     timeout 120 python $ROOT/_checkout/benchmarks/calls/pr50_scan_by_op.py .chipbench_trace/$CELL 30 ssd_in > $ROOT/chiprun_out/pr50f_heads_major_in_by_op.txt 2>&1)
    head -8 chiprun_out/pr50f_heads_major_scan_by_op.txt | cut -c1-230
  fi
  unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
  mv chiprun_out/pr50f_forms.log chiprun_out/pr50f_forms_first.log 2>/dev/null
  forms
  ;;
smol)  # (the machine's own cache, as the driver's runs find it)
  run _parent smollm3-3b.sft-1k-full 3000005147 0 smol_parent
  run _checkout smollm3-3b.sft-1k-full 3000005147 0 smol_change
  ;;
esac
