# git add -A && rm -rf _checkout && mkdir _checkout && git archive $(git write-tree) | tar -x -C _checkout
# chiprun --timeout 2700 -- bash benchmarks/calls/pr49_review.sh
# PR 49, review round, ONE call from the files committed AT THAT TIME (_checkout/): a tree whose scan was its XLA form
# ALONE (the review asked which form gives more tokens/s; that tree read 5,487 against the sweeps' 6,081 and was NOT
# landed: PERF.md section 6), the weights made a layer a program, the limits at 1.3e-4 and 0.025. First traced and then on a
# second seed under the machine's own compile cache (a cold `setup_s` and the one after it); then under a cache of
# this call's own without a size limit (pr49_cell.sh says why: tokens/s and the checks are what these runs are for):
# two more seeds, the mix's control (the int8 frozen trunk), two more seeds and one planted fault as the call's time
# allows (each optional run starts only before its second on the call's clock).
mkdir -p chiprun_out
ROOT=$PWD
CELL=granite-4.0-h-micro.sft-8k-ssd-tied-last2
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^state-space|^q/k|^a remat|unknown workload|Error|Traceback|RESOURCE'
one() {  # tag seed trace entry...
  TAG=$1; SEED=$2; TRACE=$3; shift 3
  S=$SECONDS; (cd _checkout && python "$@" --workload $CELL --seed $SEED --seconds 30 --trace $TRACE > $ROOT/chiprun_out/pr49f_$TAG.log 2>&1); CODE=$?
  echo "$TAG $SEED exit $CODE after $(( SECONDS - S )) s, the call's clock $SECONDS s"
  grep -E "$KEEP" chiprun_out/pr49f_$TAG.log | cut -c1-${WIDE:-330}
  return $CODE
}
WIDE=7000 one traced 3000004981 1 benchmarks/chipbench/run.py || { tail -40 chiprun_out/pr49f_traced.log | cut -c1-400; exit 1; }
(cd _checkout && python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 40 2 20) > chiprun_out/pr49f_scope_table.txt 2>&1; tail -64 chiprun_out/pr49f_scope_table.txt | cut -c1-200
(cd _checkout && python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$CELL) > chiprun_out/pr49f_setup_table.txt 2>&1; tail -24 chiprun_out/pr49f_setup_table.txt | cut -c1-200
cp _checkout/.chipbench_trace/$CELL/setup_spans.json chiprun_out/pr49f_setup_spans.json 2>/dev/null
one sound_2 2147486989 0 benchmarks/chipbench/run.py
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache_call JAX_COMPILATION_CACHE_MAX_SIZE=-1
one sound_3 3000004991 0 benchmarks/chipbench/run.py
one sound_4 2147487001 0 benchmarks/chipbench/run.py
one control 2147486941 0 benchmarks/chipbench/tools/control.py
[ $SECONDS -lt 2050 ] && one sound_5 3000005003 0 benchmarks/chipbench/run.py
[ $SECONDS -lt 2050 ] && one norm_before_gate 3000004943 0 benchmarks/chipbench/tools/fault_ssd.py --fault norm_before_gate
[ $SECONDS -lt 2250 ] && one sound_6 2147487011 0 benchmarks/chipbench/run.py
echo "the call's clock at its end $SECONDS s"
