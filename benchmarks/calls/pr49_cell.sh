# rm -rf _overlay && mkdir _overlay && git archive 7a0ee9582fe9b552c2fee116dbb7421c4b61c9d5 | tar -x -C _overlay
# && cp BENCHMARK.json _overlay/ && cp -r benchmarks/chipbench/. _overlay/benchmarks/chipbench/
# chiprun --timeout 3500 -- bash benchmarks/calls/pr49_cell.sh
# PR 49, the new cell: the parent with this PR's benchmark files laid over it (_overlay/) fails cleanly on the cell's
# name; untraced runs on their own seeds (the limits are set from these; SEEDS overrides the list), one traced
# (TRACED=0 leaves it out); the mix's control (the int8 frozen trunk); the planted faults (FAULTS overrides the list).
mkdir -p chiprun_out
# (the machine's own cache keeps 192 MiB and this cell's two large programs, the weights' and the step's, did not come
# back out of it in pr49_first.sh's second run: a cache of this call's own, without a size limit, so that runs after the
# first load them; `setup_s` here is therefore NOT what the driver's runs will read, tokens/s and the checks are)
export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=granite-4.0-h-micro.sft-8k-ssd-tied-last2
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^state-space|^q/k|^a remat|unknown workload|Error|Traceback|RESOURCE'
S=$(date +%s)
(cd _overlay && python benchmarks/chipbench/run.py --workload $CELL --seed 3000004911 --seconds 30 --trace 0; echo "overlay exit $? after $(( $(date +%s) - S )) s") 2>&1 | grep -v Warning | tail -4 | cut -c1-500
one() {  # tag seed trace entry...
  TAG=$1; SEED=$2; TRACE=$3; shift 3
  python "$@" --workload $CELL --seed $SEED --seconds 30 --trace $TRACE > chiprun_out/pr49b_$TAG.log 2>&1; CODE=$?; echo "$TAG $SEED exit $CODE"
  grep -E "$KEEP" chiprun_out/pr49b_$TAG.log | cut -c1-${WIDE:-330}
  return $CODE
}
N=1; for SEED in ${SEEDS:-2147486917 3000004919 2147486921 3000004931 2147486933 3000004957}; do one sound_$N $SEED 0 benchmarks/chipbench/run.py; N=$((N+1)); done
if [ "${TRACED:-1}" = 1 ]; then
WIDE=7000 one traced 3000004939 1 benchmarks/chipbench/run.py
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 40 2 20 > chiprun_out/pr49b_scope_table.txt 2>&1; tail -70 chiprun_out/pr49b_scope_table.txt | cut -c1-200
python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$CELL > chiprun_out/pr49b_setup_table.txt 2>&1
cp .chipbench_trace/$CELL/setup_spans.json chiprun_out/pr49b_setup_spans.json 2>/dev/null
fi
if [ "${CONTROL:-1}" = 1 ]; then one control 2147486941 0 benchmarks/chipbench/tools/control.py; fi
for FAULT in ${FAULTS:-no_decay norm_before_gate sqrt_scale}; do one $FAULT 3000004943 0 benchmarks/chipbench/tools/fault_ssd.py --fault $FAULT; done
