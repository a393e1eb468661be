# rm -rf _overlay && mkdir _overlay && git archive 3b0b41ed1b083871ba17a9974ffdb1b026624d79 | tar -x -C _overlay
# && cp BENCHMARK.json _overlay/ && cp -r benchmarks/chipbench/. _overlay/benchmarks/chipbench/
# chiprun --timeout 3500 -- bash benchmarks/calls/pr46_cell.sh
# PR 46, the new cell: the kernels at a small size under a watchdog and at the cell's shape; the parent with this PR's
# benchmark files laid over it (_overlay/) fails cleanly on the cell's name; six untraced runs on six seeds (the limits
# are set from these), one traced; the mix's control (the int8 frozen trunk); the three planted faults.
mkdir -p chiprun_out
ROOT=$PWD
CELL=evabyte-6.5b-d10.sft-32k-eva-last2
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^eva|^q/k|^a remat|unknown workload|Error|Traceback|RESOURCE'
python benchmarks/calls/pr46_tiny.py > chiprun_out/pr46b_tiny.log 2>&1; echo "tiny exit $?"
grep -E '^\{|Error|Traceback' chiprun_out/pr46b_tiny.log | cut -c1-900
(cd _overlay && time python benchmarks/chipbench/run.py --workload $CELL --seed 3000004611 --seconds 30 --trace 0; echo "overlay exit $?") 2>&1 | grep -v Warning | tail -8 | cut -c1-400
one() {  # tag seed trace entry...
  TAG=$1; SEED=$2; TRACE=$3; shift 3
  python "$@" --workload $CELL --seed $SEED --seconds 30 --trace $TRACE > chiprun_out/pr46b_$TAG.log 2>&1; CODE=$?; echo "$TAG $SEED exit $CODE"
  grep -E "$KEEP" chiprun_out/pr46b_$TAG.log | cut -c1-${WIDE:-330}
  return $CODE
}
one sound_1 3000004613 0 benchmarks/chipbench/run.py || { tail -30 chiprun_out/pr46b_sound_1.log | cut -c1-400; exit 1; }
N=2; for SEED in 2147486617 3000004619 2147486621 3000004631 2147486633; do one sound_$N $SEED 0 benchmarks/chipbench/run.py; N=$((N+1)); done
WIDE=7000 one traced 3000004637 1 benchmarks/chipbench/run.py
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 10 2 20 > chiprun_out/pr46b_scope_table.txt 2>&1; tail -60 chiprun_out/pr46b_scope_table.txt | cut -c1-200
python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$CELL > chiprun_out/pr46b_setup_table.txt 2>&1
cp .chipbench_trace/$CELL/setup_spans.json chiprun_out/pr46b_setup_spans.json 2>/dev/null
one control 2147486641 0 benchmarks/chipbench/tools/control.py
for FAULT in no_summaries own_window_summaries first_head_only; do one $FAULT 3000004643 0 benchmarks/chipbench/tools/fault_eva.py --fault $FAULT; done
