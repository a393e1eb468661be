# chiprun --timeout 2400 -- bash benchmarks/calls/pr49_first.sh
# PR 49, the first look: the scan's sweeps and the flash kernels at heads of 64 at a small size under a watchdog and at
# the cell's shape; then the new cell once untraced (the first readings beside their limits) and once traced.
mkdir -p chiprun_out
CELL=granite-4.0-h-micro.sft-8k-ssd-tied-last2
KEEP='^check|^\{|^set-up|^window|^reference|^chipbench|^attention|^state-space|^q/k|^a remat|unknown workload|Error|Traceback|RESOURCE'
python benchmarks/calls/pr49_tiny.py > chiprun_out/pr49a_tiny.log 2>&1; echo "tiny exit $?"
grep -E '^\{|Error|Traceback' chiprun_out/pr49a_tiny.log | cut -c1-900
one() {  # tag seed trace entry...
  TAG=$1; SEED=$2; TRACE=$3; shift 3
  python "$@" --workload $CELL --seed $SEED --seconds 30 --trace $TRACE > chiprun_out/pr49a_$TAG.log 2>&1; CODE=$?; echo "$TAG $SEED exit $CODE"
  grep -E "$KEEP" chiprun_out/pr49a_$TAG.log | cut -c1-${WIDE:-330}
  return $CODE
}
one sound_1 3000004913 0 benchmarks/chipbench/run.py || { tail -40 chiprun_out/pr49a_sound_1.log | cut -c1-400; exit 1; }
WIDE=7000 one traced 3000004937 1 benchmarks/chipbench/run.py
python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 40 2 20 > chiprun_out/pr49a_scope_table.txt 2>&1; tail -70 chiprun_out/pr49a_scope_table.txt | cut -c1-200
python benchmarks/chipbench/tools/setup_table.py .chipbench_trace/$CELL > chiprun_out/pr49a_setup_table.txt 2>&1
cp .chipbench_trace/$CELL/setup_spans.json chiprun_out/pr49a_setup_spans.json 2>/dev/null
