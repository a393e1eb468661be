# chiprun --timeout 1500 -- bash benchmarks/calls/pr51_probe2.sh
# PR 51: Program(...) alone, parent and change at ONE path (as pr51_cells.sh runs them), the change with and without the
# finder, alternating; then once a side under `python -X importtime` (the interpreter's own import times, no hook of ours).
mkdir -p chiprun_out
ROOT=$PWD
CELL=mistral-7b-d16.sft-2k-full
probe() {  # tree mode [python options]
  TREE=$1; MODE=$2; shift 2
  mv $TREE _run
  python "$@" $ROOT/_checkout_probe.py _run $CELL $MODE 2>chiprun_out/pr51_probe2_${TREE}_${MODE}.err | grep '^{' | sed "s/\"_run\"/\"$TREE\"/" | tee -a chiprun_out/pr51_probe2.jsonl | cut -c1-600
  mv _run $TREE
}
cp benchmarks/calls/pr51_program_probe.py _checkout_probe.py
for ROUND in 1 2; do
  probe _parent asis
  probe _checkout asis
  probe _checkout plain
done
probe _parent asis -X importtime
grep 'import time' chiprun_out/pr51_probe2__parent_asis.err | sort -t'|' -k2 -n -r | head -12
probe _checkout plain -X importtime
grep 'import time' chiprun_out/pr51_probe2__checkout_plain.err | sort -t'|' -k2 -n -r | head -12
probe _checkout asis -X importtime
grep 'import time' chiprun_out/pr51_probe2__checkout_asis.err | sort -t'|' -k2 -n -r | head -12
rm -f _checkout_probe.py
