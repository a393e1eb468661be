# mkdir -p _checkout && git archive <parent commit> | tar -x -C _checkout && cp BENCHMARK.json _checkout/ && cp -r benchmarks/chipbench/. _checkout/benchmarks/chipbench/
# chiprun --timeout 3400 -- bash benchmarks/chipbench/tools/calls/pr30_new_cell_first.sh
# PR 30: the parent (this PR's benchmark files laid over it, in _checkout/, ignored by git) asked for the new
# cell, which it has to refuse at once; then the new cell untraced, traced, untraced, its control, and two more seeds untraced. Should the
# chip refuse 4 rows x 1 the mix is set to 2 rows x 2 in this copy and the run says so.
mkdir -p chiprun_out
C=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
run() { # dir script seed trace tag
  (cd $1 && python benchmarks/chipbench/$2 --workload $C --seed $3 --seconds 30 --trace $4) > chiprun_out/pr30_$5.log 2>&1; echo "rc=$? $5"
}
(cd _checkout && timeout 600 python benchmarks/chipbench/run.py --workload $C --seed 1 --seconds 30 --trace 0) > chiprun_out/pr30_parent_newcell.log 2>&1
echo "rc=$? parent on the new cell"; tail -2 chiprun_out/pr30_parent_newcell.log | cut -c1-300
run . run.py 3000000011 0 new_a
if ! grep -q '^{' chiprun_out/pr30_new_a.log; then
  echo "4 rows x 1 did not run:"; grep -iE "error|resource|exhaust|memory" chiprun_out/pr30_new_a.log | head -5 | cut -c1-400
  sed -i 's/"microbatch": 4,/"microbatch": 2,/; s/"accum": 1,/"accum": 2,/' benchmarks/chipbench/traffic/sft-8k-allparams.json
  echo "now 2 rows x 2"; run . run.py 3000000011 0 new_a2
fi
run . run.py 2147483977 1 new_traced
run . run.py 3000000029 0 new_b
run . tools/control.py 2147484001 0 new_control
run . run.py 2147484033 0 new_c
run . run.py 3000000047 0 new_d
grep -h "^check\|^set-up\|^reference\|attention paths" chiprun_out/pr30_new_*.log | cut -c1-260
grep -h "^{" chiprun_out/pr30_new_*.log | cut -c1-2600
