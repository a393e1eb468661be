# chiprun --timeout 2400 -- bash benchmarks/calls/pr31_second.sh
# PR 31: the claimed cell, parent (_parent/, git archive of 9b5aecd) against the change, untraced, other seeds, in the
# order parent, change, change, parent; then Moonlight traced on both (one seed) read by operation; the traces of the
# first seed of each cell come back when they are small enough.
mkdir -p chiprun_out
M=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
L=moonlight-16b-a3b-ep8-d6.sft-4k-allparams
run() {  # side, cell, seed, trace, tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $OLDPWD/chiprun_out/pr31_$5.log 2>&1; echo "rc=$? $5")
  grep -h "^window\|^expert rows" chiprun_out/pr31_$5.log; grep -h "^{" chiprun_out/pr31_$5.log | cut -c1-200
}
run _parent $M 3000000811 0 m_parent_1
run . $M 3000000811 0 m_change_1
run . $M 2147484817 0 m_change_2
run _parent $M 2147484817 0 m_parent_2
run _parent $L 2147485321 1 l_parent_traced
python benchmarks/dispatch_by_op.py _parent/.chipbench_trace/$L auto 40 > chiprun_out/pr31_l_parent_by_op.txt 2>&1
run . $L 2147485321 1 l_change_traced
python benchmarks/dispatch_by_op.py .chipbench_trace/$L auto 40 > chiprun_out/pr31_l_change_by_op.txt 2>&1
for side in parent change; do
  echo "== moonlight $side"; grep -h "^{" chiprun_out/pr31_l_${side}_traced.log | cut -c1-2600; head -30 chiprun_out/pr31_l_${side}_by_op.txt
done
for f in _parent/.chipbench_trace/$L .chipbench_trace/$L; do
  pb=$(find $f -name "*.xplane.pb" | head -1); ls -l $pb
  if [ $(stat -c %s $pb) -lt 28000000 ]; then cp $pb chiprun_out/pr31_$(echo $f | tr '/.' '__').xplane.pb; fi
done
grep -ih "error\|exhaust\|Traceback" chiprun_out/pr31_[ml]_*.log | head -5 | cut -c1-300
