# rm -rf _step1 && mkdir -p _step1 && git archive $(git write-tree) | tar -x -C _step1
# chiprun --timeout 1800 -- bash benchmarks/chipbench/tools/calls/pr30r_final_tree.sh
# PR 30, review round: the committed files alone (git archive of the final tree, in _step1/, ignored by git) run the new
# cell as its window loop stands (one step queued): six seeds untraced, one traced, and the control under the tightened
# limits (not correct, by four of them).
mkdir -p chiprun_out
C=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
cd _step1
for seed in ${SEEDS:-3000000711 2147484713 3000000717 2147484719 3000000723 2147484729}; do
  python benchmarks/chipbench/run.py --workload $C --seed $seed --seconds 30 --trace 0 > ../chiprun_out/pr30r_$seed.log 2>&1; echo "rc=$? $seed"
  grep -h "^window" ../chiprun_out/pr30r_$seed.log; grep -h "^{" ../chiprun_out/pr30r_$seed.log | cut -c1-240
done
python benchmarks/chipbench/run.py --workload $C --seed 2147484731 --seconds 30 --trace 1 > ../chiprun_out/pr30r_traced.log 2>&1; echo "rc=$? traced"
python benchmarks/chipbench/tools/control.py --workload $C --seed 3000000737 --seconds 5 --trace 0 > ../chiprun_out/pr30r_control.log 2>&1; echo "rc=$? control"
cd ..
grep -h "^check" chiprun_out/pr30r_[0-9]*.log | sort | awk '{print $2, $3}' | sort -k1,1 -k2,2g | awk '{last[$1]=$2} END {for (k in last) print "sound largest", k, last[k]}'
grep -h "^check" chiprun_out/pr30r_control.log | cut -c1-200
grep -h "^window\|^{" chiprun_out/pr30r_traced.log | cut -c1-2600
grep -ih "error\|exhaust" chiprun_out/pr30r_*.log | head -5 | cut -c1-300
