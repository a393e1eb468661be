# chiprun --timeout 1050 -- bash benchmarks/chipbench/tools/calls/pr30_queued_step.sh   (after: git archive $(git write-tree) | tar -x -C _step1)
# PR 30: the committed files with one step queued behind the running one: five seeds untraced, one traced
mkdir -p chiprun_out
C=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
cd _step1
for seed in 3000000601 2147484603 3000000607 2147484609 3000000611; do
  python benchmarks/chipbench/run.py --workload $C --seed $seed --seconds 30 --trace 0 > ../chiprun_out/pr30_q_$seed.log 2>&1; echo "rc=$? q $seed"
  grep -h "^{" ../chiprun_out/pr30_q_$seed.log | cut -c1-150
done
python benchmarks/chipbench/run.py --workload $C --seed 2147484613 --seconds 30 --trace 1 > ../chiprun_out/pr30_q_traced.log 2>&1; echo "rc=$? q traced"
grep -h "^{" ../chiprun_out/pr30_q_traced.log | cut -c1-1800
grep -ih "error\|exhaust" ../chiprun_out/pr30_q_*.log | head -5 | cut -c1-300
