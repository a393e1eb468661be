# chiprun --timeout 2000 -- bash benchmarks/chipbench/tools/calls/pr30_same_seed_twice.sh   (after: git archive $(git write-tree) | tar -x -C _step1)
# PR 30: is the new cell's +0.6% mode set by the seed (no: F1, S1 slow, F2, S2 fast), and which operations carry it (none)
mkdir -p chiprun_out
C=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
cd _step1
for tag in F1:3000000317 S1:2147484309 F2:3000000317 S2:2147484309; do
  python benchmarks/chipbench/run.py --workload $C --seed ${tag#*:} --seconds 30 --trace 0 > ../chiprun_out/pr30_bi_${tag%%:*}.log 2>&1; echo "rc=$? $tag"
done
for tag in FT:3000000317 ST:2147484309; do
  python benchmarks/chipbench/run.py --workload $C --seed ${tag#*:} --seconds 30 --trace 1 > ../chiprun_out/pr30_bi_${tag%%:*}.log 2>&1; echo "rc=$? $tag"
  python benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$C 4 4 80 > ../chiprun_out/pr30_bi_${tag%%:*}_table.txt 2>&1
done
cd ..
grep -h "^{" chiprun_out/pr30_bi_*.log | cut -c1-200
