# rm -rf _step1 && mkdir -p _step1 && git archive $(git write-tree) | tar -x -C _step1
# chiprun --timeout 1000 -- bash benchmarks/chipbench/tools/calls/pr32_last_tree.sh
# PR 32, second session: the committed files of the last tree (the limits file's readings written down, the control
# as float8_e5m2, the mixer's scope name in the table: the same step program, lowered StableHLO byte-identical) run the
# new cell once traced and twice untraced, on seeds not used before.
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
cd _step1
python benchmarks/chipbench/run.py --workload $C --seed 2147484631 --seconds 30 --trace 1 > ../chiprun_out/pr32g_traced.log 2>&1; echo "rc=$? traced"
for seed in ${SEEDS:-3000000637 2147484641}; do
  python benchmarks/chipbench/run.py --workload $C --seed $seed --seconds 30 --trace 0 > ../chiprun_out/pr32g_$seed.log 2>&1; echo "rc=$? $seed"
done
cd ..
grep -h "^check" chiprun_out/pr32g_*.log | cut -c1-160
grep -h "^set-up\|^window\|^reference\|gated delta" chiprun_out/pr32g_*.log | cut -c1-200
grep -h "^{" chiprun_out/pr32g_[0-9]*.log | cut -c1-330
grep -h "^{" chiprun_out/pr32g_traced.log | cut -c1-4000
grep -ih "error\|exhaust" chiprun_out/pr32g_*.log | head -5 | cut -c1-300
