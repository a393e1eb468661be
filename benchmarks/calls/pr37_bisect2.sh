# chiprun --timeout 900 -- bash benchmarks/calls/pr37_bisect2.sh
# PR 37: pr37_bisect.sh found that runpy alone takes PR 36's eleven seconds away. What is left between the two ways to
# start run.py is the script's own directory (benchmarks/chipbench/, with its trace.py, check.py, ...) at the head of
# sys.path: PR 36's tree through run.py itself, then the same with python -P (the script's directory not prepended).
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
ROOT=$PWD
cd _step1
python benchmarks/chipbench/run.py --workload $C --seed 3000000841 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr37b2_plain.log 2>&1; echo "rc=$? plain"
python -P benchmarks/chipbench/run.py --workload $C --seed 3000000853 --seconds 30 --trace 0 > $ROOT/chiprun_out/pr37b2_P.log 2>&1; echo "rc=$? -P"
cd $ROOT
for t in plain P; do grep -h "^set-up: state" chiprun_out/pr37b2_$t.log | cut -c1-200; grep -h "^{" chiprun_out/pr37b2_$t.log | cut -c1-200; done
