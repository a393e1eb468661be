# chiprun --timeout 1500 -- bash benchmarks/calls/pr37_bisect.sh
# PR 37: PR 36's tree (_step1/) through run.py under runpy with one of pr37_cell.py's three differences at a time
# (pr37_bisect.py), to find which takes its eleven seconds of set-up away; its programs are in the machine's cache.
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
ROOT=$PWD
seed=3000000800
for options in "" "--imports" "--patch" "--gc"; do
  seed=$((seed + 7)); tag=bisect$(echo $options | tr -d ' -'); tag=${tag:-bisect}
  (cd _step1 && python $ROOT/benchmarks/calls/pr37_bisect.py $options -- --workload $C --seed $seed --seconds 30 --trace 0 > $ROOT/chiprun_out/pr37b_$tag.log 2>&1; echo "rc=$? $tag")
  grep -h "^pr37_bisect\|^set-up: state" chiprun_out/pr37b_$tag.log | cut -c1-200; grep -h "^{" chiprun_out/pr37b_$tag.log | cut -c1-200
done
