# chiprun --timeout 1500 -- bash benchmarks/calls/pr38_c.sh
# PR 38, third call: the recorder's cost to the step's trace and lowering alone (pr38_listen_ab.py: a process a reading,
# the three ways in turn, three times), after call A read train_step_load_s 2.2 s over the parent's in the Qwen3-Next cell.
mkdir -p chiprun_out
for i in 1 2 3; do
  for mode in plain listen spans; do
    timeout 300 python benchmarks/calls/pr38_listen_ab.py $mode 2>&1 | grep "^{" | tee -a chiprun_out/pr38c_listen_ab.jsonl
  done
done
# what stands before a run's first span (call A's table: nothing in the first 40 s)
python benchmarks/calls/pr38_program_init.py qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams 2>&1 | grep -v Warn | tail -30 | cut -c1-200 | tee chiprun_out/pr38c_program_init.txt
# the Qwen3-Next cell from the committed files (_checkout/, as in pr38_b.sh), traced: once on paths new to the cache, once warm
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
ROOT=$PWD
for tag in qwen3next_cold qwen3next_traced; do
  seed=$((${seed:-3000001000} + 13))
  (cd _checkout && timeout 700 python benchmarks/chipbench/run.py --workload $C --seed $seed --seconds 30 --trace 1 > $ROOT/chiprun_out/pr38c_$tag.log 2>&1; echo "rc=$? $tag")
  grep -h "^set-up: state" chiprun_out/pr38c_$tag.log | cut -c1-200
  python benchmarks/chipbench/tools/setup_table.py _checkout/.chipbench_trace/$C 12 > chiprun_out/pr38c_setup_table_$tag.txt 2>&1
  cp _checkout/.chipbench_trace/$C/setup_spans.json chiprun_out/pr38c_setup_spans_$tag.json
  head -24 chiprun_out/pr38c_setup_table_$tag.txt | cut -c1-170
done
