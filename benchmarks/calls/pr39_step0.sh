# rm -rf _parent && mkdir _parent && git archive 2fb8c47 | tar -x -C _parent
# chiprun --timeout 1500 -- bash benchmarks/calls/pr39_step0.sh
# PR 39, step 0, before any kernel is written: the parent's cell traced (its paths are new to the machine's cache: a cold
# set-up), read by part, by scope and by what under linear_attn is no product; then the parent's mixer alone.
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
ROOT=$PWD
(cd _parent && python benchmarks/chipbench/run.py --workload $C --seed 3000001401 --seconds 30 --trace 1 > $ROOT/chiprun_out/pr39s0_parent_traced.log 2>&1; echo "rc=$? traced at $SECONDS s")
grep -h "^set-up: state\|^window\|^gated delta" chiprun_out/pr39s0_parent_traced.log | cut -c1-220; grep -h "^{" chiprun_out/pr39s0_parent_traced.log | cut -c1-3000
python benchmarks/chipbench/tools/gdn_by_op.py _parent/.chipbench_trace/$C auto 40 2>&1 | grep -v -i warn > chiprun_out/pr39s0_gdn_by_op.txt
python benchmarks/chipbench/tools/scope_table.py _parent/.chipbench_trace/$C 4 4 12 2>&1 | grep -v -i warn > chiprun_out/pr39s0_scope_table.txt
python benchmarks/calls/pr39_not_products.py _parent/.chipbench_trace/$C 30 2>&1 | grep -v -i warn > chiprun_out/pr39s0_not_products.txt
head -12 chiprun_out/pr39s0_gdn_by_op.txt | cut -c1-200; cut -c1-200 chiprun_out/pr39s0_not_products.txt
(cd _parent && python benchmarks/gdn_kernels.py --only mixer 2>&1 | grep "^{" | tee $ROOT/chiprun_out/pr39s0_mixer.jsonl)
echo "ended at $SECONDS s"
