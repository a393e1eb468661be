# mkdir -p _parent && git archive <parent commit> | tar -x -C _parent
# chiprun --timeout 3500 -- bash benchmarks/chipbench/tools/calls/pr30_cells_before_after.sh
# PR 30: the three accepted cells, parent (in _parent/, ignored by git, with the benchmark files it had) and change,
# same chip, same seed a pair, order alternating; one accepted cell traced on the parent with this PR's benchmark
# files laid over it (_checkout/); then the new cell on six seeds, traced, and its control.
mkdir -p chiprun_out
run() { # dir cell seed trace tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4) > chiprun_out/pr30_$5.log 2>&1; echo "rc=$? $5"
}
S=smollm3-3b.sft-1k-full; M=mistral-7b-d16.sft-2k-full; L=moonlight-16b-a3b-ep8-d6.sft-4k-allparams
C=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
run _parent $L 3000000301 0 moon_parent
run .       $L 3000000301 0 moon_change
run .       $M 2147484303 0 mistral_change
run _parent $M 2147484303 0 mistral_parent
run _parent $S 3000000305 0 smol_parent
run .       $S 3000000305 0 smol_change
run _checkout $S 2147484307 1 smol_parent_traced_with_new_files
# the new cell with its token embeddings drawn at embed_std 1.0 (balanced routing): six seeds, traced, control
for seed in 2147484309 3000000311 2147484313 3000000317 2147484319 3000000323; do run . $C $seed 0 new2_$seed; done
run . $C 2147484329 1 new2_traced
(cd . && python benchmarks/chipbench/tools/control.py --workload $C --seed 3000000331 --seconds 30 --trace 0) > chiprun_out/pr30_new2_control.log 2>&1; echo "rc=$? new2_control"
grep -h "^check" chiprun_out/pr30_new2_*.log | cut -c1-200
grep -h "^{" chiprun_out/pr30_moon_*.log chiprun_out/pr30_mistral_*.log chiprun_out/pr30_smol_*.log chiprun_out/pr30_new2_*.log | cut -c1-1500
PYTHONPATH=. python benchmarks/chipbench/tools/calls/pr30_kernels.py 2>&1 | grep -v "^W\|^I" | tail -8
