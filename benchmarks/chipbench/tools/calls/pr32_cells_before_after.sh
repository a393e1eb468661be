# rm -rf _parent _checkout _step1 && mkdir -p _parent _checkout _step1 && git archive <parent commit> | tar -x -C _parent
# && git archive <parent commit> | tar -x -C _checkout && cp BENCHMARK.json _checkout/ && cp -r benchmarks/chipbench/. _checkout/benchmarks/chipbench/
# && git archive $(git write-tree) | tar -x -C _step1
# chiprun --timeout 3400 -- bash benchmarks/chipbench/tools/calls/pr32_cells_before_after.sh
# PR 32: the four accepted cells, parent (_parent/, with the benchmark files it had) and change (_step1/, the final
# tree's committed files), same chip, same seed a pair, order alternating; the parent with this PR's benchmark files
# laid over it (_checkout/) asked for the new cell (it has to refuse at once) and for one accepted cell traced; then the
# new cell's control in three lower precisions.
mkdir -p chiprun_out
run() { # dir cell seed trace tag
  (cd $1 && python benchmarks/chipbench/run.py --workload $2 --seed $3 --seconds 30 --trace $4) > chiprun_out/pr32c_$5.log 2>&1; echo "rc=$? $5"
}
S=smollm3-3b.sft-1k-full; M=mistral-7b-d16.sft-2k-full; L=moonlight-16b-a3b-ep8-d6.sft-4k-allparams
E=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams; C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
t0=$(date +%s); run _checkout $C 3000000501 0 parent_newcell; echo "parent on the new cell: $(( $(date +%s) - t0 )) s"; tail -1 chiprun_out/pr32c_parent_newcell.log | cut -c1-300
run _parent $E 2147484503 0 mellum_parent
run _step1  $E 2147484503 0 mellum_change
run _step1  $L 3000000507 0 moon_change
run _parent $L 3000000507 0 moon_parent
run _parent $M 2147484509 0 mistral_parent
run _step1  $M 2147484509 0 mistral_change
run _step1  $S 3000000511 0 smol_change
run _parent $S 3000000511 0 smol_parent
run _checkout $E 2147484513 1 mellum_parent_traced_with_new_files
# the new cell's control by variant (float8_e4m3fn in the 512-wide softmax router reads NaN: ten chosen probabilities of
# about 1/512 underflow to a sum of zero): which lower precision comes out not correct with every loss finite
T=_step1/benchmarks/chipbench/traffic/sft-8k-linear-allparams.json
control() { # tag seed control-json
  python -c "import json,sys; p='$T'; d=json.load(open(p)); d['control']=json.loads(sys.argv[1]); json.dump(d,open(p,'w'),indent=1)" "$3"
  (cd _step1 && python benchmarks/chipbench/tools/control.py --workload $C --seed $2 --seconds 5 --trace 0) > chiprun_out/pr32c_control_$1.log 2>&1; echo "rc=$? control_$1"
  grep -h "^check" chiprun_out/pr32c_control_$1.log | cut -c1-200
}
control bf16_both 3000000517 '{"router_dtype": "bfloat16", "state_dtype": "bfloat16"}'
control bf16_state 3000000517 '{"state_dtype": "bfloat16"}'
control e5m2_router 3000000517 '{"router_dtype": "float8_e5m2"}'
grep -h "^{" chiprun_out/pr32c_mellum_*.log chiprun_out/pr32c_moon_*.log chiprun_out/pr32c_mistral_*.log chiprun_out/pr32c_smol_*.log | cut -c1-1800
grep -ih "error\|exhaust" chiprun_out/pr32c_*.log | head -5 | cut -c1-300
