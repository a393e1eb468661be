# rm -rf _step1 && mkdir -p _step1 && git archive $(git write-tree) | tar -x -C _step1
# chiprun --timeout 900 -- bash benchmarks/chipbench/tools/calls/pr32_control_e5m2.sh
# PR 32: the new cell's control as the mix now states it (the router in float8_e5m2, the rule's carried state in
# bfloat16), from the committed files: not correct, with every loss finite.
mkdir -p chiprun_out
C=qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams
cd _step1
python benchmarks/chipbench/tools/control.py --workload $C --seed ${SEED:-3000000523} --seconds 5 --trace 0 > ../chiprun_out/pr32e_control.log 2>&1; echo "rc=$? control"
cd ..
grep -h "^set-up\|^reference\|^check" chiprun_out/pr32e_control.log | cut -c1-220; grep -h "^{" chiprun_out/pr32e_control.log | cut -c1-300
