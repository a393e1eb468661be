# chiprun --timeout 600 -- bash benchmarks/chipbench/tools/calls/pr30r_half_batch.sh
# PR 30, review round: what loss_abs_gap is held against, read once at the cell's load: half of each microbatch's rows
# left out of the program's loss (tools/fault.py), the reference training on all of them. Not correct, by the loss.
mkdir -p chiprun_out
C=mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams
python benchmarks/chipbench/tools/fault.py --fault half_batch --workload $C --seed ${SEED:-3000000701} --seconds 5 --trace 0 > chiprun_out/pr30r_half_batch.log 2>&1; echo "rc=$? half batch"
grep -h "^check\|^window" chiprun_out/pr30r_half_batch.log | cut -c1-220
grep -h "^{" chiprun_out/pr30r_half_batch.log | cut -c1-200
grep -ih "error\|exhaust" chiprun_out/pr30r_half_batch.log | head -5 | cut -c1-300
