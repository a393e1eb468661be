# chiprun --timeout 600 -- bash benchmarks/calls/pr38_e.sh
# PR 38, fifth call: fn.lower(...) against fn.trace(...) then .lower(), the SmolLM3 step over abstract state (pr38_split_cost.py)
mkdir -p chiprun_out
for args in "whole" "split" "split --profile" "whole --profile"; do
  timeout 200 python benchmarks/calls/pr38_split_cost.py $args 2>&1 | grep -v "Warn\|warn" | tail -22 | cut -c1-190 | tee -a chiprun_out/pr38e_split_cost.txt
done
