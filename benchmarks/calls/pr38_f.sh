# rm -rf _checkout && mkdir _checkout && git archive $(git write-tree) | tar -x -C _checkout
# chiprun --timeout 420 -- bash benchmarks/calls/pr38_f.sh
# PR 38, last call (8 chip-minutes were left): the final tree, whose _first_call makes ONE fn.lower(...) call again and
# tells its stages apart afterwards, in the Mistral cell, traced, once. Its paths' line numbers are new to the cache (a
# kernel's serialized body carries the call stack's lines, observe/xla.py's among them), so the compile is cold; the
# trace and the lowering, which no cache holds, are what is read: the parent's were 2.7 s together (3.5 less a 0.8 s hit).
mkdir -p chiprun_out
C=mistral-7b-d16.sft-2k-full
ROOT=$PWD
(cd _checkout && timeout 380 python benchmarks/chipbench/run.py --workload $C --seed 3000001201 --seconds 30 --trace 1 > $ROOT/chiprun_out/pr38f_mistral_final.log 2>&1; echo "rc=$? at $SECONDS s")
grep -h "^set-up:" chiprun_out/pr38f_mistral_final.log | cut -c1-200
python benchmarks/chipbench/tools/setup_table.py _checkout/.chipbench_trace/$C 8 > chiprun_out/pr38f_setup_table_mistral_final.txt 2>&1
head -22 chiprun_out/pr38f_setup_table_mistral_final.txt | cut -c1-170
python - chiprun_out/pr38f_mistral_final.log <<'PY'
import json, sys
line = json.loads([l for l in open(sys.argv[1]) if l.startswith("{")][-1])
print({k: round(v["value"], 3) for k, v in line["metrics"].items() if k.startswith(("train_step_", "setup_", "recompiles"))}, line["correct"])
PY
