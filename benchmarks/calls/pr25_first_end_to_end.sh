#!/bin/bash
# PR 25, chip calls 7, 8 and 11 (one chip): the working tree's kernels alone (forward, dq, dk/dv at both cells'
# shapes, at the kernel's own tile and at the FLASH_BLOCK values given), chip_smoke.py's kernel comparisons, then a
# first end-to-end look at a cell: runs of the working tree with the traces given (the parent's side is the ledger's
# PR 24 line; the pairs on one chip come with pr25_proof_from_archive.sh).
#   chiprun --chips 1 --timeout 2400 -- bash benchmarks/calls/pr25_first_end_to_end.sh smollm3-3b.sft-1k-full 2147492001 "1 0" "256"
CELL=$1; SEED=$2; TRACES=${3:-"1 0"}; BLOCKS=$4
mkdir -p chiprun_out
python3 benchmarks/perf_ledger.py --flash-only $BLOCKS > chiprun_out/pr25_kernels_final.jsonl 2> chiprun_out/pr25_kernels_final.err
echo "== kernels rc=$?"; cat chiprun_out/pr25_kernels_final.jsonl
python3 chip_smoke.py --phase kernels > chiprun_out/pr25_smoke_kernels.out 2>&1; echo "== chip_smoke kernels rc=$?"
grep -i "flash\|KERNELS_OK\|paged" chiprun_out/pr25_smoke_kernels.out
for trace in $TRACES; do
  python3 benchmarks/chipbench/run.py --workload $CELL --seed $((SEED + trace)) --seconds 30 --trace $trace \
    > chiprun_out/pr25_first_$trace.out 2> chiprun_out/pr25_first_$trace.err
  echo "== trace $trace rc=$?"; grep '^set-up\|^reference\|^check ' chiprun_out/pr25_first_$trace.out
  tail -n 1 chiprun_out/pr25_first_$trace.out | python3 -c "
import json, sys
line = json.loads(sys.stdin.read())
print(json.dumps({k: line[k] for k in ('correct', 'attempted', 'failed', 'metrics', 'device')}))"
done
