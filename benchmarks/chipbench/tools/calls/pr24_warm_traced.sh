#!/bin/bash
# PR 24, last chip call (one chip): one more traced run of the change from _checkout/ (as the proof calls left it),
# with the step's executable already in the machine's cache: train_step_load_s as a LOAD, and the scope shares on
# another seed.   chiprun --chips 1 --timeout 900 -- bash benchmarks/chipbench/tools/calls/pr24_warm_traced.sh \
#                     smollm3-3b.sft-1k-full 2147489301
mkdir -p chiprun_out
(cd _checkout && python3 benchmarks/chipbench/run.py --workload $1 --seed $2 --seconds 30 --trace 1) \
  > chiprun_out/pr24_warm_traced.out 2> chiprun_out/pr24_warm_traced.err
echo "rc=$?"; grep '^set-up\|^reference' chiprun_out/pr24_warm_traced.out
tail -n 1 chiprun_out/pr24_warm_traced.out | python3 -c "
import json, sys
line = json.loads(sys.stdin.read())
print(json.dumps({k: line[k] for k in ('correct', 'attempted', 'failed', 'metrics', 'device')}))"
