#!/bin/bash
# PR 24 (one chip, seconds): record testdata/scoped.xplane.pb again after tools/record_scoped_trace.py changed
# (in call 1 XLA had fused the loss head and the optimizer into the layers' matmul fusions: both read 0).
# Chained before a proof call, in one chiprun:
#   chiprun --chips 1 --timeout 2400 -- bash -c "bash benchmarks/chipbench/tools/calls/pr24_record_scoped.sh; \
#       bash benchmarks/chipbench/tools/calls/pr24_proof_from_archive.sh smollm3-3b.sft-1k-full 36 2147489001"
# Afterwards, here: cp chiprun_out/scoped.xplane.pb chiprun_out/scoped.expected.json benchmarks/chipbench/testdata/
mkdir -p chiprun_out
python3 benchmarks/chipbench/tools/record_scoped_trace.py > chiprun_out/pr24_scoped.out 2> chiprun_out/pr24_scoped.err
echo "scoped rc=$?"; tail -n 45 chiprun_out/pr24_scoped.out
