#!/bin/bash
# PR 43, first chip call: the by-channel sweeps at a tiny size under a watchdog, then priced alone at the Kimi cell's
# shapes beside the XLA form (benchmarks/gdn_kernels.py --only kda).
#   chiprun --timeout 1500 -- bash benchmarks/calls/pr43_first.sh
mkdir -p chiprun_out
python benchmarks/calls/pr43_tiny.py 2>&1 | tee chiprun_out/pr43a_tiny.log | tail -5
python benchmarks/gdn_kernels.py --only kda 2>&1 | tee chiprun_out/pr43a_kda.log | grep "^{"
