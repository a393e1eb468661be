#!/bin/bash
# PR 25, chip calls 2 to 6, 9 and 10 (one chip, 1.5 to 4 minutes each): the three kernels alone under variants of
# the working tree of that moment, each switched by a module-level experiment dict that the committed kernels no
# longer have, driven by a scratch script (_scratch_calls/variants.py, never committed) through
# benchmarks/perf_ledger.py's flash_kernels(). What each call varied, and what it read, is PERF.md section 6:
#   2  [rows, 1] column statistics and key-segment rows; row sums deferred; operands float32 against bfloat16
#   3  1, 2, 4 heads a forward program; forward with exp, reductions, masks, P V taken out one at a time
#   4  forward outputs and inputs at constant block indices (no DMA a program); segment test skipped statically
#   5  segment test skipped by a lax.cond in the tile loop; dk/dv on transposed scores; forward Q block 256, 128
#   6  segment test skipped by one branch a program; packed rows through the masked programs
#   9  the diagonal tile in strips of 128, 256, 512 at blocks of 256, 512, 1024
#   10 seq 2048 as one block of 2048; seq 4096 in blocks of 512 and 1024
# What of it runs on the committed tree is the tile sweep through the kernel's own knob:
#   chiprun --chips 1 --timeout 900 -- bash benchmarks/calls/pr25_kernel_variants.sh
mkdir -p chiprun_out
python3 benchmarks/perf_ledger.py --flash-only 1024 512 256 > chiprun_out/pr25_variants.jsonl 2> chiprun_out/pr25_variants.err
echo "rc=$?"; cat chiprun_out/pr25_variants.jsonl; tail -n 5 chiprun_out/pr25_variants.err
