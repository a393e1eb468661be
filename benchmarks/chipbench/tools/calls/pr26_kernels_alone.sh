# chiprun --timeout 2400 -- bash benchmarks/chipbench/tools/calls/pr26_kernels_alone.sh
# PR 26: the grouped products (ragged_dot, megablox gmm by tiling) and the flash kernels at 192/128 alone
# (PERF.md section 6, PR 26, findings 2 and 3). The flash layouts were timed when the wrapper still padded.
python benchmarks/moe_kernels.py --iters 20 2>&1 | grep "^{"
