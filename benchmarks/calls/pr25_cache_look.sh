#!/bin/bash
# PR 25 (one chip): why the change's warm runs spend 90 s on their first two steps where the parent's spend 45 s.
# Two untraced runs of _checkout/ with JAX's compile-cache logging on, the cache directory listed before, between and
# after (entry sizes, what was written, what was evicted), and the cache's hit and miss lines of each run.
#   chiprun --chips 1 --timeout 1500 -- bash benchmarks/calls/pr25_cache_look.sh smollm3-3b.sft-1k-full 2147494001
CELL=$1; SEED=$2
mkdir -p chiprun_out
env | grep -i '^JAX\|^XLA\|^LIBTPU'
look() { echo "-- cache $1: $(du -sm $JAX_COMPILATION_CACHE_DIR | cut -f1) MiB, $(ls $JAX_COMPILATION_CACHE_DIR | wc -l) files"; ls -lS $JAX_COMPILATION_CACHE_DIR | head -n 12 | awk '{print $5, $9}' | cut -c1-120; }
look before
for n in 1 2; do
  (cd _checkout && JAX_DEBUG_LOG_MODULES=jax._src.compiler,jax._src.compilation_cache,jax._src.lru_cache \
     python3 benchmarks/chipbench/run.py --workload $CELL --seed $((SEED + n)) --seconds 30 --trace 0) \
    > chiprun_out/pr25_cache_$n.out 2> chiprun_out/pr25_cache_$n.err
  echo "== run $n rc=$?"; grep '^set-up' chiprun_out/pr25_cache_$n.out
  grep -i 'cache hit\|cache miss\|not writing\|writing\|evict\|too large\|PERSISTENT' chiprun_out/pr25_cache_$n.err | cut -c1-220 | head -n 30
  tail -n 1 chiprun_out/pr25_cache_$n.out | python3 -c "
import json, sys
line = json.loads(sys.stdin.read()); print(line['correct'], {k: v['value'] for k, v in line['metrics'].items()})"
  look "after run $n"
done
