#!/bin/bash
# PR 24, chip call 1 (one chip): the cache trap, and the first trace with scopes in it. Made BEFORE
# runtime/compile_cache.py set jax_compilation_cache_include_metadata_in_key, so run 2 is the trap itself.
# Before the call, here (the parent with this PR's benchmark files laid over it, as the driver does):
#   rm -rf _parent && mkdir _parent && git archive 8eb1b290 | tar -x -C _parent
#   cp BENCHMARK.json _parent/ && cp -r benchmarks/chipbench/. _parent/benchmarks/chipbench/
#   chiprun --chips 1 --timeout 1800 -- bash benchmarks/chipbench/tools/calls/pr24_trap_mistral.sh
# 0 record the small scoped trace for testdata/; 1 the parent warms ONE cache directory; 2 the change,
# traced, out of the same directory (expected: the parent's executable, no scope metric on the line);
# 3 the change, traced, with the metadata in the cache key (expected: cold compile, scopes read).
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
echo "the machine's JAX_COMPILATION_CACHE_DIR: '${JAX_COMPILATION_CACHE_DIR}'"
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache_pr24}
CELL=mistral-7b-d16.sft-2k-full
RUN="python3 benchmarks/chipbench/run.py --workload $CELL --seconds 30"
last() { grep '^set-up\|^reference' "$1"; tail -n 1 "$1" | python3 -c "
import json, sys
line = json.loads(sys.stdin.read())
print(json.dumps({k: line[k] for k in ('correct', 'metrics', 'device', 'compile_cache_dir')}))
print(json.dumps(line.get('breakdown', {}))[:1500])"; }

python3 benchmarks/chipbench/tools/record_scoped_trace.py > $OUT/pr24_scoped.out 2> $OUT/pr24_scoped.err
echo "0 scoped rc=$?"; tail -n 40 $OUT/pr24_scoped.out

(cd _parent && $RUN --seed 2147488001 --trace 0) > $OUT/pr24_c1_parent.out 2> $OUT/pr24_c1_parent.err
echo "1 parent rc=$?"; last $OUT/pr24_c1_parent.out

$RUN --seed 2147488002 --trace 1 > $OUT/pr24_c1_trapped.out 2> $OUT/pr24_c1_trapped.err
echo "2 change, same cache, key without metadata rc=$?"; last $OUT/pr24_c1_trapped.out
python3 benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 16 2 5 2> /dev/null | head -n 30

JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=1 $RUN --seed 2147488003 --trace 1 \
  > $OUT/pr24_c1_keyed.out 2> $OUT/pr24_c1_keyed.err
echo "3 change, same cache, metadata in the key rc=$?"; last $OUT/pr24_c1_keyed.out
python3 benchmarks/chipbench/tools/scope_table.py .chipbench_trace/$CELL 16 2 25 2> /dev/null > $OUT/pr24_c1_table.txt
head -n 60 $OUT/pr24_c1_table.txt
TRACE=$(ls .chipbench_trace/$CELL/plugins/profile/*/*.xplane.pb | tail -n 1)
ls -l $TRACE
gzip -c $TRACE > $OUT/pr24_c1_mistral.xplane.pb.gz; ls -l $OUT/pr24_c1_mistral.xplane.pb.gz
