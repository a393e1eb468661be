#!/bin/bash
# PR 24, the proof from the committed files alone, one cell a call (one chip):
#   git add -A && rm -rf _checkout _parent && mkdir _checkout _parent
#   git archive $(git write-tree) | tar -x -C _checkout
#   git archive 8eb1b290 | tar -x -C _parent      # the parent, with this PR's benchmark files laid over it,
#   cp BENCHMARK.json _parent/ && cp -r benchmarks/chipbench/. _parent/benchmarks/chipbench/   # as the driver does
#   chiprun --chips 1 --timeout 2400 -- bash benchmarks/chipbench/tools/calls/pr24_proof_from_archive.sh \
#       smollm3-3b.sft-1k-full 36 2147489001
#   chiprun --chips 1 --timeout 2400 -- bash benchmarks/chipbench/tools/calls/pr24_proof_from_archive.sh \
#       mistral-7b-d16.sft-2k-full 16 2147489101 parent-traced
# Both checkouts share ONE compile cache directory. 1 the parent warms it (cold); 2 the change, traced, after
# it (all eight new metrics, scopes read although the parent's step sits in the same cache); 3 the change and
# 4 the parent, untraced and warm, on one seed (train_tokens_per_s, setup_s); with "parent-traced" also 5 the
# parent traced with this PR's readers laid over it (they find no scope, return nothing and do not raise).
CELL=$1; LAYERS=$2; SEED=$3; EXTRA=$4
mkdir -p chiprun_out
OUT=$PWD/chiprun_out; TOOLS=$PWD/benchmarks/chipbench/tools; TAG=pr24_proof_${CELL%%.*}
echo "the machine's JAX and XLA settings:"; env | grep -i '^JAX\|^XLA\|^LIBTPU'
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache_pr24}
RUN="python3 benchmarks/chipbench/run.py --workload $CELL --seconds 30"
last() { grep '^set-up\|^reference' "$1"; tail -n 1 "$1" | python3 -c "
import json, sys
line = json.loads(sys.stdin.read())
print(json.dumps({k: line[k] for k in ('correct', 'attempted', 'failed', 'metrics', 'device')}))"; }
one() {  # <directory> <name> <seed> <trace>
  (cd $1 && $RUN --seed $3 --trace $4) > $OUT/${TAG}_$2.out 2> $OUT/${TAG}_$2.err
  echo "== $2 (in $1, seed $3, trace $4) rc=$?"; last $OUT/${TAG}_$2.out
}
one _parent 1_parent_cold $SEED 0
one _checkout 2_change_traced $((SEED + 1)) 1
python3 $TOOLS/scope_table.py _checkout/.chipbench_trace/$CELL $LAYERS 2 25 2> /dev/null > $OUT/${TAG}_table.txt
head -n 45 $OUT/${TAG}_table.txt
one _checkout 3_change_warm $((SEED + 2)) 0
one _parent 4_parent_warm $((SEED + 2)) 0
if [ "$EXTRA" = parent-traced ]; then one _parent 5_parent_traced $((SEED + 3)) 1; fi
