#!/bin/bash
# PR 25, the proof from the committed files alone, one cell a call (one chip):
#   git add -A && rm -rf _checkout _parent && mkdir _checkout _parent
#   git archive $(git write-tree) | tar -x -C _checkout
#   git archive da65cf00 | tar -x -C _parent      # the parent; this PR adds nothing under benchmarks/chipbench
#   chiprun --chips 1 --timeout 2700 -- bash benchmarks/calls/pr25_proof_from_archive.sh \
#       smollm3-3b.sft-1k-full 36 2147493001
#   chiprun --chips 1 --timeout 2400 -- bash benchmarks/calls/pr25_proof_from_archive.sh \
#       mistral-7b-d16.sft-2k-full 16 2147493101
# Both checkouts share ONE compile cache directory (all but the step's program is the same on both sides).
# 1 the parent warms it (cold); 2 the change, traced (flash_fwd_roofline_pct, flash_time_pct.train, the scopes, and
# tools/scope_table.py on its trace);
# 3 the change and 4 the parent, untraced and warm, on one seed (train_tokens_per_s, setup_s); 5 and 6 the change
# on two more seeds (correct). Every run prints each number of the check beside its limit.
CELL=$1; LAYERS=$2; SEED=$3
mkdir -p chiprun_out
OUT=$PWD/chiprun_out; TAG=pr25_proof_${CELL%%.*}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache_pr25}
RUN="python3 benchmarks/chipbench/run.py --workload $CELL --seconds 30"
last() { grep '^set-up\|^reference\|^check ' "$1"; tail -n 1 "$1" | python3 -c "
import json, sys
line = json.loads(sys.stdin.read())
print(json.dumps({k: line[k] for k in ('correct', 'attempted', 'failed', 'metrics', 'device')}))"; }
one() {  # <directory> <name> <seed> <trace>
  (cd $1 && $RUN --seed $3 --trace $4) > $OUT/${TAG}_$2.out 2> $OUT/${TAG}_$2.err
  echo "== $2 (in $1, seed $3, trace $4) rc=$?"; last $OUT/${TAG}_$2.out
}
one _parent 1_parent_cold $SEED 0
one _checkout 2_change_traced $((SEED + 1)) 1
# the traced step by scope, and its copy operations by scope (PERF.md section 7 names them)
python3 benchmarks/chipbench/tools/scope_table.py _checkout/.chipbench_trace/$CELL $LAYERS 2 60 2> /dev/null > $OUT/${TAG}_table.txt
head -n 22 $OUT/${TAG}_table.txt; grep -i 'copy' $OUT/${TAG}_table.txt | head -n 12
one _checkout 3_change_warm $((SEED + 2)) 0
one _parent 4_parent_warm $((SEED + 2)) 0
one _checkout 5_change_seed $((SEED + 3)) 0
one _checkout 6_change_seed $((SEED + 4)) 0
