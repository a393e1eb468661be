#!/bin/bash
# PR 25, chip call 1 (one chip): the three flash kernels alone, forward, dq and dk/dv apart, at both training
# cells' shapes, after each step of the change.
#   _parent/  the parent commit (git archive da65cf00 | tar -x -C _parent), float32 operands
#   _step1/   the parent with step 1's ops/flash_attention.py laid over it: operands in the input dtype
#   .         the working tree: step 1 + masking by a tile's place
# (this PR's benchmarks/perf_ledger.py laid over both copies), each at the kernel's own tile and at FLASH_BLOCK
# 256 and 128; then the working tree's kernels at unequal (block_q, block_k).
#   chiprun --chips 1 --timeout 1500 -- bash benchmarks/calls/pr25_kernel_steps.sh
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
for tree in _parent _step1 .; do
  name=$(basename $(cd $tree && pwd)); [ $tree = . ] && name=change
  (cd $tree && python3 benchmarks/perf_ledger.py --flash-only 256 128) > $OUT/pr25_kernels_$name.jsonl 2> $OUT/pr25_kernels_$name.err
  echo "== $name rc=$?"; cat $OUT/pr25_kernels_$name.jsonl
done
python3 - > $OUT/pr25_kernels_tiles.jsonl 2> $OUT/pr25_kernels_tiles.err <<'PY'
import json, sys
sys.argv = ["benchmarks/perf_ledger.py"]
sys.path.insert(0, "benchmarks")
import numpy as np
import perf_ledger
from llm_fine_tune_distributed_tpu.ops import flash_attention as fa

def call(bq, bk):
    def attention(q, k, v):
        fn = fa._make_flash_fn(float(1 / np.sqrt(q.shape[-1])), bq, bk, q.shape[2] // k.shape[2], False)
        seg = np.ones(q.shape[:2], np.int32)
        return fn(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)), seg).transpose(0, 2, 1, 3)
    return attention

for bq, bk in [(512, 256), (512, 128), (256, 512), (256, 128), (128, 256), (1024, 256), (1024, 512)]:
    try:
        print(json.dumps({"block_q": bq, "block_k": bk, **perf_ledger.flash_kernels(attention=call(bq, bk))}), flush=True)
    except Exception as e:  # a tile Mosaic refuses is a result of the sweep, not its end
        print(json.dumps({"block_q": bq, "block_k": bk, "error": repr(e)[:300]}), flush=True)
PY
echo "== tiles rc=$?"; cat $OUT/pr25_kernels_tiles.jsonl; tail -n 5 $OUT/pr25_kernels_*.err
python3 chip_smoke.py --phase kernels > $OUT/pr25_smoke_kernels.out 2>&1; echo "== chip_smoke kernels rc=$?"; grep -i "flash\|KERNELS_OK\|paged" $OUT/pr25_smoke_kernels.out
