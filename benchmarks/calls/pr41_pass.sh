# chiprun --timeout 900 -- bash benchmarks/calls/pr41_pass.sh
# PR 41: the IN pass's first run on the chip (every shape tiny, under a watchdog), then the pass against the XLA form at
# the cells' shapes, and by what a builder can turn at the two claimed shapes and SmolLM3's.
mkdir -p chiprun_out
python benchmarks/calls/pr41_pass.py 2>&1 | grep "^{\|Error\|Traceback\|File" | tee chiprun_out/pr41_pass.jsonl | cut -c1-600
python benchmarks/calls/pr41_pass.py --variants trinity mellum smollm3 2>&1 | grep "^{\|Error\|Traceback\|File" | tee chiprun_out/pr41_pass_variants.jsonl | cut -c1-600
echo "ended at $SECONDS s"
