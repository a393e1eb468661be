# chiprun --timeout 900 -- bash benchmarks/calls/pr39_kernels.sh
# PR 39: the new kernels' first run on the chip (tiny, under a watchdog), then the passes and the whole mixer at the cell's
# shapes, XLA form against kernels (the builder's tool).
mkdir -p chiprun_out
python benchmarks/calls/pr39_first.py 2>&1 | grep -v -i "warn" | tee chiprun_out/pr39k_first.log | tail -8
python benchmarks/gdn_kernels.py --only mixer 2>&1 | grep "^{\|Error\|Traceback" | tee chiprun_out/pr39k_mixer.jsonl | cut -c1-1500
echo "ended at $SECONDS s"
