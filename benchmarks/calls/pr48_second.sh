#!/bin/bash
# PR 48, the second call of the final tree: the control cell, then the Kimi cell again.   chiprun --timeout 3000 -- bash benchmarks/calls/pr48_second.sh
PART=qwen bash benchmarks/calls/pr48_final.sh
PART=again bash benchmarks/calls/pr48_final.sh
