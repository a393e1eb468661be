"""Describe the newest trace of a cell for a first look by hand: planes, lines
and the heaviest event names, written to chiprun_out/. Usage:
``python benchmarks/chipbench/tools/first_look.py <cell>``."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # reading a file needs no chip

from benchmarks.chipbench import trace  # noqa: E402

cell = sys.argv[1]
path = trace.find_xplane(os.path.join(ROOT, ".chipbench_trace", cell))
out = os.path.join(ROOT, "chiprun_out")
os.makedirs(out, exist_ok=True)
with open(os.path.join(out, f"trace_{cell}.json"), "w") as f:
    json.dump(trace.describe(path), f, indent=1)
print("described", path, os.path.getsize(path), "bytes")
