"""PR 38: what stands before the first span of a run. The set-up table of the Qwen3-Next cell (call A) has no
span in its first 40 s, of which ``jax.devices()`` and the imports explain a dozen: this times the harness's
own steps up to ``Program(...)`` one by one, as ``run.py`` takes them, and profiles ``Program(...)`` itself
(``cProfile``, the twelve dearest by cumulative time). Seconds of the host, no device work.

    python benchmarks/calls/pr38_program_init.py <cell>
"""
import cProfile
import importlib
import io
import os
import pstats
import sys
import time

sys.path.insert(0, os.getcwd())
marks = [("start", time.perf_counter())]


def mark(name):
    marks.append((name, time.perf_counter()))


import jax  # noqa: E402

mark("import jax")
jax.devices()
mark("jax.devices()")
from benchmarks.chipbench import run  # noqa: E402

cell = run.load_cell(sys.argv[1], jax.devices()[0].platform == "cpu")
run.enable_cache()
mark("load_cell, enable_cache")
kind = importlib.import_module(f"benchmarks.chipbench.kind_{cell['traffic']['kind']}")
mark("import the kind")
profile = cProfile.Profile()
program = profile.runcall(kind.Program, cell["config"], cell["traffic"])
mark("Program(...) under cProfile")
for (_, a), (name, b) in zip(marks, marks[1:]):
    print(f"{name:32s} {b - a:8.2f} s", flush=True)
out = io.StringIO()
pstats.Stats(profile, stream=out).sort_stats("cumulative").print_stats(12)
print("\n".join(line[:200] for line in out.getvalue().splitlines() if line.strip())[:6000])
