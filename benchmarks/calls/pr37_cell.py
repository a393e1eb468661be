"""``benchmarks/chipbench/run.py`` of the tree this is run FROM, as the driver runs it, with four things printed
besides (PR 37: where the step's warm load goes INSIDE the cell's process): the seconds each ``Lowered.compile`` of
a second or more took, what the compile ledger records for each program, and the seconds and counts of Python's garbage collector up to the first step, at each such
compile's start and end, and over the whole run. Nothing the run measures is touched: ``train_step_load_s`` is still
the wall time of ``lower().compile()``, and ``lower()``'s share is that less the step's ``compile`` printed here.

    cd _parent && python ../benchmarks/calls/pr37_cell.py --workload <cell> --seed <n> --seconds 30 --trace 0
"""
import gc
import os
import runpy
import sys
import time

sys.path.insert(0, os.getcwd())
spent = {"s": 0.0, "n": [0, 0, 0], "t0": 0.0}


def _collected(phase, info):
    if phase == "start":
        spent["t0"] = time.perf_counter()
    else:
        spent["s"] += time.perf_counter() - spent["t0"]
        spent["n"][info["generation"]] += 1


def said(what):
    print(f"pr37: {what}: gc so far {spent['s']:.2f} s in {spent['n']} collections, {len(gc.get_objects())} objects tracked", flush=True)


gc.callbacks.append(_collected)

import jax._src.stages as stages

from benchmarks.chipbench import kind_sft
from llm_fine_tune_distributed_tpu.observe import xla

_compile, _readings, _record = stages.Lowered.compile, kind_sft.program_readings, xla.CompileLedger.record


def compile(self, *args, **kwargs):
    t0, g0 = time.perf_counter(), spent["s"]
    out = _compile(self, *args, **kwargs)
    if time.perf_counter() - t0 >= 1.0:
        said(f"Lowered.compile took {time.perf_counter() - t0:.2f} s ({spent['s'] - g0:.2f} of them gc), lowering before it done")
    return out


def program_readings(*args, **kwargs):
    said("before the first step's lower()")
    return _readings(*args, **kwargs)


def record(self, program, shapes, compile_s, *args, **kwargs):
    print(f"pr37: the ledger's {program}: lower().compile() {compile_s:.2f} s", flush=True)
    return _record(self, program, shapes, compile_s, *args, **kwargs)


stages.Lowered.compile, kind_sft.program_readings, xla.CompileLedger.record = compile, program_readings, record
sys.argv = ["benchmarks/chipbench/run.py"] + sys.argv[1:]
try:
    runpy.run_path("benchmarks/chipbench/run.py", run_name="__main__")
finally:
    said("at the end")
