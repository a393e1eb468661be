"""Which part of ``pr37_cell.py`` takes PR 36's eleven seconds of set-up away (PERF.md, PR 37: its tree reads them through
``run.py`` itself and not through that wrapper). ``run.py`` of the tree this is run FROM under ``runpy`` with, by option,
one of the wrapper's three differences: ``--imports`` (jax, ``kind_sft`` and ``observe/xla`` imported before ``main``),
``--patch`` (``Lowered.compile`` replaced by a function that calls it), ``--gc`` (a ``gc`` callback). Read the run's
``first 2 steps with their readings`` and ``setup_s``.

    cd _step1 && python ../benchmarks/calls/pr37_bisect.py --patch -- --workload <cell> --seed <n> --seconds 30 --trace 0
"""
import gc
import os
import runpy
import sys

sys.path.insert(0, os.getcwd())
options, rest = sys.argv[1:sys.argv.index("--")], sys.argv[sys.argv.index("--") + 1:]
if "--gc" in options:
    gc.callbacks.append(lambda phase, info: None)
if "--imports" in options:
    import jax._src.stages  # noqa: F401

    from benchmarks.chipbench import kind_sft  # noqa: F401
    from llm_fine_tune_distributed_tpu.observe import xla  # noqa: F401
if "--patch" in options:
    import jax._src.stages as stages

    _compile = stages.Lowered.compile
    stages.Lowered.compile = lambda self, *args, **kwargs: _compile(self, *args, **kwargs)
print(f"pr37_bisect: {' '.join(options) or 'runpy alone'}", flush=True)
sys.argv = ["benchmarks/chipbench/run.py"] + rest
runpy.run_path("benchmarks/chipbench/run.py", run_name="__main__")
