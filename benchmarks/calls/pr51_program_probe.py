"""PR 51: a cell's start as ``run.py`` makes it, up to and with ``Program(...)`` (every import of the package; no
weights, no state), in the tree given: ``python benchmarks/calls/pr51_program_probe.py TREE CELL MODE``.

The pairs of ``pr51_cells.sh`` read the change's ``setup_s`` 10 to 16 s over the parent's, before the first program,
and ``pr51_import_probe.py`` found no mode of the finder that ``import google.cloud.logging`` ALONE feels (11.2 to 12.9 s
in every mode, where the same import inside a cell takes 24 to 40 s). MODE: ``asis`` (the tree as it is: the parent has no
finder, nor has the change as handed in), ``plain`` (the PR's first version, which installed one in
``enable_cache()``, with the finder taken out after it), ``finder`` (the finder of ``pr51_finder.py`` put in after
``enable_cache()``: how the first version ran, in a tree that has none). Prints seconds before ``Program``,
seconds of ``Program(...)``, the process's and the main thread's CPU over it, and where the tree has them the
``import`` spans' dearest modules. Under ``python -X importtime`` the interpreter's own import times go to stderr.
"""
import importlib
import json
import os
import sys
import time

T0 = time.perf_counter()
tree, cell_name, mode = os.path.abspath(sys.argv[1]), sys.argv[2], sys.argv[3]
sys.path.insert(0, os.path.join(tree, "benchmarks", "chipbench"))  # the script's directory, as for run.py
sys.path.insert(0, tree)
os.chdir(tree)
from benchmarks.chipbench import run as harness  # noqa: E402

cell = harness.load_cell(cell_name, False)
import jax  # noqa: E402

jax.devices()
harness.enable_cache()
from llm_fine_tune_distributed_tpu.observe import xla  # noqa: E402

hooked = [f for f in sys.meta_path if type(f).__name__ == "ImportSpans"]
if mode == "plain":
    for f in hooked:
        sys.meta_path.remove(f)
elif mode == "finder":
    sys.path.insert(0, os.path.join(tree, "benchmarks", "calls"))
    import pr51_finder

    hooked = [pr51_finder.install_import_spans(lambda: xla._RECORDER)]
runner = importlib.import_module(f"benchmarks.chipbench.kind_{cell['traffic']['kind']}")
before = time.perf_counter() - T0
t, c, m = time.perf_counter(), time.process_time(), time.thread_time()
program = runner.Program(cell["config"], cell["traffic"])
wall, cpu, main = time.perf_counter() - t, time.process_time() - c, time.thread_time() - m
out = {"tree": os.path.basename(tree), "mode": mode, "finder": bool(hooked) and mode != "plain", "before_s": round(before, 2),
       "program_s": round(wall, 3), "process_cpu_s": round(cpu, 3), "main_thread_cpu_s": round(main, 3),
       "modules": len(sys.modules), "path": sys.path[:3]}
section = xla.CompileLedger.setup() if hasattr(xla.CompileLedger, "setup") else {"spans": []}
spans = [s for s in section["spans"] if "module" in s]
out["dearest"] = [(s["module"], round((s["end_ns"] - s["start_ns"]) / 1e9, 2)) for s in
                  sorted(spans, key=lambda s: s["start_ns"] - s["end_ns"])[:12]
                  if s["module"] in ("llm_fine_tune_distributed_tpu.train", "orbax.checkpoint", "google.api_core",
                                     "google.cloud.appengine_logging_v1", "google.cloud.logging_v2.client")]
print(json.dumps(out), flush=True)
os._exit(0)
