"""PR 51: why an import is slower under the import finder (the PR's first version: ``pr51_finder.py`` beside this
file) on the chip's machine, one process a mode.

``python benchmarks/calls/pr51_import_probe.py MODE [backend|nobackend]`` starts as ``run.py`` does (``import jax``,
``jax.devices()`` unless ``nobackend``, ``enable_compile_cache()``), then imports ``google.cloud.logging`` (what
``orbax.checkpoint`` pulls in behind ``train/__init__.py``: ``google.api_core``'s ``packages_distributions()`` scan
is 26 to 34 s of the cell's set-up there, half a second on the sandbox) and prints wall, process CPU, the main
thread's CPU, garbage collections and threads. MODE: ``plain`` (the finder taken out of ``sys.meta_path``),
``hooked`` (as every entry point runs), ``inert`` (the finder in place and answering None: its presence alone),
``untimed`` (the stand-in loader in place, the body run with no clock, record or counter), ``nogc`` (hooked, the
collector off during the import), ``noproc`` (hooked, without the process's CPU clock).
"""
import gc
import json
import sys
import threading
import time

mode = sys.argv[1]
backend = (sys.argv[2] if len(sys.argv) > 2 else "backend") == "backend"
t_start = time.perf_counter()
import jax  # noqa: E402

if backend:
    jax.devices()
import os  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from llm_fine_tune_distributed_tpu.runtime.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import pr51_finder as startup  # noqa: E402
from llm_fine_tune_distributed_tpu.observe import xla  # noqa: E402

finder = startup.install_import_spans(lambda: xla._RECORDER)
if mode == "plain":
    sys.meta_path.remove(finder)
elif mode == "inert":
    startup.ImportSpans.find_spec = lambda self, name, path=None, target=None: None
elif mode == "untimed":
    startup.ImportSpans.timed = lambda self, name, exec_module, module: exec_module(module)
elif mode == "nogc":
    gc.disable()
elif mode == "noproc":
    class _NoProcessClock:  # every clock but the process's CPU clock
        time_ns = staticmethod(time.time_ns)
        process_time = staticmethod(lambda: 0.0)
    startup.time = _NoProcessClock
else:
    assert mode == "hooked", mode


def threads():
    with open("/proc/self/status") as f:
        return int([ln for ln in f if ln.startswith("Threads:")][0].split()[1])


before_s = time.perf_counter() - t_start
gc_0 = [g["collections"] for g in gc.get_stats()]
t, c, m = time.perf_counter(), time.process_time(), time.thread_time()
import google.cloud.logging  # noqa: E402,F401

wall, cpu, main = time.perf_counter() - t, time.process_time() - c, time.thread_time() - m
t2 = time.perf_counter()
import importlib.metadata  # noqa: E402

importlib.metadata.packages_distributions()
again = time.perf_counter() - t2
counters = xla._RECORDER.section()["counters"]
print(json.dumps({"mode": mode, "backend": backend, "before_s": round(before_s, 2), "import_wall_s": round(wall, 3),
                  "process_cpu_s": round(cpu, 3), "main_thread_cpu_s": round(main, 3),
                  "scan_again_s": round(again, 3), "gc": [g["collections"] - a for g, a in zip(gc.get_stats(), gc_0)],
                  "threads": threads(), "python_threads": threading.active_count(),
                  "imports_seen": counters.get("imports_seen", 0), "meta_path": [type(f).__name__ if not isinstance(f, type) else f.__name__ for f in sys.meta_path]}))
sys.stdout.flush()
os._exit(0)
