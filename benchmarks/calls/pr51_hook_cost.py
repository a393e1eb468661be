"""PR 51: what one import costs under the import finder of the PR's first version (``benchmarks/calls/pr51_finder.py``:
taken out of the program, see there), on whatever runs this (a CPU timing of host code).

``JAX_PLATFORMS=cpu python benchmarks/calls/pr51_hook_cost.py [modules]``: writes ``modules`` (default 3000) empty
modules into a temporary directory and imports each once, three times over under fresh names: with the finder out of
``sys.meta_path`` (the import system alone), with the finder in and set-up lasting (every import timed, counted and,
being brief, not kept), and with the finder in under a frozen recorder (what every import after ``mark_warm()`` pays).
Prints microseconds an import for each and the differences. ``imports_seen`` of a cell (``setup_spans.json``,
``tools/import_table.py``) times the second difference is what the finder adds to that cell's ``setup_s``.
"""
import importlib
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import pr51_finder as startup  # noqa: E402
from llm_fine_tune_distributed_tpu.observe import xla  # noqa: E402


def import_all(directory, prefix, count):
    for i in range(count):
        with open(os.path.join(directory, f"{prefix}_{i}.py"), "w") as f:
            f.write("")
    importlib.invalidate_caches()
    os.listdir(directory)
    t0 = time.perf_counter()
    for i in range(count):
        importlib.import_module(f"{prefix}_{i}")
    return (time.perf_counter() - t0) / count * 1e6


def main(count):
    finder = startup.install_import_spans(lambda: xla._RECORDER)
    with tempfile.TemporaryDirectory() as directory:
        sys.path.insert(0, directory)
        out = {}
        for round_ in range(3):  # the rounds interleaved: the page cache and the directory's growth touch all three alike
            sys.meta_path.remove(finder)
            out.setdefault("alone_us", []).append(import_all(directory, f"alone{round_}", count))
            sys.meta_path.insert(0, finder)
            xla._RECORDER = xla.SpanRecorder()
            out.setdefault("timed_us", []).append(import_all(directory, f"timed{round_}", count))
            seen = xla._RECORDER.section()["counters"]["imports_seen"]
            xla._RECORDER.freeze()
            out.setdefault("frozen_us", []).append(import_all(directory, f"frozen{round_}", count))
    best = {k: min(v) for k, v in out.items()}
    print(json.dumps({"modules": count, "imports_seen_last_round": seen, "us_an_import": out,
                      "timed_less_alone_us": round(best["timed_us"] - best["alone_us"], 2),
                      "frozen_less_alone_us": round(best["frozen_us"] - best["alone_us"], 2)}))


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3000)
