"""What a run's set-up spent importing, by module, for a first look by hand and for PERF.md section 5:
``python benchmarks/chipbench/tools/import_table.py <setup_spans.json or a directory run.py traced into> [rows [depth]]``.

A traced run writes the program's set-up section beside its device trace (``readers/setup.py``;
``tools/setup_table.py`` prints it by phase, ``import`` and ``process/before_recorder`` among the phases). The
program makes a span of each import it wraps while set-up lasts (``observe/xla.importing``: ``train/__init__.py``,
``train/checkpoints.py``, ``parallel/optimizer.py``): ``import`` under no other import on its thread,
``import/nested`` below one, each with ``module`` and ``cpu_s``. This prints, from that file alone:

- the header: ``process/before_recorder`` (the process's start to the import of the recorder's module: no import
  before it is timed) with its ``cpu_s`` and what had happened by then; ``setup_import_s`` (the spans named
  ``import``, which do not overlap on a thread and add up) over ``setup_s``;
- the ``rows`` (default 15) dearest spans named ``import``, dearest first: seconds, self seconds (less the
  ``import/nested`` spans directly below: the module's own body and every import it made unwrapped), ``cpu_s``
  (the PROCESS's CPU seconds over the span: wall far above it is waiting on the disk or a lock, above wall other
  threads were at work), the module; and under each, its ``import/nested`` spans the same way, indented, down to
  ``depth`` levels below it (default 4; a row's self seconds hold whatever lies deeper);
- the modules of one package added up (``setup_import_program_s`` is the first line: the program's own package).

Reads with the readers' own functions, so what it prints is what the metrics add up.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)

from benchmarks.chipbench.readers import setup, startup  # noqa: E402

PROGRAM = "llm_fine_tune_distributed_tpu"


def table(section: dict, rows: int = 15, depth: int = 4) -> str:
    spans = section["spans"]
    root = spans[0]
    setup_s = section.get("setup_s") or (root["end_ns"] - root["start_ns"]) / 1e9
    clipped = {span["id"]: (end - start) / 1e9 for span, start, end in setup.within_setup(section)}
    imports = [s for s in spans[1:] if s["name"] in ("import", "import/nested") and s["id"] in clipped]
    below = {}
    for s in imports:
        below.setdefault(s["parent"], []).append(s)
    top = sorted((s for s in imports if s["name"] == "import"), key=lambda s: -clipped[s["id"]])
    out = []
    before = [s for s in spans[1:] if s["name"] == startup.BEFORE_RECORDER]
    if before:
        b = before[0]
        out.append(f"process/before_recorder {clipped.get(b['id'], 0.0):.3f} s (cpu {b.get('cpu_s', 0.0):.3f} s; jax imported "
                   f"{b.get('jax_imported')}, a backend started {b.get('backend_started')}): no import before it is timed")
    total = sum(clipped[s["id"]] for s in top)
    out += [
        f"setup_s {setup_s:.3f} s; {len(top)} spans named import, {total:.3f} s ({100.0 * total / setup_s:.2f}% of setup_s), "
        f"{len(imports) - len(top)} nested below them",
        "", f"{'seconds':>9s} {'self':>9s} {'cpu_s':>9s}  module",
    ]

    def walk(span, level):
        children = sorted(below.get(span["id"], []), key=lambda s: -clipped[s["id"]]) if level < depth else []
        own = clipped[span["id"]] - sum(clipped[ch["id"]] for ch in children)
        error = f"  ({span['error']})" if "error" in span else ""
        out.append(f"{clipped[span['id']]:9.3f} {own:9.3f} {span.get('cpu_s', 0.0):9.3f}  {'  ' * level}{span['module']}{error}")
        for child in children:
            walk(child, level + 1)

    for span in top[:rows]:
        walk(span, 0)
    if len(top) > rows:
        out.append(f"{sum(clipped[s['id']] for s in top[rows:]):9.3f} {'':9s} {'':9s}  {len(top) - rows} more spans named import")

    packages = {}
    for s in top:
        packages[s["module"].split(".")[0]] = packages.get(s["module"].split(".")[0], 0.0) + clipped[s["id"]]
    ordered = sorted(packages.items(), key=lambda kv: (kv[0] != PROGRAM, -kv[1]))
    first = "; the first line is setup_import_program_s" if PROGRAM in packages else ""
    out += ["", f"spans named import by package (seconds{first}):"]
    out += [f"{secs:9.3f}  {name}" for name, secs in ordered[:rows]]
    return "\n".join(out)


def main(argv):
    path = argv[0] if argv[0].endswith(".json") else os.path.join(argv[0], setup.DUMP)
    with open(path) as f:
        section = json.load(f)
    print(f"{path} ({os.path.getsize(path)} bytes)")
    print(table(section, *map(int, argv[1:3])))


if __name__ == "__main__":
    main(sys.argv[1:])
