"""The span of set-up that no call site can make: the time before the recorder.

``observe/xla.py`` keeps the process's ``SpanRecorder``; a span there is made
by the code that does the work (``annotate()``, ``importing()``) or reported
by JAX when a stage ends. **``process/before_recorder``** has no such place:
from the process's start to the import of the recorder's module nothing of
the program ran yet that could have read a clock, so the span is back-filled
when a recorder is made (``before_recorder()``), from the root span's start
and three facts taken at that import (``facts_at_import()``):
``jax_imported`` and ``backend_started`` (what the span holds besides the
interpreter's own start: ``import jax``, and where a backend is up,
``jax.devices()``), and ``cpu_s``, the process's CPU seconds so far (wall far
above CPU is waiting on the runtime or the disk, not Python).

Apart from ``observe/xla.py`` only because the machine's compile cache keys
move with that file's line numbers (ROADMAP.md, Design item 13) and these two
functions are needed at its top. It imports nothing but the standard library,
and ``xla.py`` imports it.

**No ``sys.meta_path`` finder.** PR 51 first timed every import with one (a
finder that asked the finders behind it and stood a stand-in for the loader
until the module's body began: 11 us an import on the sandbox, nothing
measurable in a process that only imports). In the benchmark's cells on the
chip it cost 10 to 16 s of ``setup_s``, 6 s with only the outermost imports
timed and every other answered None, nothing with the finder out (my chip
runs, PR 51, ``benchmarks/calls/pr51_cells.sh`` PART=why; PERF.md section 6):
all of it inside ``google.api_core``'s scan of the installed distributions,
which the finder takes no part in; the cause was not found. Import spans are
therefore made where the package imports (``xla.importing``).
"""

from __future__ import annotations

import sys
import time
from typing import Any, Dict

__all__ = ["before_recorder", "facts_at_import"]


def facts_at_import() -> Dict[str, Any]:
    """What ``process/before_recorder`` says of the moment it ends; the
    recorder's module calls it once, as it is imported. Asks JAX nothing that
    would start a backend. (``jax_imported`` is true in every process of this
    package, whose own ``__init__``s import jax on the way to the recorder:
    it says that the import lies inside the span, whoever made it.)"""
    bridge = sys.modules.get("jax._src.xla_bridge")
    started = getattr(bridge, "backends_are_initialized", None)
    return {"jax_imported": "jax" in sys.modules, "backend_started": bool(started()) if started is not None else False,
            "cpu_s": round(time.process_time(), 6)}


def before_recorder(span_id: int, start_ns: int, imported_ns: int, facts: Dict[str, Any]) -> Dict[str, Any]:
    """The back-filled span: directly under the root, on no one thread, over
    when it is made (so no ``TraceAnnotation``, as the ``jit/*`` spans are
    none). A root that starts after the import (a recorder given its own
    ``start_ns``) has an empty one."""
    return {"id": span_id, "name": "process/before_recorder", "start_ns": start_ns, "end_ns": max(start_ns, imported_ns),
            "parent": 0, "thread": None, **facts}
