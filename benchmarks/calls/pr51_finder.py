"""PR 51's first version timed EVERY import with this ``sys.meta_path`` finder (then ``observe/startup.py``); it was
taken out of the program because in the benchmark's cells on the chip it cost 6 to 16 s of ``setup_s`` (PERF.md
section 6, PR 51; cause not found: section 7). Kept here, out of the package, so that the calls that measured it
(``pr51_hook_cost.py``, ``pr51_import_probe.py``, ``pr51_program_probe.py``) still run and the next look at the cause
starts from the code that showed it: ``install_import_spans(lambda: xla._RECORDER)`` puts it first in
``sys.meta_path``; it opens and closes records of the recorder as ``add_jit_stage`` does.

A module whose body, with what it pulls in, takes ``BRIEF_IMPORT_NS`` or more is a span ``import`` (under no other
import on its thread) or ``import/nested`` with ``module`` and ``cpu_s``; a briefer one is counted (``imports_seen``,
``imports_brief``, ``import_brief_seconds``). Under a frozen recorder the finder answers None.
"""
import sys
import threading
import time
from typing import Any, Callable

BRIEF_IMPORT_NS = 50_000_000


class _TimedLoader:
    """Stands in for the loader of a spec that ``ImportSpans`` handed out,
    until the module's body is about to run: ``exec_module`` first puts the
    real loader back on the spec and on the module (``__spec__.loader`` and
    ``__loader__`` are then what the import system would have set, before any
    line of the module can look), then runs the real ``exec_module`` under the
    span. Every other attribute is the real loader's."""

    __slots__ = ("_loader", "_spec", "_spans")

    def __init__(self, loader: Any, spec: Any, spans: "ImportSpans"):
        self._loader, self._spec, self._spans = loader, spec, spans

    def __getattr__(self, name: str) -> Any:  # get_code, get_filename, get_resource_reader, is_package ...
        return getattr(self._loader, name)

    def create_module(self, spec: Any) -> Any:
        return self._loader.create_module(spec)

    def exec_module(self, module: Any) -> None:
        loader = self._spec.loader = self._loader
        try:
            module.__loader__ = loader
        except AttributeError:
            pass  # a module object of the loader's own making that takes no attributes
        self._spans.timed(self._spec.name, loader.exec_module, module)


class ImportSpans:
    """The ``sys.meta_path`` finder that turns imports into spans (see the
    module's docstring). ``recorder`` is called at every import for the
    process's recorder of that moment, as the compile listeners look it up
    when an event comes: the tests put a fresh one there."""

    def __init__(self, recorder: Callable[[], Any]):
        self._recorder = recorder
        self._local = threading.local()  # .depth: the import spans open on this thread

    def find_spec(self, name: str, path: Any = None, target: Any = None) -> Any:
        if self._recorder().frozen:
            return None
        finders = sys.meta_path
        try:
            behind = finders[finders.index(self) + 1:]
        except ValueError:  # taken out of the path, and still asked by a caller that held the list
            return None
        for finder in behind:
            find_spec = getattr(finder, "find_spec", None)
            spec = None if find_spec is None else find_spec(name, path, target)
            if spec is not None:
                break
        else:
            return None
        loader = spec.loader
        # a namespace package of old has no loader and a legacy one no exec_module: as they are
        if hasattr(loader, "exec_module") and hasattr(loader, "create_module"):
            spec.loader = _TimedLoader(loader, spec, self)
        return spec

    def timed(self, module_name: str, exec_module: Callable[[Any], None], module: Any) -> None:
        """Run a module's body as a span of the recorder that is the
        process's now. An import that raises closes its span with ``error``
        and raises the same."""
        recorder, local = self._recorder(), self._local
        depth = getattr(local, "depth", 0)
        record = {"id": 0, "name": "import/nested" if depth else "import", "start_ns": 0, "end_ns": 0, "parent": None,
                  "thread": 0, "module": module_name, "cpu_s": 0.0}
        recorder._open_span(record)
        local.depth = depth + 1
        cpu_0 = time.process_time()
        record["start_ns"] = time.time_ns()
        try:
            exec_module(module)
        except BaseException as e:
            record["error"] = type(e).__name__
            raise
        finally:
            record["end_ns"] = time.time_ns()
            local.depth = depth
            took_ns = record["end_ns"] - record["start_ns"]
            brief = took_ns < BRIEF_IMPORT_NS
            if not brief:
                record["cpu_s"] = round(time.process_time() - cpu_0, 6)
            recorder._close_span(record, keep=not brief)
            recorder.count("imports_seen")
            if brief:
                recorder.count("imports_brief")
                if not depth:  # under another import its seconds are in that one's, span or count
                    recorder.count("import_brief_seconds", took_ns / 1e9)


def install_import_spans(recorder: Callable[[], Any]) -> ImportSpans:
    """Put ONE ``ImportSpans`` first in ``sys.meta_path`` (in front of
    pytest's assertion-rewriting finder too, whose loader it times like any
    other); a second call finds the first's and adds none."""
    for finder in sys.meta_path:
        if isinstance(finder, ImportSpans):
            return finder
    spans = ImportSpans(recorder)
    sys.meta_path.insert(0, spans)
    return spans
