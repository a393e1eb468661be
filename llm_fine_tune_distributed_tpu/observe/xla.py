"""XLA runtime introspection: compile ledger, the process's spans,
cost-analysis utilization gauges, and on-demand profiler capture.

The serving engines and the trainer dispatch a small set of jitted
programs (decode tick, prefill chunk, speculative verify, draft step,
train step). Steady-state behaviour is: every program compiles exactly
once per shape bucket during warmup, then never again — a retrace in
steady state silently costs seconds per occurrence and is always a bug
(a stray shape bucket, a weak-type flip, a donated-buffer mismatch).
This module makes that contract observable:

- ``annotate()`` is the one span primitive: ``name``, ``start_ns``,
  ``end_ns`` on the profiler's clock (``time.time_ns()``), the ``parent``
  that was open on the same thread, small attributes. A span is a
  ``jax.profiler.TraceAnnotation`` too, so a captured trace shows it,
  and while set-up lasts it lands in the process's ``SpanRecorder``: a
  bounded list plus counters that go on when the list is full. JAX's own
  monitoring events feed it too (``install_compile_listeners``): every
  jitted function's trace, lowering and backend compile as ``jit/trace``,
  ``jit/lower``, ``jit/compile`` with its ``fun_name``, and the
  persistent cache's hits and misses as counters. ``observe/startup.py``
  brings what no call site can span: ``process/before_recorder`` (process
  start to this module's import); ``importing()`` is ``import`` / ``import/nested``.
- ``CompileLedger`` records every compilation (program name, abstract
  arg shapes, wall compile seconds split into trace, lowering and
  backend compile, cache hit or miss), deduplicates by (program,
  shapes), and exposes ``recompiles_after_warmup`` — the number that must
  stay zero once ``mark_warm()`` has been called. Listeners (the engines'
  flight recorders) are notified of post-warmup recompiles as they
  happen. It owns the recorder's export: ``mark_warm()`` ends the root
  span ``setup`` that began with the process, from then on a span is its
  ``TraceAnnotation`` alone and nothing more is recorded, and
  ``CompileLedger.setup()`` is what set-up recorded (spans, counters, the
  dearest functions): an accessor of its own, so that ``snapshot()``, which
  every scrape and logging step calls, stays at its totals.
- ``instrument()`` wraps a jitted callable so its first call registers
  with the ledger. For engine hot-path programs (``aot=True``) the
  first call goes through ``fn.lower(...)`` and ``.compile()`` under
  ``<program>/load``: the one ``lower`` call's two stages are JAX's own
  ``jit/trace`` and ``jit/lower`` spans of the function, directly under
  that span (held apart as two calls the same module cost seconds more to
  lower on the chip), ``<program>/compile`` and ``/first_dispatch`` are
  spans of their own — exact seconds by stage plus ``cost_analysis()``
  FLOPs / bytes-accessed — and the AOT executable becomes the dispatch
  target (one compile, not two). Any AOT failure falls back permanently
  to the plain jit callable with first-call wall timing (an upper bound
  on compile time), and the ledger's entry says so (``aot: false``,
  ``aot_error``).
- ``device_peak_specs()`` + ``utilization_from_cost()`` turn the cost
  analysis and the ``decode_tick_s`` histogram into
  ``model_flops_utilization`` and ``hbm_bandwidth_utilization`` gauges
  (batched decode is bandwidth-bound; the BW gauge is the one that
  should sit near its roofline).
- ``ProfilerCapture`` guards ``jax.profiler`` traces for the serving
  ``POST /v1/profile`` endpoint: one capture at a time, auto-stop after
  the requested duration, a fresh subdirectory per capture.
- ``scope()`` names the train step's operations (``STEP_SCOPES``) with
  ``jax.named_scope``: a device trace then carries, on every operation, the
  path of scopes it was traced under, and a reader can say which part of
  the step the device's time went to.
"""

from __future__ import annotations

import itertools
import os
import re
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax

from llm_fine_tune_distributed_tpu.observe import startup

__all__ = [
    "CaptureBusyError", "CompileLedger", "ProfilerCapture", "STEP_SCOPES", "SpanRecorder", "annotate",
    "device_peak_specs", "importing", "install_compile_listeners", "instrument", "mosaic_programs", "scope",
    "utilization_from_cost",
]


# ------------------------------------------------------------------- spans

MAX_SPANS = 8192  # kept spans
# A jitted function's trace, lowering or compile shorter than this is counted (the
# counters, by_function) and not kept: tracing the Qwen3-Next cell's step reports
# 7,959 such stages, 7,741 of them under a millisecond and 0.47 s in all (my chip
# run, PR 38), and it is the list's first spans that would crowd out the last.
BRIEF_NS = 1_000_000
_IMPORTED_NS = time.time_ns()
_AT_IMPORT = startup.facts_at_import()  # of process/before_recorder, which ends here

# JAX's monitoring events (jax/_src/dispatch.py, compiler.py, compilation_cache.py)
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit/lower",
    "/jax/core/compile/backend_compile_duration": "jit/compile",
}
_CACHE_COUNTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "compile_requests_use_cache",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_time_sec",
    "/jax/compilation_cache/compile_time_saved_sec": "compile_time_saved_sec",
}


def _process_start_ns() -> int:
    """When this process started, on the epoch clock: from ``/proc`` (the
    interpreter's start-up and the imports count as set-up), else the import
    of this module."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age_s = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.time_ns() - int(age_s * 1e9)
    except (OSError, ValueError, IndexError):
        return _IMPORTED_NS


class _Span:
    """One open span: a context manager that is a ``TraceAnnotation`` as well,
    so a profiler capture shows it under the same name. Once set-up is over
    (``SpanRecorder.freeze()``) it is the annotation alone: ``record`` is None,
    no clock is read and the recorder is not touched."""

    __slots__ = ("_recorder", "_annotation", "record")

    def __init__(self, recorder: "SpanRecorder", name: str, attrs: Dict[str, Any]):
        self._recorder = recorder
        self._annotation = jax.profiler.TraceAnnotation(name)
        self.record: Optional[Dict[str, Any]] = None if recorder.frozen else {
            "id": 0, "name": name, "start_ns": 0, "end_ns": 0, "parent": None, "thread": 0, **attrs}

    def set(self, **attrs: Any) -> None:
        if self.record is not None:
            self.record.update(attrs)

    def __enter__(self) -> "_Span":
        if self.record is None:
            self._annotation.__enter__()
            return self
        self._recorder._open_span(self.record)
        self._annotation.__enter__()
        self.record["start_ns"] = time.time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.record is None:
            self._annotation.__exit__(exc_type, exc, tb)
            return
        self.record["end_ns"] = time.time_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.record["error"] = exc_type.__name__
        self._recorder._close_span(self.record)


class SpanRecorder:
    """The spans and counters of the process's SET-UP. A span is a dict:
    ``id``, ``name``, ``start_ns`` and ``end_ns`` from ``time.time_ns()``
    (the clock of the profiler's host events, so a captured trace and these
    lines need no conversion beyond the capture's own start, which an xplane
    counts from), ``parent`` (the id of the span that was open on the same
    thread when it started, else the root span ``setup``, id 0, which began
    with the process), ``thread``, and small attributes (``program``, ``fun_name``,
    ``cache``, ``module``). The first is ``process/before_recorder``. Spans are kept
    until the list holds ``max_spans`` (JAX's own stages only from a millisecond up:
    ``spans_brief`` counts the briefer ones); the counters go on. ``freeze()``
    ends ``setup``: what was recorded until then is the set-up section, and nothing
    is recorded after it (the engine's tick phases and a late compile are
    ``TraceAnnotation``s and ledger entries, which have their own readers)."""

    def __init__(self, max_spans: int = MAX_SPANS, start_ns: Optional[int] = None):
        self._lock = threading.Lock()
        # per thread: .open, the ids of its open spans; .cache, what it has asked of the
        # persistent cache; .compiled_at, that tally at its last jit/compile
        self._thread = threading.local()
        self._ids = itertools.count(1)
        self._max_spans = max_spans
        self._root = {"id": 0, "name": "setup", "start_ns": _process_start_ns() if start_ns is None else start_ns,
                      "end_ns": None, "parent": None, "thread": None}
        self._spans = [startup.before_recorder(next(self._ids), self._root["start_ns"], _IMPORTED_NS, _AT_IMPORT)]
        self._by_function: Dict[str, List[float]] = {}  # fun_name -> [seconds, spans]
        self.frozen = False
        # process/before_recorder is counted as the span it is
        self.counters: Dict[str, float] = {"spans": 1, "spans_brief": 0, "spans_dropped": 0, "jit_seconds": 0.0}
        self.counters.update(dict.fromkeys(_CACHE_COUNTS.values(), 0))
        self.counters.update(dict.fromkeys(_CACHE_SECONDS.values(), 0.0))

    def span(self, name: str, **attrs: Any) -> _Span:
        return _Span(self, name, attrs)

    def count(self, counter: str, by: float = 1) -> None:
        if self.frozen:
            return
        with self._lock:
            self.counters[counter] = self.counters.get(counter, 0) + by

    def cache_tally(self) -> Tuple[int, int]:
        """(requests, hits) this THREAD has made of the persistent cache:
        JAX reports both from the thread that compiles."""
        return getattr(self._thread, "cache", (0, 0))

    def cache_verdict(self, since: Tuple[int, int]) -> str:
        """What this thread's compiles asked of the persistent cache since
        the tally ``since``: ``hit``, ``miss``, or ``off`` (nothing asked:
        no cache directory, or a backend JAX does not cache for)."""
        requests, hits = self.cache_tally()
        if hits > since[1]:
            return "hit"
        return "miss" if requests > since[0] else "off"

    def add_jit_stage(self, name: str, start_s: float, end_s: float, fun_name: str) -> None:
        """One of JAX's three compile events, as it reports them when they
        END: seconds of ``time.time()`` and the function's name (``sin`` where
        it traces, ``jit(sin)`` where it lowers and compiles: one name here).
        Its parent is the span open on this thread now; it is no
        ``TraceAnnotation`` (a capture cannot be told afterwards). One under
        ``BRIEF_NS`` is counted and not kept."""
        if self.frozen:
            return
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]
        start_ns, end_ns = int(start_s * 1e9), int(end_s * 1e9)
        record = {"id": 0, "name": name, "start_ns": start_ns, "end_ns": end_ns, "parent": None, "thread": 0,
                  "fun_name": fun_name}
        if name == "jit/compile":
            record["cache"] = self.cache_verdict(getattr(self._thread, "compiled_at", (0, 0)))
            self._thread.compiled_at = self.cache_tally()
        self._open_span(record)
        self._close_span(record, keep=end_ns - start_ns >= BRIEF_NS)

    def seconds_under(self, parent: _Span, name: str) -> Optional[float]:
        """Seconds of the kept spans ``name`` directly under ``parent``; None
        where there is none (under a millisecond, a full list, set-up over)."""
        if parent.record is None:
            return None
        with self._lock:
            found = [s["end_ns"] - s["start_ns"] for s in self._spans
                     if s["parent"] == parent.record["id"] and s["name"] == name]
        return sum(found) / 1e9 if found else None

    def _cache_event(self, counter: str) -> None:
        requests, hits = self.cache_tally()
        if counter == "compile_requests_use_cache":
            self._thread.cache = (requests + 1, hits)
        elif counter == "cache_hits":
            self._thread.cache = (requests, hits + 1)
        self.count(counter)

    def _open_span(self, record: Dict[str, Any]) -> None:
        stack = getattr(self._thread, "open", None)
        if stack is None:
            stack = self._thread.open = []
        record["id"] = next(self._ids)
        record["thread"] = threading.get_ident()
        record["parent"] = stack[-1] if stack else 0
        stack.append(record["id"])

    def _close_span(self, record: Dict[str, Any], keep: bool = True) -> None:
        self._thread.open.pop()
        seconds = (record["end_ns"] - record["start_ns"]) / 1e9
        with self._lock:
            if self.frozen:  # it was open when set-up ended
                return
            self.counters["spans"] += 1
            if not keep:
                self.counters["spans_brief"] += 1
            elif len(self._spans) < self._max_spans:
                self._spans.append(record)
            else:
                self.counters["spans_dropped"] += 1
            if "fun_name" in record:
                self.counters["jit_seconds"] += seconds
                if record["fun_name"] in self._by_function or len(self._by_function) < self._max_spans:
                    entry = self._by_function.setdefault(record["fun_name"], [0.0, 0])
                    entry[0] += seconds
                    entry[1] += 1

    def freeze(self) -> None:
        """End ``setup`` (once: the first call of a process decides)."""
        with self._lock:
            if not self.frozen:
                self._root["end_ns"] = time.time_ns()
                self.frozen = True

    def section(self) -> Dict[str, Any]:
        """What set-up recorded, the caller's own copy: the whole of it once
        ``freeze()`` has ended it, else what there is so far (``setup`` still
        open, its ``end_ns`` None). ``by_function`` adds a function's
        ``jit/*`` spans: a function that calls jitted functions counts their
        tracing too."""
        with self._lock:
            dearest = sorted(self._by_function.items(), key=lambda kv: -kv[1][0])[:20]
            return {
                "spans": [dict(self._root)] + [dict(s) for s in self._spans],
                "counters": dict(self.counters),
                "by_function": [{"fun_name": k, "seconds": round(v[0], 6), "spans": v[1]} for k, v in dearest],
            }


_RECORDER = SpanRecorder()
_LISTENING = threading.Lock()
_listeners_installed = False


def _on_compile_span(event: str, start_s: float, end_s: float, **kw: Any) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is not None:
        _RECORDER.add_jit_stage(name, start_s, end_s, str(kw.get("fun_name", "?")))


def _on_cache_event(event: str, **kw: Any) -> None:
    counter = _CACHE_COUNTS.get(event)
    if counter is not None:
        _RECORDER._cache_event(counter)


def _on_cache_seconds(event: str, seconds: float, **kw: Any) -> None:
    counter = _CACHE_SECONDS.get(event)
    if counter is not None:
        _RECORDER.count(counter, seconds)


def install_compile_listeners() -> None:
    """Feed the recorder from JAX's monitoring events: once a process,
    however often and from whichever thread it is called (the first
    ``CompileLedger()``; ``runtime/compile_cache.enable_compile_cache()``,
    which every entry point calls before anything compiles). Imports are
    spans where the package makes them (``importing``), not from a hook."""
    global _listeners_installed
    with _LISTENING:
        if _listeners_installed:
            return
        jax.monitoring.register_event_time_span_listener(_on_compile_span)
        jax.monitoring.register_event_listener(_on_cache_event)
        jax.monitoring.register_event_duration_secs_listener(_on_cache_seconds)
        # no import hook here: a sys.meta_path finder cost the benchmark's cells 6 to 16 s of set-up (startup.py)
        _listeners_installed = True


def annotate(name: str, **attrs: Any) -> _Span:
    """The span primitive: ``with annotate("prefill"):`` is a
    ``jax.profiler.TraceAnnotation`` (a captured trace lines up with the
    request timeline) and, while set-up lasts, a record in the process's
    ``SpanRecorder``; after ``mark_warm()`` it is the annotation alone."""
    return _RECORDER.span(name, **attrs)


# ------------------------------------------------------------------ ledger


_STAGE_SECONDS = ("trace_s", "lower_s", "backend_compile_s")


def _fold_program(into: Dict[str, Any], part: Dict[str, Any]) -> None:
    """Add one entry (or one snapshot's program) to a program's totals:
    counts and seconds add up, ``cache`` and ``aot`` are the newest's."""
    into["compiles"] += part["compiles"]
    into["compile_s"] += part["compile_s"]
    for key in _STAGE_SECONDS:
        if key in part:
            into[key] = into.get(key, 0.0) + part[key]
    for key in ("cache", "aot", "aot_error"):
        if key in part:
            into[key] = part[key]


def _programs_snapshot(programs: Dict[str, Dict[str, Any]], recompiles: int, warmed: bool) -> Dict[str, Any]:
    for p in programs.values():
        for key in ("compile_s", *_STAGE_SECONDS):
            if key in p:
                p[key] = round(p[key], 6)
    return {
        "programs": programs,
        "total_compiles": sum(p["compiles"] for p in programs.values()),
        "total_compile_s": round(sum(p["compile_s"] for p in programs.values()), 6),
        "recompiles_after_warmup": recompiles,
        "warmed": warmed,
    }


class CompileLedger:
    """Thread-safe registry of XLA compilations, deduplicated by
    (program, abstract shapes). Re-recording an already-seen signature
    bumps its compile count (a cache rebuild), and any record after
    ``mark_warm()`` increments ``recompiles_after_warmup`` and notifies
    listeners — steady-state recompile is a bug, and this is the counter
    that proves its absence. The ledger also exports the process's
    ``SpanRecorder`` (``setup()``, ``setup_phases()``): the first
    ``mark_warm()`` of a process ends set-up.
    """

    def __init__(self) -> None:
        install_compile_listeners()
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._seq = 0
        self._warmed = False
        self.recompiles_after_warmup = 0
        # engines stamp their supervisor generation here so a post-warmup
        # recompile is reported with the engine incarnation that made it
        # (replicas sharing one Generator share one ledger; best-effort)
        self.current_generation = 0
        self._listeners: List[Callable[..., None]] = []

    def record(
        self,
        program: str,
        shapes: Any,
        compile_s: float,
        flops: Optional[float] = None,
        bytes_accessed: Optional[float] = None,
        parts: Optional[Dict[str, Any]] = None,
    ) -> None:
        """``parts``: what ``instrument()`` knows of this compilation beyond
        its seconds: ``backend_compile_s`` and, where the recorder kept JAX's
        lowering of the function, ``trace_s`` and ``lower_s`` (the three add
        up to ``compile_s``), ``cache`` (hit, miss, off), ``aot`` and, where
        the AOT path was given up, ``aot_error``."""
        sig = shapes if isinstance(shapes, str) else str(tuple(shapes)) if isinstance(shapes, (list, tuple)) else str(shapes)
        with self._lock:
            self._seq += 1
            entry = self._entries.get((program, sig))
            if entry is None:
                entry = {"compiles": 0, "compile_s": 0.0, "flops": None, "bytes_accessed": None}
                self._entries[(program, sig)] = entry
            _fold_program(entry, dict(parts or {}, compiles=1, compile_s=float(compile_s)))
            entry["seq"] = self._seq
            if flops is not None:
                entry["flops"] = float(flops)
            if bytes_accessed is not None:
                entry["bytes_accessed"] = float(bytes_accessed)
            after_warmup = self._warmed
            if after_warmup:
                self.recompiles_after_warmup += 1
            listeners = list(self._listeners)
        if after_warmup:
            for fn in listeners:
                try:
                    fn(program, sig, float(compile_s), self.current_generation)
                except Exception:
                    pass  # a broken listener must never fail a dispatch

    def mark_warm(self) -> None:
        """Declare warmup over: every record from here on is a recompile,
        and the process's set-up has ended (``setup()`` stands)."""
        with self._lock:
            self._warmed = True
        _RECORDER.freeze()

    @property
    def warmed(self) -> bool:
        return self._warmed

    def add_listener(self, fn: Callable[..., None]) -> None:
        """``fn(program, shapes, compile_s, generation)`` on every
        post-warmup record."""
        with self._lock:
            self._listeners.append(fn)

    def cost_for(self, programs: Iterable[str]) -> Tuple[float, float]:
        """(flops, bytes_accessed) of the most recently compiled entry
        among ``programs`` that carries cost analysis; (0, 0) if none."""
        names = set(programs)
        best = None
        with self._lock:
            for (name, _), e in self._entries.items():
                if name in names and e.get("flops") is not None:
                    if best is None or e["seq"] > best["seq"]:
                        best = e
        if best is None:
            return 0.0, 0.0
        return float(best["flops"] or 0.0), float(best["bytes_accessed"] or 0.0)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            programs: Dict[str, Dict[str, Any]] = {}
            for (name, _), e in sorted(self._entries.items(), key=lambda kv: kv[1]["seq"]):
                _fold_program(programs.setdefault(name, {"compiles": 0, "compile_s": 0.0}), e)
            return _programs_snapshot(programs, self.recompiles_after_warmup, self._warmed)

    @staticmethod
    def setup() -> Dict[str, Any]:
        """What the PROCESS's set-up recorded, the same under every ledger:
        ``{"spans": [the root span setup, then every kept span], "counters":
        {...}, "by_function": the 20 dearest functions}``; the caller's own
        copy. Not in ``snapshot()``: that is read at every scrape and logging
        step, this once a run."""
        return _RECORDER.section()

    @staticmethod
    def setup_phases() -> Dict[str, Any]:
        """The operator's reading of set-up so far: seconds by span name in
        order of first start (``process/before_recorder``, ``import``,
        ``startup/weights``, ``train_step/load``; JAX's own ``jit/*`` spans and
        ``import/nested`` are their children and stay out), the seconds
        since the process started, and the persistent cache's counters."""
        section = _RECORDER.section()
        root, phases = section["spans"][0], {}
        for span in sorted(section["spans"][1:], key=lambda sp: sp["start_ns"]):
            if "fun_name" not in span and span["name"] != "import/nested":
                phases[span["name"]] = phases.get(span["name"], 0.0) + (span["end_ns"] - span["start_ns"]) / 1e9
        return {
            "phases_s": {name: round(secs, 3) for name, secs in phases.items()},
            "since_process_start_s": round(((root["end_ns"] or time.time_ns()) - root["start_ns"]) / 1e9, 3),
            **{name: section["counters"][name] for name in _CACHE_COUNTS.values()},
        }

    @staticmethod
    def merge(ledgers: Iterable["CompileLedger"]) -> Dict[str, Any]:
        """Snapshot-shaped union over DISTINCT ledgers (fleet replicas
        sharing one Generator share one ledger object — dedup by
        identity so shared compilations are not double-counted)."""
        seen: Dict[int, CompileLedger] = {}
        for led in ledgers:
            if led is not None:
                seen.setdefault(id(led), led)
        programs: Dict[str, Dict[str, Any]] = {}
        recompiles = 0
        warmed = bool(seen)
        for led in seen.values():
            snap = led.snapshot()
            for name, p in snap["programs"].items():
                _fold_program(programs.setdefault(name, {"compiles": 0, "compile_s": 0.0}), p)
            recompiles += snap["recompiles_after_warmup"]
            warmed = warmed and snap["warmed"]
        return _programs_snapshot(programs, recompiles, warmed)


# ----------------------------------------------------- program instrumenting


def _abstract_shapes(args: Any, kwargs: Any = None) -> str:
    """Compact abstract-shape signature of a call's arguments. Large
    pytrees (a train step's parameter forest) are summarized rather than
    enumerated — the signature only needs to be stable per shape bucket."""
    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    parts = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            parts.append(f"{getattr(leaf, 'dtype', '?')}{tuple(shape)}")
        else:
            parts.append(type(leaf).__name__)
    if len(parts) > 8:
        # summarize, but keep the tail's information: the head of a train
        # step's leaf list is all parameters (identical across calls) while
        # the distinguishing shapes (cache width, batch bucket) sit deeper,
        # so a plain prefix-truncation would alias genuinely different
        # signatures — and the signature dispatches AOT executables
        digest = hash(tuple(parts)) & 0xFFFFFFFF
        parts = parts[:4] + [f"...{len(parts)}leaves:{digest:08x}"]
    return "(" + ",".join(parts) + ")"


def _extract_cost(compiled: Any) -> Tuple[Optional[float], Optional[float]]:
    """FLOPs / bytes-accessed from ``Compiled.cost_analysis()``, which
    returns a dict on recent JAX and a one-element list on older ones."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None, None
    return (
        float(ca.get("flops", 0.0) or 0.0),
        float(ca.get("bytes accessed", 0.0) or 0.0),
    )


class _InstrumentedProgram:
    """Wraps a jitted callable so every NEW call signature registers with
    the ledger. ``aot=True`` compiles ahead-of-time per signature (exact
    compile seconds + cost analysis) and dispatches later same-shape
    calls straight to that executable; an AOT failure (python-scalar
    args, donation quirks, old JAX) falls back to the plain jit callable
    for that signature, timing its first call as an upper bound on
    compile time (the ledger's entry then says ``aot: false`` and, under
    ``aot_error``, what the AOT path raised). Dispatch is keyed by the abstract shapes of the actual
    call, NOT the owner's cache key: a Generator's jit-cache key doesn't
    fully determine shapes (two engines with different slot counts share
    one Generator, so one ``slot_prefill`` bucket entry sees two cache
    widths) and an AOT executable — unlike plain jit — cannot absorb a
    new shape silently. First calls are serialized so two threads racing
    a cold signature produce one ledger entry. Non-``__call__`` attributes
    (``lower``, ``eval_shape``, ...) proxy to the wrapped callable."""

    __slots__ = ("_program", "_fn", "_ledger", "_shapes", "_aot", "_lock", "_calls")

    def __init__(self, program, fn, ledger, shapes=None, aot=True):
        self._program = program
        self._fn = fn
        self._ledger = ledger
        self._shapes = shapes
        self._aot = aot
        self._lock = threading.Lock()
        self._calls: dict = {}  # signature -> AOT executable or plain jit fn

    def __getattr__(self, name):
        if name.startswith("_"):  # never proxy slot misses back into _fn
            raise AttributeError(name)
        return getattr(self._fn, name)

    def __call__(self, *args, **kwargs):
        sig = _abstract_shapes(args, kwargs)
        call = self._calls.get(sig)
        if call is not None:
            return call(*args, **kwargs)
        with self._lock:
            call = self._calls.get(sig)
            if call is not None:
                return call(*args, **kwargs)
            return self._first_call(sig, args, kwargs)

    def _first_call(self, sig, args, kwargs):
        shapes = sig if self._shapes is None else f"{self._shapes}{sig}"
        name, recorder = self._program, _RECORDER
        parts: Dict[str, Any] = {"aot": False}
        if self._aot:
            try:
                with recorder.span(f"{name}/load", program=name) as loading:
                    # ONE call, as ever: its two stages are JAX's own jit/trace and
                    # jit/lower events, which land under this span. Held apart
                    # (fn.trace(...), then traced.lower()) the same module cost the
                    # benchmark's cells 1.6 to 5.4 s more to lower on the chip
                    # (PERF.md, PR 38).
                    t0 = time.perf_counter()
                    lowered = self._fn.lower(*args, **kwargs)
                    t1 = time.perf_counter()
                    asked = recorder.cache_tally()
                    with recorder.span(f"{name}/compile", program=name) as compiling:
                        compiled = lowered.compile()
                        cache = recorder.cache_verdict(asked)
                        compiling.set(cache=cache)
                    t2 = time.perf_counter()
                    flops, nbytes = _extract_cost(compiled)
                    with recorder.span(f"{name}/first_dispatch", program=name):
                        out = compiled(*args, **kwargs)
                # record only after a successful execute: if the AOT
                # artifact can't even run, the plain-jit retry below must
                # own the ledger entry
                stages = {"backend_compile_s": t2 - t1, "cache": cache, "aot": True}
                lower_s = recorder.seconds_under(loading, "jit/lower")
                if lower_s is not None:  # the rest of the call is tracing (and the call's own handling of its arguments)
                    stages.update(trace_s=t1 - t0 - lower_s, lower_s=lower_s)
                self._ledger.record(name, shapes, t2 - t0, flops, nbytes, stages)
                self._calls[sig] = compiled
                return out
            except Exception as e:
                parts["aot_error"] = f"{type(e).__name__}: {e}"[:300]
        asked = recorder.cache_tally()
        t0 = time.perf_counter()
        with recorder.span(f"{name}/load", program=name, aot=False):
            out = self._fn(*args, **kwargs)
        parts["cache"] = recorder.cache_verdict(asked)
        self._ledger.record(name, shapes, time.perf_counter() - t0, parts=parts)
        self._calls[sig] = self._fn
        return out


def instrument(program, fn, ledger, shapes=None, aot=True):
    """Ledger-wrap a jitted callable (see ``_InstrumentedProgram``)."""
    return _InstrumentedProgram(program, fn, ledger, shapes=shapes, aot=aot)


_MOSAIC_CALL = re.compile(r'\\22body\\22: \\22([^\\]*)\\22.*?kernel_name = "([^"]*)"')


def mosaic_programs(stablehlo_text: str) -> Dict[str, Dict[str, int]]:
    """The Pallas TPU kernels of a lowered program (``jitted.lower(...).as_text()``):
    ``{kernel name: {"programs": distinct serialized Mosaic modules, "bytes": their
    lengths added up as the text carries them, "call_sites": calls}}``. What a warm
    start pays for a kernel follows these (PERF.md, PR 37), and a CPU can count them."""
    bodies: Dict[str, Dict[str, int]] = {}
    for body, name in _MOSAIC_CALL.findall(stablehlo_text):
        sites = bodies.setdefault(name, {})
        sites[body] = sites.get(body, 0) + 1
    return {name: {"programs": len(sites), "bytes": sum(map(len, sites)), "call_sites": sum(sites.values())}
            for name, sites in bodies.items()}


# -------------------------------------------------- utilization from cost


# (peak dense bf16 FLOP/s, peak HBM bytes/s) per chip, keyed by the
# ``device_kind`` string the installed runtime reports. Published peaks —
# the gauges they feed are roofline fractions, not absolute truth.
_DEVICE_PEAKS = {
    # Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16 (393 TOP/s
    # int8), 819 GB/s HBM. libtpu 0.0.34 reports a v5e as "TPU v5 lite".
    "TPU v5 lite": (197e12, 8.19e11),
}


def device_peak_specs(device=None) -> Tuple[float, float]:
    """(peak FLOP/s, peak HBM bytes/s) for the given (default: first)
    device. A CPU is a known device with no peaks: (0, 0), and downstream
    gauges read 0.0 rather than invent a roofline. An accelerator whose
    kind is not in the table raises — it does not read as zero."""
    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return 0.0, 0.0
    try:
        return _DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add it, with its source, to "
            "observe/xla.py _DEVICE_PEAKS"
        ) from None


def utilization_from_cost(
    flops: float,
    bytes_accessed: float,
    mean_step_s: float,
    peak_flops: float,
    peak_bw: float,
) -> Tuple[float, float]:
    """(model_flops_utilization, hbm_bandwidth_utilization) for a program
    whose cost analysis says it does ``flops`` / ``bytes_accessed`` per
    dispatch and whose measured mean dispatch time is ``mean_step_s``.
    Clamped to [0, 1]; 0.0 whenever any input is unknown."""

    def ratio(work, peak):
        if work <= 0.0 or peak <= 0.0 or mean_step_s <= 0.0:
            return 0.0
        return min(1.0, work / (mean_step_s * peak))

    return ratio(flops, peak_flops), ratio(bytes_accessed, peak_bw)


# ------------------------------------------------------- profiler capture


class CaptureBusyError(RuntimeError):
    """A profiler capture is already running (one at a time)."""


class ProfilerCapture:
    """On-demand ``jax.profiler`` trace for the serving ``/v1/profile``
    endpoint: one capture at a time, a fresh ``capture_NNNN``
    subdirectory per capture, auto-stop after the requested duration.
    ``on_event(kind, **fields)`` (the engine flight recorder) sees
    profile_start / profile_stop."""

    def __init__(self, base_dir: str, on_event: Optional[Callable[..., None]] = None):
        self.base_dir = base_dir
        self._on_event = on_event
        self._lock = threading.Lock()
        self._active: Optional[str] = None
        self._timer: Optional[threading.Timer] = None
        self._seq = itertools.count(1)

    @property
    def active(self) -> Optional[str]:
        return self._active

    def start(self, duration_s: float) -> str:
        """Begin a capture; returns its directory. Raises
        ``CaptureBusyError`` if one is already running."""
        if duration_s <= 0:
            raise ValueError(f"duration_s must be positive, got {duration_s}")
        with self._lock:
            if self._active is not None:
                raise CaptureBusyError(
                    f"capture already running in {self._active}"
                )
            trace_dir = os.path.join(self.base_dir, f"capture_{next(self._seq):04d}")
            os.makedirs(trace_dir, exist_ok=True)
            jax.profiler.start_trace(trace_dir)
            self._active = trace_dir
            self._timer = threading.Timer(duration_s, self.stop)
            self._timer.daemon = True
            self._timer.start()
        self._event("profile_start", dir=trace_dir, duration_s=duration_s)
        return trace_dir

    def stop(self) -> Optional[str]:
        """Stop the running capture (idempotent); returns its directory."""
        with self._lock:
            if self._active is None:
                return None
            trace_dir = self._active
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass  # already stopped underneath us; the dir still counts
            # the event must land before `active` reads None: pollers treat
            # active=None as "capture fully finished" (on_event only appends
            # to a recorder deque, so holding the lock here is safe)
            self._event("profile_stop", dir=trace_dir)
            self._active = None
        return trace_dir

    def _event(self, kind: str, **fields) -> None:
        if self._on_event is not None:
            try:
                self._on_event(kind, **fields)
            except Exception:
                pass


# The train step's scope vocabulary. ``layer`` takes the layer's index
# (``layer0`` ... ``layer35``); ``attn`` and ``mlp`` sit inside a layer. A
# scope is metadata on the traced operations (the ``op_name`` XLA keeps, the
# ``tf_op`` of a device trace) and costs nothing when no trace is taken.
# Forward, backward and recompute are not scopes: JAX writes them into the
# path itself (``jvp(layer3)``, ``transpose(jvp(layer3))``,
# ``rematted_computation``).
STEP_SCOPES = (
    "embed", "layer", "attn", "mlp", "final_norm", "loss_head", "grad_accum", "optimizer",
    # inside "mlp", in a layer of routed experts with shared experts
    "router", "experts", "shared_expert",
    # a linear-attention layer's mixer (in place of "attn") and its parts; the
    # output gate of a gated softmax-attention layer, inside "attn"
    "linear_attn", "gdn_conv", "gdn_scan", "gdn_gate_norm", "attn_gate",
    # the per-head q and k norms, inside "attn"; the norm of a half's OUTPUT in
    # a block of four norms, inside "attn" (or "linear_attn") and inside "mlp"
    "qk_norm", "out_norm",
    # between a softmax layer's projections and the attention call, inside
    # "attn": the q/k norms, rope and the hand-over of the layout, as the
    # fused pass (ops/rope.heads_in) or as the XLA form ("qk_norm" inside it)
    "attn_in",
    # inside "linear_attn" of a Kimi Delta Attention mixer, around what the other
    # linear mixer does not have: the low-rank products of the decay and of the
    # output gate, the softplus and the decay's sign and scale
    "kda_gates",
    # inside "attn" of an EVA layer (ops/eva_attention.py), between the IN pass and o_proj: the pooling of keys and
    # values into one summary a chunk (both passes), and everything else (the kernels of both key sources)
    "eva_pool", "eva_agg",
    # inside "linear_attn" of a Mamba-2 layer (ops/ssd.py), between in_proj's products and out_proj: the convolution
    # with its bias, silu and the softplus of dt; the state-space scan; the gate and the norm after it
    "ssd_in", "ssd_scan", "ssd_gate_norm",
)


def scope(name: str, index: Optional[int] = None):
    """``jax.named_scope`` for one name of ``STEP_SCOPES``."""
    assert name in STEP_SCOPES, name
    return jax.named_scope(name if index is None else f"{name}{index}")


_IMPORTING = threading.local()  # .depth: the import spans open on this thread


class importing:
    """``with importing("orbax.checkpoint"): import orbax.checkpoint as ocp``:
    the span ``import`` where no import span is open on the thread,
    ``import/nested`` below one (so the spans of one name never overlap on a
    thread and add up), with ``module`` and ``cpu_s`` (the PROCESS's CPU
    seconds over the span: wall far above it is waiting on the disk), and
    ``error`` where the import raised. Put round the few imports of the
    package that pull a third-party tree in (``train/__init__.py``,
    ``train/checkpoints.py``, ``parallel/optimizer.py``); while set-up lasts
    only: afterwards it reads no clock and is the ``TraceAnnotation`` alone."""

    __slots__ = ("_span", "_cpu_0", "_depth")

    def __init__(self, module: str):
        self._depth = getattr(_IMPORTING, "depth", 0)
        self._span = annotate("import/nested" if self._depth else "import", module=module)

    def __enter__(self) -> "importing":
        _IMPORTING.depth = self._depth + 1
        self._cpu_0 = None if self._span.record is None else time.process_time()
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        _IMPORTING.depth = self._depth
        if self._cpu_0 is not None:
            self._span.set(cpu_s=round(time.process_time() - self._cpu_0, 6))
        self._span.__exit__(exc_type, exc, tb)
