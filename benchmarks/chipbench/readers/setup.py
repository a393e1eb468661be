"""Per-layer metrics of SET-UP: what the process did between its start and the
window's start, as the program's own span recorder saw it.

The program (``observe/xla.py``) keeps one recorder a process. A span is
``id``, ``name``, ``start_ns``, ``end_ns`` (``time.time_ns()``), ``parent``,
``thread`` and small attributes: ``<program>/load`` with ``<program>/compile``
and ``/first_dispatch`` inside it where an instrumented program is made
(``program`` is set), and ``jit/trace``, ``jit/lower``, ``jit/compile`` for
every jitted function of the process, from JAX's own monitoring events
(``fun_name`` is set; ``cache`` hit, miss or off on a compile), under whatever
span was open on their thread: the two stages of the step's one
``fn.lower(...)`` call are the ``jit/trace`` and ``jit/lower`` of ``train_step``
directly under ``train_step/load``. The root span ``setup`` (id 0) runs from the
process's start to ``CompileLedger.mark_warm()``, which every training kind calls
immediately before the window starts; nothing is recorded after it (the
reference's compiles are in no span). The counters beside the spans are the
persistent cache's, as JAX reports them: ``compile_requests_use_cache``,
``cache_hits``, ``cache_misses`` (JAX counts a miss where it writes the entry),
``cache_retrieval_time_sec``, ``compile_time_saved_sec``.

The section is the process's, not a ledger's, and is no part of the ledger's
snapshot (that is read at every scrape): the readers ask the program for it,
``CompileLedger.setup()``. A program from before the recorder has no such
accessor and every reader here returns None. The section is also written beside
the device trace (``.chipbench_trace/<cell>/setup_spans.json``, with the run's
``setup_s``): ``tools/setup_table.py`` prints it.
"""

from __future__ import annotations

import json
import os

from benchmarks.chipbench import trace
from benchmarks.chipbench.readers import scopes

DUMP = "setup_spans.json"
_dumped = {}  # path -> the end of the set-up written there


def union(intervals):
    """(seconds, merged [start_ns, end_ns] pairs) of a set of intervals, by
    the trace reduction's own merge."""
    total_ns, merged = trace._union(intervals)
    return total_ns / 1e9, merged


def self_seconds(own, below):
    """Seconds of the intervals ``own`` that no interval of ``below`` covers:
    a span's duration less what its children cover, or the same for a group
    of spans and everything under them (JAX reports a jitted function inside
    a jitted function as two overlapping spans under the one that was open,
    so a group is read as the union of its intervals)."""
    total, merged = union(own)
    covered, _ = union((max(lo, s), min(hi, e)) for lo, hi in merged for s, e in below if s < hi and e > lo)
    return total - covered


def program_section():
    """The set-up section of the program this process runs; None from a
    program without the recorder."""
    from llm_fine_tune_distributed_tpu.observe import xla

    accessor = getattr(xla.CompileLedger, "setup", None)
    return None if accessor is None else accessor()


def section_of(sources):
    """The process's set-up section; None where the program has none, or
    where set-up never ended (``mark_warm()`` was not called: there is nothing
    to read a span against). The first reader that asks writes it beside the
    trace the run has just taken."""
    section = program_section()
    if section is None or section["spans"][0]["end_ns"] is None:
        return None
    xplane = scopes.newest_xplane()
    if xplane is not None:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(xplane)))), DUMP)
        if _dumped.get(path) != section["spans"][0]["end_ns"]:
            _dumped[path] = section["spans"][0]["end_ns"]
            with open(path, "w") as f:
                json.dump(dict(section, setup_s=(sources.get("end_to_end") or {}).get("setup_s")), f)
    return section


def within_setup(section):
    """The spans under ``setup``, each clipped to it: [(span, start_ns, end_ns)]."""
    root = section["spans"][0]
    lo, hi = root["start_ns"], root["end_ns"]
    return [(s, max(lo, s["start_ns"]), min(hi, s["end_ns"])) for s in section["spans"][1:]
            if s["start_ns"] < hi and s["end_ns"] > lo]


def span_seconds(sources, spec):
    """Seconds of the spans named ``spec["span"]`` (``train_step/compile``);
    with ``spec["under"]`` and ``spec["fun_name"]``, only those of that
    function directly under a span of that name (``jit/lower`` of
    ``train_step`` under ``train_step/load``). None where the program made no
    such span."""
    section = section_of(sources)
    if section is None:
        return None
    spans = within_setup(section)
    parents = {span["id"] for span, _, _ in spans if span["name"] == spec.get("under")}
    found = [e - s for span, s, e in spans if span["name"] == spec["span"]
             and ("under" not in spec or span["parent"] in parents)
             and ("fun_name" not in spec or span.get("fun_name") == spec["fun_name"])]
    return sum(found) / 1e9 if found else None


def setup_jit_s(sources, spec):
    """Seconds of set-up in which the process was making a program: the union
    of every ``jit/*`` span and every span of an instrumented program."""
    section = section_of(sources)
    if section is None:
        return None
    return union((s, e) for span, s, e in within_setup(section) if "fun_name" in span or "program" in span)[0]


def setup_counter(sources, spec):
    section = section_of(sources)
    return None if section is None else section["counters"].get(spec["counter"])


def setup_spanned_pct(sources, spec):
    """The union of every span under ``setup`` over the harness's own
    ``setup_s`` (process start to the window's start, from ``/proc``)."""
    section = section_of(sources)
    setup_s = (sources.get("end_to_end") or {}).get("setup_s")
    if section is None or not setup_s:
        return None
    return 100.0 * union((s, e) for _, s, e in within_setup(section))[0] / setup_s
