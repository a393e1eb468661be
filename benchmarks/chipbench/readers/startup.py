"""Per-layer metrics of SET-UP that read the spans PR 51 brought to the
program: ``process/before_recorder`` (``observe/startup.py``), from the
process's start to the import of the recorder's module, and ``import`` with
``import/nested`` below it (``observe/xla.importing``), one for each import
the package wraps while set-up lasts (``train/__init__.py``,
``train/checkpoints.py``, ``parallel/optimizer.py``; attributes ``module``
and ``cpu_s``).

``setup_before_recorder_s`` and ``setup_import_s`` are data files over the
accepted ``readers.setup.span_seconds``: spans named ``import`` lie under no
other import on their thread, so their seconds add up. This file has the two
readers that one cannot be: the imports of one package, and the longest
stretch of set-up under no span at all. A program without the spans (the
parent of PR 51) gives every one of them None.
"""

from __future__ import annotations

from benchmarks.chipbench.readers.setup import section_of, union, within_setup

BEFORE_RECORDER = "process/before_recorder"


def import_seconds(sources, spec):
    """Seconds of the spans named ``import`` whose ``module`` is
    ``spec["module"]`` or lies below it (``llm_fine_tune_distributed_tpu``:
    what the package's own import graph costs, with every third-party module
    it is the first to pull in). None where the program made no such span."""
    section = section_of(sources)
    if section is None:
        return None
    package = spec["module"]
    found = [e - s for span, s, e in within_setup(section) if span["name"] == "import"
             and (span.get("module") == package or span.get("module", "").startswith(package + "."))]
    return sum(found) / 1e9 if found else None


def gaps(section):
    """The stretches of ``setup`` that no span covers, longest first:
    [(seconds, start in seconds since the root's start)]."""
    root = section["spans"][0]
    _, merged = union((s, e) for _, s, e in within_setup(section))
    edges = [root["start_ns"]] + [x for pair in merged for x in pair] + [root["end_ns"]]
    return sorted((((hi - lo) / 1e9, (lo - root["start_ns"]) / 1e9) for lo, hi in zip(edges[0::2], edges[1::2])
                   if hi > lo), reverse=True)


def longest_gap_s(sources, spec):
    """The longest stretch of ``setup`` under no span: with
    ``setup_spanned_pct`` the recorder's guard, a slow phase that a later PR
    adds outside every span shows here. Read only from a program whose
    recorder starts with the process (it makes ``process/before_recorder``):
    the parent's longest gap is its whole start, and says nothing; 0.0 where
    the spans leave no gap."""
    section = section_of(sources)
    if section is None or not any(span["name"] == BEFORE_RECORDER for span in section["spans"]):
        return None
    found = gaps(section)
    return found[0][0] if found else 0.0
