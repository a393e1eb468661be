"""Tests of the readers of the spans no call site makes (``readers/startup.py``, and two data files over
``readers.setup.span_seconds``) and of ``tools/import_table.py``, on a CPU:

- each of the four metrics on a hand-made section, by the metric's own file; None from a section without the
  spans (the parent's program has the recorder and makes none of them) and from a program without the recorder;
- ``BENCHMARK.json`` lists the four for every training cell, by name;
- the table from the hand-made section;
- ``run.py --rehearse 1 --trace 1`` prints all four, they lie under ``setup_s``, and the tool prints its table
  from the section the run wrote.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/chipbench/tests/test_startup_spans.py -q``
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import run  # noqa: E402
from benchmarks.chipbench.readers import setup, startup  # noqa: E402
from benchmarks.chipbench.tools import import_table  # noqa: E402

S = 1_000_000_000  # a second, in the spans' unit
METRICS = ("setup_before_recorder_s", "setup_import_s", "setup_import_program_s", "setup_longest_gap_s")
PACKAGE = "llm_fine_tune_distributed_tpu"


def span(id, name, start_s, end_s, parent=0, **attrs):
    return {"id": id, "name": name, "start_ns": int(start_s * S), "end_ns": int(end_s * S), "parent": parent,
            "thread": 1, **attrs}


def section(with_the_spans=True):
    """Set-up of 40 s. Before the recorder until 12; the harness's kind 12.5 to 13.5; the package's ``train`` 14 to 20
    with orbax 15 to 19 below it and tensorstore 16 to 18 below that; another module of the package 20 to 20.5; an
    annotate span 21 to 22 with a package import 21.2 to 21.8 inside it; the step's load 24 to 31; an import that
    began before set-up's end and ran past it. ``with_the_spans=False``: what the parent records of the same run."""
    spans = [
        {"id": 0, "name": "setup", "start_ns": 0, "end_ns": 40 * S, "parent": None, "thread": None},
        span(1, "process/before_recorder", 0.0, 12.0, thread=None, jax_imported=True, backend_started=True, cpu_s=3.5),
        span(2, "import", 12.5, 13.5, module="benchmarks.chipbench.kind_sft", cpu_s=0.9),
        span(5, "import/nested", 16.0, 18.0, parent=4, module="tensorstore", cpu_s=0.4),
        span(4, "import/nested", 15.0, 19.0, parent=3, module="orbax.checkpoint", cpu_s=2.5),
        span(3, "import", 14.0, 20.0, module=f"{PACKAGE}.train", cpu_s=4.0),
        span(6, "import", 20.0, 20.5, module=f"{PACKAGE}.ops.ssd", cpu_s=0.5),
        span(8, "import", 21.2, 21.8, parent=7, module=PACKAGE + ".parallel.freeze", cpu_s=0.6, error="ImportError"),
        span(7, "startup/weights", 21.0, 22.0),
        span(9, "jit/trace", 24.0, 28.0, parent=10, fun_name="train_step"),
        span(10, "train_step/load", 24.0, 31.0, program="train_step"),
        span(11, "import", 39.0, 45.0, module=f"{PACKAGE}_lookalike", cpu_s=0.1),
    ]
    if not with_the_spans:
        spans = [s for s in spans if s["name"] not in ("process/before_recorder", "import", "import/nested")]
    counters = {"spans": 3011, "spans_brief": 3000, "spans_dropped": 0, "jit_seconds": 4.0,
                "compile_requests_use_cache": 1, "cache_hits": 1,
                "cache_misses": 0, "cache_retrieval_time_sec": 0.4, "compile_time_saved_sec": 30.0}
    return {"spans": spans, "counters": counters, "by_function": [{"fun_name": "train_step", "seconds": 4.0, "spans": 1}]}


SOURCES = {"end_to_end": {"setup_s": 40.0, "train_tokens_per_s": 1.0}}


@pytest.fixture
def program(monkeypatch):
    """The hand-made section in place of the program's; no trace, so nothing is written."""
    made = section()
    monkeypatch.setattr(setup, "program_section", lambda: made)
    monkeypatch.setattr(setup.scopes, "newest_xplane", lambda: None)
    return made


def spec(name):
    return run.load_json(BENCH, "metrics", name + ".json")


def read(name, src=SOURCES):
    s = spec(name)
    module, func = s["reader"].rsplit(".", 1)
    return getattr(importlib.import_module(f"benchmarks.chipbench.{module}"), func)(src, s)


@pytest.mark.parametrize("name, want", [
    ("setup_before_recorder_s", 12.0),
    ("setup_import_s", 1.0 + 6.0 + 0.5 + 0.6 + 1.0),  # not the nested ones; the last as far as set-up lasts
    ("setup_import_program_s", 6.0 + 0.5 + 0.6),      # the package's own: not the harness's kind, not a name that begins alike
    ("setup_longest_gap_s", 8.0),                     # 31 to 39; then 2.0 (22 to 24), 0.5 twice
])
def test_each_reader_on_a_hand_made_section(name, want, program):
    assert read(name) == pytest.approx(want)
    s = spec(name)
    assert s["layer"] == "runtime" and s["moves"] == "setup_s" and s["source"] == "program_counter" and s["what"]
    assert s["unit"] == "s" and s["reader"].split(".")[1] in ("setup", "startup")


@pytest.mark.parametrize("name", METRICS)
def test_each_reader_returns_none_from_a_program_without_the_spans(name, monkeypatch):
    # the parent's program: the recorder, its accessor, none of the spans
    monkeypatch.setattr(setup, "program_section", lambda: section(with_the_spans=False))
    monkeypatch.setattr(setup.scopes, "newest_xplane", lambda: None)
    assert read(name) is None
    # a program from before the recorder; a set-up that never ended
    monkeypatch.setattr(setup, "program_section", lambda: None)
    assert read(name) is None and read(name, {}) is None
    open_ended = section()
    open_ended["spans"][0]["end_ns"] = None
    monkeypatch.setattr(setup, "program_section", lambda: open_ended)
    assert read(name) is None


def test_the_gaps_and_what_the_guard_reads_where_nothing_is_left(program):
    assert startup.gaps(program)[:3] == [(8.0, 31.0), (2.0, 22.0), (0.5, 20.5)]
    assert sum(g for g, _ in startup.gaps(program)) == pytest.approx(40.0 - 28.5)  # setup_spanned_pct is 100 less their share
    assert read("setup_spanned_pct") == pytest.approx(100.0 * 28.5 / 40.0)
    assert read("setup_import_s") + read("setup_before_recorder_s") <= 40.0
    program["spans"].append(span(12, "startup/everything", 0.0, 40.0))
    assert read("setup_longest_gap_s") == 0.0  # a number, not None: the spans are there and leave no gap
    # imports alone do not make the guard speak: it needs the span that says the recorder starts with the process
    program["spans"][:] = [s for s in program["spans"] if s["name"] != "process/before_recorder"]
    assert read("setup_longest_gap_s") is None and read("setup_import_s") is not None


def test_benchmark_json_lists_the_four_for_every_training_cell():
    bench = run.load_json(ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    training = [m for m in bench["end_to_end"] if m["name"] == "train_tokens_per_s"][0]["workloads"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(METRICS) <= set(entries)  # by name: what stands at the list's tail is the next PR's to move
    for name in METRICS:
        m = entries[name]
        assert m["moves"] == "setup_s" and m["layer"] == "runtime" and m["source"] == "program_counter"
        assert set(training) <= set(m["workloads"]) <= set(cells) and m["unit"] == spec(name)["unit"] == "s"
        assert m["better"] == "lower"
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))


def test_the_table_from_a_hand_made_section():
    text = import_table.table(dict(section(), setup_s=40.0), rows=3)
    lines = text.splitlines()
    assert lines[0].startswith("process/before_recorder 12.000 s (cpu 3.500 s; jax imported True, a backend started True)")
    assert lines[1].startswith("setup_s 40.000 s; 5 spans named import, 9.100 s (22.75% of setup_s), 2 nested below them")
    rows = [ln.split() for ln in lines[4:]]
    train = rows[0]
    assert train == ["6.000", "2.000", "4.000", f"{PACKAGE}.train"]  # six seconds, four of them orbax's
    assert lines[5].endswith("  orbax.checkpoint") and rows[1][:3] == ["4.000", "2.000", "2.500"]
    assert lines[6].endswith("    tensorstore") and rows[2][:3] == ["2.000", "2.000", "0.400"]  # indented by depth
    assert rows[3][:2] == ["1.000", "1.000"] and rows[3][3] == "benchmarks.chipbench.kind_sft"
    assert rows[4][3] == f"{PACKAGE}_lookalike" and rows[4][0] == "1.000"  # clipped to set-up's end
    assert lines[9].split()[0] == "1.100" and lines[9].endswith("2 more spans named import")
    by_package = lines[lines.index(next(ln for ln in lines if ln.startswith("spans named import by package"))) + 1:]
    assert by_package[0].split() == ["7.100", PACKAGE]  # setup_import_program_s, whatever is dearer below it
    assert [ln.split()[1] for ln in by_package[1:]] == ["benchmarks", f"{PACKAGE}_lookalike"]
    shown = import_table.table(dict(section(), setup_s=40.0), rows=10, depth=1)
    assert f"{PACKAGE}.parallel.freeze  (ImportError)" in shown
    # one level below a span and no deeper: orbax's row keeps tensorstore's seconds as its own
    assert "tensorstore" not in shown and [ln.split()[:2] for ln in shown.splitlines() if "orbax" in ln] == [["4.000", "4.000"]]
    # the parent's section: a table with nothing in it, and no word of a metric it does not have
    bare = import_table.table(dict(section(with_the_spans=False), setup_s=40.0))
    assert "0 spans named import, 0.000 s" in bare and "setup_import_program_s" not in bare
    assert not bare.startswith("process/before_recorder")


def test_a_traced_rehearsal_prints_all_four_and_the_tool_prints_its_table():
    cell = "granite-4.0-h-micro.sft-8k-ssd-tied-last2"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # tests/conftest.py asks for eight virtual devices; the cell is written for one
    out = subprocess.run(
        [sys.executable, "benchmarks/chipbench/run.py", "--workload", cell, "--seed", str(2**31 + 51), "--seconds", "2",
         "--trace", "1", "--rehearse", "1"], cwd=ROOT, capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] is True and set(METRICS) <= set(got)
    dump = os.path.join(ROOT, ".chipbench_trace", cell, setup.DUMP)
    with open(dump) as f:
        written = json.load(f)
    setup_s = written["setup_s"]
    assert 0.0 < got["setup_before_recorder_s"] < setup_s and 0.0 < got["setup_longest_gap_s"] < setup_s
    assert 0.0 < got["setup_import_program_s"] <= got["setup_import_s"] < setup_s
    # the guard counts the new spans: what they cover and the longest gap cannot both be most of set-up
    assert got["setup_spanned_pct"] >= 100.0 * (got["setup_before_recorder_s"] + got["setup_import_s"]) / setup_s - 0.5
    assert got["setup_longest_gap_s"] <= setup_s * (1.0 - got["setup_spanned_pct"] / 100.0) + 0.05
    first = written["spans"][1]
    assert first["name"] == "process/before_recorder" and first["jax_imported"] and first["backend_started"]
    train = [s for s in written["spans"] if s["name"] == "import" and s.get("module") == f"{PACKAGE}.train"]
    assert len(train) == 1 and train[0]["cpu_s"] > 0.0  # Program(...)'s first import of the package's trainer
    nested = [s["module"] for s in written["spans"] if s["name"] == "import/nested"]
    assert "orbax.checkpoint" in nested and written["counters"]["spans_dropped"] == 0
    tool = subprocess.run([sys.executable, "benchmarks/chipbench/tools/import_table.py", os.path.dirname(dump), "5"],
                          cwd=ROOT, capture_output=True, text=True, env=env, timeout=300)
    assert tool.returncode == 0, tool.stderr[-2000:]
    assert tool.stdout.splitlines()[1].startswith("process/before_recorder ")
    assert f"{PACKAGE}.train" in tool.stdout and "the first line is setup_import_program_s" in tool.stdout
    # the benchmark's own table shows the new phases by itself
    phases = subprocess.run([sys.executable, "benchmarks/chipbench/tools/setup_table.py", os.path.dirname(dump), "3"],
                            cwd=ROOT, capture_output=True, text=True, env=env, timeout=300)
    assert phases.returncode == 0, phases.stderr[-2000:]
    rows = [ln.split()[0] for ln in phases.stdout.splitlines() if ln and not ln.startswith(" ")]
    assert "process/before_recorder" in rows and "import" in rows
