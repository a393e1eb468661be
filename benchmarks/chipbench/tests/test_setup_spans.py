"""Tests of the set-up readers (``readers/setup.py``) and of ``tools/setup_table.py``, on a CPU:

- intervals: the union of overlapping spans, and self time (a span's duration less what its
  children cover) on nested spans;
- each of the six metrics' readers on a hand-made section, by the metric's own file, and against
  a program without the accessor (one from before the recorder): None, nothing raised;
- ``run.py --rehearse 1 --trace 1`` on the ``sft`` kind prints all six, the three stages add up
  to ``train_step_load_s``, the section is written beside the trace, and ``setup_table.py``
  prints a table from it.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/chipbench/tests/test_setup_spans.py -q``
"""

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
from benchmarks.chipbench.readers import setup  # noqa: E402
from benchmarks.chipbench.tools import setup_table  # noqa: E402

S = 1_000_000_000  # a second, in the spans' unit
METRICS = ("train_step_trace_s", "train_step_lower_s", "train_step_compile_s", "setup_jit_s", "setup_cache_misses",
           "setup_spanned_pct")


def span(id, name, start_s, end_s, parent=0, **attrs):
    return {"id": id, "name": name, "start_ns": int(start_s * S), "end_ns": int(end_s * S), "parent": parent,
            "thread": 1, **attrs}


def section():
    """Set-up of 20 s: imports until 4; a weights program 4 to 6 (its compile a hit); the step's load 8 to 17
    (JAX's trace of the step 8 to 12 with a jitted function's inside it, its lowering 12 to 13, compile 13 to
    16.5, first dispatch 16.5 to 17); a harness span 18 to 19 that makes no program; another program named
    train_step, outside the load; a span that began before set-up's end and ran past it."""
    spans = [
        {"id": 0, "name": "setup", "start_ns": 0, "end_ns": 20 * S, "parent": None, "thread": None},
        span(1, "jit/trace", 4.0, 4.5, fun_name="_make"),
        span(2, "jit/lower", 4.5, 5.0, fun_name="_make"),
        span(3, "jit/compile", 5.0, 6.0, fun_name="_make", cache="hit"),
        span(5, "jit/trace", 8.5, 9.5, parent=4, fun_name="inner"),
        span(7, "jit/trace", 8.0, 12.0, parent=4, fun_name="train_step"),
        span(8, "jit/lower", 12.0, 13.0, parent=4, fun_name="train_step"),
        span(10, "jit/compile", 13.1, 16.4, parent=9, fun_name="train_step", cache="miss"),
        span(9, "train_step/compile", 13.0, 16.5, parent=4, program="train_step", cache="miss"),
        span(11, "train_step/first_dispatch", 16.5, 17.0, parent=4, program="train_step"),
        span(4, "train_step/load", 8.0, 17.0, program="train_step"),
        span(12, "startup/copy", 18.0, 19.0),
        span(13, "jit/lower", 18.2, 18.7, parent=12, fun_name="train_step"),  # the reference's, say: not under the load
    ]
    counters = {"spans": 39, "spans_brief": 27, "spans_dropped": 0, "jit_seconds": 10.8, "compile_requests_use_cache": 2, "cache_hits": 1,
                "cache_misses": 1, "cache_retrieval_time_sec": 0.4, "compile_time_saved_sec": 30.0}
    by_function = [{"fun_name": "train_step", "seconds": 7.3, "spans": 2}, {"fun_name": "_make", "seconds": 2.0, "spans": 3},
                   {"fun_name": "inner", "seconds": 1.0, "spans": 1}]
    return {"spans": spans, "counters": counters, "by_function": by_function}


SOURCES = {"compile_ledger": {"programs": {"train_step": {"compiles": 1, "compile_s": 8.5}}, "recompiles_after_warmup": 0},
           "end_to_end": {"setup_s": 20.0, "train_tokens_per_s": 1.0}}


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
    assert module == "readers.setup"
    return getattr(setup, func)(src, s)


def test_union_and_self_time_on_nested_spans():
    assert setup.union([(0, 4 * S), (2 * S, 6 * S), (8 * S, 9 * S), (9 * S, 9 * S)]) == (7.0, [[0, 6 * S], [8 * S, 9 * S]])
    load, below = [(8 * S, 17 * S)], [(8 * S, 12 * S), (12 * S, 13 * S), (13 * S, int(16.5 * S)), (int(16.5 * S), 17 * S)]
    assert setup.self_seconds(load, below) == pytest.approx(0.0)
    assert setup.self_seconds([(13 * S, int(16.5 * S))], [(int(13.1 * S), int(16.4 * S))]) == pytest.approx(0.2)
    # two overlapping children (a jitted function inside a jitted function) cover their union, once
    assert setup.self_seconds([(8 * S, 12 * S)], [(int(8.5 * S), int(9.5 * S)), (9 * S, 11 * S)]) == pytest.approx(1.5)
    # a child that sticks out of its parent covers only what lies inside it; no children, the whole duration
    assert setup.self_seconds([(8 * S, 12 * S)], [(11 * S, 14 * S)]) == pytest.approx(3.0)
    assert setup.self_seconds([(8 * S, 12 * S)], []) == pytest.approx(4.0)


@pytest.mark.parametrize("name, want", [
    ("train_step_trace_s", 4.0),       # the step's own trace: not the jitted function's inside it
    ("train_step_lower_s", 1.0),       # under the load: not the other program of the same name
    ("train_step_compile_s", 3.5),
    ("setup_jit_s", 2.0 + 9.0 + 0.5),  # the weights program, the step's load, the late lowering; not the harness span
    ("setup_cache_misses", 1),
    ("setup_spanned_pct", 100.0 * (2.0 + 9.0 + 1.0) / 20.0),
])
def test_each_reader_on_a_hand_made_section(name, want, program):
    assert read(name) == pytest.approx(want)
    s = spec(name)
    assert s["layer"] == "runtime" and s["moves"] == "setup_s" and s["source"] == "program_counter" and s["what"]


@pytest.mark.parametrize("name", METRICS)
def test_each_reader_returns_none_from_a_program_without_the_recorder(name, monkeypatch):
    from llm_fine_tune_distributed_tpu.observe import xla

    monkeypatch.delattr(xla.CompileLedger, "setup")  # the parent's ledger: programs, totals, no set-up
    assert read(name) is None
    assert read(name, {"end_to_end": {"setup_s": 1.0}}) is None
    assert read(name, {"compile_ledger": None}) is None


def test_the_three_stages_add_up_to_the_load_and_the_share_stays_under_100(program):
    stages = sum(read(n) for n in ("train_step_trace_s", "train_step_lower_s", "train_step_compile_s"))
    assert stages == pytest.approx(SOURCES["compile_ledger"]["programs"]["train_step"]["compile_s"])
    # a span that runs past the end of set-up counts only as far as set-up lasts
    program["spans"].append(span(14, "startup/late", 19.5, 30.0))
    assert read("setup_spanned_pct") == pytest.approx(100.0 * 12.5 / 20.0)
    # without the harness's setup_s there is no share; the seconds stand
    assert read("setup_spanned_pct", {}) is None and read("train_step_lower_s", {}) == pytest.approx(1.0)
    # set-up that never ended (mark_warm() not called) has nothing to read against
    program["spans"][0]["end_ns"] = None
    assert all(read(name) is None for name in METRICS)


def test_benchmark_json_lists_the_six_for_every_training_cell():
    bench = run.load_json(ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(METRICS)
    for name in METRICS:
        m = entries[name]
        assert m["moves"] == "setup_s" and m["layer"] == "runtime" and m["source"] == "program_counter"
        assert m["workloads"] == cells and m["unit"] == spec(name)["unit"]
        assert m["better"] == ("higher" if name == "setup_spanned_pct" else "lower")


def test_the_table_from_a_hand_made_section():
    text = setup_table.table(dict(section(), setup_s=20.0), rows=2)
    lines = text.splitlines()
    assert lines[0] == ("setup_s 20.000 s; the span setup 20.000 s; 39 spans, 12 kept, 27 under a millisecond "
                        "(counted, not kept), 0 dropped")
    assert "1 hits, 1 misses (entries written)" in lines[1]
    rows = {ln.split()[0]: ln.split() for ln in lines if ln.strip().startswith(("train_step/", "jit/", "startup/"))}
    assert rows["train_step/load"][1:4] == ["1", "9.000", "0.000"]  # nine seconds, all of them in its stages
    assert rows["train_step/compile"][1:4] == ["1", "3.500", "0.200"]
    order = [ln.strip().split()[0] for ln in lines[4:] if ln.startswith(("jit/", "train_step/", "startup/", "  "))]
    assert order[:4] == ["jit/trace", "jit/lower", "jit/compile", "train_step/load"]  # by first start, parents first
    under_load = order[order.index("train_step/load") + 1:order.index("startup/copy")]
    assert under_load == ["jit/trace", "jit/lower", "train_step/compile", "jit/compile", "train_step/first_dispatch"]
    assert any(ln.startswith("every span (setup_spanned_pct)") and ln.rstrip().endswith("60.00%") for ln in lines)
    dearest = lines[lines.index(next(ln for ln in lines if ln.startswith("the 2 dearest"))) + 1]
    assert dearest.split()[-1] == "train_step" and "0/1/0" in dearest  # its compile missed
    gaps = lines[lines.index(next(ln for ln in lines if ln.startswith("the 2 longest gaps"))) + 1:]
    assert "8.000 s in 4 gaps" in text
    assert gaps[0].split() == ["0.000", "s", "4.000", "s"] and gaps[1].split() == ["6.000", "s", "2.000", "s"]


def test_a_traced_rehearsal_prints_all_six_and_the_tool_prints_its_table():
    cell = "smollm3-3b.sft-1k-full"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # tests/conftest.py asks for eight virtual devices; the cell is written for one
    out = subprocess.run(
        [sys.executable, "benchmarks/chipbench/run.py", "--workload", cell, "--seed", str(2**31 + 38), "--seconds", "2",
         "--trace", "1", "--rehearse", "1"], cwd=ROOT, capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] is True and set(METRICS) <= set(got)
    stages = got["train_step_trace_s"] + got["train_step_lower_s"] + got["train_step_compile_s"]
    assert stages == pytest.approx(got["train_step_load_s"], abs=0.2)  # JAX's events leave out the call's own handling
    assert got["train_step_load_s"] <= got["setup_jit_s"] and 0.0 < got["setup_spanned_pct"] <= 100.0
    assert got["recompiles_in_window.train"] == 0
    dump = os.path.join(ROOT, ".chipbench_trace", cell, setup.DUMP)
    with open(dump) as f:
        section = json.load(f)
    names = {s["name"] for s in section["spans"]}
    assert {"setup", "train_step/load", "train_step/compile", "train_step/first_dispatch", "jit/trace", "jit/lower",
            "jit/compile"} <= names
    assert section["counters"]["spans_dropped"] == 0 and section["setup_s"] > 0
    assert all({"id", "name", "start_ns", "end_ns", "parent"} <= set(s) for s in section["spans"])
    assert section["counters"]["cache_misses"] == got["setup_cache_misses"]
    tool = subprocess.run([sys.executable, "benchmarks/chipbench/tools/setup_table.py", os.path.dirname(dump), "5"],
                          cwd=ROOT, capture_output=True, text=True, env=env, timeout=300)
    assert tool.returncode == 0, tool.stderr[-2000:]
    assert "train_step/compile" in tool.stdout and "every span (setup_spanned_pct)" in tool.stdout
    assert "longest gaps no span covers" in tool.stdout and "dearest functions" in tool.stdout
