"""Where a run's set-up went, for a first look by hand and for PERF.md section 5:
``python benchmarks/chipbench/tools/setup_table.py <setup_spans.json or a directory
run.py traced into> [rows]``.

A traced run writes the program's set-up section (``observe/xla.py``
``CompileLedger.setup()``: the spans and counters of the process from its start to
``CompileLedger.mark_warm()``) beside its device trace, with the run's ``setup_s`` (``readers/setup.py``). This
prints, from that file alone:

- the header: ``setup_s``, the root span's own length, how many spans set-up made,
  how many the recorder kept, counted without keeping (a jitted function's stage
  under a millisecond) and dropped (a full list), the file's bytes; the persistent cache's
  requests, hits, misses (JAX counts the entries it writes), seconds of retrieval and seconds JAX says the
  hits saved (``setup_cache_misses`` above 0: a cold run);
- **by phase**: every span grouped by the path of names above it
  (``train_step/load > train_step/compile > jit/compile``), in order of first start:
  spans, seconds (the union of the group's intervals: JAX reports a jitted function
  inside a jitted function as two overlapping spans), self seconds (less what the
  spans below cover), share of ``setup_s``. JAX's ``jit/*`` spans lie under the span
  that was open on their thread: ``jit/trace`` and ``jit/lower`` directly under
  ``train_step/load`` are the two stages of the step's one ``fn.lower(...)`` call (the
  ``jit/trace`` row is the union of the step's own trace and those of the jitted
  functions inside it; ``train_step_trace_s`` reads the step's own); ``jit/*`` rows
  directly under ``setup`` are the programs the harness and the state's builders make
  outside any instrumented program;
- the ``rows`` (default 10) dearest functions by the seconds of their ``jit/*``
  spans (a function that calls jitted functions counts their tracing too), with
  how many of their compiles hit and missed;
- **the gaps no span covers**, longest first, with their start (seconds since the
  process started) and length: interpreter start, imports and the TPU runtime's
  start before the first span; weights drawn on the chip, host copies and the first
  steps' execution between spans. ``setup_spanned_pct`` is 100 less their share.

Reads with the readers' own functions, so what it prints is what the metrics add up.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)

from benchmarks.chipbench.readers import setup  # noqa: E402


def table(section: dict, rows: int = 10) -> str:
    spans = section["spans"]
    root, by_id = spans[0], {s["id"]: s for s in spans}
    length = (root["end_ns"] - root["start_ns"]) / 1e9
    setup_s = section.get("setup_s") or length
    c = section["counters"]
    out = [
        f"setup_s {setup_s:.3f} s; the span setup {length:.3f} s; {c['spans']} spans, {len(spans) - 1} kept, "
        f"{c['spans_brief']} under a millisecond (counted, not kept), {c['spans_dropped']} dropped",
        f"persistent cache: {c['compile_requests_use_cache']} requests, {c['cache_hits']} hits, {c['cache_misses']} "
        f"misses (entries written), retrieval {c['cache_retrieval_time_sec']:.3f} s, saved by the hits "
        f"{c['compile_time_saved_sec']:.1f} s",
        "", f"{'phase':58s} {'spans':>6s} {'seconds':>9s} {'self':>9s} {'of setup_s':>10s}",
    ]

    def path(span):
        names = [span["name"]]
        while span["parent"] not in (None, 0) and span["parent"] in by_id:
            span = by_id[span["parent"]]
            names.append(span["name"])
        return tuple(reversed(names))

    groups = {}
    for span, start, end in setup.within_setup(section):
        g = groups.setdefault(path(span), {"first": start, "intervals": []})
        g["intervals"].append((start, end))
    # parents before their children, siblings in order of first start
    ordered = sorted(groups, key=lambda names: [groups[names[:i + 1]]["first"] if names[:i + 1] in groups else 0
                                                for i in range(len(names))])
    for names in ordered:
        g = groups[names]
        secs = setup.union(g["intervals"])[0]
        below = [iv for other, h in groups.items() if other[:-1] == names for iv in h["intervals"]]
        label = "  " * (len(names) - 1) + names[-1]
        out.append(f"{label:58s} {len(g['intervals']):6d} {secs:9.3f} {setup.self_seconds(g['intervals'], below):9.3f} "
                   f"{100.0 * secs / setup_s:9.2f}%")
    covered, merged = setup.union((s, e) for _, s, e in setup.within_setup(section))
    out.append(f"{'every span (setup_spanned_pct)':58s} {len(spans) - 1:6d} {covered:9.3f} {'':9s} {100.0 * covered / setup_s:9.2f}%")

    verdicts = {}
    for span in spans[1:]:
        if span["name"] == "jit/compile":
            v = verdicts.setdefault(span["fun_name"], {"hit": 0, "miss": 0, "off": 0})
            v[span.get("cache", "off")] += 1
    out += ["", f"the {rows} dearest functions (seconds of their jit/* spans; compiles hit / missed / not cached):"]
    for f in section["by_function"][:rows]:
        v = verdicts.get(f["fun_name"], {"hit": 0, "miss": 0, "off": 0})
        out.append(f"  {f['seconds']:9.3f} s  {f['spans']:5d} spans  {v['hit']}/{v['miss']}/{v['off']}  {f['fun_name']}")

    edges = [root["start_ns"]] + [x for pair in merged for x in pair] + [root["end_ns"]]
    gaps = sorted(((hi - lo, lo) for lo, hi in zip(edges[0::2], edges[1::2]) if hi > lo), reverse=True)
    out += ["", f"the {rows} longest gaps no span covers ({sum(g for g, _ in gaps) / 1e9:.3f} s in {len(gaps)} gaps): "
                "start since the process started, length"]
    for gap, lo in gaps[:rows]:
        out.append(f"  {(lo - root['start_ns']) / 1e9:9.3f} s  {gap / 1e9:9.3f} s")
    return "\n".join(out)


def main(argv):
    path = argv[0] if argv[0].endswith(".json") else os.path.join(argv[0], setup.DUMP)
    with open(path) as f:
        section = json.load(f)
    print(f"{path} ({os.path.getsize(path)} bytes)")
    print(table(section, int(argv[1]) if len(argv) > 1 else 10))


if __name__ == "__main__":
    main(sys.argv[1:])
