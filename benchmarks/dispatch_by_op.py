#!/usr/bin/env python3
"""An expert layer's device time by operation, from a trace of the train step
that ``benchmarks/chipbench/run.py --trace 1`` wrote: every device operation
whose path lies under ``mlp/router`` or ``mlp/experts`` (``observe/xla.py``
``STEP_SCOPES``), grouped by what it is, in ms a step, forward, backward and
recomputed apart. ``moe_dispatch_busy_pct.train`` is the sum of these lines
but the grouped products'; this is the table to read BEFORE predicting what a
change to the dispatch returns (PERF.md, PRs 29 and 31).

    python benchmarks/dispatch_by_op.py <trace.xplane.pb or .chipbench_trace/<cell>> <steps traced, or auto> [largest]

(``auto``: how often most operations under the ``optimizer`` scope ran, once a step.)

Reads with the benchmark's own readers; a builder's tool, nothing runs it.
"""
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import trace, xplane_meta  # noqa: E402

# what an operation is, by the first pattern its path (``tf_op``) or its name matches
KINDS = (
    ("grouped products (gmm, tgmm)", r"jit\(t?gmm\)/pallas_call|ragged_dot_general"),
    ("rows into tokens: kernel (sum_held_rows)", r"sum_held_rows/pallas_call"),
    ("rows into tokens: the kernel's plan", r"jit\(_sum_held_rows\)"),
    ("gather", r"(^|/)gather"),
    ("sort", r"(^|/)sort|jit\(argsort\)"),
    ("top_k", r"top_k"),
    ("router product", r"(^|/)dot_general"),
    ("grouped products' metadata (gmm's wrapper)", r"jit\(t?gmm\)"),
    ("overflow chunks' cond and loop, copies", r"(^|/)(while|cond)|^copy"),
)


def kind_of(inside: str, name: str) -> str:
    """``inside``: the operation's path below its ``router`` or ``experts``
    scope (the whole path holds the step's own ``while`` and ``checkpoint``)."""
    for kind, pattern in KINDS:
        if re.search(pattern, inside) or re.search(pattern, trace.short_name(name)):
            return kind
    root = inside.rstrip(":").rsplit("/", 1)[-1] or "?"
    return f"fusion whose root is {root}"  # elementwise work and reductions: XLA names a fusion's path after its root


def main(argv) -> int:
    path = argv[0] if argv[0].endswith(".pb") else trace.find_xplane(argv[0])
    red = trace.reduce_planes(trace.read_planes(path), chips=1)
    meta = xplane_meta.read(path)
    if argv[1] == "auto":
        once = [red["op_counts"][n] for n in red["op_seconds"] if "/optimizer/" in meta.get(n, {}).get("tf_op", "")]
        steps = float(max(set(once), key=once.count))
    else:
        steps = float(argv[1])
    table, largest = {}, []
    for name, secs in red["op_seconds"].items():
        tf_op = meta.get(name, {}).get("tf_op", "")
        scope = next((s for s in ("router", "experts") if f"/mlp/{s}/" in tf_op or tf_op.endswith(f"/mlp/{s}")), None)
        if scope is None:
            continue
        pass_ = "recomputed" if "rematted_computation" in tf_op else "backward" if "transpose(" in tf_op else "forward"
        key = (scope, kind_of(tf_op.split(f"/mlp/{scope}", 1)[1].lstrip("/"), name))
        row = table.setdefault(key, {"forward": 0.0, "backward": 0.0, "recomputed": 0.0, "calls": 0.0})
        row[pass_] += secs
        row["calls"] += red["op_counts"][name]
        largest.append((secs, red["op_counts"][name], trace.short_name(name), tf_op))
    print(f"{path}\nbusy {red['busy_s']:.4f} s of {red['window_s']:.4f} s; {steps:.0f} steps; ms a step")
    print(f"{'scope':8s} {'operation':48s} {'forward':>9s} {'backward':>9s} {'recomp.':>9s} {'all':>9s} {'% busy':>7s} {'calls a step':>12s}")
    total = 0.0
    for (scope, kind), row in sorted(table.items(), key=lambda kv: -sum(kv[1][p] for p in ("forward", "backward", "recomputed"))):
        ms = {p: 1e3 * row[p] / steps for p in ("forward", "backward", "recomputed")}
        whole = sum(ms.values())
        total += whole
        print(f"{scope:8s} {kind:48s} {ms['forward']:9.2f} {ms['backward']:9.2f} {ms['recomputed']:9.2f} {whole:9.2f} "
              f"{100 * whole * steps / 1e3 / red['busy_s']:7.2f} {row['calls'] / steps:12.1f}")
    print(f"{'':8s} {'all of router + experts':48s} {'':29s} {total:9.2f} {100 * total * steps / 1e3 / red['busy_s']:7.2f}")
    print("largest operations under router + experts:")
    for secs, calls, name, tf_op in sorted(largest, reverse=True)[:int(argv[2]) if len(argv) > 2 else 25]:
        print(f"  {1e3 * secs / steps:8.2f} ms a step x{calls / steps:<6.1f} {name:36s} {tf_op[-150:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
