"""PR 50: what runs under the scope ``ssd_scan`` (or another: ``ssd_in``) in a trace of the Granite cell's step that
``run.py --trace 1`` wrote, by operation: the two custom calls (ms a call and calls a step, first, recomputed and
backward apart) and every XLA operation beside them, largest first; then the calls ``readers/gdn.seconds_under``
counts for ``ssd_scan_fwd_roofline_pct`` (a layer's calls are the count most of its operations under the scope share),
layer by layer where that is not the sweeps' own count. Reads with the benchmark's own readers; a builder's tool,
nothing runs it.

    python benchmarks/calls/pr50_scan_by_op.py <trace.xplane.pb or .chipbench_trace/<cell>> [largest] [scope]
"""
import os
import sys

sys.path.insert(0, os.getcwd())

from benchmarks.chipbench import trace, xplane_meta  # noqa: E402
from benchmarks.chipbench.readers import gdn, scopes  # noqa: E402


def main(argv) -> int:
    path = argv[0] if argv[0].endswith(".pb") else trace.find_xplane(argv[0])
    red = trace.reduce_planes(trace.read_planes(path), chips=1)
    meta = xplane_meta.read(path)
    once = [red["op_counts"][n] for n in red["op_seconds"] if "/optimizer/" in meta.get(n, {}).get("tf_op", "")]
    steps = float(max(set(once), key=once.count))
    scope = argv[2] if len(argv) > 2 else "ssd_scan"
    kernels, others, by_pass = {}, [], {"forward": 0.0, "recomputed": 0.0, "backward": 0.0}
    for name, secs in red["op_seconds"].items():
        tf_op = meta.get(name, {}).get("tf_op", "").split(";", 1)[0]
        if f"/{scope}" not in tf_op:
            continue
        pass_ = "recomputed" if "rematted_computation" in tf_op else "backward" if "transpose(" in tf_op else "forward"
        kernel = next((k for k in ("ssd_scan_fwd", "ssd_scan_bwd") if f"/{k}" in tf_op), None)
        if kernel:
            row = kernels.setdefault((kernel, pass_), [0.0, 0])
            row[0] += secs
            row[1] += red["op_counts"][name]
        else:
            by_pass[pass_] += secs
            others.append((secs, red["op_counts"][name], pass_, trace.short_name(name), tf_op))
    print(f"{path}\nbusy {red['busy_s']:.4f} s of {red['window_s']:.4f} s; {steps:.0f} steps")
    swept = 0.0
    for (kernel, pass_), (secs, calls) in sorted(kernels.items()):
        swept += secs
        print(f"{kernel:14s} {pass_:10s} {1e3 * secs / calls:7.3f} ms a call x {calls / steps:5.1f} a step = {1e3 * secs / steps:7.2f} ms a step")
    beside = sum(by_pass.values())
    print(f"the sweeps {1e3 * swept / steps:.2f} ms a step; XLA operations beside them under {scope} {1e3 * beside / steps:.2f} ms a step "
          f"({', '.join(f'{p} {1e3 * s / steps:.2f}' for p, s in by_pass.items())}); the scope {1e3 * (swept + beside) / steps:.2f} ms a step, "
          f"{100 * (swept + beside) / red['busy_s']:.2f}% of busy")
    for secs, calls, pass_, name, tf_op in sorted(others, reverse=True)[:int(argv[1]) if len(argv) > 1 else 20]:
        print(f"  {1e3 * secs / steps:8.3f} ms a step x{calls / steps:<6.1f} {pass_:10s} {name:34s} {tf_op[-120:]}")
    # the reader's count of forward calls, as readers/ssd._forward_under makes it: the first and the recomputed pass apart
    tf = lambda name: meta.get(name, {}).get("tf_op", "")  # noqa: E731
    passes = {"forward": {n: v for n, v in red["op_seconds"].items() if scopes.BACKWARD not in tf(n) and scopes.RECOMPUTED not in tf(n)},
              "recomputed": {n: v for n, v in red["op_seconds"].items() if scopes.RECOMPUTED in tf(n)}}
    for pass_, ops in passes.items():
        secs, calls = gdn.seconds_under(ops, red["op_counts"], meta, scope)
        print(f"readers/gdn.seconds_under, {pass_}: {1e3 * (secs or 0.0) / steps:.2f} ms a step, {calls / steps:.1f} calls a step")
        by_layer = {}
        for name in ops:
            parts = [scopes.bare(c) for c in tf(name).split(";", 1)[0].rsplit(":", 1)[0].split("/")]
            if scope in parts and gdn.LOOP not in parts[parts.index(scope) + 1:]:
                layer = next((c for c in parts if c.startswith("layer") and c[5:].isdigit()), None)
                by_layer.setdefault(layer, []).append((red["op_counts"].get(name, 0.0), trace.short_name(name)))
        for layer, found in sorted(by_layer.items(), key=lambda kv: str(kv[0])):
            counts = [c for c, _ in found]
            if max(set(counts), key=counts.count) != 2 * steps:
                print(f"  {layer}: {found}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
