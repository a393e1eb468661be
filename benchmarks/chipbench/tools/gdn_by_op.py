"""A linear-attention layer's device time by part, from a trace of the train
step that ``run.py --trace 1`` wrote: every device operation whose path lies
under ``linear_attn`` (``observe/xla.py`` ``STEP_SCOPES``), by scope inside it
(``gdn_conv``, ``gdn_scan``, ``gdn_gate_norm``, else the projections and what
stands between) and, inside ``gdn_scan``, by what it is: the scan itself (the
``while`` and its body), the triangular inverse
(``triangular_solve``), the other matrix products (grams, U, W), the rest
(decays, masks, layouts). ms a step; forward, backward and recomputed apart.

    python benchmarks/chipbench/tools/gdn_by_op.py <trace.xplane.pb or .chipbench_trace/<cell>> <steps traced, or auto> [largest]

Reads with the benchmark's own readers; a builder's tool, nothing runs it.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import trace, xplane_meta  # noqa: E402

PARTS = ("gdn_conv", "gdn_scan", "gdn_gate_norm")


def part_of(tf_op: str) -> str:
    part = next((p for p in PARTS if f"/{p}/" in tf_op or tf_op.endswith(f"/{p}")), None)
    if part != "gdn_scan":
        return part or "projections and the rest of the mixer"
    inside = tf_op.split("/gdn_scan", 1)[1]
    if "/while" in inside:
        return "gdn_scan: the scan (while and body)"
    if "triangular_solve" in inside or "triangular-solve" in inside:
        return "gdn_scan: the triangular inverse"
    if "dot_general" in inside:
        return "gdn_scan: other products (grams, U, W)"
    return "gdn_scan: decays, masks, layouts"


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
        tf_op = meta.get(name, {}).get("tf_op", "").split(";", 1)[0]
        if "/linear_attn" not in tf_op:
            continue
        pass_ = "recomputed" if "rematted_computation" in tf_op else "backward" if "transpose(" in tf_op else "forward"
        row = table.setdefault(part_of(tf_op), {"forward": 0.0, "backward": 0.0, "recomputed": 0.0})
        row[pass_] += secs
        largest.append((secs, red["op_counts"][name], trace.short_name(name), tf_op))
    print(f"{path}\nbusy {red['busy_s']:.4f} s of {red['window_s']:.4f} s; {steps:.0f} steps; ms a step, all linear layers")
    print(f"{'part':48s} {'forward':>9s} {'backward':>9s} {'recomp.':>9s} {'all':>9s} {'% busy':>7s}")
    total = 0.0
    for part, row in sorted(table.items(), key=lambda kv: -sum(kv[1].values())):
        ms = {p: 1e3 * v / steps for p, v in row.items()}
        whole = sum(ms.values())
        total += whole
        print(f"{part:48s} {ms['forward']:9.2f} {ms['backward']:9.2f} {ms['recomputed']:9.2f} {whole:9.2f} "
              f"{100 * whole * steps / 1e3 / red['busy_s']:7.2f}")
    print(f"{'all of linear_attn':48s} {'':29s} {total:9.2f} {100 * total * steps / 1e3 / red['busy_s']:7.2f}")
    print("largest operations under linear_attn:")
    for secs, calls, name, tf_op in sorted(largest, reverse=True)[:int(argv[2]) if len(argv) > 2 else 25]:
        print(f"  {1e3 * secs / steps:8.2f} ms a step x{calls / steps:<7.1f} {name:36s} {tf_op[-150:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
