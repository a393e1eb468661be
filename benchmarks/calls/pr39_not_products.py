"""PR 39, step 0 and after: of a traced step's operations under ``linear_attn``, those in neither ``gdn_conv``, ``gdn_scan``
nor ``gdn_gate_norm`` whose path does not end in ``dot_general`` (l2 norms, gates, slices, pads, layout copies), and
the products beside them; ms a step, forward / backward / recomputed, with the largest of the former.

    python benchmarks/calls/pr39_not_products.py .chipbench_trace/<cell> [largest]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.chipbench import trace, xplane_meta  # noqa: E402
from benchmarks.chipbench.tools.gdn_by_op import PARTS  # noqa: E402


def main(argv) -> int:
    path = trace.find_xplane(argv[0])
    red, meta = trace.reduce_planes(trace.read_planes(path), chips=1), xplane_meta.read(path)
    once = [red["op_counts"][n] for n in red["op_seconds"] if "/optimizer/" in meta.get(n, {}).get("tf_op", "")]
    steps = float(max(set(once), key=once.count))
    table, largest = {}, []
    for name, secs in red["op_seconds"].items():
        tf_op = meta.get(name, {}).get("tf_op", "").split(";", 1)[0].rstrip(":")
        if "/linear_attn" not in tf_op or any(f"/{p}/" in tf_op or tf_op.endswith(f"/{p}") for p in PARTS):
            continue
        kind = "products (path ends dot_general)" if tf_op.endswith("dot_general") else "not a product"
        pass_ = "recomputed" if "rematted_computation" in tf_op else "backward" if "transpose(" in tf_op else "forward"
        table.setdefault(kind, {"forward": 0.0, "backward": 0.0, "recomputed": 0.0})[pass_] += secs
        if kind == "not a product":
            largest.append((secs, red["op_counts"][name], trace.short_name(name), tf_op))
    print(f"{path}\n{steps:.0f} steps; under linear_attn and in none of {PARTS}; ms a step")
    for kind, row in table.items():
        ms = {p: 1e3 * v / steps for p, v in row.items()}
        print(f"{kind:36s} forward {ms['forward']:8.2f}  backward {ms['backward']:8.2f}  recomputed {ms['recomputed']:8.2f}  all {sum(ms.values()):8.2f}")
    for secs, calls, name, tf_op in sorted(largest, reverse=True)[:int(argv[1]) if len(argv) > 1 else 20]:
        print(f"  {1e3 * secs / steps:8.2f} ms a step x{calls / steps:<6.1f} {name:34s} {tf_op[-120:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
