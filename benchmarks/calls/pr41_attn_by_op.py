"""PR 41: a traced step's operations under ``attn`` (a softmax layer's mixer), ms a step, by what they are: the flash
kernels; the IN pass's kernels (``attn_in_fwd``, ``attn_in_bwd``: calls a step and ms a call beside their bytes at the
HBM peak); whatever else lies under the scope ``attn_in`` (the XLA form: rope's ``slice_negate_fusion``, the q/k norms);
the gate, the output norm; the projections' products; layout copies (HLO category ``data formatting``) and the rest.
Then the step's copies in NO scope (XLA's own relayouts carry no ``tf_op``) and rope's fusion wherever it lies.

    python benchmarks/calls/pr41_attn_by_op.py .chipbench_trace/<cell> [largest]
"""
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.chipbench import trace, xplane_meta  # noqa: E402

HBM_BYTES_PER_S = 819e9
KINDS = ("flash kernels", "attn_in kernels", "attn_in, XLA: rope (slice_negate_fusion)", "attn_in, XLA: q/k norms", "attn_in, XLA: other",
         "attn_gate", "out_norm", "products (path ends dot_general)", "layout copies (data formatting)", "rest")


def kind_of(name, tf_op, category):
    parts = re.sub(r"\([^/]*", "", tf_op).split("/")  # (jvp(layer3) and the like are no scopes)
    if "flash_attention" in tf_op:
        return KINDS[0]
    if "attn_in" in parts:
        if "pallas_call" in tf_op:
            return KINDS[1]
        return KINDS[2] if "slice_negate" in name else KINDS[3] if "qk_norm" in parts else KINDS[4]
    if "slice_negate" in name:
        return KINDS[2]
    if "qk_norm" in parts:
        return KINDS[3]
    if "attn_gate" in parts:
        return KINDS[5]
    if "out_norm" in parts:
        return KINDS[6]
    if tf_op.endswith("dot_general"):
        return KINDS[7]
    return KINDS[8] if category == "data formatting" else KINDS[9]


def main(argv) -> int:
    path = trace.find_xplane(argv[0])
    red, meta = trace.reduce_planes(trace.read_planes(path), chips=1), xplane_meta.read(path)
    once = [red["op_counts"][n] for n in red["op_seconds"] if "/optimizer/" in meta.get(n, {}).get("tf_op", "")]
    steps = float(max(set(once), key=once.count))
    table, kernels, largest, bare_copies, rope = {}, {}, [], [], 0.0
    for name, secs in red["op_seconds"].items():
        m = meta.get(name, {})
        tf_op = m.get("tf_op", "").split(";", 1)[0].rstrip(":")
        short = trace.short_name(name)
        if "slice_negate" in short:
            rope += secs
        if not tf_op and m.get("hlo_category") == "data formatting":
            bare_copies.append((secs, red["op_counts"][name], short))
        if "/attn/" not in tf_op + "/":
            continue
        kind = kind_of(short, tf_op, m.get("hlo_category"))
        pass_ = "recomputed" if "rematted_computation" in tf_op else "backward" if "transpose(" in tf_op else "forward"
        table.setdefault(kind, {"forward": 0.0, "backward": 0.0, "recomputed": 0.0})[pass_] += secs
        if kind == KINDS[1]:
            entry = kernels.setdefault(re.sub(r"\.\d+$", "", short), [0.0, 0.0])
            entry[0] += secs
            entry[1] += red["op_counts"][name]
        if kind in KINDS[2:5] + KINDS[8:]:
            largest.append((secs, red["op_counts"][name], short, tf_op))
    print(f"{path}\n{steps:.0f} steps, {1e3 * red['busy_s'] / steps:.1f} ms busy a step; under attn, ms a step")
    for kind in KINDS:
        ms = {p: 1e3 * v / steps for p, v in table.get(kind, {"forward": 0.0, "backward": 0.0, "recomputed": 0.0}).items()}
        print(f"{kind:44s} forward {ms['forward']:8.2f}  backward {ms['backward']:8.2f}  recomputed {ms['recomputed']:8.2f}  all {sum(ms.values()):8.2f}")
    for kernel, (secs, calls) in sorted(kernels.items()):
        print(f"  {kernel}: {calls / steps:.0f} calls a step, {1e3 * secs / calls:.3f} ms a call")
    print(f"rope's fusion (slice_negate*) anywhere in the step: {1e3 * rope / steps:.2f} ms a step")
    total = sum(s for s, _, _ in bare_copies)
    print(f"data formatting with no tf_op (XLA's own relayouts): {1e3 * total / steps:.2f} ms a step in {len(bare_copies)} operations; the largest:")
    for secs, calls, short in sorted(bare_copies, reverse=True)[:6]:
        print(f"  {1e3 * secs / steps:8.2f} ms a step x{calls / steps:<5.1f} {1e3 * secs / calls:.3f} ms a call  {short}")
    print("the largest under attn that are neither kernel, product, gate nor output norm:")
    for secs, calls, short, tf_op in sorted(largest, reverse=True)[:int(argv[1]) if len(argv) > 1 else 16]:
        print(f"  {1e3 * secs / steps:8.2f} ms a step x{calls / steps:<5.1f} {short:34s} {tf_op[-110:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
