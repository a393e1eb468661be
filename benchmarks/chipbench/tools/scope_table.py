"""A trace of the train step, by scope, for a first look by hand and for
PERF.md section 5: ``python benchmarks/chipbench/tools/scope_table.py
<trace.xplane.pb or a directory run.py traced into> <num_hidden_layers>
<unfreeze_last_n_layers> [top]``.

Prints, as seconds and as shares of device busy time: the classes of
``readers/scopes.py`` split into forward, backward and recomputed; ``attn``
against ``mlp`` inside the layers; what XLA rematerialized by itself (which
``remat_time_pct.train`` cannot see); each HLO category; then the ``top``
largest operations that fall in no scope, and the largest of all, each with
its ``tf_op``. Reads with the readers' own functions, so what it prints is
what the metrics add up.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import trace, xplane_meta  # noqa: E402
from benchmarks.chipbench.readers import scopes  # noqa: E402


def main(argv):
    path = argv[0] if argv[0].endswith(".pb") else trace.find_xplane(argv[0])
    first_trainable = max(0, int(argv[1]) - int(argv[2]))
    top = int(argv[3]) if len(argv) > 3 else 10
    red = trace.reduce_planes(trace.read_planes(path), chips=1)
    meta = xplane_meta.read(path)
    busy = red["busy_s"]

    def pct(secs):
        return f"{secs:9.4f} s {100.0 * secs / busy:6.2f}%"

    print(f"{path}\nbusy {busy:.4f} s of a window of {red['window_s']:.4f} s; first trainable layer {first_trainable}")
    by_class = scopes.seconds_by_class(red["op_seconds"], meta, first_trainable)
    for cls in (*scopes.CLASSES, None):
        rows = {k: v for k, v in by_class.items() if k[0] == cls}
        fwd = sum(v for (_, b, r), v in rows.items() if not b and not r)
        bwd = sum(v for (_, b, r), v in rows.items() if b and not r)
        remat = sum(v for (_, b, r), v in rows.items() if r)
        print(f"{str(cls):10s} {pct(sum(rows.values()))}   forward {pct(fwd)}   backward {pct(bwd)}   recomputed {pct(remat)}")
    inside, categories = {}, {}
    for name, secs in red["op_seconds"].items():
        m = meta.get(name, {})
        tf_op = m.get("tf_op", "")
        if scopes.classify(tf_op, first_trainable)[0] in ("frozen", "tail"):
            part = next((c for c in ("attn", "mlp") if f"/{c}/" in tf_op), "neither")
            inside[part] = inside.get(part, 0.0) + secs
        cat = m.get("hlo_category", "(none)")
        categories[cat] = categories.get(cat, 0.0) + secs
    print("inside the layers: " + "   ".join(f"{k} {pct(v)}" for k, v in sorted(inside.items())))
    own = sum(secs for name, secs in red["op_seconds"].items() if ".remat" in trace.short_name(name))
    print(f"XLA's own rematerialization (instruction names that hold .remat; no marker in the path): {pct(own)}")
    print("by HLO category:")
    for cat, secs in sorted(categories.items(), key=lambda kv: -kv[1])[:12]:
        print(f"  {pct(secs)}  {cat}")
    ops = sorted(((secs, name) for name, secs in red["op_seconds"].items()), reverse=True)
    unscoped = [(s, n) for s, n in ops if scopes.classify(meta.get(n, {}).get("tf_op", ""), first_trainable)[0] is None]
    for title, rows in ((f"largest {top} operations in no scope", unscoped), (f"largest {top} operations", ops)):
        print(title + ":")
        for secs, name in rows[:top]:
            m = meta.get(name, {})
            print(f"  {pct(secs)}  x{red['op_counts'][name]:<5.0f} {trace.short_name(name):32s} "
                  f"[{m.get('hlo_category', '')}] {m.get('tf_op', '(no tf_op)')}")


if __name__ == "__main__":
    main(sys.argv[1:])
