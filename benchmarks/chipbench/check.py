"""The comparisons that decide ``correct``. Each number compared has a limit
of its own (``limits/<cell>.json``, set from readings on the chip: PERF.md
gives them), and every run prints each number beside its limit."""

from __future__ import annotations

import math
import statistics


def worst_leaf_gap(program: dict, reference: dict) -> tuple:
    """Largest gap between the program's norm and the reference's, by leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some leaves' gradients are all but zero)."""
    median = statistics.median(reference.values())
    worst, where = 0.0, None
    for path, ref in reference.items():
        got = program.get(path)
        gap = math.inf if got is None or not math.isfinite(got) else abs(got - ref) / max(ref, median)
        if gap >= worst:
            worst, where = gap, path
    return worst, where


def leaf_rel_errs(program: dict, reference: dict, reference_norms: dict) -> dict:
    """By leaf: the norm of (program's leaf - reference's leaf), against the
    reference's norm of that leaf or of the median leaf, whichever is larger.
    A leaf that is missing, misshapen or not finite reads infinity."""
    import numpy as np

    median = statistics.median(reference_norms.values())
    errs = {}
    for path, ref in reference.items():
        got = program.get(path)
        if got is None or got.shape != ref.shape:
            errs[path] = math.inf
            continue
        err = float(np.linalg.norm((got - ref).ravel())) / max(reference_norms[path], median)
        errs[path] = err if math.isfinite(err) else math.inf
    return errs


def worst_leaf_rel_err(program: dict, reference: dict, reference_norms: dict) -> tuple:
    """The largest of ``leaf_rel_errs`` and its leaf. Where a lower precision
    moves every norm by hardly more than rounding does, this error is the
    number that is steady from seed to seed."""
    errs = leaf_rel_errs(program, reference, reference_norms)
    where = max(errs, key=errs.get)
    return errs[where], where


class Checks:
    def __init__(self):
        self.rows = []

    def add(self, name: str, value: float, limit: float, note: str = "") -> None:
        ok = bool(math.isfinite(value) and value <= limit)
        self.rows.append({"name": name, "value": value, "limit": limit, "ok": ok, "note": note})
        print(f"check {name}: {value!r} (limit {limit!r}) {'ok' if ok else 'FAILED'} {note}".rstrip(), flush=True)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)
