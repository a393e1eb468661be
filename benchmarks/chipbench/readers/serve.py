"""Per-layer metrics of the serving cells, from the engine's own counters and
histograms (their change between the window's start and the end of its last
request), the load generator's record, and the trace."""

from benchmarks.chipbench.readers.train import device_idle_pct, recompiles_in_window  # noqa: F401


def _delta_percentile(sources, name: str, q: float):
    """The q-th percentile of what histogram ``name`` took in since the
    window opened, by linear interpolation inside the bucket it falls in (the
    program's histograms are log-bucketed: coarse, and so a per-layer number)."""
    try:
        before = sources["stats_before"]["hist"][name]
        after = sources["stats_after"]["hist"][name]
    except KeyError:
        return None
    counts = [a - b for a, b in zip(after["counts"], before["counts"])]
    total, bounds = sum(counts), after["bounds"]
    if total <= 0:
        return None
    rank, seen = q / 100.0 * total, 0
    for i, c in enumerate(counts):
        if c and seen + c >= rank:
            if i >= len(bounds):
                return bounds[-1]
            lo = bounds[i - 1] if i else 0.0
            return lo + (bounds[i] - lo) * min(max((rank - seen) / c, 0.0), 1.0)
        seen += c
    return bounds[-1]


def hist_ms(sources, spec):
    value = _delta_percentile(sources, spec["histogram"], float(spec["percentile"]))
    return None if value is None else 1e3 * value


def counter_ratio(sources, spec):
    try:
        a0, a1 = sources["stats_before"]["counters"], sources["stats_after"]["counters"]
        num = a1[spec["numerator"]] - a0[spec["numerator"]]
        den = a1[spec["denominator"]] - a0[spec["denominator"]]
    except KeyError:
        return None
    return None if den <= 0 else num / den


def summary_value(sources, spec):
    return (sources.get("summary") or {}).get(spec["key"])
