"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Read with nothing but JAX (``jax.profiler.ProfileData``). A device plane is
one named ``/device:TPU:<n>``; its operations are the events of its ``XLA
Ops`` line (every line of the plane where it has none), each named by its
whole HLO instruction. Busy time is the union of those events' intervals,
averaged over the chips used; an operation's time is its self time (a
``while`` that holds the accumulation loop keeps only what its body leaves);
the window runs from the first to the last event of the device operations and of the
harness's own host spans (``chipbench/...``), which cover the traced window.
A gap between device operations is attributed to the host span (the
harness's, or a ``TraceAnnotation`` of the program such as the engine's
``prefill`` and ``sample``) that covers most of it.

Checked by ``tests/test_chipbench.py`` on hand-made planes and on the small
trace recorded on the chip in ``testdata/``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"[.\d]+$")


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _union(intervals):
    total, merged = 0.0, []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    for start, end in merged:
        total += end - start
    return total, merged


def op_group(name: str) -> str:
    """``fusion.123`` -> ``fusion``: the operation's kind, for the top list."""
    return _SUFFIX.sub("", name) or name


def short_name(text: str) -> str:
    """The chip names a device event by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``): keep the instruction's name."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_planes(path: str):
    """[(plane name, [(line name, [(event name, start_ns, duration_ns)])])]"""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]))
        planes.append((plane.name, lines))
    return planes


def self_times(ops):
    """[(name, self_ns)]: an operation that holds others (a ``while`` holds
    its body) keeps only the time in which none of them ran."""
    out, stack = [], []  # stack of [name, end, self]
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    out.extend((name, self_ns) for name, _, self_ns in stack)
    return out


def reduce_planes(planes, chips: int = 1, host_prefixes=("chipbench/",), host_names=("prefill", "sample")):
    device = []
    for name, lines in planes:
        m = DEVICE_PLANE.match(name)
        if m:
            ops = [ev for ln, evs in lines if ln == OPS_LINE for ev in evs]
            if not ops:
                ops = [ev for _, evs in lines for ev in evs]
            if ops:
                device.append((int(m.group(1)), ops))
    device.sort()
    device = device[:chips]
    if not device:
        return None
    host = []
    for name, lines in planes:
        if DEVICE_PLANE.match(name):
            continue
        for _, evs in lines:
            for ev_name, start, dur in evs:
                if ev_name.startswith(host_prefixes) or ev_name in host_names:
                    host.append((ev_name, start, start + dur))
    starts = [s for _, ops in device for _, s, _ in ops] + [s for _, s, _ in host]
    ends = [s + d for _, ops in device for _, s, d in ops] + [e for _, _, e in host]
    t0, t1 = min(starts), max(ends)
    busy, by_name, counts, gaps = 0.0, {}, {}, {}
    for _, ops in device:
        total, merged = _union([(s, s + d) for _, s, d in ops])
        busy += total
        for text, self_ns in self_times(ops):
            by_name[text] = by_name.get(text, 0.0) + self_ns
            counts[text] = counts.get(text, 0) + 1
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi - lo <= 0:
                continue
            best, best_overlap = "host: no span", 0.0
            for name, s, e in host:
                overlap = min(hi, e) - max(lo, s)
                if overlap > best_overlap:
                    best, best_overlap = name, overlap
            gaps[best] = gaps.get(best, 0.0) + (hi - lo)
    n = len(device)
    grouped = {}
    for text, ns in by_name.items():
        key = op_group(short_name(text))
        grouped[key] = grouped.get(key, 0.0) + ns
    top = sorted(grouped.items(), key=lambda kv: -kv[1])
    return {
        "chips": n,
        "busy_s": busy / n / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "op_seconds": {k: v / n / 1e9 for k, v in by_name.items()},
        "op_counts": {k: v / n for k, v in counts.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in top[:10]],
        "idle_gaps": [[k, v / n / 1e9] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }


def kernel_seconds(reduced: dict, needle: str):
    """(seconds, calls) of the device operations whose instruction text
    contains ``needle`` (a Pallas kernel's name is part of it)."""
    secs = sum(v for k, v in reduced["op_seconds"].items() if needle in k)
    calls = sum(v for k, v in reduced["op_counts"].items() if needle in k)
    return secs, calls


def reduce_dir(trace_dir: str, chips: int = 1):
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_planes(read_planes(path), chips=chips)


def describe(path: str, top: int = 25) -> dict:
    """What a trace holds, for a first look by hand: planes, lines, and the
    most frequent event names of each line."""
    out = {}
    for plane, lines in read_planes(path):
        out[plane] = {}
        for line, evs in lines:
            names = {}
            for name, _, dur in evs:
                c = names.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += dur / 1e9
            out[plane][line] = {
                "events": len(evs),
                "top": sorted(([k, v[0], v[1]] for k, v in names.items()), key=lambda r: -r[2])[:top],
                "custom_calls": sorted(([k[:400], v[0], v[1]] for k, v in names.items() if "custom-call" in k or "custom_call" in k), key=lambda r: -r[2])[:top],
            }
    return out
