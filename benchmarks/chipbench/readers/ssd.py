"""Per-layer metrics of a cell whose model has Mamba-2 state-space layers (kind ``sft_ssd``).

The program scopes a Mamba-2 layer's mixer as it scopes the linear mixers' (``layer<i>/linear_attn``) and, inside
it, the convolution with its bias, silu and dt's softplus under ``ssd_in``, the scan under ``ssd_scan`` and the gate
and norm under ``ssd_gate_norm`` (``observe/xla.py`` ``STEP_SCOPES``): their device time is read by
``readers.gdn.scope_share_pct`` over data files. What is left is the scan's roofline (the recurrence:
``flops_ssd.ssd_scan_fwd_cost``, one yardstick for the sweeps and the XLA form) and the counter the kind hands over
from ``ops/ssd.py`` (``CALLS``).

A reader returns None where it finds nothing to read: no trace, no such scope in it, no such counter among the sources
(a program without it), a configuration without such layers.
"""

from __future__ import annotations

import os

from benchmarks.chipbench import flops, flops_ssd
from benchmarks.chipbench.readers import gdn, scopes


def ssd_scan_fwd_roofline_pct(sources, spec, xplane_path=None):
    """The least time the chip could take for the traced forward calls of the state-space scan, counted as the
    recurrence (``flops_ssd.ssd_scan_fwd_cost``, ``peaks.json``), over the time EVERY forward operation under
    ``ssd_scan`` took (first and recomputed runs are both forward passes of the scan, and both are counted as calls, as
    ``readers/gdn.seconds_under`` counts them: outside ``transpose(``, or under it and ``rematted_computation``)."""
    cfg = sources.get("config", {})
    if sources.get("peaks") is None or "mamba_n_heads" not in cfg:
        return None
    secs, calls, _ = _forward_under(sources, spec, xplane_path)
    if not secs or not calls:
        return None
    cost = flops_ssd.ssd_scan_fwd_cost(sources["microbatch"], sources["seq_len"], cfg)
    return 100.0 * flops.roofline_seconds(cost, sources["peaks"])["seconds"] * calls / secs


def _forward_under(sources, spec, xplane_path):
    """``readers.gdn._under`` over the forward operations, the recomputed ones INCLUDED: under full remat with nothing
    of the scan kept, a frozen layer's forward sweep runs once in the forward pass and once more recomputed, and the
    two are the same work. The two passes are counted apart and added (a layer's calls are the count most of its
    operations share, and a recomputed operation is another operation of the same layer)."""
    red = sources.get("trace")
    if not red or red["busy_s"] <= 0:
        return None, 0, None
    path = xplane_path or scopes.newest_xplane()
    if path is None:
        return None, 0, None
    metadata = scopes._metadata(path, os.path.getmtime(path))
    tf_op = lambda name: metadata.get(name, {}).get("tf_op", "")  # noqa: E731
    first = {name: s for name, s in red["op_seconds"].items() if scopes.BACKWARD not in tf_op(name) and scopes.RECOMPUTED not in tf_op(name)}
    again = {name: s for name, s in red["op_seconds"].items() if scopes.RECOMPUTED in tf_op(name)}  # (its path lies under ``transpose(`` too)
    found = [gdn.seconds_under(part, red["op_counts"], metadata, spec["scope"]) for part in (first, again)]
    if all(secs is None for secs, _ in found):
        return None, 0, red
    return sum(secs or 0.0 for secs, _ in found), sum(calls for _, calls in found), red


def ssd_kernel_calls_pct(sources, spec):
    """Of the scan's calls traced into this process's programs, the share whose form ends ``spec["form"]``."""
    counted = sources.get("ssd_calls") or {}
    total = sum(n for n, _ in counted.values())
    if not total:
        return None
    return 100.0 * sum(n for n, form in counted.values() if form.endswith(spec["form"])) / total
