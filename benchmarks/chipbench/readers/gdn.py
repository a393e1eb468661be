"""Per-layer metrics of a cell whose model has linear-attention layers (kind
``sft_gdn_moe``).

The program scopes a linear layer's whole mixer ``layer<i>/linear_attn`` (a
full layer keeps ``attn``) and, inside it, the convolution ``gdn_conv``, the
gated delta rule ``gdn_scan`` and the gated norm ``gdn_gate_norm``
(``observe/xla.py`` ``STEP_SCOPES``); forward, backward and recomputed are in
the path as for every scope (``readers/scopes.py``). The rule's ``lax.scan``
is a ``while`` under ``gdn_scan``: its body's operations carry
``.../gdn_scan/.../while/body/...``, the ``while`` event itself no path at all.
Which form each traced call of the rule took the program counts where it is
traced (``ops/gated_delta.CALLS``), handed over by the cell's kind.

A reader returns None where it finds nothing to read: no trace, no such scope
in it (a program without them), no counter.
"""

from __future__ import annotations

import os
import re

from benchmarks.chipbench import flops, flops_gdn_moe
from benchmarks.chipbench.readers import scopes

LOOP = "while"  # what a path holds below the scope where the operation runs once a chunk, not once a call
_LAYER = re.compile(r"^layer\d+$")


def seconds_under(op_seconds: dict, op_counts: dict, metadata: dict, scope: str, forward_only: bool = False):
    """``(seconds, calls)`` over the operations whose path holds ``scope``;
    with ``forward_only`` neither under ``transpose(`` nor recomputed. On the
    chip the scan's own ``while`` event carries no path (my chip run, PR 32),
    so the calls are counted from what stands around it: of a layer's
    operations under the scope and outside any loop below it, each runs once a
    call, and the count most of them share is that layer's calls (one the
    compiler hoists or clones does not move it); the layers' calls add up. A
    kernel in place of the scan is read the same way. ``(None, 0)`` where no
    operation holds the scope."""
    secs, found, by_layer = 0.0, False, {}
    for name, s in op_seconds.items():
        tf_op = metadata.get(name, {}).get("tf_op", "")
        path = tf_op.split(";", 1)[0].rsplit(":", 1)[0]
        parts = [scopes.bare(c) for c in path.split("/")]
        if scope not in parts:
            continue
        found = True
        if forward_only and (scopes.BACKWARD in path or scopes.RECOMPUTED in path):
            continue
        secs += s
        if LOOP not in parts[parts.index(scope) + 1:]:
            layer = next((c for c in parts if _LAYER.match(c)), None)
            by_layer.setdefault(layer, []).append(op_counts.get(name, 0.0))
    calls = sum(max(set(counts), key=counts.count) for counts in by_layer.values())
    return (secs if found else None), calls


def _under(sources, spec, xplane_path, forward_only=False):
    red = sources.get("trace")
    if not red or red["busy_s"] <= 0:
        return None, 0, None
    path = xplane_path or scopes.newest_xplane()
    if path is None:
        return None, 0, None
    secs, calls = seconds_under(red["op_seconds"], red["op_counts"], scopes._metadata(path, os.path.getmtime(path)),
                                spec["scope"], forward_only)
    return secs, calls, red


def scope_share_pct(sources, spec, xplane_path=None):
    """Busy time under ``spec["scope"]`` (forward, backward and recomputed),
    in percent of the device's busy time."""
    secs, _, red = _under(sources, spec, xplane_path)
    return None if secs is None else 100.0 * secs / red["busy_s"]


def gdn_scan_fwd_roofline_pct(sources, spec, xplane_path=None):
    """The least time the chip could take for the traced forward calls of the
    gated delta rule, counted as the recurrence
    (``flops_gdn_moe.gdn_scan_fwd_cost``, ``peaks.json``), over the time the
    operations under ``gdn_scan`` took in the forward pass."""
    cfg = sources.get("config", {})
    if sources.get("peaks") is None or "linear_num_value_heads" not in cfg:
        return None
    secs, calls, _ = _under(sources, spec, xplane_path, forward_only=True)
    if not secs or not calls:
        return None
    cost = flops_gdn_moe.gdn_scan_fwd_cost(sources["microbatch"], sources["seq_len"], cfg)
    return 100.0 * flops.roofline_seconds(cost, sources["peaks"])["seconds"] * calls / secs


def gdn_chunked_calls_pct(sources, spec):
    """Of the linear layers' calls of the rule traced into this process's
    programs, the share that took the form ``spec["form"]``."""
    counted = sources.get("gdn_calls") or {}
    total = sum(n for n, _ in counted.values())
    if not total:
        return None
    return 100.0 * sum(n for n, form in counted.values() if form.startswith(spec["form"])) / total
