"""Per-layer metrics of a cell whose model has EVA attention (kind ``sft_eva``).

The program scopes an EVA layer's mixer as any layer of heads (``layer<i>/attn``
with ``attn_in`` inside) and, between the IN pass and ``o_proj``, the pooling
under ``eva_pool`` and everything else under ``eva_agg``: their device time is
read by ``readers.gdn.scope_share_pct`` over data files. What is left is the
aggregate's roofline (the pairs the masks keep: ``flops_eva.eva_agg_fwd_cost``,
one yardstick for the kernels and the XLA form) and the two counters the kind
hands over from ``ops/eva_attention.py`` (``CALLS``, ``GRID_TILES``).

A reader returns None where it finds nothing to read: no trace, no such scope
in it, no such counter among the sources (a program without it).
"""

from __future__ import annotations

from benchmarks.chipbench import flops, flops_eva
from benchmarks.chipbench.readers import gdn


def eva_agg_fwd_roofline_pct(sources, spec, xplane_path=None):
    """The least time the chip could take for the traced forward calls of the aggregate over both key sources,
    counted as the pairs the masks keep (``flops_eva.eva_agg_fwd_cost``, ``peaks.json``), over the time the
    operations under ``eva_agg`` took in the forward pass (the calls counted as ``readers/gdn.seconds_under`` counts
    them)."""
    cfg = sources.get("config", {})
    if sources.get("peaks") is None or "window_size" not in cfg:
        return None
    secs, calls, _ = gdn._under(sources, spec, xplane_path, forward_only=True)
    if not secs or not calls:
        return None
    cost = flops_eva.eva_agg_fwd_cost(sources["microbatch"], sources["seq_len"], cfg)
    return 100.0 * flops.roofline_seconds(cost, sources["peaks"])["seconds"] * calls / secs


def eva_kernel_calls_pct(sources, spec):
    """Of the operator's calls traced into this process's programs, the share whose form is ``spec["form"]``."""
    counted = sources.get("eva_calls") or {}
    total = sum(n for n, _ in counted.values())
    if not total:
        return None
    return 100.0 * sum(n for n, form in counted.values() if form == spec["form"]) / total


def eva_tiles_pct(sources, spec):
    """Tiles the grids and loops of the operator's kernels visit over the tiles the two key sources need, summed
    over the kernels the program built."""
    counted = list((sources.get("eva_grid_tiles") or {}).values())
    if not counted or not sum(v[1] for v in counted):
        return None
    return 100.0 * sum(v[0] for v in counted) / sum(v[1] for v in counted)
