"""Per-layer metrics of a cell whose model has Kimi Delta Attention layers
(kind ``sft_kda_moe``).

The program scopes a KDA layer's mixer as it scopes the other linear mixer's
(``layer<i>/linear_attn`` with ``gdn_conv``, ``gdn_scan``, ``gdn_gate_norm``
inside: ``readers/gdn.py`` reads those, and ``scope_share_pct`` over
``kda_gates`` is a data file), so what is left is the rule's roofline: the
recurrence with a decay a CHANNEL asks for other operations and bytes than the
scalar one (``flops_kda_moe.kda_scan_fwd_cost``).

A reader returns None where it finds nothing to read: no trace, no such scope
in it (a program without it), a configuration without such layers.
"""

from __future__ import annotations

from benchmarks.chipbench import flops, flops_kda_moe
from benchmarks.chipbench.readers import gdn


def kda_scan_fwd_roofline_pct(sources, spec, xplane_path=None):
    """The least time the chip could take for the traced forward calls of the
    delta rule with a decay a channel, counted as the recurrence
    (``flops_kda_moe.kda_scan_fwd_cost``, ``peaks.json``), over the time the
    operations under ``gdn_scan`` took in the forward pass (the calls counted as
    ``readers/gdn.seconds_under`` counts them)."""
    cfg = sources.get("config", {})
    if sources.get("peaks") is None or "kda_layers" not in (cfg.get("linear_attn_config") or {}):
        return None
    secs, calls, _ = gdn._under(sources, spec, xplane_path, forward_only=True)
    if not secs or not calls:
        return None
    cost = flops_kda_moe.kda_scan_fwd_cost(sources["microbatch"], sources["seq_len"], cfg)
    return 100.0 * flops.roofline_seconds(cost, sources["peaks"])["seconds"] * calls / secs
