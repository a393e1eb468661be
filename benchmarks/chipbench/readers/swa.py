"""Per-layer metrics of a cell whose model mixes window and global attention
layers (kind ``sft_swa_moe``).

The program gives the streamed flash kernels of a window layer and of a
global layer different names (``ops/flash_attention._stream_name``:
``flash_attention_window_fwd`` / ``flash_attention_causal_fwd`` and their
``dq``/``dkv``), which a device event's instruction text carries, and scopes
each layer's attention ``layer<i>/attn`` (``observe/xla.py``); which layers
have a window the configuration's ``layer_types`` says. The tiles a window
layer's grids visit are counted by the program where it builds them
(``flash_attention.GRID_TILES``) and handed over by the cell's kind.

A reader returns None where it finds nothing to read: no trace, no such
kernel or scope in it (a program without them), no counter.
"""

from __future__ import annotations

import os

from benchmarks.chipbench import flops, flops_swa_moe, trace
from benchmarks.chipbench.readers import scopes

WINDOW = "sliding_attention"


def flash_kind_fwd_roofline_pct(sources, spec):
    """The least time the chip could take for the traced forward calls of
    ``spec["kernel"]`` (the window layers' or the global layers' streamed
    kernel; ``flops_swa_moe.flash_fwd_cost`` over the pairs the mask keeps,
    ``peaks.json``) over the time they took."""
    red, cfg = sources.get("trace"), sources.get("config", {})
    if not red or sources.get("peaks") is None or "layer_types" not in cfg:
        return None
    secs, calls = trace.kernel_seconds(red, spec["kernel"])
    if secs == 0:
        return None
    window = cfg["sliding_window"] if spec["layers"] == WINDOW else None
    cost = flops_swa_moe.flash_fwd_cost(sources["microbatch"], sources["seq_len"], cfg, window)
    return 100.0 * flops.roofline_seconds(cost, sources["peaks"])["seconds"] * calls / secs


def attn_seconds_by_kind(op_seconds: dict, metadata: dict, layer_types) -> dict:
    """{layer type: seconds} over the operations whose path lies under
    ``attn`` inside a ``layer<i>``, every pass."""
    out = {}
    for name, secs in op_seconds.items():
        tf_op = metadata.get(name, {}).get("tf_op", "")
        path = [scopes.bare(c) for c in tf_op.split(";", 1)[0].rsplit(":", 1)[0].split("/")]
        layer = next((m for m in map(scopes._LAYER.match, path) if m), None)
        if layer is None or "attn" not in path or int(layer.group(1)) >= len(layer_types):
            continue
        kind = layer_types[int(layer.group(1))]
        out[kind] = out.get(kind, 0.0) + secs
    return out


def attn_kind_time_pct(sources, spec, xplane_path=None):
    """Busy time under ``attn`` of the layers of ``spec["layers"]``'s type
    (projections, rope, kernels, every pass), in percent of the device's."""
    red, cfg = sources.get("trace"), sources.get("config", {})
    if not red or red["busy_s"] <= 0 or "layer_types" not in cfg:
        return None
    path = xplane_path or scopes.newest_xplane()
    if path is None:
        return None
    by_kind = attn_seconds_by_kind(red["op_seconds"], scopes._metadata(path, os.path.getmtime(path)), cfg["layer_types"])
    if not by_kind:
        return None  # the program has no such scopes: nothing to read
    return 100.0 * by_kind.get(spec["layers"], 0.0) / red["busy_s"]


def flash_band_tiles_pct(sources, spec):
    """Score tiles the kernels of a window layer visit over the tiles of its
    causal triangle, from the grids the program built: the sum over the
    kernels whose name holds ``spec["kernels"]``."""
    tiles = sources.get("flash_grid_tiles") or {}
    counted = [v for name, v in tiles.items() if spec["kernels"] in name]
    if not counted:
        return None
    return 100.0 * sum(v[0] for v in counted) / sum(v[1] for v in counted)
