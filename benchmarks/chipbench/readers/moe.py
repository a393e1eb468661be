"""Per-layer metrics of a cell with latent attention and routed experts.

The program names the parts of an expert layer with scopes inside ``mlp``
(``observe/xla.py`` ``STEP_SCOPES``: ``router``, ``experts``,
``shared_expert``), and a grouped product keeps its primitive's name at the
end of its path (``.../mlp/experts/ragged_dot_general``, or a kernel's
``.../jit(gmm)/pallas_call``). ``xplane_meta`` reads each device operation's
``tf_op``; joined with the self time ``trace.py`` measured, that gives the
time under the expert scopes, the part of it that is not a grouped product
(scores, top-k, sort, gather, scatter, combine), and the grouped products'
share of their roofline. The two counters come from the step's own metrics
(``train/step.py``), handed over by ``kind_sft_moe.py``.

A reader returns None where it finds nothing to read: no trace, a trace whose
operations carry no expert scope (a program without them), no counter.
"""

from __future__ import annotations

import os

from benchmarks.chipbench import flops, flops_mla_moe, trace
from benchmarks.chipbench.readers import scopes

EXPERT_SCOPES = ("router", "experts", "shared_expert")


def _path(tf_op: str) -> list:
    return [scopes.bare(c) for c in tf_op.split(";", 1)[0].rsplit(":", 1)[0].split("/")]


PRODUCT_OPS = ("pallas_call", "ragged_dot_general")  # what a grouped product's own path ends in


def seconds_by_part(op_seconds: dict, op_counts: dict, metadata: dict, products=()):
    """{scope or "product": (seconds, calls)} over the operations under an
    expert scope; a grouped product counts under "product" and not under its
    scope: an operation whose path ends in a kernel's call or in the ragged
    dot's primitive and holds one of ``products`` (``jit(gmm)``, ``jit(tgmm)``,
    ``ragged_dot_general``). What a kernel's wrapper computes around the call
    (the group metadata) stays with the scope: it is dispatch."""
    out = {}
    for name, secs in op_seconds.items():
        path = _path(metadata.get(name, {}).get("tf_op", ""))
        part = next((c for c in path if c in EXPERT_SCOPES), None)
        if part is None:
            continue
        if part != "shared_expert" and path[-1] in PRODUCT_OPS and any(p in c for c in path for p in products):
            part = "product"
        s, n = out.get(part, (0.0, 0.0))
        out[part] = (s + secs, n + op_counts.get(name, 0.0))
    return out


def _parts(sources, spec, xplane_path=None):
    red = sources.get("trace")
    if not red or red["busy_s"] <= 0:
        return None, None
    path = xplane_path or scopes.newest_xplane()
    if path is None:
        return None, None
    parts = seconds_by_part(red["op_seconds"], red["op_counts"],
                            scopes._metadata(path, os.path.getmtime(path)), tuple(spec.get("products", ())))
    return (parts or None), red


def moe_time_pct(sources, spec, xplane_path=None):
    """Busy time under ``router`` + ``experts`` + ``shared_expert`` (every
    pass), or with ``dispatch_only`` what of ``router`` and ``experts`` is not
    a grouped product, in percent of the device's busy time."""
    parts, red = _parts(sources, spec, xplane_path)
    if parts is None:
        return None
    keep = ("router", "experts") if spec.get("dispatch_only") else ("router", "experts", "shared_expert", "product")
    return 100.0 * sum(parts.get(k, (0.0, 0.0))[0] for k in keep) / red["busy_s"]


def expert_gmm_roofline_pct(sources, spec, xplane_path=None):
    """The least time the chip could take for the traced grouped products
    over the time they took. Every product of an expert's SwiGLU, forward,
    recomputed or one of the backward's two, multiplies the pairs' rows by
    hidden x expert width (``flops_mla_moe.grouped_product_cost``); the pairs
    of one call are what the step counted, a microbatch and expert layer."""
    parts, _ = _parts(sources, spec, xplane_path)
    if parts is None or "product" not in parts or sources.get("peaks") is None:
        return None
    pairs = sources.get("expert_pairs_per_token")
    if pairs is None:
        return None
    cfg = sources["config"]
    secs, calls = parts["product"]
    rows = pairs * sources["microbatch"] * sources["seq_len"]
    cost = flops_mla_moe.grouped_product_cost(
        rows, cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    )
    return 100.0 * flops.roofline_seconds(cost, sources["peaks"])["seconds"] * calls / secs


def mla_flash_fwd_roofline_pct(sources, spec):
    """As ``readers.train.flash_fwd_roofline_pct``, with the operations of
    heads that differ: QK^T at the q/k width, PV at the v width."""
    red = sources.get("trace")
    if not red or sources.get("peaks") is None or "kv_lora_rank" not in sources.get("config", {}):
        return None
    secs, calls = trace.kernel_seconds(red, spec["kernel"])
    if secs == 0:
        return None
    cost = flops_mla_moe.flash_fwd_cost(sources["microbatch"], sources["seq_len"], sources["config"])
    return 100.0 * flops.roofline_seconds(cost, sources["peaks"])["seconds"] * calls / secs


def step_counter(sources, spec):
    """A counter of the step's own metrics, reduced over the window by the
    cell's kind (mean of the pairs a token, worst of the load's skew)."""
    return sources.get(spec["counter"])
