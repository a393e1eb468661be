"""Per-layer metrics of the softmax mixer's IN pass (PR 41): what stands between
a layer's q, k, v projections and its attention call.

The program counts, where a layer of heads is traced (``models/transformer.
_softmax_mixer``), which form each call took: ``ops/rope.CALLS``, ``{(shape, form): calls}`` with the
form ``fused`` (one Pallas kernel forward and one back: q/k norms, rope, the
head-major layout) or ``xla (<why>)``. The counter is the process's, so this
reader asks the program for it (as ``readers/setup.py`` asks for the set-up
section) and no kind has to hand it over: a program from before the pass has
no such counter and the reader returns None. The device time of either form
lies under the scope ``attn_in`` and is read by ``readers.gdn.scope_share_pct``.
"""

from __future__ import annotations


def program_calls():
    """``ops/rope.CALLS`` of the program this process runs; None from a
    program without it."""
    try:
        from llm_fine_tune_distributed_tpu.ops import rope
    except ImportError:
        return None
    calls = getattr(rope, "CALLS", None)
    return calls if isinstance(calls, dict) else None


def attn_in_fused_calls_pct(sources, spec):
    """Of the hand-overs traced into this process's programs, the share whose
    form starts with ``spec["form"]``."""
    counted = program_calls()
    total = sum(counted.values()) if counted else 0
    if not total:
        return None
    return 100.0 * sum(n for (_, form), n in counted.items() if form.startswith(spec["form"])) / total
