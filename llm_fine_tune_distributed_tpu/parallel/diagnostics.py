"""Parallelism-dispatch diagnostics.

The sequence-parallel attention impls ("ring", "ulysses") fall back to
flash/XLA attention when their shape preconditions fail
(ops/attention._route; _warn_seq_axis_unused). The fallback warns when a provisioned
seq axis goes unused, but a warning is easy to miss — an earlier review found a
"ulysses parity test" whose mesh violated the batch-divisibility precondition,
so it silently tested the fallback and passed anyway. ``assert_seq_parallel``
is the un-missable form: it turns the fallback warning into an error AND
positively asserts (via the trace-time dispatch ledger in ops/attention.py)
that the claimed implementation actually ran. Every ring/ulysses parity test
wraps its forward in this guard; users can wrap their own first training step
to prove a long-context mesh is live (docs/operating-manual.md
troubleshooting table).
"""

from __future__ import annotations

import warnings
from contextlib import contextmanager

_FALLBACK_MSG = ".*seq axis is NOT being used.*"


@contextmanager
def assert_seq_parallel(expected: str):
    """Fail unless an ``attention(impl=expected)`` call inside the block
    dispatched to the REAL sequence-parallel path (no silent fallback).

    ``expected``: "ring" | "ulysses" | "ring_manual" | "ulysses_manual".
    The check is trace-time: wrap the first (compiling) call of a jitted
    function, not a cache-hit re-execution.
    """
    import importlib

    # ops/__init__.py re-exports the attention FUNCTION under the same name,
    # so attribute-style imports would resolve to it — fetch the module.
    att = importlib.import_module("llm_fine_tune_distributed_tpu.ops.attention")

    valid = ("ring", "ulysses", "ring_manual", "ulysses_manual")
    if expected not in valid:
        raise ValueError(f"expected must be one of {valid}, got {expected!r}")
    before = att.dispatch_count(expected)
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=_FALLBACK_MSG)
        yield
    after = att.dispatch_count(expected)
    if after <= before:
        raise AssertionError(
            f"attention impl {expected!r} never dispatched inside the guarded "
            f"block — the code under test ran a different attention path "
            f"(check seq-axis size, batch % (data*fsdp), seq-length and "
            f"head/kv-head divisibility: parallel/ring_attention."
            f"seq_parallel_preconditions)"
        )
