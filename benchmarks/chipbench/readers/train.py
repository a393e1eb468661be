"""Per-layer metrics of the training cells. A reader takes the run's collected
sources and its metric's own file; it returns a number, or None where it finds
nothing to read (the harness then leaves the metric out of the line)."""

import statistics

from benchmarks.chipbench import flops, trace


def recompiles_in_window(sources, spec):
    ledger = sources.get("compile_ledger")
    return None if ledger is None else ledger["recompiles_after_warmup"]


def train_mfu_pct(sources, spec):
    """Required operations a token (flops.py: no recomputation, causal
    attention halved, backward only where needed) x tokens/s/chip / peak, at
    the window's median step: a traced run writes its trace out inside the
    window (about 20 s on the chip), and that stall is not the step's."""
    ends = sources.get("step_ends_s")
    if sources.get("peaks") is None or "flops_per_token" not in sources or not ends:
        return None
    step_s = statistics.median(b - a for a, b in zip([0.0] + ends, ends))
    rate = sources["microbatch"] * sources["accum"] * sources["seq_len"] / step_s / sources["chips"]
    return 100.0 * sources["flops_per_token"]["total"] * rate / sources["peaks"]["flops_bf16"]


def train_peak_hbm_gib(sources, spec):
    """Buffers in use at the window's end plus the scratch the chip keeps
    reserved for the step's program (the compiler sizes it to what is left)."""
    held = sources.get("memory_held_bytes")
    return None if not held else held / 2**30


def device_idle_pct(sources, spec):
    red = sources.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def kernel_time_pct(sources, spec):
    """Share of the device's busy time spent in the kernels whose names
    contain any of ``spec["kernels"]``."""
    red = sources.get("trace")
    if not red or red["busy_s"] <= 0:
        return None
    secs = sum(trace.kernel_seconds(red, k)[0] for k in spec["kernels"])
    return None if secs == 0 else 100.0 * secs / red["busy_s"]


def flash_fwd_roofline_pct(sources, spec):
    """The least time the chip could take for the traced calls of the flash
    forward kernel (flops.py, peaks.json) over the time they took."""
    red = sources.get("trace")
    if not red or sources.get("peaks") is None:
        return None
    secs, calls = trace.kernel_seconds(red, spec["kernel"])
    if secs == 0:
        return None
    cfg = sources["config"]
    cost = flops.flash_fwd_cost(
        sources["microbatch"], sources["seq_len"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
    )
    bound = flops.roofline_seconds(cost, sources["peaks"])
    return 100.0 * bound["seconds"] * calls / secs
