"""The one general traffic generator: a mix is a data file, never code.

Every seed gets the same work. Lengths and gaps are the evenly spaced
quantiles of the mix's distributions (so their multiset is fixed by the mix
and the window's length alone). ``--seed`` draws the token ids and permutes
lengths and gaps, unless the mix names an ``arrangement_seed``: on the chip
the arrangement alone moved the mean gap between tokens by 7% (PERF.md), so
a serving mix fixes it and runs then differ by their token ids alone.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, *stream])


# ----------------------------------------------------------------- training


def sft_batch(mix: dict, vocab: int, seed: int, step: int) -> dict:
    """Step ``step``'s batch, [accum, rows, seq]: token ids drawn from the
    seed, every row full (all-ones masks). A different batch each step."""
    shape = (int(mix["accum"]), int(mix["microbatch"]), int(mix["seq_len"]))
    if mix.get("rows", "full") != "full":
        raise ValueError("the sft generator knows rows='full' only")
    ids = rng_for(seed, 1, step).integers(0, vocab, shape, dtype=np.int32)
    return {
        "input_ids": ids,
        "loss_mask": np.ones(shape, np.float32),
        "attention_mask": np.ones(shape, np.int32),
    }


# ------------------------------------------------------------------ serving


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    raw = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(raw), int(spec["min"]), int(spec["max"])).astype(np.int64)


def _arrivals(spec: dict, n: int, span_s: float, rng) -> np.ndarray:
    """``n`` Poisson arrival times in [0, span_s): the quantiles of the
    exponential gap, shuffled, scaled to fill the span."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-_quantiles(n))
    rng.shuffle(gaps)
    return (np.cumsum(gaps) - gaps / 2.0) * (span_s / gaps.sum())


def serve_schedule(mix: dict, vocab: int, seed: int, seconds: float) -> list:
    """Requests as dicts ``due`` (seconds from the window's start; negative in
    the ramp), ``prompt`` (token ids), ``max_new`` and ``measured``. The same
    mix is offered at the same rate for ``ramp_s`` before the window and for
    ``tail_s`` after it, unmeasured: the ramp so that the window opens on
    populated slots, the tail so that the window's requests finish under the
    load they started in. Both have to outlast the mix's longest answer."""
    arr = mix["arrivals"]
    rate = float(arr["rate_per_s"])
    ramp, tail = float(arr.get("ramp_s", 0.0)), float(arr.get("tail_s", 0.0))
    out = []
    for phase, (start, span) in enumerate(((-ramp, ramp), (0.0, float(seconds)), (float(seconds), tail))):
        n = int(round(rate * span))
        # the arrangement (which length meets which gap) decides how requests
        # overlap, and that changes the work; a mix that names an
        # ``arrangement_seed`` fixes it for every run, and ``--seed`` then
        # draws the token ids alone
        rng = rng_for(mix.get("arrangement_seed", seed), 2, phase)
        ids = rng_for(seed, 5, phase)
        times = _arrivals(arr, n, span, rng) + start
        plens = _lengths(mix["prompt_len"], n)
        olens = _lengths(mix["output_len"], n)
        rng.shuffle(plens)
        rng.shuffle(olens)
        for i in range(n):
            out.append({
                "due": float(times[i]),
                "prompt": ids.integers(0, vocab, int(plens[i]), dtype=np.int32),
                "max_new": int(olens[i]),
                "measured": phase == 1,
            })
    out.sort(key=lambda r: r["due"])
    return out


def warmup_requests(mix: dict, vocab: int) -> list:
    """One request for every program the mix's shapes can reach: each
    final-chunk pad bucket, a whole chunk, and each power-of-two block count
    a decode tick can gather. (prompt, max_new) pairs, served one at a time."""
    eng = mix["engine"]
    bucket, chunk, blk = int(eng["prompt_bucket"]), int(eng["prefill_chunk"]), int(eng["block_len"])
    pmin, pmax = _length_range(mix["prompt_len"])
    omax = _length_range(mix["output_len"])[1]
    # a final-chunk program is keyed by its pad bucket alone: for each bucket
    # the shortest prompt of the mix whose last chunk pads to it; one prompt
    # longer than a chunk reaches the whole-chunk program
    reqs = []
    for b in range(bucket, chunk + 1, bucket):
        for base in range(0, pmax, chunk):
            p = max(base + b - bucket + 1, pmin)
            if p <= min(base + b, pmax):
                reqs.append((p, 2))
                break
    if pmax > chunk:
        reqs.append((max(chunk + 1, pmin), 2))
    nb, top = 1, (min(pmax + omax, int(eng["buf_len"])) - 1) // blk + 1
    while True:  # decode programs: widest live slot's blocks, rounded up
        lo = (nb // 2) * blk + 1 if nb > 1 else 1
        p = min(max(lo, pmin), pmax)
        new = max(2, lo - p + 2) if p < lo else 2
        reqs.append((p, min(new, omax)))
        if nb >= top:
            break
        nb *= 2
    rng = np.random.default_rng(0)
    return [(rng.integers(0, vocab, p, dtype=np.int32), n) for p, n in dict.fromkeys(reqs)]


def _length_range(spec: dict):
    return int(spec["min"]), int(spec["max"])
