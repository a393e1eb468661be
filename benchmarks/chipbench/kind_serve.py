"""Cells of kind ``serve``: ``Generator`` + ``PagedContinuousBatchingEngine``
built in this process from seeded weights, driven through ``engine.stream()``
(the call ``infer/server.py`` makes) by an open loop: every request is sent
when it is due, whether or not earlier ones have finished, each on a thread of
its own as an HTTP handler would be, and is timed on the benchmark's clock
from when it was DUE. No HTTP server and no model directory.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmarks.chipbench import check, reference, traffic, weights
from benchmarks.chipbench.kind_sft import model_config


def build_engine(cfg: dict, mix: dict, seed: int):
    import jax.numpy as jnp

    from llm_fine_tune_distributed_tpu.infer.engine import PagedContinuousBatchingEngine
    from llm_fine_tune_distributed_tpu.infer.generate import Generator

    flat = weights.make_flat(seed, cfg)
    generator = Generator(
        weights.nest(flat), model_config(cfg), tokenizer=None,
        compute_dtype=jnp.bfloat16, eos_token_ids=(),
    )
    eng = mix["engine"]
    engine = PagedContinuousBatchingEngine(
        generator,
        slots=int(eng["slots"]),
        buf_len=int(eng["buf_len"]),
        prompt_bucket=int(eng["prompt_bucket"]),
        block_len=int(eng["block_len"]),
        prefill_chunk=int(eng["prefill_chunk"]),
        num_blocks=int(eng["num_blocks"]),
        kv_quant=eng.get("kv_quant", "none"),
    )
    return flat, engine


def greedy(max_new: int):
    from llm_fine_tune_distributed_tpu.infer.sampling import GenerationConfig

    return GenerationConfig(
        max_new_tokens=int(max_new), do_sample=False, temperature=1.0, top_p=1.0,
        top_k=None, repetition_penalty=1.0,
    )


def warm_up(engine, mix: dict, vocab: int) -> None:
    """Every program the mix's shapes can reach, through the engine's own
    entry, one request at a time; then the program's ledger is marked warm."""
    for prompt, max_new in traffic.warmup_requests(mix, vocab):
        engine.submit(prompt.tolist(), greedy(max_new), timeout=1800.0)
    engine.mark_compile_warm()


class _Client(threading.Thread):
    def __init__(self, engine, req, t0, token_timeout_s):
        super().__init__(daemon=True)
        self.engine, self.req, self.t0 = engine, req, t0
        self.token_timeout_s = token_timeout_s
        self.sent = None
        self.times, self.tokens, self.error = [], [], None

    def run(self):
        try:
            self.sent = time.perf_counter() - self.t0
            stream = self.engine.stream(
                self.req["prompt"].tolist(), greedy(self.req["max_new"]),
                timeout=self.token_timeout_s,
            )
            for tok in stream:
                self.times.append(time.perf_counter() - self.t0)
                self.tokens.append(int(tok))
        except Exception as e:  # a failed request is counted, not hidden
            self.error = f"{type(e).__name__}: {e}"


def offer(engine, schedule, seconds, harness=None, token_timeout_s=120.0, on_window=None, on_close=None):
    """Send every request of ``schedule`` when it is due. ``on_window`` is
    called as the window opens and ``on_close`` as it closes; the tail goes on
    being sent until the last measured request has ended. Returns the clients
    sent, in schedule order, once every measured one has ended (what is left
    of the tail goes on in the background)."""
    first_due = min(r["due"] for r in schedule)
    t0 = time.perf_counter() - first_due  # window opens at t0 on this clock
    clients, opened, closed = [], False, False

    def close():
        _sleep_until(t0 + seconds)
        if harness is not None:
            harness.stop_window()
        if on_close is not None:
            on_close()

    for req in schedule:
        if not opened and req["due"] >= 0.0:
            _sleep_until(t0)
            opened = True
            if on_window is not None:
                on_window()
        if req["due"] >= seconds:
            if not closed:
                close()
                closed = True
            if not any(c.is_alive() for c in clients if c.req["measured"]):
                break
        _sleep_until(t0 + req["due"])
        client = _Client(engine, req, t0, token_timeout_s)
        client.start()
        clients.append(client)
        if harness is not None and opened and not closed:
            harness.trace_tick(time.perf_counter() - t0, background=True)
    if not closed:
        close()
    for client in clients:
        if client.req["measured"]:
            client.join()
    return clients


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05) if left > 0.002 else 0)


def hist_state(stats) -> dict:
    return {name: {"bounds": list(h.bounds), "counts": list(h._state()[0])} for name, h in stats.hist.items()}


def counters(engine) -> dict:
    snap = engine.stats_snapshot()
    return {k: v for k, v in snap.items() if isinstance(v, (int, float))}


def summarize(clients, seconds: float) -> dict:
    measured = [c for c in clients if c.req["measured"]]
    done = [c for c in measured if c.error is None and len(c.tokens) == c.req["max_new"]]
    ttft = [c.times[0] - c.req["due"] for c in measured if c.times]
    gaps = [b - a for c in measured for a, b in zip(c.times, c.times[1:])]
    lag = [c.sent - c.req["due"] for c in measured if c.sent is not None]
    return {
        "attempted": len(measured),
        "failed": len(measured) - len(done),
        "errors": sorted({c.error for c in measured if c.error})[:5],
        "ttft_mean_ms": 1e3 * float(np.mean(ttft)) if ttft else None,
        "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)) if ttft else None,
        "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)) if ttft else None,
        "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)) if gaps else None,
        "itl_p50_ms": 1e3 * float(np.percentile(gaps, 50)) if gaps else None,
        "itl_mean_ms": 1e3 * float(np.mean(gaps)) if gaps else None,
        "serve_tokens_per_s": sum(len(c.tokens) for c in done) / seconds,
        "generator_lag_p95_ms": 1e3 * float(np.percentile(lag, 95)) if lag else None,
        "last_finish_s": max((c.times[-1] for c in measured if c.times), default=0.0),
        "done": done,
    }


def sample_for_check(done, seed: int, count: int) -> list:
    """The longest finished request and ``count - 1`` others drawn from the seed."""
    if not done:
        return []
    order = sorted(range(len(done)), key=lambda i: -(len(done[i].req["prompt"]) + len(done[i].tokens)))
    rest = order[1:]
    picks = traffic.rng_for(seed, 4).permutation(len(rest))[: max(0, count - 1)]
    return [done[order[0]]] + [done[rest[i]] for i in picks]


def served_gaps(flat, cfg, sample) -> np.ndarray:
    """For every served token of the sampled requests, how far its logit
    lies below the reference's best at its position (0 where the served token
    is the reference's own choice)."""
    if not sample:
        return np.zeros(0)
    return np.concatenate([
        reference.served_token_gaps(flat, cfg, c.req["prompt"], c.tokens) for c in sample
    ])


def run(cell, args, harness):
    cfg, mix, limits = cell["config"], cell["traffic"], cell["limits"]
    vocab = cfg["vocab_size"]
    t_a = time.perf_counter()
    flat, engine = build_engine(cfg, mix, args.seed)
    t_b = time.perf_counter()
    warm_up(engine, mix, vocab)
    print(f"set-up: weights and engine {t_b - t_a:.1f} s, warm-up of every program "
          f"{time.perf_counter() - t_b:.1f} s", flush=True)
    schedule = traffic.serve_schedule(mix, vocab, args.seed, args.seconds)
    before = {}

    def on_window():
        before["hist"] = hist_state(engine.stats)
        before["counters"] = counters(engine)
        harness.start_window()

    after = {}

    def on_close():
        after["hist"] = hist_state(engine.stats)
        after["counters"] = counters(engine)

    clients = offer(engine, schedule, args.seconds, harness, on_window=on_window, on_close=on_close)
    memory = harness.memory_peak()
    ledger = engine.compile_ledger.snapshot()
    summary = summarize(clients, args.seconds)
    done = summary.pop("done")

    checks = check.Checks()
    short = sum(1 for c in clients if c.req["measured"] and c.error is None and len(c.tokens) != c.req["max_new"])
    checks.add("requests_with_wrong_token_count", float(short), 0.0)
    sample = sample_for_check(done, args.seed, int(limits["sample_requests"]))
    t_ref = time.perf_counter()
    gaps = served_gaps(flat, cfg, sample)
    note = f"{len(gaps)} tokens of {len(sample)} requests"
    inf = float("inf")
    # the widest gap swings by its nature; the mean square is the steady one
    checks.add("served_token_logit_gap_max", float(gaps.max()) if len(gaps) else inf,
               limits["served_token_logit_gap_max"], note)
    checks.add("served_token_logit_gap_mean_sq", float(np.mean(gaps ** 2)) if len(gaps) else inf,
               limits["served_token_logit_gap_mean_sq"], note)
    print(f"reference: {time.perf_counter() - t_ref:.1f} s; mean gap {float(np.mean(gaps)) if len(gaps) else inf!r}, "
          f"share of served tokens that are not the reference's choice "
          f"{float(np.mean(gaps > 0)) if len(gaps) else inf!r} (read, not limited)", flush=True)
    if summary["errors"]:
        print("request errors:", summary["errors"], flush=True)

    # every latency and rate of the benchmark's own clock; BENCHMARK.json says which are end-to-end
    end_to_end = {k: v for k, v in summary.items() if k.endswith(("_ms", "_per_s")) and v is not None}
    return {
        "end_to_end": end_to_end,
        "attempted": summary["attempted"],
        "failed": summary["failed"],
        "checks": checks,
        "memory_peak_bytes": memory,
        "sources": {
            "kind": "serve",
            "summary": summary,
            "stats_before": before,
            "stats_after": after,
            "compile_ledger": ledger,
            "memory_peak_bytes": memory,
        },
    }
