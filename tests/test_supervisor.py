"""Self-healing serving (infer/supervisor.py + the supervision loop in
infer/engine.py). Pins the recovery contracts:

- an injected retryable decode failure fails every IN-FLIGHT request fast
  with RetryableEngineError (no waiter ever hangs), the worker rebuilds
  device state in-process, and the NEXT greedy request is bit-identical to
  solo ``generate_ids`` — on both the dense and the paged engine;
- repeated failures inside the sliding window open the circuit breaker:
  the engine goes terminally unhealthy and everything (queued, in-flight,
  and later submits) resolves with CircuitOpenError;
- bounded admission sheds overflow with a 429-mapped QueueOverflowError
  carrying a FINITE Retry-After, and queue-wait deadlines shed stale
  waiters before prefill;
- graceful drain closes admission while in-flight work finishes;
- the decode worker pokes the step watchdog (runtime/watchdog.py) every
  device round-trip and pauses it while legitimately idle.
"""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
from llm_fine_tune_distributed_tpu.infer import GenerationConfig, Generator
from llm_fine_tune_distributed_tpu.infer.engine import (
    ContinuousBatchingEngine,
    PagedContinuousBatchingEngine,
)
from llm_fine_tune_distributed_tpu.infer.errors import (
    CircuitOpenError,
    DrainingError,
    QueueDeadlineError,
    QueueOverflowError,
    RetryableEngineError,
    ServingError,
    error_payload,
    is_retryable_failure,
)
from llm_fine_tune_distributed_tpu.infer.supervisor import (
    EngineSupervisor,
    FaultInjector,
)
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import init_params

GREEDY = GenerationConfig(max_new_tokens=6, do_sample=False)


@pytest.fixture(scope="module")
def generator():
    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    return Generator(
        params, mc, ByteChatMLTokenizer(), compute_dtype=jnp.float32, eos_token_ids=[]
    )


def _prompts():
    tok = ByteChatMLTokenizer()
    return [tok.encode(t) for t in ("alpha", "beta bravo", "the quick brown fox")]


def _make(generator, kind, **kw):
    """Fresh engine with test-speed supervision defaults."""
    kw.setdefault("restart_backoff_s", 0.01)
    kw.setdefault("restart_backoff_max_s", 0.02)
    if kind == "paged":
        return PagedContinuousBatchingEngine(
            generator, slots=4, buf_len=96, prompt_bucket=16,
            block_len=16, prefill_chunk=32, **kw,
        )
    return ContinuousBatchingEngine(
        generator, slots=4, buf_len=96, prompt_bucket=16, **kw
    )


# ------------------------------------------------------------------ policy


def test_supervisor_policy_window_and_backoff():
    sup = EngineSupervisor(
        restart_backoff_s=0.5, restart_backoff_max_s=2.0,
        circuit_threshold=3, circuit_window_s=10.0,
    )
    assert sup.record_failure(now=0.0) == "restart"
    assert sup.backoff_delay() == 0.5
    assert sup.record_failure(now=1.0) == "restart"
    assert sup.backoff_delay() == 1.0  # doubled
    sup.restarted()
    assert sup.generation == 1
    # the first two failures age OUT of the 10s window: count resets to 1
    assert sup.record_failure(now=11.5) == "restart"
    assert sup.failure_count == 1
    # three failures INSIDE the window trip the breaker
    assert sup.record_failure(now=12.0) == "restart"
    assert sup.record_failure(now=12.5) == "open"
    assert sup.circuit_open
    # backoff is capped
    assert sup.backoff_delay() == 2.0


def test_fault_injector_self_disarms():
    fi = FaultInjector()
    fi.fail_decode_next(2)
    fi.fail_decode_at(7)
    for step in (1, 2):
        with pytest.raises(Exception):
            fi.maybe_fail_decode(step)
    fi.maybe_fail_decode(3)  # healed
    with pytest.raises(Exception):
        fi.maybe_fail_decode(7)  # absolute-index arm
    fi.maybe_fail_decode(7)
    fi.maybe_fail_prefill()  # inert unless armed
    fi.fail_prefill_next(1)
    with pytest.raises(Exception):
        fi.maybe_fail_prefill()
    fi.maybe_fail_prefill()


def test_error_taxonomy_statuses_and_payloads():
    assert error_payload(QueueOverflowError("full", retry_after_s=3.0))[0] == 429
    assert error_payload(RetryableEngineError("x"))[0] == 503
    assert error_payload(CircuitOpenError("x"))[0] == 503
    assert error_payload(DrainingError("x"))[0] == 503
    status, payload, retry = error_payload(
        QueueOverflowError("full", retry_after_s=3.0)
    )
    assert payload["error"]["kind"] == "queue_overflow"
    assert payload["error"]["retryable"] is True
    assert retry == 3.0
    # generic exceptions: retryable unless on the fatal allowlist
    assert is_retryable_failure(RuntimeError("transient"))
    assert not is_retryable_failure(MemoryError("oom"))
    assert not is_retryable_failure(NotImplementedError("no kernel"))


# ------------------------------------------------------- crash -> recover


@pytest.mark.parametrize("kind", ["continuous", "paged"])
def test_decode_crash_recovers_bit_identical(generator, kind):
    """The acceptance gate: injected retryable decode failure -> every
    in-flight waiter resolves with RetryableEngineError (none hang), the
    engine restarts in-process, and the next greedy request reproduces
    solo generate_ids bit-for-bit."""
    prompts = _prompts()
    solo = [generator.generate_ids(p, GREEDY) for p in prompts]
    engine = _make(generator, kind)
    # warm: prove the engine decodes correctly before the chaos
    assert engine.submit(prompts[0], GREEDY, timeout=240) == solo[0]

    engine.faults.fail_decode_next(1)
    outcomes = [None] * len(prompts)

    def ask(i):
        try:
            outcomes[i] = ("ok", engine.submit(prompts[i], GREEDY, timeout=60))
        except BaseException as e:  # noqa: BLE001 - recording outcome
            outcomes[i] = ("err", e)

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(not t.is_alive() for t in threads), "a waiter hung"
    # at least one request rode the failed generation and got the retryable
    # error; anything that reports ok must still be bit-exact
    errs = [o[1] for o in outcomes if o[0] == "err"]
    assert errs, outcomes
    assert all(isinstance(e, RetryableEngineError) for e in errs)
    assert all(e.retry_after_s > 0 for e in errs)

    # recovered: same prompts, bit-identical to solo decode
    after = [engine.submit(p, GREEDY, timeout=240) for p in prompts]
    assert after == solo
    snap = engine.stats_snapshot()
    assert snap["engine_restarts"] >= 1
    assert snap["engine_generation"] >= 1
    assert snap["circuit_state"] == "closed"
    assert engine.healthy


@pytest.mark.parametrize("kind", ["continuous", "paged"])
def test_crash_during_speculation_recovers_bit_identical(generator, kind):
    """PR 3 recovery semantics are unchanged by speculation: a decode crash
    on a speculative tick fails the in-flight waiter retryable, the rebuilt
    engine (fresh target AND draft state) reproduces solo speculative decode
    bit-for-bit, and the jitted spec programs survive on the Generator."""
    tok = ByteChatMLTokenizer()
    rep = tok.encode("water water water water water")  # drafting engages
    spec = GenerationConfig(
        max_new_tokens=8, do_sample=False, speculative_lookup=4
    )
    solo = generator.generate_ids(rep, spec)
    engine = _make(generator, kind, speculative_k=4)
    warm = engine.submit_full(rep, spec, timeout=240)
    assert warm.result == solo  # warm: speculation correct before the chaos
    assert warm.draft_tokens_proposed > 0  # the crash hits a REAL spec tick

    engine.faults.fail_decode_next(1)
    with pytest.raises(RetryableEngineError):
        engine.submit(rep, spec, timeout=60)

    after = engine.submit_full(rep, spec, timeout=240)
    assert after.result == solo
    assert after.draft_tokens_proposed > 0
    snap = engine.stats_snapshot()
    assert snap["engine_restarts"] >= 1
    assert engine.healthy


@pytest.mark.parametrize("kind", ["continuous", "paged"])
def test_prefill_crash_recovers(generator, kind):
    """A device failure during prefill takes the same supervision path. On
    the dense engine the not-yet-committed request is requeued and retried
    transparently (the waiter never sees the blip)."""
    prompts = _prompts()
    solo = generator.generate_ids(prompts[1], GREEDY)
    engine = _make(generator, kind)
    assert engine.submit(prompts[0], GREEDY, timeout=240) is not None  # warm
    engine.faults.fail_prefill_next(1)
    if kind == "continuous":
        # nothing host-side is committed before the dense prefill call, so
        # the request retries against the rebuilt state transparently
        assert engine.submit(prompts[1], GREEDY, timeout=60) == solo
    else:
        # paged prefill runs AFTER blocks are mapped: the in-flight request
        # fails retryable, but the engine heals for the next one
        try:
            engine.submit(prompts[1], GREEDY, timeout=60)
        except RetryableEngineError:
            pass
        assert engine.submit(prompts[1], GREEDY, timeout=240) == solo
    assert engine.stats_snapshot()["engine_restarts"] >= 1
    assert engine.healthy


def test_circuit_opens_after_repeated_failures(generator):
    """Failures beyond the threshold stop the restart loop: the engine goes
    terminally unhealthy, in-flight work resolves with CircuitOpenError,
    and later submits are rejected at admission."""
    prompts = _prompts()
    engine = _make(generator, "continuous", circuit_threshold=2,
                   circuit_window_s=60.0)
    assert engine.submit(prompts[0], GREEDY, timeout=240) is not None  # warm
    engine.faults.fail_decode_next(10)  # keeps failing across restarts

    with pytest.raises(RetryableEngineError):
        engine.submit(prompts[0], GREEDY, timeout=60)  # failure 1: restart
    with pytest.raises((RetryableEngineError, CircuitOpenError)):
        engine.submit(prompts[1], GREEDY, timeout=60)  # failure 2: open

    deadline = time.monotonic() + 10
    while engine.healthy and time.monotonic() < deadline:
        try:
            engine.submit(prompts[2], GREEDY, timeout=10)
        except ServingError:
            pass
    assert not engine.healthy
    assert engine.circuit_state == "open"
    assert isinstance(engine.terminal_error, CircuitOpenError)
    with pytest.raises(CircuitOpenError):
        engine.submit(prompts[0], GREEDY, timeout=10)  # shed at admission
    snap = engine.stats_snapshot()
    assert snap["circuit_state"] == "open"


# ------------------------------------------------------------- admission


def _wait_until(probe, timeout_s: float = 60.0):
    deadline = time.monotonic() + timeout_s
    while not probe() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert probe(), "the engine never got there"


def test_queue_overflow_sheds_429_with_finite_retry_after(generator):
    prompts = _prompts()
    engine = ContinuousBatchingEngine(
        generator, slots=1, buf_len=96, prompt_bucket=16, max_queue_depth=1,
    )
    long_cfg = GenerationConfig(max_new_tokens=48, do_sample=False)
    occupier = threading.Thread(
        target=lambda: engine.submit(prompts[0], long_cfg, timeout=240)
    )
    occupier.start()
    # polls on the engine's own probes, not fixed sleeps: while the occupier
    # is still QUEUED (its prefill compiles first) the depth-1 queue is full
    # and the waiter itself would be the one shed
    _wait_until(lambda: engine.live_slots == 1)  # occupant holds the only slot
    waiter = threading.Thread(
        target=lambda: engine.submit(prompts[1], long_cfg, timeout=240)
    )
    waiter.start()
    _wait_until(lambda: engine.queue_depth == 1)  # waiter fills the depth-1 queue
    with pytest.raises(QueueOverflowError) as exc:
        engine.submit(prompts[2], GREEDY, timeout=30)
    assert exc.value.status == 429
    assert exc.value.retry_after_s is not None
    assert 0.0 < exc.value.retry_after_s < 600.0 + 1e-9
    occupier.join(timeout=240)
    waiter.join(timeout=240)
    assert engine.stats_snapshot()["requests_shed_overflow"] == 1


def test_queue_deadline_sheds_before_prefill(generator):
    prompts = _prompts()
    # buf_len=112 is unique to this test: the occupier's jits compile fresh
    # INSIDE its admission, so the waiter below reliably outlives its
    # deadline while still queued (same trick as the abandonment test in
    # tests/test_engine.py)
    engine = ContinuousBatchingEngine(
        generator, slots=1, buf_len=112, prompt_bucket=16, queue_deadline_s=0.3,
    )
    long_cfg = GenerationConfig(max_new_tokens=64, do_sample=False)
    occupier = threading.Thread(
        target=lambda: engine.submit(prompts[0], long_cfg, timeout=240)
    )
    occupier.start()
    # wait for the occupier to actually be ADMITTED (not a fixed sleep: under
    # full-suite load a slow pickup would shed the occupier on its own
    # deadline and hand the waiter the free slot); its fresh compile + 64
    # greedy tokens then hold the slot far past the waiter's 0.3s deadline
    _wait_until(lambda: engine.stats_snapshot()["requests_admitted"] >= 1)
    with pytest.raises(QueueDeadlineError):
        engine.submit(prompts[1], GREEDY, timeout=240)
    occupier.join(timeout=240)
    snap = engine.stats_snapshot()
    assert snap["requests_shed_deadline"] == 1
    # the shed request was never admitted (no prefill for a gone waiter)
    assert snap["requests_admitted"] == 1


@pytest.mark.parametrize("kind", ["continuous", "paged"])
def test_drain_finishes_in_flight(generator, kind):
    prompts = _prompts()
    solo = generator.generate_ids(
        prompts[0], GenerationConfig(max_new_tokens=24, do_sample=False)
    )
    engine = _make(generator, kind)
    engine.submit(prompts[0], GREEDY, timeout=240)  # warm the jit caches
    result = []
    inflight = threading.Thread(
        target=lambda: result.append(
            engine.submit(
                prompts[0],
                GenerationConfig(max_new_tokens=24, do_sample=False),
                timeout=240,
            )
        )
    )
    inflight.start()
    time.sleep(0.1)
    engine.begin_drain()
    with pytest.raises(DrainingError) as exc:
        engine.submit(prompts[1], GREEDY, timeout=30)
    assert exc.value.retry_after_s is not None
    assert engine.wait_drained(timeout_s=120.0)
    inflight.join(timeout=240)
    assert result == [solo]  # the in-flight request finished, unharmed
    assert engine.draining


def test_no_hung_waiter_under_crash_storm(generator):
    """Many concurrent submits racing an injected failure: every single one
    resolves (result or ServingError) — the no-hung-waiter invariant."""
    prompts = _prompts()
    engine = _make(generator, "continuous")
    engine.submit(prompts[0], GREEDY, timeout=240)  # warm
    engine.faults.fail_decode_next(1)
    outcomes = [None] * 8

    def ask(i):
        try:
            outcomes[i] = ("ok", engine.submit(
                prompts[i % len(prompts)], GREEDY, timeout=90
            ))
        except BaseException as e:  # noqa: BLE001 - recording outcome
            outcomes[i] = ("err", e)

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert all(not t.is_alive() for t in threads), "a waiter hung"
    assert all(o is not None for o in outcomes)
    for tag, val in outcomes:
        if tag == "err":
            assert isinstance(val, ServingError), val
        else:
            assert isinstance(val, list)
    with engine._plock:
        assert engine._pending == 0  # ledger balanced: one settle per submit


# -------------------------------------------------------------- watchdog


class _RecordingWatchdog:
    """StepWatchdog-shaped probe: counts pokes/pauses instead of aborting."""

    def __init__(self):
        self.pokes = 0
        self.pauses = 0
        self.stopped = False

    def poke(self, step):
        self.pokes += 1

    def pause(self):
        self.pauses += 1

    def stop(self):
        self.stopped = True


def test_decode_worker_pokes_watchdog(generator):
    wd = _RecordingWatchdog()
    engine = ContinuousBatchingEngine(
        generator, slots=2, buf_len=96, prompt_bucket=16, watchdog=wd,
    )
    engine.submit(_prompts()[0], GREEDY, timeout=240)
    # one poke per prefill + one per decode sync; >= max_new_tokens total
    assert wd.pokes >= GREEDY.max_new_tokens
    time.sleep(0.2)  # worker goes idle -> watchdog paused, not poked
    assert wd.pauses >= 1


# ------------------------------------------------------ multi-tenant recovery


def _mk_tenant_adapter(base_params, outdir, seed):
    """PEFT adapter dir with a non-zero, seed-distinct B so each tenant's
    delta is non-trivial and distinguishable."""
    from llm_fine_tune_distributed_tpu.config import TrainConfig
    from llm_fine_tune_distributed_tpu.parallel.lora import (
        add_lora_params,
        save_lora_adapter,
    )

    params = add_lora_params(
        base_params, jax.random.PRNGKey(seed), rank=4, alpha=8.0
    )

    def bump(node):
        if isinstance(node, dict):
            if "lora_b" in node:
                node = dict(node)
                node["lora_b"] = jnp.ones_like(node["lora_b"]) * (0.01 * seed)
                return node
            return {k: bump(v) for k, v in node.items()}
        return node

    save_lora_adapter(
        bump(params), outdir,
        TrainConfig(freeze_strategy="lora", lora_rank=4, lora_alpha=8.0),
    )


@pytest.mark.parametrize("kind", ["continuous", "paged"])
def test_multitenant_crash_restores_residents_bit_identical(
    generator, kind, tmp_path
):
    """A crash mid-multi-tenant decode keeps PR-3 recovery semantics AND
    the adapter pool: in-flight waiters fail retryable, the supervised
    restart restores the RESIDENT adapter set (``_startup`` ->
    ``registry.rebuild()``), and post-recovery greedy decode per tenant is
    bit-identical to that tenant's adapter merged into the weights solo."""
    from llm_fine_tune_distributed_tpu.infer.adapters import AdapterRegistry
    from llm_fine_tune_distributed_tpu.parallel.lora import (
        load_lora_adapter,
        merge_lora,
    )

    base = generator.params
    tok = ByteChatMLTokenizer()
    for name, seed in (("t1", 1), ("t2", 2)):
        _mk_tenant_adapter(base, str(tmp_path / name), seed)
    reg = AdapterRegistry(base, str(tmp_path), max_adapters=4)
    engine = _make(generator, kind, adapters=reg)
    prompts = _prompts()
    merged = {
        name: Generator(
            merge_lora(load_lora_adapter(base, str(tmp_path / name))),
            generator.config, tok,
            compute_dtype=jnp.float32, eos_token_ids=[],
        )
        for name in ("t1", "t2")
    }
    solo = {
        "t1": merged["t1"].generate_ids(prompts[0], GREEDY),
        "t2": merged["t2"].generate_ids(prompts[1], GREEDY),
    }
    # warm both tenants: adapted decode is correct before the chaos
    assert engine.submit(prompts[0], GREEDY, timeout=240, adapter="t1") == solo["t1"]
    assert engine.submit(prompts[1], GREEDY, timeout=240, adapter="t2") == solo["t2"]
    assert sorted(reg.resident()) == ["t1", "t2"]

    engine.faults.fail_decode_next(1)
    outcomes = [None, None]

    def ask(i, name):
        try:
            outcomes[i] = (
                "ok", engine.submit(prompts[i], GREEDY, timeout=60, adapter=name)
            )
        except BaseException as e:  # noqa: BLE001 - recording outcome
            outcomes[i] = ("err", e)

    threads = [
        threading.Thread(target=ask, args=(0, "t1")),
        threading.Thread(target=ask, args=(1, "t2")),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(not t.is_alive() for t in threads), "a waiter hung"
    errs = [o[1] for o in outcomes if o[0] == "err"]
    assert errs, outcomes
    assert all(isinstance(e, RetryableEngineError) for e in errs)
    # every crashed request still released its pin (the single-settle path)
    assert reg.refcount("t1") == 0 and reg.refcount("t2") == 0
    # the resident set SURVIVED the restart (rebuild() in _startup)
    assert sorted(reg.resident()) == ["t1", "t2"]

    # post-recovery: each tenant is bit-identical to its merged-solo run
    assert engine.submit(prompts[0], GREEDY, timeout=240, adapter="t1") == solo["t1"]
    assert engine.submit(prompts[1], GREEDY, timeout=240, adapter="t2") == solo["t2"]
    snap = engine.stats_snapshot()
    assert snap["engine_restarts"] >= 1
    assert snap["adapters_resident"] == 2
    assert snap["per_tenant"]["t1"]["queue_depth"] == 0
    assert engine.healthy
