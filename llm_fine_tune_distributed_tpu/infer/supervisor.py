"""Engine supervision policy: restart backoff, circuit breaker, and
deterministic fault injection.

The policy half of the self-healing loop in infer/engine.py. The engine
worker catches a failed tick, asks ``EngineSupervisor.record_failure()``
whether to restart or give up, sleeps ``backoff_delay()``, rebuilds its
device state (params stay resident, jit caches stay warm — a restart costs
milliseconds, not a recompilation), and bumps ``generation``. N failures
inside a sliding window open the circuit: the worker stops restarting,
fails everything fast, and ``/healthz`` goes unhealthy so the orchestrator
recycles the pod. That split — in-process recovery for blips, external
restart for persistent faults — is the difference between a transient
device stall costing one batch of requests versus a full pod
bounce with cold HBM and a dropped prefix cache.

``FaultInjector`` is the deterministic chaos hook the tests and
``benchmarks/serve_bench.py --chaos`` drive: fail decode at an absolute
step index, fail the next k decode steps, or fail the next k prefills.
Inert unless armed; armed faults raise ``InjectedFault`` inside the worker
so they take exactly the classification path a real device error would.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Optional

from llm_fine_tune_distributed_tpu.infer.errors import InjectedFault


class EngineSupervisor:
    """Restart/backoff/circuit policy for one engine worker.

    All mutation happens on the engine worker thread; ``generation`` and
    ``circuit_open`` are read from server threads (single-word reads, safe
    under the GIL).
    """

    def __init__(
        self,
        restart_backoff_s: float = 0.5,
        restart_backoff_max_s: float = 30.0,
        circuit_threshold: int = 5,
        circuit_window_s: float = 60.0,
        flight_dir: Optional[str] = None,
    ):
        self.restart_backoff_s = max(0.0, float(restart_backoff_s))
        self.restart_backoff_max_s = max(
            self.restart_backoff_s, float(restart_backoff_max_s)
        )
        self.circuit_threshold = max(1, int(circuit_threshold))
        self.circuit_window_s = float(circuit_window_s)
        self.flight_dir = flight_dir
        self.generation = 0
        self.circuit_open = False
        # True from the moment a restart is decided until the worker is
        # serving again — the fleet router (infer/fleet.py) reads it to
        # drop a mid-recovery replica from the candidate set (single-word
        # read, safe under the GIL like generation/circuit_open)
        self.recovering = False
        self._failures: "deque[float]" = deque()
        self._dump_seq = 0

    def record_failure(self, now: Optional[float] = None) -> str:
        """Record one retryable worker failure; returns ``"restart"`` or
        ``"open"`` (threshold failures inside the sliding window)."""
        now = time.monotonic() if now is None else now
        self._failures.append(now)
        while self._failures and now - self._failures[0] > self.circuit_window_s:
            self._failures.popleft()
        if len(self._failures) >= self.circuit_threshold:
            self.circuit_open = True
            return "open"
        return "restart"

    def backoff_delay(self) -> float:
        """Exponential backoff keyed to in-window failure count: the first
        failure restarts after ``restart_backoff_s``, each further one
        doubles it, capped at ``restart_backoff_max_s``."""
        n = max(0, len(self._failures) - 1)
        return min(self.restart_backoff_s * (2.0 ** n), self.restart_backoff_max_s)

    def begin_recovery(self) -> None:
        """The worker decided to restart: backoff + rebuild are imminent.
        Routers should place elsewhere until ``restarted()``."""
        self.recovering = True

    def restarted(self) -> None:
        """The worker rebuilt device state and is serving again."""
        self.generation += 1
        self.recovering = False

    @property
    def failure_count(self) -> int:
        return len(self._failures)

    def dump_flight(
        self,
        recorder,
        reason: str,
        error: Optional[str] = None,
        compile_ledger=None,
    ) -> Optional[str]:
        """Serialize the engine's flight recorder to a JSON artifact.

        Called on the worker thread at the moments worth a post-mortem —
        after a crash's restart transition has been recorded, and when the
        circuit opens or a fatal error kills the worker. Returns the
        artifact path, or ``None`` when no ``flight_dir`` is configured.
        ``compile_ledger`` (observe/xla.CompileLedger) adds a ``compile``
        section — per-program compile counts and the post-warmup recompile
        counter — so retrace churn around a crash is in the artifact.
        Dump failures are swallowed: the recorder must never take down a
        recovery that would otherwise succeed.
        """
        if not self.flight_dir or recorder is None:
            return None
        try:
            os.makedirs(self.flight_dir, exist_ok=True)
            self._dump_seq += 1
            path = os.path.join(
                self.flight_dir,
                f"flight_{reason}_gen{self.generation}_{self._dump_seq}.json",
            )
            payload = {
                "reason": reason,
                "error": error,
                "generation": self.generation,
                "failures_in_window": self.failure_count,
                "circuit_open": self.circuit_open,
                "dumped_at_unix": time.time(),
                "events": recorder.events(),
            }
            if compile_ledger is not None:
                payload["compile"] = compile_ledger.snapshot()
            with open(path, "w") as f:
                json.dump(payload, f, indent=2)
            return path
        except OSError:
            return None


class FaultInjector:
    """Deterministic fault hooks the engine worker polls each tick.

    Armed from any thread, fired on the worker thread; every fire raises
    ``InjectedFault`` and disarms itself, so "fail k times then heal" is
    just ``fail_decode_next(k)``.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._decode_at: set = set()  # absolute decode step indices
        self._decode_next = 0
        self._prefill_next = 0
        # tiered-KV / migration fault points (infer/engine.py): spill =
        # the host-tier gather on eviction/preemption/export, restore = the
        # device scatter at admission, migrate = per-request inside an
        # export (so "crash mid-migration" lands between two requests, the
        # worst spot for double-settle bugs)
        self._spill_next = 0
        self._restore_next = 0
        self._migrate_next = 0
        # disaggregation: handoff = the prefill→decode hand-over of one
        # finished-prefill request; failure degrades to decode-in-place
        self._handoff_next = 0
        # latency (not failure) injection: (remaining ticks, seconds each)
        self._decode_delay = (0, 0.0)

    def fail_decode_at(self, *steps: int) -> None:
        """Fail the decode tick whose absolute step index (1-based, counted
        over the engine's lifetime) matches — "fail decode at step K"."""
        with self._lock:
            self._decode_at.update(int(s) for s in steps)

    def fail_decode_next(self, k: int = 1) -> None:
        """Fail the next ``k`` decode ticks, then heal."""
        with self._lock:
            self._decode_next += int(k)

    def fail_prefill_next(self, k: int = 1) -> None:
        """Fail the next ``k`` prefill operations, then heal."""
        with self._lock:
            self._prefill_next += int(k)

    def fail_spill_next(self, k: int = 1) -> None:
        """Fail the next ``k`` host-tier spills (the block gather on
        eviction/preemption/export), then heal. The engine degrades each
        failed spill to today's plain discard — lost reuse, never lost
        data — and counts ``prefix_blocks_discarded``."""
        with self._lock:
            self._spill_next += int(k)

    def fail_restore_next(self, k: int = 1) -> None:
        """Fail the next ``k`` host-tier restores (the device scatter at
        admission), then heal. The engine falls back to the full re-prefill
        path — greedy output stays bit-identical either way."""
        with self._lock:
            self._restore_next += int(k)

    def fail_migrate_next(self, k: int = 1) -> None:
        """Fail the next ``k`` per-request migration export steps, then
        heal — a crash MID export, after some requests already left. The
        engine re-adopts every already-detached request and the fleet falls
        back to drain-wait; the request completes on exactly one replica."""
        with self._lock:
            self._migrate_next += int(k)

    def fail_handoff_next(self, k: int = 1) -> None:
        """Fail the next ``k`` prefill→decode handoffs, then heal. The
        prefill replica keeps the request and decodes it in place —
        greedy output stays bit-identical, only the disaggregation win is
        lost for that request."""
        with self._lock:
            self._handoff_next += int(k)

    def delay_decode_next(self, k: int = 1, seconds: float = 0.05) -> None:
        """Slow (don't fail) the next ``k`` decode ticks by ``seconds``
        each — a pure latency regression, invisible to error-rate gates.
        This is what the SERVE_SLO bench arm injects into a canary to
        prove the latency verdict catches what the error backstop can't."""
        with self._lock:
            self._decode_delay = (
                self._decode_delay[0] + int(k), float(seconds)
            )

    def clear_delays(self) -> None:
        """Disarm any pending decode delays (bench cleanup)."""
        with self._lock:
            self._decode_delay = (0, 0.0)

    def maybe_fail_decode(self, step_index: int) -> None:
        delay = 0.0
        with self._lock:
            remaining, seconds = self._decode_delay
            if remaining > 0:
                self._decode_delay = (remaining - 1, seconds)
                delay = seconds
        if delay > 0.0:
            time.sleep(delay)
        with self._lock:
            if step_index in self._decode_at:
                self._decode_at.discard(step_index)
            elif self._decode_next > 0:
                self._decode_next -= 1
            else:
                return
        raise InjectedFault(f"injected decode failure at step {step_index}")

    def maybe_fail_prefill(self) -> None:
        with self._lock:
            if self._prefill_next <= 0:
                return
            self._prefill_next -= 1
        raise InjectedFault("injected prefill failure")

    def maybe_fail_spill(self) -> None:
        with self._lock:
            if self._spill_next <= 0:
                return
            self._spill_next -= 1
        raise InjectedFault("injected host-tier spill failure")

    def maybe_fail_restore(self) -> None:
        with self._lock:
            if self._restore_next <= 0:
                return
            self._restore_next -= 1
        raise InjectedFault("injected host-tier restore failure")

    def maybe_fail_migrate(self) -> None:
        with self._lock:
            if self._migrate_next <= 0:
                return
            self._migrate_next -= 1
        raise InjectedFault("injected migration failure")

    def maybe_fail_handoff(self) -> None:
        with self._lock:
            if self._handoff_next <= 0:
                return
            self._handoff_next -= 1
        raise InjectedFault("injected prefill->decode handoff failure")
