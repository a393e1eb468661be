"""Serving failure taxonomy: retryable vs fatal, with HTTP surface.

The engines (infer/engine.py) raise exactly one family of exceptions at
their public edge so the server (infer/server.py) can map every failure to
a structured JSON body and a meaningful status code instead of a blanket
500. Two axes matter to a client:

- **retryable** — the request failed for a reason that does not implicate
  the request itself (device blip mid-decode, queue overflow, drain); the
  same request against the same or another replica is expected to succeed.
  Served as 503 (or 429 for overflow) with a ``Retry-After`` hint where
  the engine can derive one from observed service time.
- **fatal** — retrying is pointless: the engine hit a non-recoverable
  condition (host OOM, assertion, circuit opened after repeated failures)
  or the request was malformed. Served as 500 (taxonomy classes carry
  their own status).

``is_retryable_failure`` classifies raw worker exceptions for the engine
supervisor (infer/supervisor.py): anything not on the explicit fatal list
is presumed transient — a device stall surfaces as a generic runtime
error, and XLA device errors arrive as
backend-specific RuntimeError subclasses, so an allowlist of retryables
would misclassify exactly the failures this layer exists for. Repeated
"transient" failures are contained by the supervisor's circuit breaker,
not by classification.
"""

from __future__ import annotations

from typing import Optional, Tuple


class ServingError(RuntimeError):
    """Base class for every error the serving stack raises at its edge.

    Class attributes give each subclass its identity; instances add the
    human message and optional retry/generation hints.
    """

    kind = "serving_error"
    status = 500
    retryable = False

    def __init__(
        self,
        message: str,
        retry_after_s: Optional[float] = None,
        generation: Optional[int] = None,
    ):
        super().__init__(message)
        self.retry_after_s = retry_after_s
        self.generation = generation

    def to_dict(self) -> dict:
        """Structured JSON body the server returns (and SSE error chunks)."""
        d = {"kind": self.kind, "message": str(self), "retryable": self.retryable}
        if self.retry_after_s is not None:
            d["retry_after_s"] = round(float(self.retry_after_s), 3)
        if self.generation is not None:
            d["generation"] = int(self.generation)
        return d


class RetryableEngineError(ServingError):
    """The engine worker failed mid-flight and is restarting; this request
    was failed fast (its KV state is gone) but the next attempt should hit
    a healthy generation."""

    kind = "engine_restarting"
    status = 503
    retryable = True


class FatalEngineError(ServingError):
    """The engine worker died for a non-recoverable reason; the process
    needs external restart (``/healthz`` goes unhealthy)."""

    kind = "engine_fatal"
    status = 500
    retryable = False


class CircuitOpenError(ServingError):
    """Too many worker failures inside the sliding window: the supervisor
    stopped restarting. Requests are failed fast until the pod is recycled."""

    kind = "circuit_open"
    status = 503
    retryable = False


class QueueOverflowError(ServingError):
    """Bounded admission queue is full; shed at submit with 429 and a
    Retry-After derived from observed service time. Carries the shed
    request's priority tier so clients (and the fleet router) can tell a
    best-effort displacement from total saturation."""

    kind = "queue_overflow"
    status = 429
    retryable = True

    def __init__(
        self,
        message: str,
        retry_after_s: Optional[float] = None,
        generation: Optional[int] = None,
        tier: Optional[str] = None,
    ):
        super().__init__(message, retry_after_s, generation)
        self.tier = tier

    def to_dict(self) -> dict:
        d = super().to_dict()
        if self.tier is not None:
            d["tier"] = self.tier
        return d


class BrownoutShedError(QueueOverflowError):
    """Brownout stage 3: the engine is shedding ``best_effort`` traffic
    before it ever enqueues. A subclass of QueueOverflowError so the fleet
    router's overflow reroute (try a sibling, then the aggregate 429 with
    min predicted drain) applies unchanged — a replica in brownout looks
    exactly like a full replica to placement."""

    kind = "brownout_shed"
    status = 429
    retryable = True


class QueueDeadlineError(ServingError):
    """The request waited longer than its queue deadline before prefill;
    shed un-decoded (the client has likely given up or will retry)."""

    kind = "queue_deadline"
    status = 503
    retryable = True


class DeadlineExceededError(ServingError):
    """The request's client-supplied deadline (``deadline_ms``) expired —
    at queue, at prefill start, or mid-decode at a scheduler tick. Not
    retryable as-is (the client's budget is spent); the body carries the
    tokens generated before cancellation so partial work is not lost."""

    kind = "deadline_exceeded"
    status = 504
    retryable = False

    def __init__(
        self,
        message: str,
        tokens: Optional[Tuple[int, ...]] = None,
        retry_after_s: Optional[float] = None,
        generation: Optional[int] = None,
    ):
        super().__init__(message, retry_after_s, generation)
        self.tokens = list(tokens) if tokens else []

    def to_dict(self) -> dict:
        d = super().to_dict()
        d["tokens_generated"] = len(self.tokens)
        d["partial_tokens"] = [int(t) for t in self.tokens]
        return d


class DrainingError(ServingError):
    """The server is draining (SIGTERM): admission is closed, in-flight
    work finishes. Retry against another replica."""

    kind = "draining"
    status = 503
    retryable = True


class UnknownAdapterError(ServingError):
    """The request named an adapter the registry cannot resolve (no such
    directory under ``--adapter-dir``, or no registry configured at all).
    Carries the known-adapter list so the 404 body tells the client what IS
    servable."""

    kind = "unknown_adapter"
    status = 404
    retryable = False

    def __init__(
        self,
        message: str,
        known: Optional[Tuple[str, ...]] = None,
        retry_after_s: Optional[float] = None,
        generation: Optional[int] = None,
    ):
        super().__init__(message, retry_after_s, generation)
        self.known = tuple(known) if known else ()

    def to_dict(self) -> dict:
        d = super().to_dict()
        d["known_adapters"] = list(self.known)
        return d


class AdapterPoolFullError(ServingError):
    """Every adapter pool slot is pinned by live requests: the named adapter
    cannot be hot-loaded right now. Retry when a resident tenant's requests
    drain (Retry-After from observed service time)."""

    kind = "adapter_pool_full"
    status = 429
    retryable = True


class TenantQuotaError(ServingError):
    """The tenant already has its quota of admitted requests in flight;
    shed at submit with a per-tenant 429 + Retry-After so one tenant cannot
    monopolize the co-batched decode."""

    kind = "tenant_quota"
    status = 429
    retryable = True


class NoHealthyReplicaError(ServingError):
    """Every replica in the fleet is terminally dead (circuit open or
    fatal): the front-door router has nowhere to place the request. The
    whole pod needs a recycle (fleet ``/healthz`` goes unhealthy)."""

    kind = "no_healthy_replica"
    status = 503
    retryable = False


class InjectedFault(RuntimeError):
    """Deterministic test/chaos fault raised inside the engine worker by
    FaultInjector (infer/supervisor.py). Deliberately NOT a ServingError:
    it models a raw device failure and must take the classification path."""


# Exceptions that end the worker for good: retrying cannot help, and a
# restart loop would only mask them. Everything else — including backend
# RuntimeErrors, injected faults, and numpy conversion errors from a dead
# device — is presumed transient and handled by restart + circuit breaker.
_FATAL_TYPES = (
    MemoryError,
    NotImplementedError,
    AssertionError,
    KeyboardInterrupt,
    SystemExit,
)


def is_retryable_failure(exc: BaseException) -> bool:
    """Classify a raw engine-worker exception for the supervisor."""
    if isinstance(exc, ServingError):
        return exc.retryable
    return not isinstance(exc, _FATAL_TYPES)


def error_payload(exc: BaseException) -> Tuple[int, dict, Optional[float]]:
    """(http_status, json_body, retry_after_s) for any exception reaching
    the server edge. Taxonomy classes carry their own status; raw
    exceptions fall back to timeout→503 / other→500."""
    if isinstance(exc, ServingError):
        return exc.status, {"error": exc.to_dict()}, exc.retry_after_s
    if isinstance(exc, TimeoutError):
        return 503, {
            "error": {"kind": "timeout", "message": str(exc), "retryable": True}
        }, None
    return 500, {
        "error": {
            "kind": "internal",
            "message": f"{type(exc).__name__}: {exc}",
            "retryable": False,
        }
    }, None
