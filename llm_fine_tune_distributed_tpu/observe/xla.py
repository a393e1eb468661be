"""XLA runtime introspection: compile ledger, cost-analysis utilization
gauges, and on-demand profiler capture.

The serving engines and the trainer dispatch a small set of jitted
programs (decode tick, prefill chunk, speculative verify, draft step,
train step). Steady-state behaviour is: every program compiles exactly
once per shape bucket during warmup, then never again — a retrace in
steady state silently costs seconds per occurrence and is always a bug
(a stray shape bucket, a weak-type flip, a donated-buffer mismatch).
This module makes that contract observable:

- ``CompileLedger`` records every compilation (program name, abstract
  arg shapes, wall compile seconds, engine generation), deduplicates by
  (program, shapes), and exposes ``recompiles_after_warmup`` — the
  number that must stay zero once ``mark_warm()`` has been called.
  Listeners (the engines' flight recorders) are notified of post-warmup
  recompiles as they happen.
- ``instrument()`` wraps a jitted callable so its first call registers
  with the ledger. For engine hot-path programs (``aot=True``) the
  first call goes through ``fn.lower(...).compile()`` — exact compile
  wall time plus ``cost_analysis()`` FLOPs / bytes-accessed — and the
  AOT executable becomes the dispatch target (one compile, not two).
  Any AOT failure falls back permanently to the plain jit callable with
  first-call wall timing (an upper bound on compile time).
- ``device_peak_specs()`` + ``utilization_from_cost()`` turn the cost
  analysis and the ``decode_tick_s`` histogram into
  ``model_flops_utilization`` and ``hbm_bandwidth_utilization`` gauges
  (batched decode is bandwidth-bound; the BW gauge is the one that
  should sit near its roofline).
- ``ProfilerCapture`` guards ``jax.profiler`` traces for the serving
  ``POST /v1/profile`` endpoint: one capture at a time, auto-stop after
  the requested duration, a fresh subdirectory per capture.
- ``annotate()`` yields ``jax.profiler.TraceAnnotation`` spans so tick
  phases (admit/prefill/verify/sample) line up with captured traces.
- ``scope()`` names the train step's operations (``STEP_SCOPES``) with
  ``jax.named_scope``: a device trace then carries, on every operation, the
  path of scopes it was traced under, and a reader can say which part of
  the step the device's time went to.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import re
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax

__all__ = [
    "CaptureBusyError",
    "CompileLedger",
    "ProfilerCapture",
    "STEP_SCOPES",
    "annotate",
    "device_peak_specs",
    "instrument",
    "mosaic_programs",
    "scope",
    "utilization_from_cost",
]


# ------------------------------------------------------------------ ledger


class CompileLedger:
    """Thread-safe registry of XLA compilations, deduplicated by
    (program, abstract shapes). Re-recording an already-seen signature
    bumps its compile count (a cache rebuild), and any record after
    ``mark_warm()`` increments ``recompiles_after_warmup`` and notifies
    listeners — steady-state recompile is a bug, and this is the counter
    that proves its absence.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._seq = 0
        self._warmed = False
        self.recompiles_after_warmup = 0
        # engines stamp their supervisor generation here so ledger entries
        # attribute to the engine incarnation that compiled them (replicas
        # sharing one Generator share one ledger; the stamp is best-effort)
        self.current_generation = 0
        self._listeners: List[Callable[..., None]] = []

    def record(
        self,
        program: str,
        shapes: Any,
        compile_s: float,
        flops: Optional[float] = None,
        bytes_accessed: Optional[float] = None,
    ) -> None:
        sig = shapes if isinstance(shapes, str) else str(tuple(shapes)) if isinstance(shapes, (list, tuple)) else str(shapes)
        with self._lock:
            self._seq += 1
            entry = self._entries.get((program, sig))
            if entry is None:
                entry = {
                    "compiles": 0,
                    "compile_s": 0.0,
                    "flops": None,
                    "bytes_accessed": None,
                    "generation": self.current_generation,
                }
                self._entries[(program, sig)] = entry
            entry["compiles"] += 1
            entry["compile_s"] += float(compile_s)
            entry["seq"] = self._seq
            entry["generation"] = self.current_generation
            if flops is not None:
                entry["flops"] = float(flops)
            if bytes_accessed is not None:
                entry["bytes_accessed"] = float(bytes_accessed)
            after_warmup = self._warmed
            if after_warmup:
                self.recompiles_after_warmup += 1
            listeners = list(self._listeners)
        if after_warmup:
            for fn in listeners:
                try:
                    fn(program, sig, float(compile_s), self.current_generation)
                except Exception:
                    pass  # a broken listener must never fail a dispatch

    def mark_warm(self) -> None:
        """Declare warmup over: every record from here on is a recompile."""
        with self._lock:
            self._warmed = True

    @property
    def warmed(self) -> bool:
        return self._warmed

    def add_listener(self, fn: Callable[..., None]) -> None:
        """``fn(program, shapes, compile_s, generation)`` on every
        post-warmup record."""
        with self._lock:
            self._listeners.append(fn)

    def cost_for(self, programs: Iterable[str]) -> Tuple[float, float]:
        """(flops, bytes_accessed) of the most recently compiled entry
        among ``programs`` that carries cost analysis; (0, 0) if none."""
        names = set(programs)
        best = None
        with self._lock:
            for (name, _), e in self._entries.items():
                if name in names and e.get("flops") is not None:
                    if best is None or e["seq"] > best["seq"]:
                        best = e
        if best is None:
            return 0.0, 0.0
        return float(best["flops"] or 0.0), float(best["bytes_accessed"] or 0.0)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            programs: Dict[str, Dict[str, float]] = {}
            for (name, _), e in self._entries.items():
                p = programs.setdefault(name, {"compiles": 0, "compile_s": 0.0})
                p["compiles"] += e["compiles"]
                p["compile_s"] += e["compile_s"]
            for p in programs.values():
                p["compile_s"] = round(p["compile_s"], 6)
            return {
                "programs": programs,
                "total_compiles": sum(p["compiles"] for p in programs.values()),
                "total_compile_s": round(
                    sum(p["compile_s"] for p in programs.values()), 6
                ),
                "recompiles_after_warmup": self.recompiles_after_warmup,
                "warmed": self._warmed,
            }

    @staticmethod
    def merge(ledgers: Iterable["CompileLedger"]) -> Dict[str, Any]:
        """Snapshot-shaped union over DISTINCT ledgers (fleet replicas
        sharing one Generator share one ledger object — dedup by
        identity so shared compilations are not double-counted)."""
        seen: Dict[int, CompileLedger] = {}
        for led in ledgers:
            if led is not None:
                seen.setdefault(id(led), led)
        programs: Dict[str, Dict[str, float]] = {}
        recompiles = 0
        warmed = bool(seen)
        for led in seen.values():
            snap = led.snapshot()
            for name, p in snap["programs"].items():
                agg = programs.setdefault(name, {"compiles": 0, "compile_s": 0.0})
                agg["compiles"] += p["compiles"]
                agg["compile_s"] += p["compile_s"]
            recompiles += snap["recompiles_after_warmup"]
            warmed = warmed and snap["warmed"]
        for p in programs.values():
            p["compile_s"] = round(p["compile_s"], 6)
        return {
            "programs": programs,
            "total_compiles": sum(p["compiles"] for p in programs.values()),
            "total_compile_s": round(
                sum(p["compile_s"] for p in programs.values()), 6
            ),
            "recompiles_after_warmup": recompiles,
            "warmed": warmed,
        }


# ----------------------------------------------------- program instrumenting


def _abstract_shapes(args: Any, kwargs: Any = None) -> str:
    """Compact abstract-shape signature of a call's arguments. Large
    pytrees (a train step's parameter forest) are summarized rather than
    enumerated — the signature only needs to be stable per shape bucket."""
    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    parts = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            parts.append(f"{getattr(leaf, 'dtype', '?')}{tuple(shape)}")
        else:
            parts.append(type(leaf).__name__)
    if len(parts) > 8:
        # summarize, but keep the tail's information: the head of a train
        # step's leaf list is all parameters (identical across calls) while
        # the distinguishing shapes (cache width, batch bucket) sit deeper,
        # so a plain prefix-truncation would alias genuinely different
        # signatures — and the signature dispatches AOT executables
        digest = hash(tuple(parts)) & 0xFFFFFFFF
        parts = parts[:4] + [f"...{len(parts)}leaves:{digest:08x}"]
    return "(" + ",".join(parts) + ")"


def _extract_cost(compiled: Any) -> Tuple[Optional[float], Optional[float]]:
    """FLOPs / bytes-accessed from ``Compiled.cost_analysis()``, which
    returns a dict on recent JAX and a one-element list on older ones."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None, None
    return (
        float(ca.get("flops", 0.0) or 0.0),
        float(ca.get("bytes accessed", 0.0) or 0.0),
    )


class _InstrumentedProgram:
    """Wraps a jitted callable so every NEW call signature registers with
    the ledger. ``aot=True`` compiles ahead-of-time per signature (exact
    compile seconds + cost analysis) and dispatches later same-shape
    calls straight to that executable; an AOT failure (python-scalar
    args, donation quirks, old JAX) falls back to the plain jit callable
    for that signature, timing its first call as an upper bound on
    compile time. Dispatch is keyed by the abstract shapes of the actual
    call, NOT the owner's cache key: a Generator's jit-cache key doesn't
    fully determine shapes (two engines with different slot counts share
    one Generator, so one ``slot_prefill`` bucket entry sees two cache
    widths) and an AOT executable — unlike plain jit — cannot absorb a
    new shape silently. First calls are serialized so two threads racing
    a cold signature produce one ledger entry. Non-``__call__`` attributes
    (``lower``, ``eval_shape``, ...) proxy to the wrapped callable."""

    __slots__ = ("_program", "_fn", "_ledger", "_shapes", "_aot", "_lock", "_calls")

    def __init__(self, program, fn, ledger, shapes=None, aot=True):
        self._program = program
        self._fn = fn
        self._ledger = ledger
        self._shapes = shapes
        self._aot = aot
        self._lock = threading.Lock()
        self._calls: dict = {}  # signature -> AOT executable or plain jit fn

    def __getattr__(self, name):
        if name.startswith("_"):  # never proxy slot misses back into _fn
            raise AttributeError(name)
        return getattr(self._fn, name)

    def __call__(self, *args, **kwargs):
        sig = _abstract_shapes(args, kwargs)
        call = self._calls.get(sig)
        if call is not None:
            return call(*args, **kwargs)
        with self._lock:
            call = self._calls.get(sig)
            if call is not None:
                return call(*args, **kwargs)
            return self._first_call(sig, args, kwargs)

    def _first_call(self, sig, args, kwargs):
        shapes = sig if self._shapes is None else f"{self._shapes}{sig}"
        if self._aot:
            try:
                t0 = time.perf_counter()
                compiled = self._fn.lower(*args, **kwargs).compile()
                dt = time.perf_counter() - t0
                flops, nbytes = _extract_cost(compiled)
                out = compiled(*args, **kwargs)
                # record only after a successful execute: if the AOT
                # artifact can't even run, the plain-jit retry below must
                # own the ledger entry
                self._ledger.record(self._program, shapes, dt, flops, nbytes)
                self._calls[sig] = compiled
                return out
            except Exception:
                pass
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        self._ledger.record(self._program, shapes, dt)
        self._calls[sig] = self._fn
        return out


def instrument(program, fn, ledger, shapes=None, aot=True):
    """Ledger-wrap a jitted callable (see ``_InstrumentedProgram``)."""
    return _InstrumentedProgram(program, fn, ledger, shapes=shapes, aot=aot)


_MOSAIC_CALL = re.compile(r'\\22body\\22: \\22([^\\]*)\\22.*?kernel_name = "([^"]*)"')


def mosaic_programs(stablehlo_text: str) -> Dict[str, Dict[str, int]]:
    """The Pallas TPU kernels of a lowered program (``jitted.lower(...).as_text()``):
    ``{kernel name: {"programs": distinct serialized Mosaic modules, "bytes": their
    lengths added up as the text carries them, "call_sites": calls}}``. What a warm
    start pays for a kernel follows these (PERF.md, PR 37), and a CPU can count them."""
    bodies: Dict[str, Dict[str, int]] = {}
    for body, name in _MOSAIC_CALL.findall(stablehlo_text):
        sites = bodies.setdefault(name, {})
        sites[body] = sites.get(body, 0) + 1
    return {name: {"programs": len(sites), "bytes": sum(map(len, sites)), "call_sites": sum(sites.values())}
            for name, sites in bodies.items()}


# -------------------------------------------------- utilization from cost


# (peak dense bf16 FLOP/s, peak HBM bytes/s) per chip, keyed by the
# ``device_kind`` string the installed runtime reports. Published peaks —
# the gauges they feed are roofline fractions, not absolute truth.
_DEVICE_PEAKS = {
    # Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16 (393 TOP/s
    # int8), 819 GB/s HBM. libtpu 0.0.34 reports a v5e as "TPU v5 lite".
    "TPU v5 lite": (197e12, 8.19e11),
}


def device_peak_specs(device=None) -> Tuple[float, float]:
    """(peak FLOP/s, peak HBM bytes/s) for the given (default: first)
    device. A CPU is a known device with no peaks: (0, 0), and downstream
    gauges read 0.0 rather than invent a roofline. An accelerator whose
    kind is not in the table raises — it does not read as zero."""
    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return 0.0, 0.0
    try:
        return _DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add it, with its source, to "
            "observe/xla.py _DEVICE_PEAKS"
        ) from None


def utilization_from_cost(
    flops: float,
    bytes_accessed: float,
    mean_step_s: float,
    peak_flops: float,
    peak_bw: float,
) -> Tuple[float, float]:
    """(model_flops_utilization, hbm_bandwidth_utilization) for a program
    whose cost analysis says it does ``flops`` / ``bytes_accessed`` per
    dispatch and whose measured mean dispatch time is ``mean_step_s``.
    Clamped to [0, 1]; 0.0 whenever any input is unknown."""

    def ratio(work, peak):
        if work <= 0.0 or peak <= 0.0 or mean_step_s <= 0.0:
            return 0.0
        return min(1.0, work / (mean_step_s * peak))

    return ratio(flops, peak_flops), ratio(bytes_accessed, peak_bw)


# ------------------------------------------------------- profiler capture


class CaptureBusyError(RuntimeError):
    """A profiler capture is already running (one at a time)."""


class ProfilerCapture:
    """On-demand ``jax.profiler`` trace for the serving ``/v1/profile``
    endpoint: one capture at a time, a fresh ``capture_NNNN``
    subdirectory per capture, auto-stop after the requested duration.
    ``on_event(kind, **fields)`` (the engine flight recorder) sees
    profile_start / profile_stop."""

    def __init__(self, base_dir: str, on_event: Optional[Callable[..., None]] = None):
        self.base_dir = base_dir
        self._on_event = on_event
        self._lock = threading.Lock()
        self._active: Optional[str] = None
        self._timer: Optional[threading.Timer] = None
        self._seq = itertools.count(1)

    @property
    def active(self) -> Optional[str]:
        return self._active

    def start(self, duration_s: float) -> str:
        """Begin a capture; returns its directory. Raises
        ``CaptureBusyError`` if one is already running."""
        if duration_s <= 0:
            raise ValueError(f"duration_s must be positive, got {duration_s}")
        with self._lock:
            if self._active is not None:
                raise CaptureBusyError(
                    f"capture already running in {self._active}"
                )
            trace_dir = os.path.join(self.base_dir, f"capture_{next(self._seq):04d}")
            os.makedirs(trace_dir, exist_ok=True)
            jax.profiler.start_trace(trace_dir)
            self._active = trace_dir
            self._timer = threading.Timer(duration_s, self.stop)
            self._timer.daemon = True
            self._timer.start()
        self._event("profile_start", dir=trace_dir, duration_s=duration_s)
        return trace_dir

    def stop(self) -> Optional[str]:
        """Stop the running capture (idempotent); returns its directory."""
        with self._lock:
            if self._active is None:
                return None
            trace_dir = self._active
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass  # already stopped underneath us; the dir still counts
            # the event must land before `active` reads None: pollers treat
            # active=None as "capture fully finished" (on_event only appends
            # to a recorder deque, so holding the lock here is safe)
            self._event("profile_stop", dir=trace_dir)
            self._active = None
        return trace_dir

    def _event(self, kind: str, **fields) -> None:
        if self._on_event is not None:
            try:
                self._on_event(kind, **fields)
            except Exception:
                pass


def annotate(name: str):
    """``jax.profiler.TraceAnnotation`` span (nullcontext when the
    profiler lacks it) — wraps tick phases so captures line up with the
    request timeline."""
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()


# The train step's scope vocabulary. ``layer`` takes the layer's index
# (``layer0`` ... ``layer35``); ``attn`` and ``mlp`` sit inside a layer. A
# scope is metadata on the traced operations (the ``op_name`` XLA keeps, the
# ``tf_op`` of a device trace) and costs nothing when no trace is taken.
# Forward, backward and recompute are not scopes: JAX writes them into the
# path itself (``jvp(layer3)``, ``transpose(jvp(layer3))``,
# ``rematted_computation``).
STEP_SCOPES = (
    "embed", "layer", "attn", "mlp", "final_norm", "loss_head", "grad_accum", "optimizer",
    # inside "mlp", in a layer of routed experts with shared experts
    "router", "experts", "shared_expert",
    # a linear-attention layer's mixer (in place of "attn") and its parts; the
    # output gate of a gated softmax-attention layer, inside "attn"
    "linear_attn", "gdn_conv", "gdn_scan", "gdn_gate_norm", "attn_gate",
)


def scope(name: str, index: Optional[int] = None):
    """``jax.named_scope`` for one name of ``STEP_SCOPES``."""
    assert name in STEP_SCOPES, name
    return jax.named_scope(name if index is None else f"{name}{index}")
