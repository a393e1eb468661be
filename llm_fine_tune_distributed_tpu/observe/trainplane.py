"""Training control plane: live trainer introspection + anomaly sentinels.

The serving stack has been deeply observable for a while (/metrics,
/v1/stats, flight recorder, SLO burn rates); the trainer was log lines and
a JSON dump at exit. This module gives a *running* training job the same
surface, served from a primary-host-only HTTP thread that never touches
the step hot path:

- ``GET /metrics`` — Prometheus text exposition (``training_*`` prefix):
  loss/grad-norm/lr gauges, throughput, the per-step phase histograms
  (data_wait / step / checkpoint), compile-ledger counters, roofline
  MFU / HBM-BW gauges, the preemption flag, and
  ``training_anomalies_total{kind=...}``.
- ``GET /v1/train/status`` — step/epoch/ETA, last + best eval, checkpoint
  and publish history, anomaly summary, and ``startup``: where the time
  from process start to the first optimizer step went (seconds by phase,
  compile-cache hits and misses), which a preempted job pays at every
  restart.
- ``GET /v1/train/flight`` — the trainer-owned FlightRecorder ring: step
  milestones, evals, checkpoint save/restore, publishes, watchdog events,
  SIGTERM/preemption.
- ``POST /v1/train/profile`` — on-demand ``jax.profiler`` capture
  (observe/xla.ProfilerCapture), one at a time.

**Anomaly sentinels** watch the per-step metric stream host-side: a hard
non-finite detector (NaN/Inf loss or grad norm) plus EWMA-band detectors
for loss spikes and grad-norm explosions. Every firing lands as a flight
event and a ``training_anomalies_total{kind=}`` increment, and gates
publication: a checkpoint whose trailing window contains an anomaly is
published with ``anomaly_clean: false`` (or skipped outright under
``publish_require_clean``), so the serving side can refuse to promote a
checkpoint cut mid-divergence.

Hot-path discipline: the trainer feeds the sentinels and the status dict
ONLY at its existing log/eval/save boundaries, where the metric scalars
have already been synced to the host — zero extra clock reads or device
syncs ride the per-step loop. Everything here is host-side bookkeeping
read by HTTP handler threads under a lock.
"""

from __future__ import annotations

import hashlib
import json
import math
import threading
import uuid
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional

from llm_fine_tune_distributed_tpu.observe.metrics import (
    PROMETHEUS_CONTENT_TYPE,
    prometheus_exposition,
)
from llm_fine_tune_distributed_tpu.observe.tracing import FlightRecorder
from llm_fine_tune_distributed_tpu.runtime.distributed import is_primary_host

__all__ = [
    "ANOMALY_KINDS",
    "TRAIN_COUNTERS",
    "TRAIN_GAUGES",
    "TRAIN_HIST_KEYS",
    "AnomalySentinels",
    "TrainTelemetry",
    "TrainControlPlane",
    "hparams_digest",
    "new_run_id",
    "trainer_exposition",
]

# Sentinel taxonomy. The exposition seeds every kind at 0 unconditionally
# so the metric schema is identical on a healthy run (the same
# load-independence contract the serving shed-tier counter keeps).
ANOMALY_KINDS = ("non_finite", "loss_spike", "grad_explosion")

# Monotonic trainer counters -> ``training_<name>_total``. "anomalies" is
# deliberately NOT here: it is emitted kind-labelled (plus an unlabelled
# aggregate) by trainer_exposition itself.
TRAIN_COUNTERS = (
    "evals",
    "checkpoints_saved",
    "publishes",
    "publishes_skipped_dirty",
    "watchdog_trips",
)

# Gauge key set of the exposition — seeded at 0 so the schema never
# depends on how far the run has progressed.
TRAIN_GAUGES = (
    "step",
    "total_steps",
    "epoch",
    "epochs",
    "loss",
    "learning_rate",
    "grad_norm",
    "eval_loss",
    "best_eval",
    "samples_per_second",
    "samples_per_second_per_chip",
    "steps_per_second",
    "tokens_per_second_per_chip",
    "real_tokens_per_second_per_chip",
    "packing_efficiency",
    "preempted",
    "model_flops_utilization",
    "hbm_bandwidth_utilization",
)

# Trainer phase histograms (train loop phase_hist keys) -> exposition
# names; the _s suffix becomes _seconds via metrics._prom_name.
TRAIN_HIST_KEYS = ("data_wait", "step", "checkpoint")


def new_run_id() -> str:
    """Short, collision-safe identity of one training run — the key that
    ties serving-side weight generations back to this trainer (manifest
    ``run_id``, ``GET /v1/lineage``)."""
    return uuid.uuid4().hex[:12]


def hparams_digest(hparams: Dict[str, Any]) -> str:
    """16-hex identity of a run's hyperparameters (the flattened config
    dict the trainer already hands to the metric sinks). Two runs with the
    same digest trained with the same knobs — the lineage answer to "was
    generation N trained like generation M?"."""
    try:
        blob = json.dumps(hparams, sort_keys=True, default=str)
    except (TypeError, ValueError):
        blob = repr(sorted(hparams.items(), key=lambda kv: str(kv[0])))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


class _Ewma:
    """Exponentially-weighted mean + variance of a scalar stream (host
    floats only — the values arrive already synced at log boundaries)."""

    def __init__(self, alpha: float):
        self.alpha = float(alpha)
        self.n = 0
        self.mean = 0.0
        self.var = 0.0

    def update(self, x: float) -> None:
        if self.n == 0:
            self.mean = x
            self.var = 0.0
        else:
            d = x - self.mean
            self.mean += self.alpha * d
            # EW variance (West 1979 form): decays old surprise, folds new
            self.var = (1.0 - self.alpha) * (self.var + self.alpha * d * d)
        self.n += 1

    @property
    def std(self) -> float:
        return math.sqrt(max(self.var, 0.0))


class AnomalySentinels:
    """Host-side rolling-window detectors over the per-step metric stream.

    - ``non_finite``: NaN/Inf loss or grad norm — the hard sentinel, fires
      from observation one.
    - ``loss_spike``: loss above the EWMA mean by more than ``band_sigma``
      EW standard deviations, after ``warmup`` finite observations.
    - ``grad_explosion``: the same band on the grad norm.

    Anomalous values are NOT folded into the band (a divergence must not
    widen the band that detects it). ``clean_since(step)`` answers the
    publish gate: has any sentinel fired at or after ``step``?
    """

    def __init__(
        self,
        *,
        band_sigma: float = 6.0,
        warmup: int = 8,
        ewma_alpha: float = 0.1,
        on_anomaly=None,
    ):
        if band_sigma <= 0:
            raise ValueError(f"band_sigma must be positive, got {band_sigma}")
        self.band_sigma = float(band_sigma)
        self.warmup = max(1, int(warmup))
        self._on_anomaly = on_anomaly
        self._loss = _Ewma(ewma_alpha)
        self._grad = _Ewma(ewma_alpha)
        self._lock = threading.Lock()
        self.counts: Dict[str, int] = {k: 0 for k in ANOMALY_KINDS}
        self.last_step: Dict[str, Optional[int]] = {k: None for k in ANOMALY_KINDS}
        self.last_anomaly_step: Optional[int] = None

    def _fire(self, kind: str, step: int, **fields) -> None:
        self.counts[kind] += 1
        self.last_step[kind] = step
        self.last_anomaly_step = (
            step
            if self.last_anomaly_step is None
            else max(self.last_anomaly_step, step)
        )
        if self._on_anomaly is not None:
            try:
                self._on_anomaly(kind, step, **fields)
            except Exception:
                pass  # telemetry must never take down the train loop

    def _band_check(
        self, kind: str, ewma: _Ewma, value: float, step: int
    ) -> bool:
        if ewma.n >= self.warmup:
            # std floor: a perfectly flat warmup (synthetic data, tiny lr)
            # must not make ANY movement a 6-sigma event
            floor = max(ewma.std, 1e-3 * max(1.0, abs(ewma.mean)))
            if value - ewma.mean > self.band_sigma * floor:
                self._fire(
                    kind, step,
                    value=round(value, 6),
                    band_mean=round(ewma.mean, 6),
                    band_std=round(floor, 6),
                )
                return True
        ewma.update(value)
        return False

    def observe(
        self,
        step: int,
        loss: Optional[float] = None,
        grad_norm: Optional[float] = None,
    ) -> List[str]:
        """Feed one step's already-host-side scalars; returns the kinds
        that fired (empty on a clean step)."""
        fired: List[str] = []
        with self._lock:
            for name, value in (("loss", loss), ("grad_norm", grad_norm)):
                if value is None:
                    continue
                value = float(value)
                if not math.isfinite(value):
                    self._fire("non_finite", step, signal=name, value=str(value))
                    fired.append("non_finite")
                    continue
                if name == "loss":
                    if self._band_check("loss_spike", self._loss, value, step):
                        fired.append("loss_spike")
                else:
                    if self._band_check(
                        "grad_explosion", self._grad, value, step
                    ):
                        fired.append("grad_explosion")
        return fired

    def clean_since(self, step_lo: int) -> bool:
        """True when no sentinel fired at step >= ``step_lo`` — the
        publish gate's trailing-window cleanliness check."""
        with self._lock:
            return (
                self.last_anomaly_step is None
                or self.last_anomaly_step < step_lo
            )

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "counts": dict(self.counts),
                "last_step": dict(self.last_step),
                "last_anomaly_step": self.last_anomaly_step,
                "total": sum(self.counts.values()),
            }


class TrainTelemetry:
    """The trainer's shared observability state: flight recorder, anomaly
    sentinels, monotonic counters, and the status dict the control plane
    serves. The trainer mutates it at log/eval/save boundaries (where the
    scalars are already host floats); HTTP handler threads read snapshots
    under the lock."""

    def __init__(
        self,
        *,
        run_id: Optional[str] = None,
        hparams: Optional[Dict[str, Any]] = None,
        flight_capacity: int = 2048,
        band_sigma: float = 6.0,
        anomaly_window_steps: int = 100,
        sentinel_warmup: int = 8,
    ):
        self.run_id = run_id or new_run_id()
        self.hparams_digest = hparams_digest(hparams or {})
        self.recorder = FlightRecorder(flight_capacity)
        self.anomaly_window_steps = max(1, int(anomaly_window_steps))
        self.sentinels = AnomalySentinels(
            band_sigma=band_sigma,
            warmup=sentinel_warmup,
            on_anomaly=self._on_anomaly,
        )
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {k: 0 for k in TRAIN_COUNTERS}
        self._status: Dict[str, Any] = {
            "run_id": self.run_id,
            "hparams_digest": self.hparams_digest,
            "state": "initializing",
            "step": 0,
            "total_steps": 0,
            "epoch": 0.0,
            "epochs": 0,
            "preempted": False,
            # where start-up went, once the first step has finished: seconds
            # by phase (observe/xla.py spans: process/before_recorder, import,
            # startup/data, startup/weights, startup/optimizer with
            # startup/opt_state inside it, startup/restore, startup/first_step
            # with the step program's train_step/load inside it), the seconds since the
            # process started, the persistent compile cache's hits and misses
            # (CompileLedger.setup_phases(); a restarted job pays all of it)
            "startup": None,
        }
        self._checkpoints: deque = deque(maxlen=64)
        self._publishes: deque = deque(maxlen=64)
        # attached live objects (read-only from the HTTP side)
        self.phase_hist: Optional[Dict[str, Any]] = None
        self.compile_ledger = None

    # ------------------------------------------------------------- wiring

    def attach(self, *, phase_hist=None, compile_ledger=None) -> None:
        """Hand the control plane references to the train loop's live
        phase histograms and compile ledger (both already thread-safe to
        read)."""
        if phase_hist is not None:
            self.phase_hist = phase_hist
        if compile_ledger is not None:
            self.compile_ledger = compile_ledger

    # ----------------------------------------------------------- mutation

    def _on_anomaly(self, kind: str, step: int, **fields) -> None:
        self.recorder.record("anomaly", anomaly=kind, step=step, **fields)

    def incr(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def set_counter(self, name: str, value: int) -> None:
        """Absolute update for counters owned elsewhere (the watchdog's
        monotonic ``trips``); folded in at log boundaries."""
        with self._lock:
            self._counters[name] = max(self._counters.get(name, 0), int(value))

    def update(self, **fields) -> None:
        with self._lock:
            self._status.update(fields)

    def on_step(self, step: int, logs: Dict[str, Any]) -> List[str]:
        """Boundary hook: fold one log record (already host floats) into
        the sentinels, the flight timeline, and the status dict. Returns
        the anomaly kinds that fired."""
        fired = self.sentinels.observe(
            step, loss=logs.get("loss"), grad_norm=logs.get("grad_norm")
        )
        numeric = {
            k: float(v)
            for k, v in logs.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        }
        with self._lock:
            self._status["step"] = int(step)
            self._status.update(numeric)
            self._status["state"] = "training"
        event = {"step": step}
        for key in ("loss", "grad_norm", "learning_rate"):
            if key in numeric:
                event[key] = round(numeric[key], 6) if math.isfinite(
                    numeric[key]
                ) else str(numeric[key])
        self.recorder.record("step", **event)
        if "eval_loss" in logs:
            self.incr("evals")
            ev = logs["eval_loss"]
            self.recorder.record(
                "eval", step=step,
                eval_loss=round(float(ev), 6) if math.isfinite(float(ev))
                else str(ev),
            )
        return fired

    def note_checkpoint(self, step: int, duration_s: float) -> None:
        self.incr("checkpoints_saved")
        self.recorder.record(
            "checkpoint_save", step=step, duration_s=round(duration_s, 4)
        )
        with self._lock:
            self._checkpoints.append(
                {"step": int(step), "duration_s": round(duration_s, 4)}
            )

    def note_restore(self, step: int) -> None:
        self.recorder.record("checkpoint_restore", step=step)

    def note_publish(
        self,
        step: int,
        *,
        clean: bool,
        skipped: bool = False,
        fingerprint: Optional[str] = None,
    ) -> None:
        self.incr("publishes_skipped_dirty" if skipped else "publishes")
        self.recorder.record(
            "publish_skipped_dirty" if skipped else "publish",
            step=step, anomaly_clean=clean, fingerprint=fingerprint,
        )
        with self._lock:
            self._publishes.append({
                "step": int(step),
                "anomaly_clean": bool(clean),
                "skipped": bool(skipped),
                "fingerprint": fingerprint,
            })

    def publish_clean(self, step: int) -> bool:
        """Is the trailing ``anomaly_window_steps`` window ending at
        ``step`` free of sentinel firings? Stamped into the manifest as
        ``anomaly_clean`` and enforced by ``publish_require_clean``."""
        return self.sentinels.clean_since(step - self.anomaly_window_steps + 1)

    # ------------------------------------------------------------ reading

    def counters_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def status(self) -> Dict[str, Any]:
        """One coherent JSON-ready view (``GET /v1/train/status``)."""
        with self._lock:
            out = dict(self._status)
            counters = dict(self._counters)
            out["checkpoints"] = list(self._checkpoints)
            out["publishes"] = list(self._publishes)
        out["counters"] = counters
        out["anomalies"] = self.sentinels.snapshot()
        # ETA from the meter's steady step rate over the remaining steps —
        # computed here on the HTTP thread, never on the step path
        sps = float(out.get("steps_per_second") or 0.0)
        total = int(out.get("total_steps") or 0)
        step = int(out.get("step") or 0)
        out["eta_s"] = (
            round((total - step) / sps, 1) if sps > 0 and total > step else None
        )
        out["flight_events"] = len(self.recorder)
        return out


def trainer_exposition(telemetry: TrainTelemetry, memory=None) -> str:
    """Render the trainer's telemetry as Prometheus text (prefix
    ``training_``), through the same exposition machinery the serving
    stack scrapes: pinned gauge set, counter set, compile-ledger samples,
    phase histograms, per-device HBM gauges, and the kind-labelled anomaly
    counter. ``memory`` defaults to a live ``device_memory_report()``."""
    status = telemetry.status()
    snap: Dict[str, Any] = {key: 0.0 for key in TRAIN_GAUGES}
    for key in TRAIN_GAUGES:
        value = status.get(key)
        if isinstance(value, bool):
            snap[key] = int(value)
        elif isinstance(value, (int, float)):
            snap[key] = value
    snap.update(telemetry.counters_snapshot())
    for key in TRAIN_COUNTERS:
        snap.setdefault(key, 0)
    snap["run_id"] = telemetry.run_id
    snap["hparams_digest"] = telemetry.hparams_digest
    snap["state"] = str(status.get("state", "unknown"))
    if telemetry.compile_ledger is not None:
        snap["compile"] = telemetry.compile_ledger.snapshot()
        # roofline utilization of the train step: ledger cost analysis over
        # the mean observed step time (0.0 on CPU / unknown hardware)
        hist = (telemetry.phase_hist or {}).get("step")
        total = int(getattr(hist, "total", 0) or 0) if hist is not None else 0
        if total > 0:
            from llm_fine_tune_distributed_tpu.observe.xla import (
                device_peak_specs,
                utilization_from_cost,
            )

            flops, nbytes = telemetry.compile_ledger.cost_for(("train_step",))
            peak_flops, peak_bw = device_peak_specs()
            mfu, bw = utilization_from_cost(
                flops, nbytes, float(hist.sum) / total, peak_flops, peak_bw
            )
            snap["model_flops_utilization"] = mfu
            snap["hbm_bandwidth_utilization"] = bw
    hists = {
        f"{key}_s": (telemetry.phase_hist or {}).get(key)
        for key in TRAIN_HIST_KEYS
        if (telemetry.phase_hist or {}).get(key) is not None
    }
    if memory is None:
        from llm_fine_tune_distributed_tpu.observe.profiler import (
            device_memory_report,
        )

        memory = device_memory_report()
    text = prometheus_exposition(
        snap, hists or None, memory=memory, prefix="training",
        counters=set(TRAIN_COUNTERS),
    )
    # kind-labelled anomaly counter, every kind seeded (schema must not
    # depend on whether the run has misbehaved yet)
    counts = telemetry.sentinels.snapshot()["counts"]
    lines = ["# TYPE training_anomalies_total counter"]
    for kind in ANOMALY_KINDS:
        lines.append(
            f'training_anomalies_total{{kind="{kind}"}} '
            f"{int(counts.get(kind, 0))}"
        )
    return text + "\n".join(lines) + "\n"


class TrainControlPlane:
    """Primary-host-only HTTP server over a ``TrainTelemetry`` (same
    ``ThreadingHTTPServer`` pattern as infer/server.py). ``port`` 0 binds
    an ephemeral port (tests, benches); read it back from ``.port`` after
    ``start()``. Non-primary hosts no-op entirely: ``start()`` returns
    False and opens no socket."""

    def __init__(
        self,
        telemetry: TrainTelemetry,
        port: int,
        *,
        host: str = "0.0.0.0",
        profile_dir: Optional[str] = None,
    ):
        self.telemetry = telemetry
        self.host = host
        self.port = int(port)
        self.profile_dir = profile_dir
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._capture = None

    def start(self) -> bool:
        if not is_primary_host():
            return False
        if self._server is not None:
            return True
        if self.profile_dir:
            from llm_fine_tune_distributed_tpu.observe.xla import (
                ProfilerCapture,
            )

            self._capture = ProfilerCapture(
                self.profile_dir, on_event=self.telemetry.recorder.record
            )
        telemetry = self.telemetry
        capture = self._capture

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _send(self, code, payload, content_type=None):
                body = (
                    payload if isinstance(payload, str) else json.dumps(payload)
                ).encode()
                self.send_response(code)
                self.send_header(
                    "Content-Type",
                    content_type
                    or (
                        "text/plain"
                        if isinstance(payload, str)
                        else "application/json"
                    ),
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 (stdlib casing)
                path, _, query = self.path.partition("?")
                if path == "/metrics":
                    self._send(
                        200,
                        trainer_exposition(telemetry),
                        content_type=PROMETHEUS_CONTENT_TYPE,
                    )
                elif path == "/v1/train/status":
                    self._send(200, telemetry.status())
                elif path == "/v1/train/flight":
                    from urllib.parse import parse_qs

                    qs = parse_qs(query)
                    try:
                        limit = int((qs.get("limit") or [256])[0])
                        if limit <= 0:
                            raise ValueError
                    except ValueError:
                        self._send(400, {
                            "error": "'limit' must be a positive integer",
                        })
                        return
                    self._send(
                        200,
                        {"events": telemetry.recorder.events()[-limit:]},
                    )
                else:
                    self._send(404, {"error": "not found"})

            def do_POST(self):  # noqa: N802
                if self.path != "/v1/train/profile":
                    self._send(404, {"error": "not found"})
                    return
                if capture is None:
                    self._send(404, {
                        "error": "profiling disabled; start training with "
                                 "profile_dir / PROFILE_DIR set",
                    })
                    return
                from llm_fine_tune_distributed_tpu.observe.xla import (
                    CaptureBusyError,
                )

                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    req = json.loads(self.rfile.read(length) or b"{}")
                    if not isinstance(req, dict):
                        raise TypeError("body must be a JSON object")
                    duration_s = float(req.get("duration_s", 3.0))
                    trace_dir = capture.start(duration_s)
                except CaptureBusyError as e:
                    self._send(409, {"error": str(e)})
                    return
                except (ValueError, TypeError) as e:
                    self._send(400, {"error": f"bad request: {e}"})
                    return
                self._send(200, {
                    "profiling": True,
                    "trace_dir": trace_dir,
                    "duration_s": duration_s,
                })

            def log_message(self, fmt, *args):
                pass  # scrapes must not spam the training log

        self._server = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name="train-control-plane",
            daemon=True,
        )
        self._thread.start()
        self.telemetry.recorder.record("control_plane_start", port=self.port)
        return True

    def stop(self) -> None:
        if self._capture is not None:
            self._capture.stop()
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
