"""Single-process step watchdog: detect a wedged device link.

The multi-host failure story (native/heartbeat.cc + runtime/failure.py)
detects a DEAD PEER; nothing detected a dead DEVICE LINK under a
single-process run. Observed in an early flagship run: the run wedged
PERMANENTLY between two train steps — steady steps, then an infinite
block inside a device sync, log silent, process sleeping. Kubernetes sees
a healthy process and never restarts it; resume-from-checkpoint never
gets its chance.

The watchdog is a daemon thread the trainer pokes once per loop iteration.
If no poke arrives within ``timeout_s`` it reports loudly to stderr (with
the stall duration and last step), and in ``action="abort"`` mode hard-exits
the process (``os._exit``) so the job manager restarts it and training
resumes from the latest checkpoint — turning an invisible infinite hang
into the same restart->resume path a dead host takes. ``os._exit`` is
deliberate: a wedged XLA sync cannot be interrupted from Python, so a
cooperative shutdown would itself hang.

Cost: one event-wait thread; the poke is a timestamp store.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Optional


class StepWatchdog:
    def __init__(
        self,
        timeout_s: float,
        action: str = "warn",
        on_trip=None,
        poll_s: Optional[float] = None,
        start_paused: bool = False,
        recorder=None,
    ):
        """``start_paused=True``: stay disarmed until the FIRST poke — the
        trainer uses this so the startup window (mid-epoch resume
        fast-forward + multi-minute first-step compile) can never
        false-trip into an unrecoverable abort/restart loop.

        ``recorder``: optional FlightRecorder; trips are recorded from the
        poller thread and re-arms from ``poke`` only on the paused->armed
        transition, so the per-step poke stays a bare timestamp store."""
        if action not in ("warn", "abort"):
            raise ValueError(f"watchdog action must be warn|abort, got {action!r}")
        self.timeout_s = float(timeout_s)
        self.action = action
        self._on_trip = on_trip  # test hook; called instead of os._exit
        self._recorder = recorder
        self._poll_s = poll_s if poll_s is not None else min(self.timeout_s / 4, 10.0)
        self._last_poke = time.monotonic()
        self._last_step = 0
        self._tripped = 0  # count of warnings fired (monotonic)
        self._paused = start_paused
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="step-watchdog", daemon=True
        )
        self._thread.start()

    # ------------------------------------------------------------------ API

    def poke(self, step: int) -> None:
        """Call once per training-loop iteration (host side, cheap).
        A poke is definite progress, so it also re-arms a paused watchdog."""
        self._last_poke = time.monotonic()
        self._last_step = step
        if self._paused:
            # paused->armed happens only at eval/checkpoint boundaries, so
            # the flight event (a clock read) never rides the hot path
            self._paused = False
            if self._recorder is not None:
                self._recorder.record("watchdog_rearm", step=step)

    def pause(self) -> None:
        """Disarm during legitimately long host-side phases (checkpoint
        restore, artifact export) so slow-but-progressing IO never trips."""
        self._paused = True

    def resume(self) -> None:
        self._last_poke = time.monotonic()
        self._paused = False

    @property
    def trips(self) -> int:
        return self._tripped

    def stop(self) -> None:
        self._stop.set()

    # -------------------------------------------------------------- internal

    def _run(self) -> None:
        while not self._stop.wait(self._poll_s):
            if self._paused:
                continue
            silent = time.monotonic() - self._last_poke
            if silent < self.timeout_s:
                continue
            self._tripped += 1
            if self._recorder is not None:
                self._recorder.record(
                    "watchdog_trip",
                    silent_s=round(silent, 1),
                    last_step=self._last_step,
                    action=self.action,
                )
            print(
                f"[watchdog] no training-loop progress for {silent:.0f}s "
                f"(last step {self._last_step}, timeout {self.timeout_s:.0f}s) "
                "— the device link may be wedged"
                + (
                    "; aborting for restart+resume"
                    if self.action == "abort"
                    else ""
                ),
                file=sys.stderr,
                flush=True,
            )
            if self.action == "abort":
                if self._on_trip is not None:
                    self._on_trip()
                    return
                os._exit(42)
            # warn mode: re-arm so a persisting stall warns once per timeout
            self._last_poke = time.monotonic()
