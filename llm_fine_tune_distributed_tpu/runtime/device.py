"""Which device a measurement may run on.

A rate, a utilization or a speed gate means something only on the chip, so a
measurement path that finds no accelerator fails; it does not quietly
measure the CPU instead. A CPU run is a rehearsal (control flow, counts,
correctness at a tiny size) and happens only when the caller asked for the
CPU in so many words: ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import os
from typing import Mapping, Optional


class NoAcceleratorError(RuntimeError):
    """JAX found only a CPU and nobody asked for one."""


def cpu_requested(environ: Optional[Mapping[str, str]] = None) -> bool:
    environ = os.environ if environ is None else environ
    return environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def on_accelerator(
    platform: str, environ: Optional[Mapping[str, str]] = None
) -> bool:
    """True on an accelerator, False on a CPU the caller asked for; raises
    :class:`NoAcceleratorError` on a CPU that was merely what JAX found.
    ``platform`` is ``jax.devices()[0].platform``."""
    if platform != "cpu":
        return True
    if cpu_requested(environ):
        return False
    raise NoAcceleratorError(
        "JAX found no accelerator (platform 'cpu') and the CPU was not asked "
        "for: a measurement does not fall back to it. Run on the chip, or "
        "set JAX_PLATFORMS=cpu for a tiny rehearsal whose numbers are not "
        "rates."
    )
