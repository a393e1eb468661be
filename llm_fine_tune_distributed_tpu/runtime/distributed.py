"""Multi-host runtime bootstrap.

Replaces the reference's C1 component (``setup_distributed``,
reference ``training.py:16-47``): instead of exporting
``MASTER_ADDR``/``MASTER_PORT``/``RANK`` for torch/NCCL rendezvous, we call
``jax.distributed.initialize`` — the coordinator (process 0) plays the
MASTER_ADDR role and XLA handles all collective transport over ICI/DCN.

For deployment-manifest compatibility the reference env names are honored:
``MASTER_ADDR:MASTER_PORT`` map to the coordinator address, ``WORLD_SIZE`` to
num_processes, ``RANK`` to process_id (the Kubeflow operator injects RANK,
reference ``deploy/pytorchjob.yaml:124-128``; a JobSet does the equivalent via
the downward API).
"""

from __future__ import annotations

import os
import socket
from dataclasses import dataclass
from typing import Optional

import jax


@dataclass
class RuntimeInfo:
    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int
    platform: str
    device_kind: str
    hostname: str

    @property
    def is_primary(self) -> bool:
        return self.process_index == 0


def initialize_distributed(environ=None) -> RuntimeInfo:
    """Initialize multi-host JAX if the env describes a multi-process world.

    Single-process (the common dev / single-host case) is a no-op — exactly
    like the reference, where WORLD_SIZE defaults to 1
    (reference ``training.py:19``).
    """
    env = os.environ if environ is None else environ
    world = int(env.get("WORLD_SIZE", env.get("JAX_NUM_PROCESSES", "1")))
    # Decide from the env alone — touching any jax device API here would
    # initialize the local XLA backend and make distributed init impossible
    # (it must run before backends come up).
    if world > 1:
        rank = int(env.get("RANK", env.get("JAX_PROCESS_ID", "0")))
        addr = env.get("MASTER_ADDR", env.get("JAX_COORDINATOR_ADDRESS", "localhost"))
        port = env.get("MASTER_PORT", env.get("JAX_COORDINATOR_PORT", "23456"))
        try:
            jax.distributed.initialize(
                coordinator_address=f"{addr}:{port}",
                num_processes=world,
                process_id=rank,
            )
        except RuntimeError as e:
            # Already initialized (e.g. called twice) — keep going.
            if "already" not in str(e).lower():
                raise
    return runtime_info()


def runtime_info() -> RuntimeInfo:
    devices = jax.devices()
    return RuntimeInfo(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=len(devices),
        platform=devices[0].platform,
        device_kind=devices[0].device_kind,
        hostname=socket.gethostname(),
    )


def is_primary_host() -> bool:
    """Host-0 check — the analog of the reference's rank-0 gating for mkdir,
    artifact saves and Aim writes (reference ``training.py:62-64,309``)."""
    return jax.process_index() == 0


def device_preflight(verbose: bool = True) -> dict:
    """Device/memory preflight report — the analog of the reference's CUDA
    assert + VRAM print (C3, reference ``training.py:75-111``). The trainer
    runs wherever JAX put it: the TPU where one is attached, a CPU for a
    simulation run (``--platform cpu`` / ``JAX_PLATFORMS=cpu``). It does not
    choose, so the report names the platform and the device kind, and a
    caller that needs the chip (chip_smoke.py, the bench scripts) reads them
    and fails on anything else."""
    info = runtime_info()
    report = {
        "platform": info.platform,
        "device_kind": info.device_kind,
        "process": f"{info.process_index}/{info.process_count}",
        "local_devices": info.local_device_count,
        "global_devices": info.global_device_count,
    }
    stats = getattr(jax.local_devices()[0], "memory_stats", lambda: None)()
    if stats:
        report["bytes_in_use"] = stats.get("bytes_in_use")
        report["bytes_limit"] = stats.get("bytes_limit")
    if verbose and is_primary_host():
        print(f"[runtime] {report}")
    return report
