"""Device mesh construction.

The reference's distributed world is a flat NCCL rank list
(``WORLD_SIZE``/``RANK``, reference ``training.py:19-23``). The TPU-native
analog is an N-D logical mesh over the physical ICI/DCN topology; XLA emits the
collectives (psum / all-gather / reduce-scatter) from sharding annotations —
there is no NCCL env-var zoo (reference ``deploy/pytorchjob.yaml:51-64``).

Axis order puts ``data`` outermost so that, on multi-slice systems, the pure
data-parallel axis (which only communicates once per step for the gradient
reduction) maps onto DCN while fsdp/tensor/seq traffic stays on ICI —
the standard scaling-book layout. ``make_mesh`` enforces this for real: when
the device pool spans multiple slices (``device.slice_index`` differs) it
builds the mesh with ``mesh_utils.create_hybrid_device_mesh``, spreading
ONLY the data axis across slices and refusing shapes that would put any
other axis on DCN (~6 GB/s/chip vs ~90 GB/s ICI — see
observe/scaling.py:V5E).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from llm_fine_tune_distributed_tpu.config import MeshConfig

MESH_AXES = ("data", "pipe", "fsdp", "tensor", "seq", "expert")


def make_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a Mesh with axes (data, fsdp, tensor, seq) from a MeshConfig.

    ``jax.make_mesh`` lays the axes out along the physical ICI topology (a
    failure there raises): on a 2x2 v5e host an axis of four is the ring of
    neighbours 0, 1, 3, 2. The same holds for an explicit list of TPU
    devices, attached or only described (a deviceless compile), so that a
    program compiled without the chip is partitioned as it will be on it —
    the compiler's choices depend on that order (PERF.md, PR 21). Devices of
    any other platform (virtual CPU devices in tests) are taken as listed.
    """
    config = config or MeshConfig()
    if devices is None:
        devices = jax.devices()
    try:
        sizes = config.axis_sizes(len(devices))
    except ValueError:
        # Fully-specified mesh smaller than the device pool: use a prefix of
        # the devices (tests / deliberate under-subscription).
        explicit = {"data": config.data, "fsdp": config.fsdp,
                    "tensor": config.tensor, "seq": config.seq,
                    "expert": config.expert, "pipe": config.pipe}
        if -1 in explicit.values():
            raise
        product = 1
        for v in explicit.values():
            product *= v
        if product > len(devices):
            raise
        devices = list(devices)[:product]
        sizes = config.axis_sizes(product)
    shape = tuple(sizes[a] for a in MESH_AXES)
    # Auto axis types: sharding propagates GSPMD/Shardy-style from the
    # annotations on params/batch plus with_sharding_constraint points.
    # (jax.make_mesh defaults to Explicit axis types as of jax 0.9, which
    # instead type-checks every intermediate — not what we want here.)
    auto = (AxisType.Auto,) * len(MESH_AXES)
    n_slices = len({getattr(d, "slice_index", 0) or 0 for d in devices})
    if n_slices > 1:
        return _make_hybrid_mesh(sizes, devices, n_slices, auto)
    return jax.make_mesh(shape, MESH_AXES, axis_types=auto, devices=list(devices))


def _make_hybrid_mesh(sizes: dict, devices, n_slices: int, axis_types) -> Mesh:
    """Multi-slice mesh: the data axis (and only it) spreads across slices.

    Per-slice traffic (fsdp all-gathers, tensor psums, seq permutes, pipe
    boundaries, expert dispatch) must ride ICI; the pure data axis carries
    one gradient reduction per accumulation step — the only volume DCN can
    afford. Shapes that cannot place every
    non-data axis within a slice are rejected rather than silently built
    slow."""
    from jax.experimental import mesh_utils

    if sizes["data"] % n_slices:
        raise ValueError(
            f"multi-slice mesh: data={sizes['data']} must be divisible by "
            f"the slice count ({n_slices}) — only the pure data axis may "
            "span slices (DCN); fsdp/tensor/seq/pipe/expert traffic needs ICI"
        )
    per_slice = len(devices) // n_slices
    ici = dict(sizes, data=sizes["data"] // n_slices)
    ici_product = 1
    for a in MESH_AXES:
        ici_product *= ici[a]
    if ici_product != per_slice:
        raise ValueError(
            f"multi-slice mesh: non-data axes need {ici_product} devices per "
            f"slice but each slice has {per_slice}"
        )
    dcn = {a: (n_slices if a == "data" else 1) for a in MESH_AXES}
    dev_array = mesh_utils.create_hybrid_device_mesh(
        tuple(ici[a] for a in MESH_AXES),
        tuple(dcn[a] for a in MESH_AXES),
        devices=list(devices),
    )
    return Mesh(dev_array, MESH_AXES, axis_types=axis_types)


def data_parallel_size(mesh: Mesh) -> int:
    """Number of data-parallel replicas = data * fsdp (batch is sharded over
    both; fsdp additionally shards params). Drives the lr x world_size rule
    (reference ``training.py:263``)."""
    return mesh.shape["data"] * mesh.shape["fsdp"]


def describe_mesh(mesh: Mesh) -> str:
    parts = [f"{a}={mesh.shape[a]}" for a in mesh.axis_names]
    return f"Mesh({', '.join(parts)}) over {mesh.size} devices"
