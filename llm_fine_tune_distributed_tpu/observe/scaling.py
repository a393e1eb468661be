"""Mesh-scaling evidence: abstract compiles of the sharded train step, their
collective volumes, and an ICI/DCN cost model projecting multi-chip throughput.

Real multi-chip hardware is not available in this environment, so scaling
claims ride on *compiled-program* evidence instead of wall clocks:

1. ``abstract_train_setup`` builds the EXACT state/batch/step the trainer
   builds (same freeze split, dtypes, shardings — mirroring
   ``train/trainer.py:_prepare_state``) but from ``jax.ShapeDtypeStruct``
   leaves, so the flagship-at-16-devices program can be lowered and compiled
   without materializing a single parameter;
2. ``observe/comm_accounting.py`` reads per-step collective bytes per mesh
   axis out of the optimized HLO;
3. ``project_step_time`` combines those bytes with the v5e link model and the
   MEASURED single-chip step time into a projected multi-chip step time
   (compute-communication overlap assumed only where XLA can actually overlap
   — see the function docstring).

``tests/test_comm_accounting.py`` pins (1)+(2) against analytic expectations;
``benchmarks/project_scaling.py`` renders (3) as a table.

Hardware constants (stated assumptions, public v5e specs / scaling-book):

- ICI: each v5e chip has 4 links x 45 GB/s one-way. A 16-chip slice is a
  4x4 2D torus: a 1-D ring along one mesh axis uses 2 links (both
  directions) => ~90 GB/s per chip of ring bandwidth per torus dimension;
  two mesh axes can ride the two torus dimensions concurrently.
- HBM: 819 GB/s, 16 GiB per chip.  MXU: 197 bf16 TFLOP/s.
- DCN (multi-slice): ~25 GB/s per host egress (4 chips/host on v5e) =>
  ~6.25 GB/s per chip — two orders below ICI, which is why only the pure
  data axis may span slices (``runtime/mesh.py``).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

V5E = {
    "ici_ring_gbps": 90e9,     # bytes/s per chip per torus dim (bidi ring)
    "dcn_gbps": 6.25e9,        # bytes/s per chip across slices
    "hbm_gbps": 819e9,
    "bf16_flops": 197e12,
    "hbm_bytes": 16 * 2**30,
}


def _bytes(tree) -> int:
    return sum(
        int(np.prod(l.shape)) * l.dtype.itemsize for l in jax.tree.leaves(tree)
    )


@dataclass
class AbstractSetup:
    """Everything needed to lower/compile one sharded train step abstractly."""

    mesh: object
    step: object                    # jitted step fn (donates state)
    state: object                   # TrainState of ShapeDtypeStructs
    batch: Dict[str, object]        # abstract batch [accum, B, seq]
    model_config: object
    train_config: object
    trainable_bytes: int = 0
    frozen_bytes: int = 0

    def lower(self):
        return self.step.lower(self.state, self.batch)

    def compile(self):
        return self.lower().compile()

    def comm_report(self):
        from llm_fine_tune_distributed_tpu.observe.comm_accounting import (
            account_compiled,
        )

        return account_compiled(self.compile(), self.mesh)


def abstract_train_setup(
    mesh_shape: Dict[str, int],
    preset: str = "tiny",
    *,
    devices: Optional[Sequence] = None,
    accum: int = 2,
    seq: int = 64,
    per_dp_batch: int = 1,
    param_dtype: str = "float32",
    train_kwargs: Optional[dict] = None,
    model_overrides: Optional[dict] = None,
) -> AbstractSetup:
    """Build the trainer's sharded train step over ``mesh_shape`` with
    abstract (ShapeDtypeStruct) state — no parameter materialization, so the
    3B flagship compiles on CPU in seconds.

    Mirrors ``train/trainer.py:_prepare_state`` leaf-for-leaf: same freeze
    split, same master dtypes (trainable = ``param_dtype``, frozen =
    compute dtype), same path-rule shardings, same optimizer-state shardings
    (``parallel/optimizer.opt_state_shardings``), and the
    pipe-mode stacked-layer representation when ``pipe > 1``.
    ``model_overrides`` replaces fields of the preset (a compile test cuts
    ``num_layers`` and keeps the widths).
    """
    from llm_fine_tune_distributed_tpu.config import (
        MeshConfig,
        TrainConfig,
        str_to_dtype,
    )
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
    from llm_fine_tune_distributed_tpu.parallel.optimizer import (
        build_optimizer,
        opt_state_shardings,
    )
    from llm_fine_tune_distributed_tpu.parallel.sharding import (
        _validate_spec,
        param_spec,
    )
    from llm_fine_tune_distributed_tpu.runtime.mesh import (
        data_parallel_size,
        make_mesh,
    )
    from llm_fine_tune_distributed_tpu.train.state import TrainState
    from llm_fine_tune_distributed_tpu.train.step import (
        build_train_step,
        jit_train_step,
    )
    from llm_fine_tune_distributed_tpu.utils.tree import split_by_mask

    mc = dataclasses.replace(get_preset(preset), **(model_overrides or {}))
    kwargs = dict(
        model_preset=preset,
        per_device_batch_size=per_dp_batch,
        gradient_accumulation_steps=accum,
        max_seq_length=seq,
        gradient_checkpointing=True,
        param_dtype=param_dtype,
    )
    kwargs.update(train_kwargs or {})
    tc = TrainConfig(**kwargs)

    mesh = make_mesh(MeshConfig(**mesh_shape), devices)
    dp = data_parallel_size(mesh)
    pipe = mesh.shape.get("pipe", 1)

    p_dtype = str_to_dtype(tc.param_dtype)
    c_dtype = str_to_dtype(tc.compute_dtype)

    shapes = jax.eval_shape(
        partial(init_params, config=mc, dtype=jnp.float32), jax.random.PRNGKey(0)
    )
    mask = trainable_mask(shapes, mc, tc)
    trainable, frozen = split_by_mask(shapes, mask)

    layer_vec = None
    if pipe > 1:
        from llm_fine_tune_distributed_tpu.parallel.pipeline import (
            build_pipeline_state_leaves,
            layer_trainable_vector,
        )
        from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

        flat_mask = flatten_dict(mask)
        # stacking is a jnp op: run it under eval_shape to stay abstract;
        # the (tiny, concrete) layer mask is rebuilt directly from the policy
        trainable, frozen, _ = jax.eval_shape(
            partial(
                build_pipeline_state_leaves,
                flat_mask=flat_mask,
                num_layers=mc.num_layers,
            ),
            trainable,
            frozen,
        )
        layer_vec = layer_trainable_vector(flat_mask, mc.num_layers)

    def spec_for(k: str, v) -> P:
        if pipe > 1:
            from llm_fine_tune_distributed_tpu.parallel.pipeline import (
                pipeline_param_spec,
            )

            return _validate_spec(pipeline_param_spec(k, v, mesh), v.shape, mesh)
        return _validate_spec(param_spec(k, v.ndim), v.shape, mesh)

    def abstract(flat, dtype_fn):
        return {
            k: jax.ShapeDtypeStruct(
                v.shape, dtype_fn(k, v), sharding=NamedSharding(mesh, spec_for(k, v))
            )
            for k, v in flat.items()
        }

    trainable = abstract(trainable, lambda k, v: p_dtype)
    frozen = abstract(
        frozen,
        lambda k, v: c_dtype
        if jnp.issubdtype(v.dtype, jnp.floating) and "absmax" not in k
        else v.dtype,
    )

    optimizer = build_optimizer(tc, None, total_steps=4, data_parallel_size=dp)
    opt_state = jax.tree.map(
        lambda struct, sh: jax.ShapeDtypeStruct(struct.shape, struct.dtype, sharding=sh),
        jax.eval_shape(optimizer.init, trainable),
        opt_state_shardings(optimizer, trainable, mesh),
    )

    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
        trainable=trainable,
        frozen=frozen,
        opt_state=opt_state,
    )

    seq_sharded = tc.attention_impl in ("ring", "ulysses") and mesh.shape["seq"] > 1
    seq_ax = "seq" if seq_sharded else None
    batch_sh = NamedSharding(mesh, P(None, ("data", "fsdp"), seq_ax))
    B = per_dp_batch * dp
    batch = {
        "input_ids": jax.ShapeDtypeStruct((accum, B, seq), jnp.int32, sharding=batch_sh),
        "loss_mask": jax.ShapeDtypeStruct((accum, B, seq), jnp.float32, sharding=batch_sh),
        "attention_mask": jax.ShapeDtypeStruct((accum, B, seq), jnp.int32, sharding=batch_sh),
    }

    if pipe > 1:
        from llm_fine_tune_distributed_tpu.parallel.pipeline import (
            build_pipeline_train_step,
        )

        step = jit_train_step(
            build_pipeline_train_step(mc, tc, optimizer, mesh, layer_vec),
            mesh=mesh,
        )
    else:
        act = NamedSharding(mesh, P(("data", "fsdp"), seq_ax, None))
        step = jit_train_step(
            build_train_step(mc, tc, optimizer, activation_sharding=act),
            mesh=mesh,
        )

    return AbstractSetup(
        mesh=mesh,
        step=step,
        state=state,
        batch=batch,
        model_config=mc,
        train_config=tc,
        trainable_bytes=_bytes(trainable),
        frozen_bytes=_bytes(frozen),
    )


# ------------------------------------------------------------------ projection


@dataclass
class Projection:
    mesh_shape: Dict[str, int]
    compute_s: float            # per-step compute time (from measured 1-chip rate)
    comm_s_by_axis: Dict[Tuple[str, ...], float]
    exposed_comm_s: float       # serialized (non-overlapped) communication
    step_s: float
    samples_per_step: int

    @property
    def samples_per_sec(self) -> float:
        return self.samples_per_step / self.step_s

    @property
    def scaling_efficiency(self) -> float:
        """Achieved fraction of perfect linear scaling vs 1 chip."""
        n = int(np.prod(list(self.mesh_shape.values())))
        perfect = self.samples_per_step / self.compute_s
        return self.samples_per_sec / perfect if perfect else 0.0


def project_step_time(
    report,
    mesh_shape: Dict[str, int],
    *,
    single_chip_samples_per_sec: float,
    samples_per_step: int,
    dcn_axes: Tuple[str, ...] = (),
    overlap_fraction: float = 0.0,
    hw: Dict[str, float] = V5E,
) -> Projection:
    """Project per-step time on real hardware from accounted wire bytes.

    - compute time = samples_per_step / (single_chip_rate x n_chips): the
      per-chip compute is identical to the measured single-chip program (same
      per-device batch), so the measured rate IS the compute model;
    - each mesh-axis' wire bytes ride one torus dimension at
      ``ici_ring_gbps``; axes in ``dcn_axes`` ride DCN instead;
    - ``overlap_fraction`` of communication hides under compute
      (conservative default 0: all collective time exposed. XLA's async
      collectives + latency-hiding scheduler typically hide the FSDP
      all-gathers behind the matmuls they feed, so real steps land between
      the 0%-overlap and 100%-overlap projections).
    """
    n = int(np.prod(list(mesh_shape.values())))
    compute_s = samples_per_step / (single_chip_samples_per_sec * n)
    comm_by_axis = {}
    for axes, byts in report.wire_bytes_by_axis().items():
        bw = hw["dcn_gbps"] if any(a in dcn_axes for a in axes) else hw["ici_ring_gbps"]
        comm_by_axis[axes] = byts / bw
    # distinct mesh axes can ride distinct torus dims concurrently, but a
    # serialized sum is the honest upper bound for a compiled program whose
    # collectives are data-dependent (gather -> matmul -> reduce chains)
    exposed = sum(comm_by_axis.values()) * (1.0 - overlap_fraction)
    return Projection(
        mesh_shape=mesh_shape,
        compute_s=compute_s,
        comm_s_by_axis=comm_by_axis,
        exposed_comm_s=exposed,
        step_s=compute_s + exposed,
        samples_per_step=samples_per_step,
    )
