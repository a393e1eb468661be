"""Optimizer chain — the first-party replacement for what the reference gets
from HF Trainer's create_optimizer/scheduler inside TRL (C9):

  AdamW + linear-decay-to-zero schedule (HF default ``lr_scheduler_type``),
  global-norm clip 1.0 (reference ``training.py:264``),
  lr x data_parallel_size scaling (reference ``training.py:263``),
  frozen params get NO optimizer state (optax.multi_transform) — preserving
  the memory profile of the freezing policy (C5).

Beyond reference parity, ``config.optimizer`` selects "adafactor" (factored
second moment — near-zero optimizer-state HBM, the classic TPU choice for
big models) or "lion" (sign momentum, one state slot) in the same chain.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from llm_fine_tune_distributed_tpu.config import TrainConfig
from llm_fine_tune_distributed_tpu.observe.xla import annotate, importing

with importing("optax"):  # chex and absl behind it: most of a second where this module is the first to ask
    import optax


def build_lr_schedule(config: TrainConfig, total_steps: int, data_parallel_size: int):
    peak = config.scaled_learning_rate(data_parallel_size)
    warmup = int(total_steps * config.warmup_ratio)
    if config.lr_schedule == "constant":
        return optax.constant_schedule(peak)
    if config.lr_schedule == "linear":
        # HF default: optional warmup, then linear decay to 0 over total steps.
        if warmup > 0:
            return optax.join_schedules(
                [
                    optax.linear_schedule(0.0, peak, warmup),
                    optax.linear_schedule(peak, 0.0, max(total_steps - warmup, 1)),
                ],
                [warmup],
            )
        return optax.linear_schedule(peak, 0.0, max(total_steps, 1))
    if config.lr_schedule == "cosine":
        return optax.warmup_cosine_decay_schedule(
            0.0, peak, max(warmup, 1), max(total_steps, 2)
        )
    raise ValueError(f"unknown lr_schedule {config.lr_schedule!r}")


def build_optimizer(
    config: TrainConfig,
    trainable_mask=None,
    *,
    total_steps: int,
    data_parallel_size: int,
) -> optax.GradientTransformation:
    """AdamW chain.

    The trainer normally partitions params into trainable/frozen pytrees
    up front (utils/tree.py:split_by_mask) and applies this optimizer to the
    trainable subset only — pass ``trainable_mask=None`` for that. Passing a
    boolean mask pytree instead wraps the chain in ``optax.multi_transform``
    so frozen leaves get no state (for callers that keep one joint pytree).
    """
    schedule = build_lr_schedule(config, total_steps, data_parallel_size)
    if config.optimizer == "adamw":
        core = optax.adamw(
            learning_rate=schedule,
            b1=config.adam_b1,
            b2=config.adam_b2,
            eps=config.adam_eps,
            weight_decay=config.weight_decay,
        )
    elif config.optimizer == "adafactor":
        # Factored second moment: optimizer state is O(rows + cols) per
        # matrix instead of O(rows * cols) — the classic TPU big-model
        # choice. Momentum off (that is Adafactor's memory win).
        core = optax.adafactor(
            learning_rate=schedule,
            multiply_by_parameter_scale=False,
            clipping_threshold=None,  # global-norm clip handles it below
            weight_decay_rate=config.weight_decay or None,
        )
    elif config.optimizer == "lion":
        # Lion's published/optax defaults (b1=0.9, b2=0.99) — deliberately
        # NOT config.adam_b1/b2: those tune the adamw baseline, and Lion's
        # momentum horizon is a different animal (b2=0.999 would ~10x it).
        # Be loud if the user tuned adam betas expecting them to apply here.
        if (config.adam_b1, config.adam_b2) != (0.9, 0.999):
            import warnings

            warnings.warn(
                "optimizer='lion' ignores adam_b1/adam_b2 "
                f"({config.adam_b1}/{config.adam_b2}) and uses Lion's own "
                "defaults (0.9/0.99)",
                stacklevel=2,
            )
        core = optax.lion(
            learning_rate=schedule,
            weight_decay=config.weight_decay,
        )
    else:
        raise ValueError(
            f"unknown optimizer {config.optimizer!r}; expected "
            "'adamw', 'adafactor', or 'lion'"
        )
    inner = optax.chain(
        optax.clip_by_global_norm(config.max_grad_norm),
        core,
    )
    if trainable_mask is None:
        return inner
    labels = jax.tree.map(lambda t: "train" if t else "freeze", trainable_mask)
    return optax.multi_transform(
        {"train": inner, "freeze": optax.set_to_zero()}, labels
    )


def opt_state_shardings(optimizer: optax.GradientTransformation, trainable, mesh):
    """A sharding for every leaf of ``optimizer.init(trainable)``: a leaf
    that mirrors a parameter (Adam's mu and nu, keyed and shaped like it)
    takes the parameter's sharding, everything else (step counts, factored
    moments) is replicated over ``mesh``. Left to the compiler the moments
    come out replicated — zeros depend on no input, so nothing propagates —
    and every device of an fsdp mesh holds all of them (1.6 GB instead of
    0.4 a device for the SmolLM3-3B flagship recipe on fsdp=4: PR 21).
    ``trainable``: flat ``{path: array or ShapeDtypeStruct}`` with shardings."""
    replicated = NamedSharding(mesh, P())

    def for_leaf(path, leaf):
        key = getattr(path[-1], "key", None) if path else None
        param = trainable.get(key) if isinstance(key, str) else None
        if param is not None and param.shape == leaf.shape:
            return param.sharding
        return replicated

    return jax.tree_util.tree_map_with_path(
        for_leaf, jax.eval_shape(optimizer.init, trainable)
    )


def init_opt_state(optimizer: optax.GradientTransformation, trainable, mesh):
    """``optimizer.init`` with the state laid out by ``opt_state_shardings``:
    the whole state lives on the full mesh (restore-from-checkpoint builds
    its target shardings from it). While set-up lasts it is the span
    ``startup/opt_state`` (the program's trace, compile and dispatch; the
    device's time is not waited for)."""
    with annotate("startup/opt_state"):
        return jax.jit(
            optimizer.init, out_shardings=opt_state_shardings(optimizer, trainable, mesh)
        )(trainable)
