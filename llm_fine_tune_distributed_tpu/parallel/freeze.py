"""Partial-layer freezing policy.

Reference behavior (C5, ``training.py:113-149``): freeze every param, then
unfreeze the LAST 2 transformer layers + lm_head, yielding 418.9M/3.075B =
13.62% trainable on SmolLM3-3B (``claude.md:241-245``). On error the reference
falls back to full fine-tuning (``training.py:143-145``).

TPU-native expression: a boolean mask pytree consumed by
``optax.masked`` / ``multi_transform`` so frozen params get no optimizer state
(the memory win) and their gradients are never materialized into updates.
With tied embeddings, "lm_head" trainable means the embedding matrix is
trainable (same tensor — matching what torch does for tied weights).
"""

from __future__ import annotations

import re
from typing import Callable

from llm_fine_tune_distributed_tpu.config import ModelConfig, TrainConfig
from llm_fine_tune_distributed_tpu.observe.xla import annotate
from llm_fine_tune_distributed_tpu.utils.tree import (
    count_params,
    count_params_where,
    map_with_path,
    tree_paths,
)

_LAYER_RE = re.compile(r"model/layers/(\d+)/")


# Leaves that are buffers under every strategy: the router's selection bias of
# a DeepSeek-V3-style expert layer takes no gradient (ops/moe.route); its
# owners move it by a rule of their own between steps, which is not here.
_BUFFERS = ("e_score_correction_bias",)


def trainable_predicate(config: ModelConfig, train: TrainConfig) -> Callable[[str], bool]:
    pred = _strategy_predicate(config, train)
    return lambda path: pred(path) and not path.endswith(_BUFFERS)


def _strategy_predicate(config: ModelConfig, train: TrainConfig) -> Callable[[str], bool]:
    strategy = train.freeze_strategy
    if strategy == "none":
        return lambda path: True
    if strategy in ("lora", "qlora"):
        # Only adapter matrices train; base weights AND the (constant)
        # alpha/r scale stay frozen. For qlora the frozen base is additionally
        # NF4-quantized after the split (parallel/qlora.py).
        return lambda path: path.endswith(("lora_a", "lora_b"))
    if strategy == "last_n_and_head":
        cutoff = config.num_layers - train.unfreeze_last_n_layers

        def pred(path: str) -> bool:
            m = _LAYER_RE.search(path)
            if m:
                return int(m.group(1)) >= cutoff
            if "lm_head" in path:
                return True
            if config.tie_word_embeddings and "embed_tokens" in path:
                return True  # tied: the lm_head IS the embedding matrix
            return False  # final norm + embeddings(untied) stay frozen

        return pred
    raise ValueError(f"unknown freeze_strategy {strategy!r}")


def trainable_mask(params, config: ModelConfig, train: TrainConfig):
    """Boolean pytree: True = trainable."""
    pred = trainable_predicate(config, train)
    return map_with_path(lambda path, leaf: pred(path), params)


def frozen_trunk_boundary(flat_mask: dict, num_layers: int) -> int:
    """Number of leading *entirely frozen* transformer layers — the trunk.

    ``flat_mask`` is the flattened trainable mask (path -> bool). Returns the
    earliest layer index with ANY trainable leaf; layers ``[0, boundary)``
    form the frozen trunk eligible for the int8 fast path
    (``TrainConfig.frozen_compute``). 0 means "no trunk":

    - ``last_n_and_head`` (unfreeze_last_n_layers=n) -> ``num_layers - n``;
    - lora/qlora (trainable lora_a/lora_b in every layer) -> 0;
    - ``none`` (full fine-tune) -> 0.

    Note the boundary is *layer*-based: a trainable non-layer leaf (tied
    ``embed_tokens``/``lm_head``) does not shrink the trunk. Under int8
    frozen-compute the tied embedding's gradient contribution *through the
    trunk's input lookup* is dropped by the boundary ``stop_gradient`` — a
    documented approximation (docs/architecture.md "Training fast path");
    the lm_head-side gradient of the tied matrix is unaffected.
    """
    boundary = num_layers
    for path, trainable in flat_mask.items():
        if not trainable:
            continue
        m = _LAYER_RE.search(path)
        if m:
            boundary = min(boundary, int(m.group(1)))
            if boundary == 0:
                break
    return boundary


def quantize_trunk_int8(frozen: dict, boundary: int):
    """Quantize the projection kernels of the frozen trunk (layers
    ``[0, boundary)``) to the serving int8 sibling layout: each 2-D
    ``.../kernel`` leaf is replaced by ``kernel_int8`` codes +
    ``kernel_int8_scale`` per-output-channel f32 scales (ops/int8.py).
    Norms, embeddings, and the MoE router gate pass through unchanged —
    they run bf16 in the trunk too. Quantize from full precision (before
    any bf16 cast) so the 8-bit rounding is the only rounding.

    Returns ``(new_flat, n_quantized)``. Shared by the trainer
    (_prepare_state) and bench.py so the two can never disagree on which
    leaves the w8a8 fast path covers. While set-up lasts it is the span
    ``startup/quantize_trunk`` (one small program a kernel shape, dispatched
    leaf by leaf; the device's time is not waited for).
    """
    from llm_fine_tune_distributed_tpu.ops.int8 import INT8_SUFFIXES, quantize_int8

    quantized = {}
    n_quant = 0
    with annotate("startup/quantize_trunk", boundary=boundary) as span:
        for k, v in frozen.items():
            m = _LAYER_RE.search(k)
            if (
                m is not None
                and int(m.group(1)) < boundary
                and k.endswith("/kernel")
                and not k.endswith("block_sparse_moe/gate/kernel")
                and getattr(v, "ndim", 0) == 2
            ):
                q = quantize_int8(v)
                for suffix in INT8_SUFFIXES:
                    quantized[f"{k}_{suffix}"] = q[suffix]
                n_quant += 1
            else:
                quantized[k] = v
        span.set(quantized=n_quant)
    return quantized, n_quant


def describe_trainable(params, mask) -> dict:
    """Trainable-parameter report (the reference prints this at
    ``training.py:147-149``; values recorded into training_summary.json at
    ``training.py:323-326``)."""
    total = count_params(params)
    flat_mask = {p: m for p, m in tree_paths(mask)}
    trainable = count_params_where(params, lambda p: flat_mask[p])
    return {
        "total_parameters": total,
        "trainable_parameters": trainable,
        "trainable_percent": round(100.0 * trainable / total, 2),
    }
