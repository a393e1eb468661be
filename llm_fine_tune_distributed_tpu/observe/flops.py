"""Analytic matmul-FLOP model of the train step, split by phase.

``cost_analysis()`` (observe/xla.CompileLedger) gives the compiled
program's TOTAL FLOPs — useful for MFU, useless for attribution: it
cannot say which FLOPs belong to the frozen trunk (forward-only under
``frozen_compute``), the trainable tail (forward + backward + remat
recompute), or the loss head. This module is the attribution side:
closed-form per-token matmul FLOPs per phase from the model config, the
trunk boundary, and the remat setting. bench.py and
benchmarks/perf_ledger.py report the phase shares next to the measured
numbers so a throughput regression can be localized before profiling.

Conventions (the standard 2*params accounting, same as bench.py's
baseline derivation):

- a ``[in, out]`` matmul costs ``2*in*out`` FLOPs per token, forward;
- backward costs 2x forward (the dx and dW products each match the
  forward GEMM);
- remat adds one extra forward per backward for the rematerialized
  region (policy ``dots_no_batch`` saves matmul outputs, so the re-run
  is mostly non-matmul — counting a full extra forward is the
  conservative upper bound);
- attention scores/values cost ``4*seq*heads*head_dim`` per token
  (QK^T + AV, un-causal — the flash kernel's causal skip would halve
  it; kept whole so the model stays an upper bound);
- norms / RoPE / softmax / elementwise are excluded: this is a MATMUL
  FLOP model (they are the "non-matmul tax" the measured ledger covers).

The phase split assumes the ``last_n_and_head`` freeze layout that the
fast path targets: layers below the boundary do forward only (backward
is DCE'd past the ``stop_gradient``); layers at/above it do forward +
full backward. ``frozen_layers=0`` degenerates to every layer paying
full backward — correct for full fine-tuning, an upper bound for
lora/qlora (adapter dW is rank-r, counted at full rank here).
"""

from __future__ import annotations

from typing import Dict

from llm_fine_tune_distributed_tpu.config import ModelConfig

__all__ = ["layer_matmul_flops_per_token", "train_step_flop_split"]


def layer_matmul_flops_per_token(mc: ModelConfig, seq_len: int) -> float:
    """Forward matmul FLOPs per token for ONE transformer layer: the seven
    projections (q/k/v/o, gate/up/down — MoE counts the router plus the
    per-token active experts) plus the attention score/value products at
    ``seq_len``."""
    h = mc.hidden_size
    d = mc.head_dim or h // mc.num_heads
    q_dim = mc.num_heads * d
    kv_dim = mc.num_kv_heads * d
    attn_proj = h * q_dim + 2 * h * kv_dim + q_dim * h  # q, k, v, o
    if mc.num_experts:
        mlp = h * mc.num_experts  # router gate
        mlp += mc.num_experts_per_tok * 3 * h * mc.intermediate_size
    else:
        mlp = 3 * h * mc.intermediate_size  # gate, up, down
    scores = 2 * seq_len * mc.num_heads * d  # QK^T + AV, per token
    return 2.0 * (attn_proj + mlp) + 2.0 * scores


def train_step_flop_split(
    mc: ModelConfig,
    seq_len: int,
    frozen_layers: int = 0,
    remat: bool = True,
) -> Dict[str, object]:
    """Per-token matmul FLOPs of one train step, split into phases:

    - ``trunk``: layers ``[0, frozen_layers)`` — forward only (the
      boundary ``stop_gradient`` kills their backward, and remat never
      wraps them);
    - ``trainable``: the remaining layers — forward + 2x backward
      (+1 forward remat recompute when ``remat``);
    - ``loss``: the unembed projection ``[h, vocab]`` — forward + 2x
      backward (lm_head trains under every strategy this model targets).

    Returns ``{"per_token": {phase: flops}, "fractions": {phase: share},
    "total_per_token": flops}``. Multiply ``total_per_token`` by
    tokens/sec for an analytic FLOP/s to sanity-check measured MFU.
    """
    frozen_layers = max(0, min(int(frozen_layers), mc.num_layers))
    layer_fwd = layer_matmul_flops_per_token(mc, seq_len)
    bwd_mult = 3.0 + (1.0 if remat else 0.0)  # fwd + dx + dW (+ refwd)
    trunk = frozen_layers * layer_fwd
    trainable = (mc.num_layers - frozen_layers) * layer_fwd * bwd_mult
    loss = 3.0 * 2.0 * mc.hidden_size * mc.vocab_size
    total = trunk + trainable + loss
    return {
        "per_token": {"trunk": trunk, "trainable": trainable, "loss": loss},
        "fractions": {
            "trunk": trunk / total,
            "trainable": trainable / total,
            "loss": loss / total,
        },
        "total_per_token": total,
    }
