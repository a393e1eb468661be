"""Operations and bytes a ``granitemoehybrid`` configuration requires, from shapes alone (``flops.py``'s rules: forward
over every layer, backward only where a trainable leaf or a path to one needs it, recomputation not counted, a
multiply-add is 2, elementwise work not counted).

The state-space scan is counted as the RECURRENCE it is, whatever implements it: a token and head of ``P`` channels
with a state ``[P, N]`` decays the state (``P N``), adds ``dt x B^T`` (``2 P N``), reads ``S C`` (``2 P N``) and adds
the skip (``2 P``): ``5 P N + 2 P``. (The chunked form trades the decay's pass over the state for ``[chunk, chunk]``
products and runs more operations than that; the count is the work, not the program.) The convolution is ``2 taps``
a channel.

Granite 4.0-H Micro, whole (40 layers: 36 Mamba-2, attention at 5, 15, 25, 35), one row of 8192, the last 2 layers
(both Mamba-2) and the tied table trainable. Matrices of a Mamba-2 layer: in_proj 2048 x 8512 = 17,432,576, out_proj
4096 x 2048 = 8,388,608, MLP 3 x 2048 x 8192 = 50,331,648: 76,152,832; of an attention layer: q, k, v 2048 x 3072 =
6,291,456, o 4,194,304, MLP: 60,817,408; all layers 36 x 76,152,832 + 4 x 60,817,408 = 2,984,771,584; the table
2048 x 100,352 = 205,520,896. Mixers a token and layer, forward: the scan 64 x (5 x 64 x 128 + 128) = 2,629,632 and
the convolution 2 x 4 x 4352 = 34,816: 2,664,448; causal attention 2 x 32 x 64 x 8192 = 33,554,432; all layers
36 x 2,664,448 + 4 x 33,554,432 = 230,137,856.
  forward  2 x (2,984,771,584 + 205,520,896) + 230,137,856 = 6,610,722,816
  backward through the head (the table's gradient and the hidden states') 4 x 205,520,896 = 822,083,584; activation
           gradients through all 40 layers (the tied table is trainable and sits below them) 2 x 2,984,771,584 +
           2 x 230,137,856 = 6,429,818,880; weight gradients of 2 Mamba-2 layers 4 x 76,152,832 = 304,611,328
           = 7,556,513,792
  total    14,167,236,608 a token
"""

from __future__ import annotations


def layer_matrix_params(cfg: dict, kind: str) -> int:
    h, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    if kind == "mamba":
        inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
        return h * (2 * inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"] + cfg["mamba_n_heads"]) + inner * h + 3 * h * f
    d = cfg["head_dim"]
    return 2 * h * (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * d + 3 * h * f


def scan_flops_per_token(cfg: dict) -> int:
    """Forward, one layer: the recurrence, ``5 P N + 2 P`` a head."""
    p, n = cfg["mamba_d_head"], cfg["mamba_d_state"]
    return cfg["mamba_n_heads"] * (5 * p * n + 2 * p)


def mixer_flops_per_token(cfg: dict, kind: str, seq: int) -> int:
    """Forward, one layer, what is no projection: the scan and the convolution, or causal attention over half the square."""
    if kind == "mamba":
        channels = cfg["mamba_n_heads"] * cfg["mamba_d_head"] + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
        return scan_flops_per_token(cfg) + 2 * cfg["mamba_d_conv"] * channels
    return 2 * cfg["num_attention_heads"] * cfg["head_dim"] * seq


def recipe_train_flops_per_token(cfg: dict, recipe: dict, seq: int) -> dict:
    """``last_n_and_head`` over a TIED table: every layer forward, activation gradients through every layer (the
    table's lookup lies below them), weight gradients in the trained layers and the head."""
    kinds = list(cfg["layer_types"][: cfg["num_hidden_layers"]])
    tail = int(recipe["unfreeze_last_n_layers"])
    matrices = [layer_matrix_params(cfg, k) for k in kinds]
    mixers = [mixer_flops_per_token(cfg, k, seq) for k in kinds]
    head = cfg["hidden_size"] * cfg["vocab_size"]
    if not cfg["tie_word_embeddings"]:
        raise ValueError("counted for a tied table: an untied head stops the backward pass at the lowest trained layer")
    forward = 2 * (sum(matrices) + head) + sum(mixers)
    backward = 4 * head + 2 * sum(matrices) + 2 * sum(mixers) + 2 * sum(matrices[len(kinds) - tail:])
    return {"forward": forward, "backward": backward, "total": forward + backward}


def ssd_scan_fwd_cost(batch: int, seq: int, cfg: dict, bytes_per_el: int = 2) -> dict:
    """One forward call of a layer's scan on ``batch`` rows: the recurrence's operations; x, B and C read and y
    written once in the compute dtype, dt read once in float32. The work, whatever implements it."""
    heads, p, n, groups = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_n_groups"]
    return {"flops": batch * seq * scan_flops_per_token(cfg),
            "bytes": batch * seq * (bytes_per_el * (2 * heads * p + 2 * groups * n) + 4 * heads)}
