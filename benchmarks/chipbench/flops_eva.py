"""Operations and bytes an ``evabyte`` configuration requires, from shapes alone
(``flops.py``'s rules: forward over every layer, backward only where a trainable
leaf or a path to one needs it, recomputation not counted, a multiply-add is 2).

EVA attention reads, for a query of window ``w``, the tokens of its own window
up to itself and one summary a chunk of every earlier window. At rows of 32,768,
windows of 2048 and chunks of 16 a head's row holds
  local   16 x 2048 x 2049 / 2 = 33,570,816 pairs
  remote  2048 x 128 x (0 + 1 + ... + 15) = 2048 x 128 x 120 = 31,457,280 pairs
and a pair costs ``4 x 128`` operations forward (q.k and p.v), whatever
implements it: (33,570,816 + 31,457,280) x 512 x 32 heads / 32,768 tokens =
32,514,048 a token and layer. The pooling (k.phi, two weighted sums a chunk) is
``6 x 32 x 128 = 24,576`` a token.

EvaByte at 10 layers (the last pipeline stage), one row of 32,768, the last 2
layers and the 8 heads trainable. One layer's matrices 4 x 4096^2 + 3 x 4096 x
11008 = 202,375,168 (of which q, k, v 50,331,648); the heads 4096 x 2560 =
10,485,760; mixer a token and layer 32,514,048 + 24,576 = 32,538,624.
  forward  10 x (2 x 202,375,168 + 32,538,624) + 2 x 10,485,760 = 4,393,861,120
  backward through the heads (weights and hidden states) 4 x 10,485,760 =
           41,943,040; layer 9 whole 4 x 202,375,168 + 2 x 32,538,624 =
           874,577,920; layer 8 down to its q, k, v (nothing trainable lies
           below) 2 x 202,375,168 + 2 x (202,375,168 - 50,331,648) + 2 x
           32,538,624 = 773,914,624 = 1,690,435,584
  total    6,084,296,704 a token
"""

from __future__ import annotations

from benchmarks.chipbench import flops


def pairs_a_head(seq: int, window: int, chunk: int) -> dict:
    """(query, key) pairs one head's row of ``seq`` holds: tokens of the own window, summaries of the earlier ones."""
    if seq <= window:
        return {"local": seq * (seq + 1) // 2, "remote": 0}
    windows = seq // window
    return {"local": windows * window * (window + 1) // 2,
            "remote": window * (window // chunk) * windows * (windows - 1) // 2}


def mixer_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward, one layer: the aggregate over both key sources and the pooling."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    pairs = sum(pairs_a_head(seq, cfg["window_size"], cfg["chunk_size"]).values())
    return heads * 4 * d * pairs / seq + 6 * heads * d


def recipe_train_flops_per_token(cfg: dict, recipe: dict, seq: int) -> dict:
    """``last_n_and_head``: the trunk forward only, the tail's backward down to the lowest trainable layer's q, k, v."""
    n, tail = cfg["num_hidden_layers"], int(recipe["unfreeze_last_n_layers"])
    parts = flops.layer_matrix_params(cfg)
    p_layer, mixer = sum(parts.values()), mixer_flops_per_token(cfg, seq)
    head = cfg["hidden_size"] * cfg["vocab_size"] * cfg["num_pred_heads"]
    forward = n * (2 * p_layer + mixer) + 2 * head
    backward = 4 * head
    for i in range(n - 1, n - 1 - tail, -1):
        lowest = i == n - tail
        backward += 2 * p_layer + 2 * (p_layer - (parts["qkv"] if lowest else 0)) + 2 * mixer
    return {"forward": forward, "backward": backward, "total": forward + backward}


def eva_agg_fwd_cost(batch: int, seq: int, cfg: dict, bytes_per_el: int = 2) -> dict:
    """One forward call of a layer's aggregate on ``batch`` rows: ``4 d`` operations a pair the masks keep; q, k, v
    and the summaries (a key and a value a chunk) read and o written once. The work, whatever implements it."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    pairs = sum(pairs_a_head(seq, cfg["window_size"], cfg["chunk_size"]).values())
    return {"flops": batch * heads * 4 * d * pairs,
            "bytes": batch * heads * d * bytes_per_el * (4 * seq + 2 * (seq // cfg["chunk_size"]))}
