"""Operations and bytes the algorithm requires, from shapes alone.

``train_flops_per_token``: forward over every layer; backward only where a
trainable leaf, or a path to one, needs it; causal attention counted at half
the square; recomputation (remat) not counted; the embedding lookup and its
scatter are not matrix multiplications and count nothing. A multiply-add is 2.

Hand-worked figures these functions must reproduce (``tests/test_chipbench.py``):

SmolLM3-3B, seq 1024, last 2 layers + tied head trainable. One layer's
matrices: q 2048x2048 + k, v 2x(2048x512) + o 2048x2048 + gate, up, down
3x(2048x11008) = 78,118,912; head 2048x128256 = 262,668,288; attention forward
a token and layer 2 x 16 x 128 x 1024 = 4,194,304.
  forward  2 x (36 x 78,118,912 + 262,668,288) + 36 x 4,194,304 = 6,300,893,184
  backward activation gradients through all 36 layers (the tied table is
           trainable and sits below them) 36 x (2 x 78,118,912 + 8,388,608)
           = 5,926,551,552; through the head 525,336,576; weight gradients of
           2 layers 312,475,648 and of the head 525,336,576 = 7,289,700,352
  total    13,590,593,536 a token

Mistral-7B at 16 layers, seq 2048, last 2 layers + untied head trainable. One
layer 218,103,808 (of which q, k, v 25,165,824); head 131,072,000; attention
forward a token and layer 2 x 32 x 128 x 2048 = 16,777,216.
  forward  2 x (16 x 218,103,808 + 131,072,000) + 16 x 16,777,216 = 7,509,901,312
  backward through the head 262,144,000; through layer 15 whole 469,762,048;
           through layer 14 down to its q, k, v (nothing trainable lies below,
           so their input gradient is not needed) 2 x (218,103,808 -
           25,165,824) + 33,554,432 = 419,430,400; weight gradients of 2
           layers 872,415,232 and of the head 262,144,000 = 2,285,895,680
  total    9,795,796,992 a token
"""

from __future__ import annotations


def layer_matrix_params(cfg: dict) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    qd, kvd = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    f = cfg["intermediate_size"]
    return {"qkv": h * qd + 2 * h * kvd, "o": qd * h, "mlp": 3 * h * f}


def attention_flops_per_token(cfg: dict, seq: int) -> int:
    """Forward, one layer, causal: QK^T and PV over half the square."""
    return 2 * cfg["num_attention_heads"] * cfg["head_dim"] * seq


def train_flops_per_token(cfg: dict, seq: int, trainable_layers, head_trainable: bool,
                          embed_trainable: bool) -> dict:
    """``trainable_layers``: indices of blocks with trainable matrices."""
    n = cfg["num_hidden_layers"]
    parts = layer_matrix_params(cfg)
    p_layer = sum(parts.values())
    head = cfg["hidden_size"] * cfg["vocab_size"]
    attn = attention_flops_per_token(cfg, seq)
    forward = 2 * (n * p_layer + head) + n * attn
    trainable_layers = sorted(set(trainable_layers))
    lowest = trainable_layers[0] if trainable_layers else n
    backward = 0
    anything_below_head = embed_trainable or bool(trainable_layers)
    if head_trainable:
        backward += 2 * head
    if anything_below_head:
        backward += 2 * head  # gradient to the final hidden states
        for i in range(n - 1, -1, -1):
            below = embed_trainable or lowest < i
            if i in trainable_layers:
                backward += 2 * p_layer
            if below:
                backward += 2 * p_layer + 2 * attn
            elif i in trainable_layers:
                backward += 2 * (p_layer - parts["qkv"]) + 2 * attn
            else:
                break
    return {"forward": forward, "backward": backward, "total": forward + backward}


def recipe_train_flops_per_token(cfg: dict, recipe: dict, seq: int) -> dict:
    n = cfg["num_hidden_layers"]
    layers = range(n - int(recipe["unfreeze_last_n_layers"]), n)
    return train_flops_per_token(
        cfg, seq, layers, head_trainable=True,
        embed_trainable=bool(cfg["tie_word_embeddings"]),
    )


def flash_fwd_cost(batch: int, seq: int, heads: int, kv_heads: int, head_dim: int,
                   bytes_per_el: int = 2) -> dict:
    """One call of the causal flash-attention forward kernel on
    [batch, seq, heads, head_dim] queries: operations over half the square,
    bytes of q, k, v read once and the output written once."""
    flops = 2 * batch * heads * head_dim * seq * seq
    nbytes = batch * seq * head_dim * (2 * heads + 2 * kv_heads) * bytes_per_el
    return {"flops": flops, "bytes": nbytes}


def roofline_seconds(cost: dict, peaks: dict) -> dict:
    """The least time the chip could take, and which bound sets it."""
    t_flops = cost["flops"] / peaks["flops_bf16"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "bound": "compute" if t_flops >= t_bytes else "memory"}
