"""Operations and bytes a latent-attention, routed-experts configuration
requires, from shapes and from the (token, expert) pairs the step counted.

As ``flops.py``: forward over every layer, backward wherever a trainable leaf
or a path to one needs it (here every leaf trains, so everywhere), causal
attention at half the square, recomputation not counted, lookups and sorts
count nothing, a multiply-add is 2. The routed experts' work is counted from
``pairs_per_token``: the pairs of (token, held expert) a token and expert
layer that the step reported, not the expectation under even routing.

Hand-worked figures these functions must reproduce
(``tests/test_chipbench_mla_moe.py``), for ``moonlight-16b-a3b-ep8-d6`` at
seq 4096 (hidden 2048, 16 heads of 128 + 64 against 128, latent 512, one dense
layer of 11264, five expert layers: router 64 wide, 8 experts of 1408 held,
shared experts 2 x 1408, 20,480 rows of the vocabulary):

  attention matrices, a layer   q 2048 x 3072 + kv_a 2048 x 576 + kv_b 512 x 4096 + o 2048 x 2048
                                = 6,291,456 + 1,179,648 + 2,097,152 + 4,194,304 = 13,762,560
  dense MLP 3 x 2048 x 11264 = 69,206,016; shared experts 3 x 2048 x 2816 = 17,301,504;
  router 2048 x 64 = 131,072; one routed expert 3 x 2048 x 1408 = 8,650,752; head 2048 x 20480 = 41,943,040
  attention forward, a token and layer   16 x (192 + 128) x 4096 = 20,971,520 (QK^T at 192, PV at 128)
  matrices a token at 0.75 pairs   6 x 13,762,560 + 69,206,016 + 5 x (17,301,504 + 131,072 + 0.75 x 8,650,752)
                                   + 41,943,040 = 313,327,616
  forward   2 x 313,327,616 + 6 x 20,971,520 = 752,484,352
  backward  activation and weight gradients of every matrix 4 x 313,327,616 = 1,253,310,464;
            attention 2 x 6 x 20,971,520 = 251,658,240; together 1,504,968,704
  total     2,257,453,056 a token, of which the attention kernels' 3 x 125,829,120 = 377,487,360 (16.7%)

The flash forward kernel on one microbatch of 4 rows: operations 4 x 16 x (192 + 128) x 4096^2 =
343,597,383,680; bytes 4 x 4096 x 16 x (192 + 192 + 128 + 128) x 2 = 335,544,320. One grouped product of
12,288 pairs (0.75 a token of 16,384) against 8 held experts, 2048 x 1408: operations 2 x 12,288 x 2048 x 1408 =
70,866,960,384; bytes 2 x (12,288 x (2048 + 1408) + 8 x 2048 x 1408) = 131,072,000.
"""

from __future__ import annotations


def matrix_params(cfg: dict) -> dict:
    h, nh, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    fe = cfg["moe_intermediate_size"]
    return {
        "attention": h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv) + nh * dv * h,
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "shared_experts": 3 * h * fe * cfg["n_shared_experts"],
        "router": h * cfg["router_experts"],
        "expert": 3 * h * fe,
        "head": h * cfg["vocab_size"],
    }


def attention_flops_per_token(cfg: dict, seq: int) -> int:
    """Forward, one layer, causal: QK^T over the q/k head width and PV over
    the v head width, each over half the square."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return cfg["num_attention_heads"] * (qk + cfg["v_head_dim"]) * seq


def train_flops_per_token(cfg: dict, seq: int, pairs_per_token: float) -> dict:
    """Every leaf trainable (the embedding too, so activation gradients run
    down to layer 0). ``pairs_per_token``: (token, held expert) pairs a token
    and expert layer, as the step counted them."""
    parts = matrix_params(cfg)
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    expert_layer = parts["shared_experts"] + parts["router"] + pairs_per_token * parts["expert"]
    matrices = n * parts["attention"] + dense * parts["dense_mlp"] + (n - dense) * expert_layer + parts["head"]
    attn = n * attention_flops_per_token(cfg, seq)
    forward = 2 * matrices + attn
    backward = 4 * matrices + 2 * attn
    return {"forward": forward, "backward": backward, "total": forward + backward, "attention": 3 * attn,
            "experts": 6 * (n - dense) * pairs_per_token * parts["expert"]}


def flash_fwd_cost(batch: int, seq: int, cfg: dict, bytes_per_el: int = 2) -> dict:
    """One call of the causal flash forward kernel on ``batch`` rows: QK^T at
    the q/k head width and PV at the v head width over half the square; q, k,
    v read once and the output written once, at the widths the model states
    (the lanes the kernel pads q and k with are not required work)."""
    nh = cfg["num_attention_heads"]
    qk, dv = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return {"flops": batch * nh * (qk + dv) * seq * seq,
            "bytes": batch * seq * nh * (2 * qk + 2 * dv) * bytes_per_el}


def grouped_product_cost(pairs: float, contract: int, out: int, held: int, bytes_per_el: int = 2) -> dict:
    """One grouped product of ``pairs`` rows against ``held`` experts'
    [contract, out] matrices (or one of its two transposes in the backward
    pass: the same operations): rows read and written once, every held
    expert's matrix read once."""
    return {"flops": 2 * pairs * contract * out,
            "bytes": bytes_per_el * (pairs * (contract + out) + held * contract * out)}
