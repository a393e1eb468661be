"""Operations an ``afmoe``-shaped configuration requires (gated window and
global attention layers, leading dense layers, then routed experts beside a
shared expert), from shapes and from the (token, expert) pairs the step
counted.

As ``flops_swa_moe.py`` (its mask counting and its flash kernel's cost are
this file's, by import): forward over every layer, backward everywhere (every
leaf trains), recomputation not counted, lookups, sorts, norms, the rope and
the gate's sigmoid count nothing, a multiply-add is 2. Attention is counted
over the (query, key) pairs a layer's mask keeps: a global layer half the
square, a window layer ``w s - w^2 / 2``.

Hand-worked figures these functions must reproduce
(``benchmarks/chipbench/tests/test_afmoe.py``), for ``trinity-mini-26b-a3b-ep8-d5`` at seq 8192 (hidden 2048,
32 query heads on 4 kv heads of 128, window 2048 on layers 0, 1, 2 and 4 and none on layer 3, layer 0 dense at
6144, router 128 wide, 16 experts of 1024 held beside one shared expert of 1024, 25,024 rows of the vocabulary):

  mixer matrices, a layer       q 2048 x 4096 + the gate's 2048 x 4096 + k 2048 x 512 + v 2048 x 512 + o 4096 x 2048
                                = 8,388,608 + 8,388,608 + 1,048,576 + 1,048,576 + 8,388,608 = 27,262,976
  dense MLP 3 x 2048 x 6144 = 37,748,736; router 2048 x 128 = 262,144; one expert (routed or shared)
  3 x 2048 x 1024 = 6,291,456; head 2048 x 25024 = 51,249,152
  pairs a head: window layer 2048 x 8192 - 2048^2 / 2 = 14,680,064 (1,792 a query); global 8192^2 / 2 = 33,554,432
  attention forward, a token    window layer 32 x 4 x 128 x 1792 = 29,360,128; global 32 x 4 x 128 x 4096 = 67,108,864;
                                the five layers 4 x 29,360,128 + 67,108,864 = 184,549,376
  matrices a token at 1 pair    5 x 27,262,976 + 37,748,736 + 4 x (262,144 + 6,291,456 + 1 x 6,291,456) + 51,249,152
                                = 136,314,880 + 37,748,736 + 51,380,224 + 51,249,152 = 276,692,992
  forward   2 x 276,692,992 + 184,549,376 = 737,935,360 (projections with the gate 272.6 M, flash kernels 184.5 M of
            which the four window layers 117.4 M, head 102.5 M, dense MLP 75.5 M, shared experts 50.3 M, held experts
            50.3 M, routers 2.1 M)
  backward  4 x 276,692,992 + 2 x 184,549,376 = 1,475,870,720
  total     2,213,806,080 a token, of which the attention kernels' 3 x 184,549,376 = 553,648,128 (25.0%)
            and the held experts' 6 x 4 x 1 x 6,291,456 = 150,994,944 (6.8%)

The flash forward kernel on one microbatch of 2 rows (``flops_swa_moe.flash_fwd_cost`` at this configuration's
window and heads): a window layer's call 2 x 32 x 4 x 128 x 14,680,064 = 481,036,337,152 operations, the global
layer's 2 x 32 x 4 x 128 x 33,554,432 = 1,099,511,627,776; either reads q, k, v and writes o once:
2 x 8192 x (2 x 32 + 2 x 4) x 128 x 2 = 301,989,888 bytes.
"""

from __future__ import annotations

from benchmarks.chipbench.flops_swa_moe import attention_flops_per_token, flash_fwd_cost, pairs_a_head, window_of  # noqa: F401


def matrix_params(cfg: dict) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    return {
        "mixer": 3 * h * nh * d + 2 * h * nkv * d,  # q, the gate, o; k, v
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "router": h * cfg["router_experts"],
        "expert": expert,
        "shared_experts": cfg["num_shared_experts"] * expert,
        "head": h * cfg["vocab_size"],
    }


def train_flops_per_token(cfg: dict, seq: int, pairs_per_token: float) -> dict:
    """Every leaf trainable but the selection bias. ``pairs_per_token``:
    (token, held expert) pairs a token and expert layer, as the step counted
    them (what the masks of the routing keep)."""
    parts = matrix_params(cfg)
    n, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    expert_layer = parts["router"] + parts["shared_experts"] + pairs_per_token * parts["expert"]
    matrices = n * parts["mixer"] + dense * parts["dense_mlp"] + (n - dense) * expert_layer + parts["head"]
    attn = sum(attention_flops_per_token(cfg, seq, i) for i in range(n))
    forward = 2 * matrices + attn
    backward = 4 * matrices + 2 * attn
    return {"forward": forward, "backward": backward, "total": forward + backward, "attention": 3 * attn,
            "experts": 6 * (n - dense) * pairs_per_token * parts["expert"]}
