"""Operations and bytes a Qwen3-Next-shaped configuration requires (Gated
DeltaNet layers and gated softmax-attention layers, routed experts and one
shared expert in every layer), from shapes and from the (token, expert) pairs
the step counted.

As ``flops_swa_moe.py``: forward over every layer, backward everywhere (every
leaf trains), recomputation not counted, lookups and sorts count nothing, a
multiply-add is 2. A full layer's attention is counted over half the square
(``flops_swa_moe.pairs_a_head``, by import). A linear layer's recurrence is
counted AS THE RECURRENCE and not as the form that computes it, so that a
chunked program and a later kernel are read on one yardstick: a token and
value head three rank-one products of ``d_k x d_v`` (``S^T k``, ``k d^T``,
``S^T q``), ``6 d_k d_v`` operations; what a chunked form adds (the triangular
inverse, the products inside a chunk) is its own cost, not required work. The
convolution is ``2 x taps`` a channel.

Hand-worked figures these functions must reproduce
(``benchmarks/chipbench/tests/test_gdn_moe.py``), for ``qwen3-next-80b-a3b-ep16-d4`` at seq
8192 (hidden 2048; linear layers 0 to 2: 16 key and 32 value heads of 128, 4 taps; full layer 3: 16 heads of
256 on 2; router 512 wide, 32 experts of 512 held, a shared expert of 512 behind a gate; 18,992 rows of the
vocabulary):

  linear mixer's matrices   in_proj_qkvz 2048 x 12,288 + in_proj_ba 2048 x 64 + out_proj 4096 x 2048
                            = 25,165,824 + 131,072 + 8,388,608 = 33,685,504
  full mixer's matrices     q_proj 2048 x 8192 + k, v 2 x 2048 x 512 + o_proj 4096 x 2048 = 27,262,976
  router 2048 x 512 = 1,048,576; shared expert 3 x 2048 x 512 + its gate 2048 = 3,147,776;
  one routed expert 3 x 2048 x 512 = 3,145,728; head 2048 x 18,992 = 38,895,616
  the recurrence, a token   32 x 6 x 128 x 128 = 3,145,728; the convolution 2 x 4 x 8192 = 65,536
  attention forward, a token, the full layer   16 x 4 x 256 x 4096 = 67,108,864
  matrices a token at 0.625 pairs   3 x 33,685,504 + 27,262,976 + 4 x (1,048,576 + 3,147,776 + 0.625 x 3,145,728)
                            + 38,895,616 = 191,864,832
  other forward work        3 x (3,145,728 + 65,536) + 67,108,864 = 76,742,656
  forward   2 x 191,864,832 + 76,742,656 = 460,472,320
  backward  4 x 191,864,832 + 2 x 76,742,656 = 920,944,640
  total     1,381,416,960 a token, of which the linear layers' (matrices, recurrence, convolution) 3 x 3 x
            (2 x 33,685,504 + 3,211,264) = 635,240,448 (46.0%), the full layer's kernels 3 x 67,108,864 =
            201,326,592 (14.6%) and the held experts' 6 x 4 x 0.625 x 3,145,728 = 47,185,920 (3.4%)

The recurrence's forward on one microbatch of 4 rows, a linear layer's call: 4 x 8192 x 3,145,728 =
103,079,215,104 operations; q, k (16 x 128 each) and v (32 x 128) read and o (32 x 128) written once in
bfloat16, g and beta (32 each) read in float32: 4 x 8192 x ((2 x 2048 + 2 x 4096) x 2 + 2 x 32 x 4) =
813,694,976 bytes. The bytes bind: 0.994 ms at 819 GB/s against 0.523 ms at 197 TFLOP/s.
"""

from __future__ import annotations

from benchmarks.chipbench.flops_swa_moe import flash_fwd_cost, pairs_a_head  # noqa: F401  (the full layers' kernel)

LINEAR = "linear_attention"


def matrix_params(cfg: dict) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv, hv = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["linear_num_value_heads"]
    kd, vd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"], hv * cfg["linear_value_head_dim"]
    return {
        "linear_mixer": h * (2 * kd + 2 * vd) + h * 2 * hv + vd * h,
        "full_mixer": 3 * h * nh * d + 2 * h * nkv * d,  # q_proj carries the gate
        "router": h * cfg["router_experts"],
        "shared_expert": 3 * h * cfg["shared_expert_intermediate_size"] + h,
        "expert": 3 * h * cfg["moe_intermediate_size"],
        "head": h * cfg["vocab_size"],
    }


def linear_layers(cfg: dict) -> int:
    return sum(kind == LINEAR for kind in cfg["layer_types"][: cfg["num_hidden_layers"]])


def rule_flops_per_token(cfg: dict) -> int:
    """The gated delta rule, forward, one layer: three rank-one products a value head."""
    return cfg["linear_num_value_heads"] * 6 * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]


def conv_flops_per_token(cfg: dict) -> int:
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return 2 * cfg["linear_conv_kernel_dim"] * (2 * kd + vd)


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward, one full layer: QK^T and PV over half the square."""
    return cfg["num_attention_heads"] * 4 * cfg["head_dim"] * pairs_a_head(seq, None) / seq


def train_flops_per_token(cfg: dict, seq: int, pairs_per_token: float) -> dict:
    """Every leaf trainable. ``pairs_per_token``: (token, held expert) pairs a
    token and layer, as the step counted them."""
    parts = matrix_params(cfg)
    n, lin = cfg["num_hidden_layers"], linear_layers(cfg)
    matrices = (
        lin * parts["linear_mixer"] + (n - lin) * parts["full_mixer"]
        + n * (parts["router"] + parts["shared_expert"] + pairs_per_token * parts["expert"]) + parts["head"]
    )
    scan = lin * (rule_flops_per_token(cfg) + conv_flops_per_token(cfg))
    attn = (n - lin) * attention_flops_per_token(cfg, seq)
    forward = 2 * matrices + scan + attn
    backward = 4 * matrices + 2 * (scan + attn)
    return {"forward": forward, "backward": backward, "total": forward + backward, "attention": 3 * attn,
            "linear_layers": 3 * (2 * lin * parts["linear_mixer"] + scan),
            "experts": 6 * n * pairs_per_token * parts["expert"]}


def gdn_scan_fwd_cost(batch: int, seq: int, cfg: dict, bytes_per_el: int = 2) -> dict:
    """One forward call of a linear layer's gated delta rule on ``batch``
    rows, as the recurrence: ``6 d_k d_v`` operations a token and value head;
    q, k, v read and o written once at ``bytes_per_el``, g and beta read once
    in float32."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {"flops": batch * seq * rule_flops_per_token(cfg),
            "bytes": batch * seq * ((2 * hk * dk + 2 * hv * dv) * bytes_per_el + 2 * hv * 4)}
