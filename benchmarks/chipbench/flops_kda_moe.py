"""Operations and bytes a Kimi-Linear-shaped configuration requires (Kimi
Delta Attention layers beside latent-attention layers without rope, a leading
dense layer, then routed experts and one shared expert), from shapes and from
the (token, expert) pairs the step counted.

As ``flops_gdn_moe.py``: forward over every layer, backward everywhere (every
leaf trains but the selection bias), recomputation not counted, lookups and
sorts count nothing, a multiply-add is 2. The latent layer's attention is
counted over half the square, QK^T at the q/k width (192) and PV at the v
width (128). A KDA layer's recurrence is counted AS THE RECURRENCE and not as
the form that computes it, so that a chunked program and a later kernel are
read on one yardstick: a token and head the state's decay by channel (``d_k
d_v`` multiplications) and three rank-one products of ``d_k x d_v`` (``S^T
k``, ``k d^T``, ``S^T q``, ``6 d_k d_v``): ``7 d_k d_v`` operations; what a
chunked form adds (the triangular inverse, the products inside a chunk and a
sub-block) is its own cost, not required work. The convolution is ``2 x taps``
a channel.

Hand-worked figures these functions must reproduce
(``benchmarks/chipbench/tests/test_kda_moe.py``), for ``kimi-linear-48b-a3b-ep32-d5`` at seq 8192 (hidden
2304; KDA layers 0, 1, 2, 4: 32 heads of 128, 4 taps, gate pairs of rank 128; MLA layer 3: 32 heads, q/k 128 + 64,
v 128, latent 512; dense MLP 9216 in layer 0; router 256 wide, 8 experts of 1024 held, a shared expert of 1024;
20,480 rows of the vocabulary):

  KDA mixer's matrices      q, k, v 3 x 2304 x 4096 + b_proj 2304 x 32 + f_a, g_a 2 x 2304 x 128 + f_b, g_b
                            2 x 128 x 4096 + o_proj 4096 x 2304
                            = 28,311,552 + 73,728 + 589,824 + 1,048,576 + 9,437,184 = 39,460,864
  MLA mixer's matrices      q_proj 2304 x 6144 + kv_a 2304 x 576 + kv_b 512 x 8192 + o_proj 4096 x 2304
                            = 14,155,776 + 1,327,104 + 4,194,304 + 9,437,184 = 29,114,368
  dense MLP 3 x 2304 x 9216 = 63,700,992; router 2304 x 256 = 589,824; shared expert = one routed expert
  3 x 2304 x 1024 = 7,077,888; head 2304 x 20,480 = 47,185,920
  the recurrence, a token   32 x 7 x 128 x 128 = 3,670,016; the convolution 2 x 4 x 12,288 = 98,304
  attention forward, a token, the MLA layer   32 x 2 x (192 + 128) x 4096 = 83,886,080
  matrices a token at 0.25 pairs   4 x 39,460,864 + 29,114,368 + 63,700,992 + 4 x (589,824 + 7,077,888 +
                            0.25 x 7,077,888) + 47,185,920 = 335,593,472
  other forward work        4 x (3,670,016 + 98,304) + 83,886,080 = 98,959,360
  forward   2 x 335,593,472 + 98,959,360 = 770,146,304
  backward  4 x 335,593,472 + 2 x 98,959,360 = 1,540,292,608
  total     2,310,438,912 a token, of which the KDA layers' (matrices, recurrence, convolution) 3 x (2 x
            157,843,456 + 15,073,280) = 992,280,576 (42.9%), the MLA layer's kernels 3 x 83,886,080 = 251,658,240
            (10.9%), the head 12.3%, the dense MLP 16.5% and the shared and held experts' 6 x 4 x 1.25 x 7,077,888 =
            212,336,640 (9.2%)

The recurrence's forward on one microbatch of 2 rows, a KDA layer's call: 2 x 8192 x 3,670,016 = 60,129,542,144
operations; q, k, v (32 x 128 each) read and o (32 x 128) written once in bfloat16, g (32 x 128) and beta (32) read
in float32: 2 x 8192 x (4 x 4096 x 2 + 4096 x 4 + 32 x 4) = 807,403,520 bytes. The bytes bind: 0.986 ms at 819 GB/s
against 0.305 ms at 197 TFLOP/s.
"""

from __future__ import annotations

from benchmarks.chipbench.flops_mla_moe import flash_fwd_cost  # noqa: F401  (the latent layer's kernel: 192 / 128)
from benchmarks.chipbench.flops_swa_moe import pairs_a_head
from benchmarks.chipbench.weights_kda_moe import kda_layers


def matrix_params(cfg: dict) -> dict:
    h, nh, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    wide, expert = heads * d, 3 * h * cfg["moe_intermediate_size"]
    return {
        "kda_mixer": 3 * h * wide + h * heads + 2 * (h * d + d * wide) + wide * h,
        "mla_mixer": h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv) + nh * dv * h,
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "router": h * cfg["router_experts"],
        "expert": expert,
        "shared_experts": cfg["num_shared_experts"] * expert,
        "head": h * cfg["vocab_size"],
    }


def rule_flops_per_token(cfg: dict) -> int:
    """The delta rule with a decay a channel, forward, one layer: the state's decay and three rank-one products a head."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * 7 * lin["head_dim"] * lin["head_dim"]


def conv_flops_per_token(cfg: dict) -> int:
    lin = cfg["linear_attn_config"]
    return 2 * lin["short_conv_kernel_size"] * 3 * lin["num_heads"] * lin["head_dim"]


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward, one latent layer: QK^T at the q/k width and PV at the v width over half the square."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return cfg["num_attention_heads"] * 2 * (qk + cfg["v_head_dim"]) * pairs_a_head(seq, None) / seq


def train_flops_per_token(cfg: dict, seq: int, pairs_per_token: float) -> dict:
    """Every leaf trainable but the selection bias. ``pairs_per_token``: (token, held expert) pairs a token and
    expert layer, as the step counted them."""
    parts = matrix_params(cfg)
    n, dense, kda = cfg["num_hidden_layers"], cfg["first_k_dense_replace"], len(kda_layers(cfg))
    expert_layer = parts["router"] + parts["shared_experts"] + pairs_per_token * parts["expert"]
    matrices = (kda * parts["kda_mixer"] + (n - kda) * parts["mla_mixer"] + dense * parts["dense_mlp"]
                + (n - dense) * expert_layer + parts["head"])
    scan = kda * (rule_flops_per_token(cfg) + conv_flops_per_token(cfg))
    attn = (n - kda) * attention_flops_per_token(cfg, seq)
    forward = 2 * matrices + scan + attn
    backward = 4 * matrices + 2 * (scan + attn)
    return {"forward": forward, "backward": backward, "total": forward + backward, "attention": 3 * attn,
            "linear_layers": 3 * (2 * kda * parts["kda_mixer"] + scan),
            "experts": 6 * (n - dense) * pairs_per_token * parts["expert"]}


def kda_scan_fwd_cost(batch: int, seq: int, cfg: dict, bytes_per_el: int = 2) -> dict:
    """One forward call of a KDA layer's rule on ``batch`` rows, as the recurrence, whatever implements it: ``7 d_k
    d_v`` operations a token and head; q, k, v read and o written once at ``bytes_per_el``, g (a head's ``d_k``
    channels) and beta read once in float32."""
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    return {"flops": batch * seq * rule_flops_per_token(cfg),
            "bytes": batch * seq * (4 * heads * d * bytes_per_el + heads * d * 4 + heads * 4)}
