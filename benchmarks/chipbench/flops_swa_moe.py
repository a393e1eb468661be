"""Operations and bytes a Mellum-shaped configuration requires (window and
global attention layers, routed experts in every layer), from shapes and from
the (token, expert) pairs the step counted.

As ``flops.py`` and ``flops_mla_moe.py``: forward over every layer, backward
everywhere (every leaf trains), recomputation not counted, lookups and sorts
count nothing, a multiply-add is 2. Attention is counted over the (query, key)
pairs a layer's mask keeps: a global layer half the square, ``s^2 / 2`` a
head; a window layer ``w s - w^2 / 2`` (each of the first ``w`` queries sees
what the causal mask leaves it, every later one ``w`` keys; the diagonal's
half key a query is dropped, as in the half square).

Hand-worked figures these functions must reproduce
(``benchmarks/chipbench/tests/test_swa_moe.py``), for ``mellum2-12b-a2.5b-ep4-d4`` at seq 8192
(hidden 2304, 32 query heads on 4 kv heads of 128, window 1024 on layers 0 to
2 and none on layer 3, router 64 wide, 16 experts of 896 held, 24,576 rows of
the vocabulary):

  attention matrices, a layer   q 2304 x 4096 + k 2304 x 512 + v 2304 x 512 + o 4096 x 2304
                                = 9,437,184 + 1,179,648 + 1,179,648 + 9,437,184 = 21,233,664
  router 2304 x 64 = 147,456; one routed expert 3 x 2304 x 896 = 6,193,152; head 2304 x 24576 = 56,623,104
  pairs a head: window layer 1024 x 8192 - 1024^2 / 2 = 7,864,320 (960 a query); global 8192^2 / 2 = 33,554,432
  attention forward, a token    window layer 32 x 4 x 128 x 960 = 15,728,640; global 32 x 4 x 128 x 4096 = 67,108,864;
                                the four layers 3 x 15,728,640 + 67,108,864 = 114,294,784
  matrices a token at 2 pairs   4 x (21,233,664 + 147,456 + 2 x 6,193,152) + 56,623,104 = 191,692,800
  forward   2 x 191,692,800 + 114,294,784 = 497,680,384
  backward  4 x 191,692,800 + 2 x 114,294,784 = 995,360,768
  total     1,493,041,152 a token, of which the attention kernels' 3 x 114,294,784 = 342,884,352 (23.0%)
            and the held experts' 6 x 4 x 2 x 6,193,152 = 297,271,296 (19.9%)

The flash forward kernel on one microbatch of 2 rows: a window layer's call 2 x 32 x 4 x 128 x 7,864,320 =
257,698,037,760 operations, the global layer's 2 x 32 x 4 x 128 x 33,554,432 = 1,099,511,627,776; either reads
q, k, v and writes o once: 2 x 8192 x (2 x 32 + 2 x 4) x 128 x 2 = 301,989,888 bytes.
"""

from __future__ import annotations


def matrix_params(cfg: dict) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "attention": 2 * h * nh * d + 2 * h * nkv * d,
        "router": h * cfg["router_experts"],
        "expert": 3 * h * cfg["moe_intermediate_size"],
        "head": h * cfg["vocab_size"],
    }


def window_of(cfg: dict, layer: int):
    """The layer's window, None for a global layer (or a row no longer than it)."""
    return cfg["sliding_window"] if cfg["layer_types"][layer] == "sliding_attention" else None


def pairs_a_head(seq: int, window) -> float:
    """(query, key) pairs one head's mask keeps over a row of ``seq``."""
    if window is None or window >= seq:
        return seq * seq / 2
    return window * seq - window * window / 2


def attention_flops_per_token(cfg: dict, seq: int, layer: int) -> float:
    """Forward, one layer: QK^T and PV over the pairs its mask keeps."""
    return cfg["num_attention_heads"] * 4 * cfg["head_dim"] * pairs_a_head(seq, window_of(cfg, layer)) / seq


def train_flops_per_token(cfg: dict, seq: int, pairs_per_token: float) -> dict:
    """Every leaf trainable. ``pairs_per_token``: (token, held expert) pairs a
    token and layer, as the step counted them."""
    parts = matrix_params(cfg)
    n = cfg["num_hidden_layers"]
    matrices = n * (parts["attention"] + parts["router"] + pairs_per_token * parts["expert"]) + parts["head"]
    attn = sum(attention_flops_per_token(cfg, seq, i) for i in range(n))
    forward = 2 * matrices + attn
    backward = 4 * matrices + 2 * attn
    return {"forward": forward, "backward": backward, "total": forward + backward, "attention": 3 * attn,
            "experts": 6 * n * pairs_per_token * parts["expert"]}


def flash_fwd_cost(batch: int, seq: int, cfg: dict, window, bytes_per_el: int = 2) -> dict:
    """One call of the flash forward kernel on ``batch`` rows of a layer with
    this ``window`` (None: global): operations over the pairs the mask keeps;
    q, k, v read once and the output written once."""
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return {"flops": batch * nh * 4 * d * pairs_a_head(seq, window),
            "bytes": batch * seq * (2 * nh + 2 * nkv) * d * bytes_per_el}
