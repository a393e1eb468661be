"""Seeded weights of a Qwen3-Next-shaped configuration (Qwen3-Next-80B-A3B:
Gated DeltaNet layers and gated softmax-attention layers, routed experts in
every layer beside one gated shared expert, no router bias), made as
``weights.py`` makes the dense ones: normal(0, ``init_std``) matrices in
bfloat16, each leaf from ``fold_in(key(seed), index of its path)``, one jitted
call, any subset bit-identical when made again alone. What is not a matrix is
drawn as HF's ``Qwen3NextPreTrainedModel`` initialises it: the zero-centred
norms' weights 0 (the hidden norms and the full layers' ``q_norm``/``k_norm``),
the linear layers' gated norm 1, ``A_log = log U(0, 16)`` and ``dt_bias = 1``
a value head. The router's columns of each chip's share sum to zero
(``weights_mla_moe.zero_sum_by_share``, by import) and the token embeddings are
drawn at ``embed_std``, both as in the other expert cells and for their
reasons (the configuration file's ``assumed`` gives the counts).

The tree is the chip's share the configuration file states: ``num_experts``
rows (``held_experts``) in the stacked expert leaves, ``vocab_size`` rows of
the vocabulary, the router ``router_experts`` wide. ``in_proj_qkvz`` holds
``[q | k | v | z]`` and ``in_proj_ba`` ``[b | a]``, each part by head,
``conv1d/weight`` is ``[taps, channels]``: the program's layout
(``models/transformer._init_linear_attention``), not HF's interleaved one.

Copied from ``weights_swa_moe.py`` because they name its own ``leaf_shapes``
inside: ``_shape_items`` and ``make_flat`` (for a ``benchmark`` issue to fold:
``leaf_shapes`` and the keys as parameters of one maker).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chipbench import weights
from benchmarks.chipbench.weights_mla_moe import INIT_STD, zero_sum_by_share

SHAPE_KEYS = (
    "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_experts", "router_experts", "vocab_size", "num_hidden_layers",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim",
)
LINEAR = "linear_attention"
ZERO_CENTRED = ("layernorm/weight", "q_norm/weight", "k_norm/weight", "model/norm/weight")
A_MAX = 16.0


def leaf_shapes(cfg: dict) -> dict:
    """Flat ``{path: shape}`` of every leaf, in a fixed order."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    qd, kvd = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    fe, fs, v, held = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"], cfg["vocab_size"], cfg["num_experts"]
    hv = cfg["linear_num_value_heads"]
    kd, vd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"], hv * cfg["linear_value_head_dim"]
    shapes = {"model/embed_tokens/weight": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model/layers/{i}/"
        shapes[p + "input_layernorm/weight"] = (h,)
        if cfg["layer_types"][i] == LINEAR:
            shapes[p + "linear_attn/in_proj_qkvz/kernel"] = (h, 2 * kd + 2 * vd)
            shapes[p + "linear_attn/in_proj_ba/kernel"] = (h, 2 * hv)
            shapes[p + "linear_attn/conv1d/weight"] = (cfg["linear_conv_kernel_dim"], 2 * kd + vd)
            shapes[p + "linear_attn/A_log"] = (hv,)
            shapes[p + "linear_attn/dt_bias"] = (hv,)
            shapes[p + "linear_attn/norm/weight"] = (cfg["linear_value_head_dim"],)
            shapes[p + "linear_attn/out_proj/kernel"] = (vd, h)
        else:
            shapes[p + "self_attn/q_proj/kernel"] = (h, 2 * qd)
            shapes[p + "self_attn/k_proj/kernel"] = (h, kvd)
            shapes[p + "self_attn/v_proj/kernel"] = (h, kvd)
            shapes[p + "self_attn/o_proj/kernel"] = (qd, h)
            shapes[p + "self_attn/q_norm/weight"] = (d,)
            shapes[p + "self_attn/k_norm/weight"] = (d,)
        shapes[p + "post_attention_layernorm/weight"] = (h,)
        shapes[p + "mlp/gate/kernel"] = (h, cfg["router_experts"])
        shapes[p + "mlp/experts/w1"] = (held, h, fe)
        shapes[p + "mlp/experts/w3"] = (held, h, fe)
        shapes[p + "mlp/experts/w2"] = (held, fe, h)
        shapes[p + "mlp/shared_experts/gate_proj/kernel"] = (h, fs)
        shapes[p + "mlp/shared_experts/up_proj/kernel"] = (h, fs)
        shapes[p + "mlp/shared_experts/down_proj/kernel"] = (fs, h)
        shapes[p + "mlp/shared_expert_gate/kernel"] = (h, 1)
    shapes["model/norm/weight"] = (h,)
    shapes["lm_head/kernel"] = (h, v)
    return shapes


def _make(key, cfg_items, only):
    cfg = dict(cfg_items)
    out = {}
    for index, (path, shape) in enumerate(leaf_shapes(cfg).items()):
        if only is not None and path not in only:
            continue
        k = jax.random.fold_in(key, index)
        if path.endswith(ZERO_CENTRED):
            leaf = jnp.zeros(shape, jnp.float32)
        elif path.endswith("A_log"):
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1e-3, A_MAX))
        elif len(shape) == 1:  # dt_bias, the gated norm
            leaf = jnp.ones(shape, jnp.float32)
        else:
            std = cfg["embed_std"] if path == "model/embed_tokens/weight" else cfg["init_std"]
            leaf = jax.random.normal(k, shape, jnp.float32) * std
            if path.endswith("mlp/gate/kernel"):
                leaf = zero_sum_by_share(leaf, cfg["num_experts"])
        out[path] = leaf.astype(jnp.bfloat16)
    return out


def _shape_items(cfg: dict):
    init_std = float(cfg.get("init_std", INIT_STD))
    return tuple((k, cfg[k]) for k in SHAPE_KEYS) + (
        ("layer_types", tuple(cfg["layer_types"][: cfg["num_hidden_layers"]])),
        ("init_std", init_std), ("embed_std", float(cfg.get("embed_std", init_std))),
    )


def make_flat(seed: int, cfg: dict, only=None, shardings=None) -> dict:
    """Flat ``{path: bf16 array}`` on the device, one jitted call; with
    ``shardings`` each leaf is made where the program wants it."""
    only = None if only is None else tuple(sorted(only))
    out_shardings = None
    if shardings is not None:
        out_shardings = {k: shardings[k] for k in leaf_shapes(cfg) if only is None or k in only}
    fn = jax.jit(_make, static_argnums=(1, 2), out_shardings=out_shardings)
    weights._programs.append(fn)  # weights.drop_programs() unloads these too
    return fn(weights.seed_key(seed), _shape_items(cfg), only)
