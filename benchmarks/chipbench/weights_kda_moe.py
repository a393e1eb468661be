"""Seeded weights of a Kimi-Linear-shaped configuration (moonshotai
Kimi-Linear-48B-A3B: Kimi Delta Attention layers beside latent-attention
layers, a leading dense layer, then routed experts behind a sigmoid router with
a selection bias beside one shared expert), made as ``weights.py`` makes the
dense ones: normal(0, ``init_std``) matrices and unit norms in bfloat16, each
leaf from ``fold_in(key(seed), index of its path)``, one jitted call, any
subset bit-identical when made again alone. What is not a matrix is drawn as
the family draws it (the configuration file's ``assumed``): ``A_log = log U(1,
16)`` a head, ``dt_bias`` a channel so that ``softplus(dt_bias)`` is
log-uniform in [0.001, 0.1], the selection bias zeros. The router's columns of
each chip's share sum to zero (``weights_mla_moe.zero_sum_by_share``, by
import) and the token embeddings are drawn at ``embed_std``, both as in the
other expert cells and for their reasons.

The tree is the chip's share the configuration file states: ``num_experts``
rows (``held_experts``) in the stacked expert leaves, ``vocab_size`` rows of
the vocabulary, the router ``router_experts`` wide; the first
``first_k_dense_replace`` layers hold a dense SwiGLU of ``intermediate_size``.
A KDA layer's leaves lie under ``linear_attn`` with ONE ``conv1d/weight [taps,
q | k | v channels]``: the program's layout
(``models/transformer._init_kda_attention``), HF's three convolutions joined
(``models/hf_io.py``).

Copied from ``weights_afmoe.py`` because they name its own ``leaf_shapes``
inside: ``_shape_items`` and ``make_flat`` (for a ``benchmark`` issue to fold:
``leaf_shapes`` and the keys as parameters of one maker).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.chipbench import weights
from benchmarks.chipbench.weights_mla_moe import INIT_STD, zero_sum_by_share

SHAPE_KEYS = (
    "hidden_size", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "intermediate_size", "moe_intermediate_size", "num_shared_experts", "first_k_dense_replace", "num_experts",
    "router_experts", "vocab_size", "num_hidden_layers",
)
BUFFER = "mlp/gate/e_score_correction_bias"
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 0.1)


def kda_layers(cfg: dict) -> tuple:
    """0-based indices of the Kimi Delta Attention layers up to the depth (``kda_layers`` is 1-based)."""
    return tuple(i for i in range(cfg["num_hidden_layers"]) if i + 1 in cfg["linear_attn_config"]["kda_layers"])


def leaf_shapes(cfg: dict) -> dict:
    """Flat ``{path: shape}`` of every leaf, in a fixed order."""
    h, nh, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f, fe, v, held = cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["vocab_size"], cfg["num_experts"]
    fs = fe * cfg["num_shared_experts"]
    lin = cfg["linear_attn_config"]
    heads, d, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    wide, kda = heads * d, kda_layers(cfg)
    shapes = {"model/embed_tokens/weight": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model/layers/{i}/"
        shapes[p + "input_layernorm/weight"] = (h,)
        if i in kda:
            for name in ("q_proj", "k_proj", "v_proj"):
                shapes[p + f"linear_attn/{name}/kernel"] = (h, wide)
            shapes[p + "linear_attn/conv1d/weight"] = (taps, 3 * wide)
            shapes[p + "linear_attn/b_proj/kernel"] = (h, heads)
            shapes[p + "linear_attn/f_a_proj/kernel"] = (h, d)
            shapes[p + "linear_attn/f_b_proj/kernel"] = (d, wide)
            shapes[p + "linear_attn/A_log"] = (heads,)
            shapes[p + "linear_attn/dt_bias"] = (wide,)
            shapes[p + "linear_attn/g_a_proj/kernel"] = (h, d)
            shapes[p + "linear_attn/g_b_proj/kernel"] = (d, wide)
            shapes[p + "linear_attn/norm/weight"] = (d,)
            shapes[p + "linear_attn/out_proj/kernel"] = (wide, h)
        else:
            shapes[p + "self_attn/q_proj/kernel"] = (h, nh * (dn + dr))
            shapes[p + "self_attn/kv_a_proj_with_mqa/kernel"] = (h, r + dr)
            shapes[p + "self_attn/kv_a_layernorm/weight"] = (r,)
            shapes[p + "self_attn/kv_b_proj/kernel"] = (r, nh * (dn + dv))
            shapes[p + "self_attn/o_proj/kernel"] = (nh * dv, h)
        shapes[p + "post_attention_layernorm/weight"] = (h,)
        if i < cfg["first_k_dense_replace"]:
            shapes[p + "mlp/gate_proj/kernel"] = (h, f)
            shapes[p + "mlp/up_proj/kernel"] = (h, f)
            shapes[p + "mlp/down_proj/kernel"] = (f, h)
        else:
            shapes[p + "mlp/gate/kernel"] = (h, cfg["router_experts"])
            shapes[p + BUFFER] = (cfg["router_experts"],)
            shapes[p + "mlp/experts/w1"] = (held, h, fe)
            shapes[p + "mlp/experts/w3"] = (held, h, fe)
            shapes[p + "mlp/experts/w2"] = (held, fe, h)
            shapes[p + "mlp/shared_experts/gate_proj/kernel"] = (h, fs)
            shapes[p + "mlp/shared_experts/up_proj/kernel"] = (h, fs)
            shapes[p + "mlp/shared_experts/down_proj/kernel"] = (fs, h)
    shapes["model/norm/weight"] = (h,)
    shapes["lm_head/kernel"] = (h, v)
    return shapes


def _make(key, cfg_items, only):
    cfg = dict(cfg_items)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"])
    out = {}
    for index, (path, shape) in enumerate(leaf_shapes(cfg).items()):
        if only is not None and path not in only:
            continue
        k = jax.random.fold_in(key, index)
        if path.endswith(BUFFER):
            leaf = jnp.zeros(shape, jnp.float32)
        elif path.endswith("A_log"):
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, *A_RANGE))
        elif path.endswith("dt_bias"):  # softplus^-1 of a log-uniform draw
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif len(shape) == 1:
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
    lin = cfg["linear_attn_config"]
    return tuple((k, cfg[k]) for k in SHAPE_KEYS) + (
        ("linear_attn_config", tuple((k, tuple(v) if isinstance(v, list) else v) for k, v in sorted(lin.items()))),
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
