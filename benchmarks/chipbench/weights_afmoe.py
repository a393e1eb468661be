"""Seeded weights of an ``afmoe``-shaped configuration (arcee-ai Trinity-Mini:
gated grouped-query attention with per-head q/k norms on window and global
layers, four norms a block, leading dense layers, then routed experts behind a
sigmoid router with a selection bias beside one shared expert), made as
``weights.py`` makes the dense ones: normal(0, ``init_std``) matrices and unit
norms in bfloat16, each leaf from ``fold_in(key(seed), index of its path)``,
one jitted call, any subset bit-identical when made again alone. The router's
columns of each chip's share sum to zero (``weights_mla_moe.zero_sum_by_share``,
by import) and the token embeddings are drawn at ``embed_std``, both as in the
other expert cells and for their reasons; the selection bias (``expert_bias``,
the tree's ``mlp/gate/e_score_correction_bias``) is zeros, as the family draws
it (the configuration file's ``assumed`` gives the counts and the reasons).

The tree is the chip's share the configuration file states: ``num_experts``
rows (``held_experts``) in the stacked expert leaves, ``vocab_size`` rows of
the vocabulary, the router ``router_experts`` wide; the first
``num_dense_layers`` layers hold a dense SwiGLU of ``intermediate_size``.
``self_attn/q_proj/kernel`` holds ``[q | gate]`` by head: the program's layout
(``models/transformer._init_heads_attention``), HF's ``q_proj`` and
``gate_proj`` joined (``models/hf_io.py``).

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
    "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "intermediate_size",
    "moe_intermediate_size", "num_shared_experts", "num_dense_layers", "num_experts", "router_experts", "vocab_size",
    "num_hidden_layers",
)
BUFFER = "mlp/gate/e_score_correction_bias"


def leaf_shapes(cfg: dict) -> dict:
    """Flat ``{path: shape}`` of every leaf, in a fixed order."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    qd, kvd = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    f, fe, v, held = cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["vocab_size"], cfg["num_experts"]
    fs = fe * cfg["num_shared_experts"]
    shapes = {"model/embed_tokens/weight": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model/layers/{i}/"
        shapes[p + "input_layernorm/weight"] = (h,)
        shapes[p + "self_attn/q_proj/kernel"] = (h, 2 * qd)
        shapes[p + "self_attn/k_proj/kernel"] = (h, kvd)
        shapes[p + "self_attn/v_proj/kernel"] = (h, kvd)
        shapes[p + "self_attn/o_proj/kernel"] = (qd, h)
        shapes[p + "self_attn/q_norm/weight"] = (d,)
        shapes[p + "self_attn/k_norm/weight"] = (d,)
        shapes[p + "post_attention_layernorm/weight"] = (h,)
        shapes[p + "pre_feedforward_layernorm/weight"] = (h,)
        shapes[p + "post_feedforward_layernorm/weight"] = (h,)
        if i < cfg["num_dense_layers"]:
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
    out = {}
    for index, (path, shape) in enumerate(leaf_shapes(cfg).items()):
        if only is not None and path not in only:
            continue
        if path.endswith(BUFFER):
            out[path] = jnp.zeros(shape, jnp.bfloat16)
        elif len(shape) == 1:
            out[path] = jnp.ones(shape, jnp.bfloat16)
        else:
            std = cfg["embed_std"] if path == "model/embed_tokens/weight" else cfg["init_std"]
            leaf = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32) * std
            if path.endswith("mlp/gate/kernel"):
                leaf = zero_sum_by_share(leaf, cfg["num_experts"])
            out[path] = leaf.astype(jnp.bfloat16)
    return out


def _shape_items(cfg: dict):
    init_std = float(cfg.get("init_std", INIT_STD))
    return tuple((k, cfg[k]) for k in SHAPE_KEYS) + (
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
