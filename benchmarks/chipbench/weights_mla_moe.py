"""Seeded weights of a latent-attention, routed-experts configuration
(DeepSeek-V3 layout: Moonlight-16B-A3B), made as ``weights.py`` makes the
dense ones: normal(0, ``init_std``) matrices and unit norms in bfloat16, each
leaf from ``fold_in(key(seed), index of its path)``, one jitted call, any
subset bit-identical when made again alone. The router's selection bias
(``e_score_correction_bias``, all zeros in a fresh model) is a fixed pattern of
standard deviation ``router_bias_std`` (``router_bias``), so that the selection
differs from a plain top-k of the scores, and the router's columns of each
chip's share sum to zero (``zero_sum_by_share``), so that the seed moves the
load of a share as little as it can.
The tree is the chip's share the configuration file states: ``held_experts`` rows in the stacked expert leaves, ``vocab_size`` rows of the
vocabulary, the router ``router_experts`` wide.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chipbench import weights

INIT_STD = 0.02
ROUTER_BIAS_STD = 0.02
SHAPE_KEYS = (
    "hidden_size", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "intermediate_size", "moe_intermediate_size", "n_shared_experts", "n_routed_experts",
    "router_experts", "first_k_dense_replace", "vocab_size", "num_hidden_layers",
)


def leaf_shapes(cfg: dict) -> dict:
    """Flat ``{path: shape}`` of every leaf, in a fixed order."""
    h, nh, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f, fe, v = cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["vocab_size"]
    held, fs = cfg["n_routed_experts"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    shapes = {"model/embed_tokens/weight": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model/layers/{i}/"
        shapes[p + "input_layernorm/weight"] = (h,)
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
            shapes[p + "mlp/gate/e_score_correction_bias"] = (cfg["router_experts"],)
            shapes[p + "mlp/experts/w1"] = (held, h, fe)
            shapes[p + "mlp/experts/w3"] = (held, h, fe)
            shapes[p + "mlp/experts/w2"] = (held, fe, h)
            shapes[p + "mlp/shared_experts/gate_proj/kernel"] = (h, fs)
            shapes[p + "mlp/shared_experts/up_proj/kernel"] = (h, fs)
            shapes[p + "mlp/shared_experts/down_proj/kernel"] = (fs, h)
    shapes["model/norm/weight"] = (h,)
    shapes["lm_head/kernel"] = (h, v)
    return shapes


def router_bias(n_experts: int, std: float):
    """Four values in turn, zero mean over any four experts in a row: every
    chip's share of the experts (a multiple of four) has the same expected
    load, whatever the seed. Drawn from the seed instead, a bias of 0.02 moved
    the pairs held here by +-8% from seed to seed and ``train_tokens_per_s``
    by +-0.5% with them, more than the cell's bound admits (PERF.md, PR 26)."""
    return std * ((jnp.arange(n_experts) % 4) - 1.5) / (1.25 ** 0.5)


def zero_sum_by_share(router, share: int):
    """The router's columns, drawn from the seed, with the mean column of each
    chip's share (``share`` experts in a row) taken out and the spread put
    back: whatever direction the hidden states have in common then moves the
    scores of a share's experts by amounts that sum to zero. Left as drawn, the
    pairs held here differed by 4% from seed to seed (CPU count at the real
    widths, 0.65 to 0.85 a token by layer) and ``train_tokens_per_s`` by 0.26%
    with them, which two sets of six runs do not fit under half the bound."""
    if share < 2 or router.shape[-1] % share:
        return router
    groups = router.reshape(router.shape[0], -1, share)
    groups = (groups - groups.mean(-1, keepdims=True)) * (share / (share - 1)) ** 0.5
    return groups.reshape(router.shape)


def _make(key, cfg_items, only):
    cfg = dict(cfg_items)
    out = {}
    for index, (path, shape) in enumerate(leaf_shapes(cfg).items()):
        if only is not None and path not in only:
            continue
        if path.endswith("e_score_correction_bias"):
            out[path] = router_bias(shape[0], cfg["router_bias_std"]).astype(jnp.bfloat16)
        elif len(shape) == 1:
            out[path] = jnp.ones(shape, jnp.bfloat16)
        else:
            k = jax.random.fold_in(key, index)
            leaf = jax.random.normal(k, shape, jnp.float32) * cfg["init_std"]
            if path.endswith("mlp/gate/kernel"):
                leaf = zero_sum_by_share(leaf, cfg["n_routed_experts"])
            out[path] = leaf.astype(jnp.bfloat16)
    return out


def _shape_items(cfg: dict):
    return tuple((k, cfg[k]) for k in SHAPE_KEYS) + (
        ("init_std", float(cfg.get("init_std", INIT_STD))),
        ("router_bias_std", float(cfg.get("router_bias_std", ROUTER_BIAS_STD))),
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
