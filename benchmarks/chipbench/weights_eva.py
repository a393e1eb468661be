"""Seeded weights of an ``evabyte`` configuration (EvaByte/EvaByte), made on the
device as ``weights.py`` makes the dense ones: each leaf from ``fold_in(key(seed),
index of its path)``, so that any subset comes out bit-identical alone.

What differs from ``weights.py``: two leaves a layer for EVA attention,
``self_attn/adaptive_phi`` and ``self_attn/adaptive_mu_k`` ``[heads, head_dim]``,
drawn ``clip(normal, -1, 1) * head_dim ** -0.5`` as the family draws them; the
head ``[hidden, num_pred_heads x vocab]`` (head i's vocabulary at columns ``[i x
vocab, (i + 1) x vocab)``); norms at 0, which under the unit offset
(``norm_add_unit_offset``) is the multiplier 1; matrices normal at the
configuration's own ``init_std`` (0.01275).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chipbench import weights
from benchmarks.chipbench.weights import drop_programs, nest, seed_key  # noqa: F401  (one module a kind asks)

EVA_LEAVES = ("self_attn/adaptive_phi", "self_attn/adaptive_mu_k")


def leaf_shapes(cfg: dict) -> dict:
    """Flat ``{path: shape}`` of every leaf, in a fixed order: the dense tree's, a layer's two EVA leaves after its
    ``o_proj``, the head as wide as its ``num_pred_heads``."""
    shapes = {}
    heads_by_d = (cfg["num_attention_heads"], cfg["head_dim"])
    for path, shape in weights.leaf_shapes(cfg).items():
        shapes[path] = shape
        if path.endswith("self_attn/o_proj/kernel"):
            for leaf in EVA_LEAVES:
                shapes[path[: -len("self_attn/o_proj/kernel")] + leaf] = heads_by_d
    shapes["lm_head/kernel"] = (cfg["hidden_size"], cfg["num_pred_heads"] * cfg["vocab_size"])
    return shapes


def _shape_items(cfg: dict):
    return weights._shape_items(cfg) + (("num_pred_heads", cfg["num_pred_heads"]),)


def _make(key, cfg_items, only):
    cfg = dict(cfg_items)
    out = {}
    for index, (path, shape) in enumerate(leaf_shapes(cfg).items()):
        if only is not None and path not in only:
            continue
        k = jax.random.fold_in(key, index)
        if len(shape) == 1:
            out[path] = jnp.zeros(shape, jnp.bfloat16)
        elif path.endswith(EVA_LEAVES):
            out[path] = (jnp.clip(jax.random.normal(k, shape, jnp.float32), -1.0, 1.0) * cfg["head_dim"] ** -0.5).astype(jnp.bfloat16)
        else:
            out[path] = (jax.random.normal(k, shape, jnp.float32) * cfg["init_std"]).astype(jnp.bfloat16)
    return out


def make_flat(seed: int, cfg: dict, only=None, shardings=None) -> dict:
    """``weights.make_flat`` over this tree."""
    only = None if only is None else tuple(sorted(only))
    out_shardings = None
    if shardings is not None:
        out_shardings = {k: shardings[k] for k in leaf_shapes(cfg) if only is None or k in only}
    fn = jax.jit(_make, static_argnums=(1, 2), out_shardings=out_shardings)
    weights._programs.append(fn)
    return fn(seed_key(seed), _shape_items(cfg), only)
