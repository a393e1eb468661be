"""Seeded weights, made on the device by the benchmark and by nobody else.

The tree has the layout the program serves and trains (nested dicts whose
paths mirror the HF checkpoint names, linear kernels as ``[in, out]``), but
every value comes from here: normal(0, 0.02) matrices and unit norms in
bfloat16, each leaf from ``fold_in(key(seed), index of its path)``, so that
any subset (the trainable leaves, say) can be made again alone and comes out
bit-identical. One jitted call makes the whole tree; the reference and the
program are both handed copies of it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def leaf_shapes(cfg: dict) -> dict:
    """Flat ``{path: shape}`` of every leaf, in a fixed order."""
    h = cfg["hidden_size"]
    d = cfg["head_dim"]
    qd, kvd = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    shapes = {"model/embed_tokens/weight": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model/layers/{i}/"
        shapes[p + "input_layernorm/weight"] = (h,)
        shapes[p + "self_attn/q_proj/kernel"] = (h, qd)
        shapes[p + "self_attn/k_proj/kernel"] = (h, kvd)
        shapes[p + "self_attn/v_proj/kernel"] = (h, kvd)
        shapes[p + "self_attn/o_proj/kernel"] = (qd, h)
        shapes[p + "post_attention_layernorm/weight"] = (h,)
        shapes[p + "mlp/gate_proj/kernel"] = (h, f)
        shapes[p + "mlp/up_proj/kernel"] = (h, f)
        shapes[p + "mlp/down_proj/kernel"] = (f, h)
    shapes["model/norm/weight"] = (h,)
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head/kernel"] = (h, v)
    return shapes


def seed_key(seed: int):
    """A key for any whole number: ``--seed`` may pass 2**31."""
    seed = int(seed)
    key = jax.random.key(seed & 0x3FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 30) & 0x3FFFFFFF)


def _make(key, cfg_items, only):
    cfg = dict(cfg_items)
    out = {}
    for index, (path, shape) in enumerate(leaf_shapes(cfg).items()):
        if only is not None and path not in only:
            continue
        if len(shape) == 1:
            out[path] = jnp.ones(shape, jnp.bfloat16)
        else:
            k = jax.random.fold_in(key, index)
            out[path] = (
                jax.random.normal(k, shape, jnp.float32) * cfg.get("init_std", INIT_STD)
            ).astype(jnp.bfloat16)
    return out


_programs = []


def _shape_items(cfg: dict):
    keys = (
        "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "vocab_size", "num_hidden_layers",
        "tie_word_embeddings",
    )
    return tuple((k, cfg[k]) for k in keys) + (("init_std", float(cfg.get("init_std", INIT_STD))),)


def make_flat(seed: int, cfg: dict, only=None, shardings=None) -> dict:
    """Flat ``{path: bf16 array}`` on the device, one jitted call. With
    ``shardings`` (``{path: Sharding}``) each leaf is made where the program
    wants it, so that no copy follows."""
    only = None if only is None else tuple(sorted(only))
    out_shardings = None
    if shardings is not None:
        out_shardings = {k: shardings[k] for k in leaf_shapes(cfg) if only is None or k in only}
    fn = jax.jit(_make, static_argnums=(1, 2), out_shardings=out_shardings)
    _programs.append(fn)
    return fn(seed_key(seed), _shape_items(cfg), only)


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return tree


def drop_programs() -> None:
    """Unload the programs that made the weights: the chip keeps a loaded
    program's scratch reserved, and the cells fill the memory."""
    while _programs:
        _programs.pop().clear_cache()
