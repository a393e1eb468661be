"""Seeded weights of a ``granitemoehybrid`` configuration (IBM Granite 4.0-H), made on the device as ``weights.py``
makes the dense ones: each leaf from ``fold_in(key(seed), index of its path)``, so that any subset comes out
bit-identical alone.

What differs from ``weights.py``: a layer is a Mamba-2 layer or an attention layer by the configuration's
``layer_types``. A Mamba-2 layer's mixer is the subtree ``mamba``: ``in_proj`` ``[hidden, z | x | B | C | dt]``, the
convolution ``conv1d/weight [taps, x | B | C channels]`` with its ``bias``, ``A_log``, ``D`` and ``dt_bias`` a head,
the gated norm's weight over the inner width, ``out_proj``. Drawn as the family draws them: ``A_log_h = log(h + 1)``,
``D = 1``, ``dt_bias = softplus^-1`` of a log-uniform draw in [0.001, 0.1], the convolution's taps and bias uniform in
``+- taps ** -0.5`` (torch's ``Conv1d`` default, which the family's initializer leaves as it is), norms 1, every
matrix normal at the configuration's ``initializer_range``. The table is tied: no ``lm_head``.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp

from benchmarks.chipbench import weights
from benchmarks.chipbench.weights import drop_programs, nest, seed_key  # noqa: F401  (one module a kind asks)

INITIALIZER_RANGE = 0.02


def sizes(cfg: dict) -> dict:
    heads, p, n, groups = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_n_groups"]
    return {"heads": heads, "p": p, "n": n, "groups": groups, "inner": heads * p, "bc": 2 * groups * n, "taps": cfg["mamba_d_conv"]}


def leaf_shapes(cfg: dict) -> dict:
    """Flat ``{path: shape}`` of every leaf, in a fixed order."""
    h, d, f = cfg["hidden_size"], cfg["head_dim"], cfg["shared_intermediate_size"]
    qd, kvd = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    m = sizes(cfg)
    shapes = {"model/embed_tokens/weight": (cfg["vocab_size"], h)}
    for i, kind in enumerate(cfg["layer_types"][: cfg["num_hidden_layers"]]):
        p = f"model/layers/{i}/"
        shapes[p + "input_layernorm/weight"] = (h,)
        if kind == "mamba":
            shapes[p + "mamba/in_proj/kernel"] = (h, 2 * m["inner"] + m["bc"] + m["heads"])
            shapes[p + "mamba/conv1d/weight"] = (m["taps"], m["inner"] + m["bc"])
            shapes[p + "mamba/conv1d/bias"] = (m["inner"] + m["bc"],)
            shapes[p + "mamba/A_log"] = (m["heads"],)
            shapes[p + "mamba/D"] = (m["heads"],)
            shapes[p + "mamba/dt_bias"] = (m["heads"],)
            shapes[p + "mamba/norm/weight"] = (m["inner"],)
            shapes[p + "mamba/out_proj/kernel"] = (m["inner"], h)
        else:
            shapes[p + "self_attn/q_proj/kernel"] = (h, qd)
            shapes[p + "self_attn/k_proj/kernel"] = (h, kvd)
            shapes[p + "self_attn/v_proj/kernel"] = (h, kvd)
            shapes[p + "self_attn/o_proj/kernel"] = (qd, h)
        shapes[p + "post_attention_layernorm/weight"] = (h,)
        shapes[p + "mlp/gate_proj/kernel"] = (h, f)
        shapes[p + "mlp/up_proj/kernel"] = (h, f)
        shapes[p + "mlp/down_proj/kernel"] = (f, h)
    shapes["model/norm/weight"] = (h,)
    return shapes


def _leaf(key, index, path: str, shape, taps: int, std: float):
    """One leaf from ``fold_in(key, index of its path in leaf_shapes)``; ``index`` may be traced."""
    k = jax.random.fold_in(key, index)
    if path.endswith("mamba/A_log"):
        leaf = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    elif path.endswith("mamba/dt_bias"):
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
        leaf = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    elif "mamba/conv1d/" in path:
        leaf = jax.random.uniform(k, shape, jnp.float32, -taps ** -0.5, taps ** -0.5)
    elif len(shape) == 1:  # norms, D
        leaf = jnp.ones(shape, jnp.float32)
    else:
        leaf = jax.random.normal(k, shape, jnp.float32) * std
    return leaf.astype(jnp.bfloat16)


def _make_group(key, first, leaves, taps, std):
    """A group's leaves (``leaves``: ``(name inside the group, shape)`` in ``leaf_shapes``' order, None for one left
    out), the group's first leaf at index ``first`` (traced: one program serves every layer of a kind)."""
    return {leaf[0]: _leaf(key, first + at, *leaf, taps, std) for at, leaf in enumerate(leaves) if leaf is not None}


def make_flat(seed: int, cfg: dict, only=None, shardings=None) -> dict:
    """``weights.make_flat`` over this tree, a GROUP of leaves a call: a layer, the table, the final norm. Layers of one
    kind share one jitted program (the group's first index is an argument), so the model's 506 leaves compile as
    four small programs (a Mamba-2 layer, an attention layer, the table, the final norm) and not as one that took
    166 s on the chip and missed the machine's compile cache (PERF.md, PR 49). Each leaf is what the one call made."""
    only = None if only is None else frozenset(only)
    key, taps, std = seed_key(seed), int(cfg["mamba_d_conv"]), float(cfg.get("initializer_range", INITIALIZER_RANGE))
    groups: dict = {}  # prefix -> [(index, path, shape)], in order
    for index, (path, shape) in enumerate(leaf_shapes(cfg).items()):
        prefix = re.match(r"model/layers/\d+/|", path).group()
        groups.setdefault(prefix or path, []).append((index, path, shape))
    programs, out = {}, {}
    for prefix, members in groups.items():
        cut = len(prefix) if prefix.endswith("/") else 0
        wanted = [only is None or path in only for _, path, _ in members]
        if not any(wanted):
            continue
        leaves = tuple((path[cut:], shape) if w else None for (_, path, shape), w in zip(members, wanted))
        placed = None if shardings is None else tuple(shardings[path] for (_, path, _), w in zip(members, wanted) if w)
        if (leaves, placed) not in programs:
            out_shardings = None if placed is None else dict(zip((leaf[0] for leaf in leaves if leaf), placed))
            programs[leaves, placed] = jax.jit(_make_group, static_argnums=(2, 3, 4), out_shardings=out_shardings)
            weights._programs.append(programs[leaves, placed])  # weights.drop_programs() unloads these too
        made = programs[leaves, placed](key, jnp.int32(members[0][0]), leaves, taps, std)
        out.update({prefix[:cut] + name: leaf for name, leaf in made.items()})
    return dict(sorted(out.items()))  # (the order one jitted call gave its dict)
