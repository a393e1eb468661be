"""The plain reference of a Mellum-shaped decoder (Mellum2-12B-A2.5B):
grouped-query attention on window layers and global layers side by side, each
kind with its own rope, and in every layer routed experts behind a softmax
router with no shared expert; token-mean cross-entropy, the gradients of every
leaf, AdamW behind a global-norm clip (``reference.py``'s optimizer functions,
by import).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, one layer and one row of the batch at
a time, over the bfloat16 weights ``weights_swa_moe.py`` made from the seed.
The window is a mask on the whole ``[seq, seq]`` scores; every held expert is
applied to ALL tokens and its result kept under the expert's weight: no band,
no sort, no grouping, no kernel. It imports nothing of the program.

The layer equations (``h = RMSNorm(x)``, eps 1e-6, before each half of a block;
no bias anywhere):

  attention   q_i = rope(h W_q) (32 heads of 128), k_j = rope(h W_k), v_j = h W_v (4 heads; 8 queries a kv head);
              P_i = softmax over the keys j that query t sees of q_i k^T / sqrt(128); x += concat(P_i v) W_o
    window layer (``layer_types[l] == "sliding_attention"``): key s is seen by query t iff t - window < s <= t;
              rope is the default one: angle = position * theta^(-2i/128), halves rotated
    global layer (``"full_attention"``): key s is seen iff s <= t; rope is YaRN
              (``rope_parameters.full_attention``, HF ``_compute_yarn_parameters``):
              extrap_i = theta^(-2i/128), interp_i = extrap_i / factor,
              ramp_i = clip((i - low) / (high - low), 0, 1), low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
              c(r) = 128 ln(original / (2 pi r)) / (2 ln theta), both clamped to 0..127,
              inv_freq_i = interp_i ramp_i + extrap_i (1 - ramp_i); cos and sin times ``attention_factor``
  experts     p = softmax(h W_g) over ALL router_experts; S = the top k of p;
              g_e = p_e / sum_{j in S} p_j for e in S (``norm_topk_prob``);
              x += sum_{e in S, e held here} g_e W2_e (silu(W1_e h) * W3_e h)

Departures from the published model, each on purpose:

- The chip's share (the configuration file states it): only ``held_experts``
  of the ``router_experts`` are here; what the absent ones would add is left
  out and the partial result goes on. The normaliser of g runs over all k
  selected experts, held or not. The vocabulary is a slice: logits, loss and
  ids are over ``vocab_size`` rows. ``layer_types`` is read up to
  ``num_hidden_layers``.
- Not in the published config and therefore not here (``assumed`` in the
  configuration file): no per-head q/k norm, no auxiliary router loss, no
  multi-token-prediction head.
- Computed in blocks so that it fits: the query heads go through attention
  ``HEADS_A_BLOCK`` at a time, one block after another (``lax.map``), and a
  block's scores are made a second time in the backward pass instead of being
  held (``jax.checkpoint`` around one block: 32 heads' probabilities of one
  8192-token row are 8.6 GB). The same float32 arithmetic, twice; nothing is
  left out and nothing approximated.
- Masters of the trainable leaves are bfloat16 between steps, as the recipe
  states (``param_dtype``): the update is computed in float32 and the sum
  rounded once.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chipbench.reference import (
    F32, _adam_apply, _add, _delta_sq, _embed, _embed_grad, _highest, _rotate, _scale, _sq_norm,
    layer_leaves, lr_at, rms_norm,
)
from benchmarks.chipbench.reference_mla_moe import _head_loss_grad, _logits, swiglu

CFG_KEYS = (
    "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
    "rms_norm_eps", "router_experts", "sliding_window",
)
HEADS_A_BLOCK = 4


def cfg_items(cfg: dict):
    """What a layer's function reads of the configuration, hashable."""
    ropes = cfg["rope_parameters"]
    return tuple((k, cfg[k]) for k in CFG_KEYS) + (
        ("held_experts", tuple(cfg["held_experts"])),
        ("rope", tuple((kind, tuple(sorted(ropes[kind].items()))) for kind in sorted(ropes))),
    )


def layer_kind(cfg: dict, layer: int) -> str:
    return cfg["layer_types"][layer]


def inv_freq(rope: dict, head_dim: int):
    """``(inv_freq [head_dim / 2], factor on cos and sin)`` of one layer
    type's ``rope_parameters`` entry: ``default`` or ``yarn``."""
    theta, half = float(rope["rope_theta"]), head_dim // 2
    extrap = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    if rope["rope_type"] == "default":
        return extrap, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"the reference knows default and yarn rope, not {rope['rope_type']!r}")
    factor, original = float(rope["factor"]), float(rope["original_max_position_embeddings"])

    def dim_of(rotations):
        return head_dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rope["beta_slow"]))), head_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low) / max(high - low, 0.001), 0.0, 1.0)
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return extrap / factor * ramp + extrap * (1.0 - ramp), float(attention_factor)


def rope_tables(rope: dict, t: int, head_dim: int):
    inv, factor = inv_freq(rope, head_dim)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def seen(t: int, window):
    """[t, t] bool: query row sees key column."""
    gap = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    return (gap >= 0) if window is None else (gap >= 0) & (gap < window)


def attention(w, x, cfg, kind: str):
    """``x [rows, seq, hidden]`` float32 -> x + attention(RMSNorm(x)) of a
    layer of ``kind`` (``sliding_attention`` | ``full_attention``)."""
    b, t, _ = x.shape
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    h = rms_norm(x, w["input_layernorm/weight"], cfg["rms_norm_eps"])
    q = (h @ w["self_attn/q_proj/kernel"]).reshape(b, t, nh, d)
    k = (h @ w["self_attn/k_proj/kernel"]).reshape(b, t, nkv, d)
    v = (h @ w["self_attn/v_proj/kernel"]).reshape(b, t, nkv, d)
    cos, sin = rope_tables(dict(dict(cfg["rope"])[kind]), t, d)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    mask = seen(t, cfg["sliding_window"] if kind == "sliding_attention" else None)

    @jax.checkpoint
    def heads(q_blk, k_head, v_head):
        """[rows, seq, block, d] queries of one kv head against its k, v [rows, seq, d]."""
        scores = jnp.einsum("bthd,bsd->bhts", q_blk, k_head) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bsd->bthd", probs, v_head)

    blk = min(HEADS_A_BLOCK, nh // nkv)
    q_blocks = q.reshape(b, t, nh // blk, blk, d).transpose(2, 0, 1, 3, 4)
    kv_of = jnp.arange(nh // blk) * blk // (nh // nkv)
    out = jax.lax.map(lambda a: heads(a[0], k[:, :, a[1]], v[:, :, a[1]]), (q_blocks, kv_of))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, t, nh * d)
    return x + out @ w["self_attn/o_proj/kernel"]


def router(w, h, cfg):
    """Probabilities, the 0/1 selection and the combine weights, each
    ``[..., router_experts]``: ``g`` is zero outside the selection."""
    p = jax.nn.softmax(h @ w["mlp/gate/kernel"], axis=-1)
    _, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    selected = jax.nn.one_hot(chosen, cfg["router_experts"], dtype=F32).sum(-2)
    return p, selected, p * selected / (p * selected).sum(-1, keepdims=True)


def experts(w, h, cfg, held=None):
    """Routed part for the experts ``held`` (default: the configuration's
    share), each applied to every token and kept under its weight, which is
    zero where it was not selected."""
    _, _, g = router(w, h, cfg)
    y = jnp.zeros_like(h)
    for row, expert in enumerate(cfg["held_experts"]):
        if held is None or expert in held:
            y = y + g[..., expert, None] * swiglu(h, w["mlp/experts/w1"][row], w["mlp/experts/w3"][row],
                                                  w["mlp/experts/w2"][row])
    return y


def layer_fn(lp, x, cfg, kind: str):
    """One block. ``lp``: the layer's leaves by their path below the layer."""
    w = {k: v.astype(F32) for k, v in lp.items()}
    x = attention(w, x, cfg, kind)
    return x + experts(w, rms_norm(x, w["post_attention_layernorm/weight"], cfg["rms_norm_eps"]), cfg)


@partial(jax.jit, static_argnums=(2, 3))
@_highest
def _layer_fwd(lp, x, items, kind):
    return layer_fn(lp, x, dict(items), kind)


@partial(jax.jit, static_argnums=(3, 4))
@_highest
def _layer_bwd(lp, x, dy, items, kind):
    """Gradients to the block's leaves (float32, taken at the bfloat16
    values) and to its input."""
    lp32 = {k: v.astype(F32) for k, v in lp.items()}
    _, vjp = jax.vjp(lambda ww, xx: layer_fn(ww, xx, dict(items), kind), lp32, x)
    return vjp(dy)


@partial(jax.jit, static_argnums=(2, 3))
@_highest
def _selection(lp, x, items, kind):
    """The layer's 0/1 selection ``[rows, seq, router_experts]``."""
    cfg = dict(items)
    w = {k: v.astype(F32) for k, v in lp.items()}
    h = rms_norm(attention(w, x, cfg, kind), w["post_attention_layernorm/weight"], cfg["rms_norm_eps"])
    return router(w, h, cfg)[1]


def forward_hidden(flat: dict, cfg: dict, ids):
    """Final hidden states (before the final norm) and every block's input."""
    items = cfg_items(cfg)
    x = _embed(flat["model/embed_tokens/weight"], jnp.asarray(ids, jnp.int32))
    inputs = []
    for i in range(cfg["num_hidden_layers"]):
        inputs.append(x)
        x = _layer_fwd(layer_leaves(flat, i), x, items, layer_kind(cfg, i))
    return x, inputs


def logits(flat: dict, cfg: dict, ids):
    x, _ = forward_hidden(flat, cfg, ids)
    return _logits(x, flat["model/norm/weight"], flat["lm_head/kernel"], cfg["rms_norm_eps"])


def selections(flat: dict, cfg: dict, ids) -> dict:
    """{layer: 0/1 selection [rows, seq, router_experts]}."""
    _, inputs = forward_hidden(flat, cfg, ids)
    items = cfg_items(cfg)
    return {i: _selection(layer_leaves(flat, i), inputs[i], items, layer_kind(cfg, i))
            for i in range(cfg["num_hidden_layers"])}


def rows_grads(flat: dict, cfg: dict, ids, scale: float, into=None):
    """``scale`` x the token-mean loss of ``ids [rows, seq]`` and its
    gradients of every leaf (float32), added to ``into``."""
    items = cfg_items(cfg)
    ids = jnp.asarray(ids, jnp.int32)
    x, inputs = forward_hidden(flat, cfg, ids)
    loss, (dx, dnorm, dhead) = _head_loss_grad(
        x, flat["model/norm/weight"], flat["lm_head/kernel"], ids, scale, cfg["rms_norm_eps"]
    )
    grads = {} if into is None else into

    def give(path, g):
        grads[path] = _add(grads[path], g) if path in grads else g

    give("model/norm/weight", dnorm)
    give("lm_head/kernel", dhead)
    for i in range(cfg["num_hidden_layers"] - 1, -1, -1):
        dlp, dx = _layer_bwd(layer_leaves(flat, i), inputs[i], dx, items, layer_kind(cfg, i))
        inputs[i] = None
        for k, g in dlp.items():
            give(f"model/layers/{i}/{k}", g)
    table = "model/embed_tokens/weight"
    grads[table] = _embed_grad(grads.get(table, jnp.zeros(flat[table].shape, F32)), ids, dx)
    return loss, grads


def sft_reference(flat: dict, cfg: dict, recipe: dict, batches, fresh_leaves, keep_first_grad=False) -> dict:
    """``reference_mla_moe.sft_reference`` for this architecture (every leaf
    trains; there is no buffer): each step's loss, the first gradient's norm
    before the clip, its norm by leaf after the clip, and the norm by leaf of
    the parameters' change. ``batches``: one [accum, rows, seq] int array a
    step; rows go through one at a time (full rows of one length: the mean of
    the row means is the step's token mean)."""
    if recipe.get("optimizer", "adamw") != "adamw" or recipe.get("weight_decay", 0.0):
        raise ValueError("the reference knows AdamW without weight decay")
    flat = dict(flat)
    train = sorted(flat)
    b1, b2, eps = float(recipe["adam_b1"]), float(recipe["adam_b2"]), float(recipe["adam_eps"])
    max_norm = float(recipe["max_grad_norm"])
    history = []
    out = {"losses": []}
    for step, batch in enumerate(batches):
        rows = np.asarray(batch).reshape(-1, np.asarray(batch).shape[-1])
        total, loss_sum = None, 0.0
        for row in rows:
            loss, total = rows_grads(flat, cfg, row[None, :], 1.0 / len(rows), into=total)
            loss_sum += float(loss)
        out["losses"].append(loss_sum)
        gnorm = math.sqrt(sum(float(_sq_norm(g)) for g in total.values()))
        clip = 1.0 if gnorm < max_norm else max_norm / gnorm
        total = {k: _scale(g, clip) for k, g in total.items()}
        if step == 0:
            out["grad_norm"] = gnorm
            out["first_grad_norms"] = {k: math.sqrt(float(_sq_norm(g))) for k, g in total.items()}
            if keep_first_grad:  # whole, on the host, for the error by leaf
                out["first_grad"] = {k: np.asarray(g) for k, g in total.items()}
        history.append(total)
        lr_t = lr_at(recipe, step)
        for k in train:
            flat[k] = _adam_apply(flat[k], [h[k] for h in history], b1, b2, eps, lr_t)
    del history, total
    out["delta_norms"] = {}
    for k in train:  # one leaf of the seed's weights at a time
        p0 = fresh_leaves([k])[k]
        out["delta_norms"][k] = math.sqrt(float(_delta_sq(flat.pop(k), p0)))
    return out
