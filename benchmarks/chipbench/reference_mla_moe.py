"""The plain reference of a DeepSeek-V3-shaped decoder (Moonlight-16B-A3B):
latent attention, one leading dense SwiGLU layer, then layers of routed
experts behind a sigmoid router with a selection bias, beside shared experts;
token-mean cross-entropy, the gradients of every trainable leaf, AdamW behind
a global-norm clip (``reference.py``'s optimizer functions, by import).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, one layer and one row of the batch at
a time (a row's [heads, seq, seq] scores are 1 GiB at 4096), over the bfloat16
weights ``weights_mla_moe.py`` made from the seed. Every held expert is
applied to ALL tokens and its result kept under the expert's mask: no sort, no
grouping, no kernel. It imports nothing of the program.

The layer equations (``h = RMSNorm(x)`` before each half of a block):

  attention   c = h W_kva (kv_lora_rank + qk_rope_head_dim); c_kv = RMSNorm(c[:rank]);
              k_pe = rope(c[rank:]), one for all heads; [k_nope_i | v_i] = c_kv W_kvb;
              q_i = h W_q = [q_nope_i | q_pe_i], q_pe_i roped; k_i = [k_nope_i | k_pe];
              P_i = softmax_causal(q_i k_i^T / sqrt(qk_nope + qk_rope)); x += concat(P_i v_i) W_o
  dense MLP   x += W_down (silu(W_gate h) * W_up h)                      (layers below first_k_dense_replace)
  experts     s = sigmoid(h W_g) over ALL router_experts; S = top-k of s + b (b: a buffer, selects only);
              g_e = routed_scaling_factor * s_e / (sum_{j in S} s_j + 1e-20) for e in S;
              x += sum_{e in S, e held here} g_e E_e(h) + Shared(h)

Departures from the published model, each on purpose:

- The chip's share (the configuration file states it): only ``held_experts``
  of the ``router_experts`` are here; what the absent ones would add is left
  out and the partial result goes on. The normaliser of g runs over all k
  selected experts, held or not. The vocabulary is a slice: logits, loss and
  ids are over ``vocab_size`` rows.
- Rope rotates halves. DeepSeek's checkpoints store each rotated pair adjacent
  and HF's attention de-interleaves q_pe and k_pe before rotating halves; the
  program's ``models/hf_io.py`` applies that permutation to the two
  projections that make rope dimensions when it loads a checkpoint. With
  seeded random weights the two layouts are the same distribution.
- The latent's RMSNorm uses the model's ``rms_norm_eps`` (1e-5), as ISSUE 26
  writes the equations; HF builds that one norm with its class default 1e-6.
- No auxiliary loss: with ``topk_method`` noaux_tc HF's DeepseekV3 computes
  none (``aux_loss_alpha`` is listed under ``assumed`` as 0).
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
    layer_leaves, lr_at, rms_norm, rope_tables,
)

CFG_KEYS = (
    "hidden_size", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "num_experts_per_tok", "routed_scaling_factor", "rope_theta", "rms_norm_eps",
    "first_k_dense_replace", "router_experts",
)
BUFFER = "e_score_correction_bias"


def cfg_items(cfg: dict):
    return tuple((k, cfg[k]) for k in CFG_KEYS) + (("held_experts", tuple(cfg["held_experts"])),)


def attention(w, x, cfg):
    """``x [rows, seq, hidden]`` float32 -> x + attention(RMSNorm(x))."""
    b, t, _ = x.shape
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, w["input_layernorm/weight"], eps)
    q = (h @ w["self_attn/q_proj/kernel"]).reshape(b, t, nh, dn + dr)
    c = h @ w["self_attn/kv_a_proj_with_mqa/kernel"]
    c_kv = rms_norm(c[..., :r], w["self_attn/kv_a_layernorm/weight"], eps)
    kv = (c_kv @ w["self_attn/kv_b_proj/kernel"]).reshape(b, t, nh, dn + dv)
    cos, sin = rope_tables(jnp.arange(t), dr, cfg["rope_theta"])
    q_pe = _rotate(q[..., dn:], cos, sin)
    k_pe = _rotate(c[..., r:].reshape(b, t, 1, dr), cos, sin)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (b, t, nh, dr))], axis=-1)
    v = kv[..., dn:]
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(dn + dr)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, v).reshape(b, t, nh * dv)
    return x + out @ w["self_attn/o_proj/kernel"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def router(w, h, cfg):
    """Scores, the 0/1 selection and the combine weights, each
    ``[..., router_experts]``: ``g`` is zero outside the selection."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ w["mlp/gate/kernel"])
    _, chosen = jax.lax.top_k(s + w["mlp/gate/" + BUFFER], k)
    selected = jax.nn.one_hot(chosen, cfg["router_experts"], dtype=F32).sum(-2)
    denom = (s * selected).sum(-1, keepdims=True) + 1e-20
    return s, selected, cfg["routed_scaling_factor"] * s * selected / denom


def experts(w, h, cfg):
    """Routed part for the experts held here (each applied to every token,
    kept under its weight, which is zero where it was not selected) plus the
    shared experts."""
    _, _, g = router(w, h, cfg)
    y = swiglu(h, w["mlp/shared_experts/gate_proj/kernel"], w["mlp/shared_experts/up_proj/kernel"],
               w["mlp/shared_experts/down_proj/kernel"])
    for row, expert in enumerate(cfg["held_experts"]):
        y = y + g[..., expert, None] * swiglu(h, w["mlp/experts/w1"][row], w["mlp/experts/w3"][row],
                                              w["mlp/experts/w2"][row])
    return y


def layer_fn(lp, x, cfg, has_experts: bool):
    """One block. ``lp``: the layer's leaves by their path below the layer."""
    w = {k: v.astype(F32) for k, v in lp.items()}
    x = attention(w, x, cfg)
    h = rms_norm(x, w["post_attention_layernorm/weight"], cfg["rms_norm_eps"])
    if has_experts:
        return x + experts(w, h, cfg)
    return x + swiglu(h, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"], w["mlp/down_proj/kernel"])


@partial(jax.jit, static_argnums=(2, 3))
@_highest
def _layer_fwd(lp, x, items, has_experts):
    return layer_fn(lp, x, dict(items), has_experts)


@partial(jax.jit, static_argnums=(3, 4))
@_highest
def _layer_bwd(lp, x, dy, items, has_experts):
    """Gradients to the block's leaves (float32, taken at the bfloat16
    values; the selection bias gets none) and to its input."""
    lp32 = {k: v.astype(F32) for k, v in lp.items()}
    _, vjp = jax.vjp(lambda ww, xx: layer_fn(ww, xx, dict(items), has_experts), lp32, x)
    return vjp(dy)


@partial(jax.jit, static_argnums=(2, 3))
@_highest
def _selection(lp, x, items, has_experts):
    """The layer's 0/1 selection ``[rows, seq, router_experts]`` (what a test
    compares with the program's to count the choices that differ)."""
    cfg = dict(items)
    w = {k: v.astype(F32) for k, v in lp.items()}
    h = rms_norm(attention(w, x, cfg), w["post_attention_layernorm/weight"], cfg["rms_norm_eps"])
    return router(w, h, cfg)[1]


@partial(jax.jit, static_argnums=(5,))
@_highest
def _head_loss_grad(x, norm_w, head, ids, scale, eps):
    """``scale`` x the token-mean next-token cross-entropy of these rows, and
    its gradients to the final hidden states, the final norm and the head."""

    def loss_of(xx, nw, tab):
        logits = rms_norm(xx[:, :-1], nw, eps) @ tab
        gold = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), ids[:, 1:, None], axis=-1)[..., 0]
        return -jnp.mean(gold) * scale

    return jax.value_and_grad(loss_of, argnums=(0, 1, 2))(x, norm_w.astype(F32), head.astype(F32))


@partial(jax.jit, static_argnums=(3,))
@_highest
def _logits(x, norm_w, head, eps):
    return rms_norm(x, norm_w.astype(F32), eps) @ head.astype(F32)


def has_experts(cfg: dict, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def forward_hidden(flat: dict, cfg: dict, ids):
    """Final hidden states (before the final norm) and every block's input."""
    items = cfg_items(cfg)
    x = _embed(flat["model/embed_tokens/weight"], jnp.asarray(ids, jnp.int32))
    inputs = []
    for i in range(cfg["num_hidden_layers"]):
        inputs.append(x)
        x = _layer_fwd(layer_leaves(flat, i), x, items, has_experts(cfg, i))
    return x, inputs


def logits(flat: dict, cfg: dict, ids):
    x, _ = forward_hidden(flat, cfg, ids)
    return _logits(x, flat["model/norm/weight"], flat["lm_head/kernel"], cfg["rms_norm_eps"])


def selections(flat: dict, cfg: dict, ids) -> dict:
    """{layer: 0/1 selection [rows, seq, router_experts]} of the expert layers."""
    _, inputs = forward_hidden(flat, cfg, ids)
    items = cfg_items(cfg)
    return {i: _selection(layer_leaves(flat, i), inputs[i], items, True)
            for i in range(cfg["num_hidden_layers"]) if has_experts(cfg, i)}


def trainable_paths(all_paths) -> list:
    """Every leaf trains (``freeze_strategy`` "none") but the router's
    selection bias, a buffer."""
    return [p for p in all_paths if not p.endswith(BUFFER)]


def rows_grads(flat: dict, cfg: dict, ids, scale: float, into=None):
    """``scale`` x the token-mean loss of ``ids [rows, seq]`` and its
    gradients of every trainable leaf (float32), added to ``into``."""
    items = cfg_items(cfg)
    n = cfg["num_hidden_layers"]
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
    for i in range(n - 1, -1, -1):
        dlp, dx = _layer_bwd(layer_leaves(flat, i), inputs[i], dx, items, has_experts(cfg, i))
        inputs[i] = None
        for k, g in dlp.items():
            if not k.endswith(BUFFER):
                give(f"model/layers/{i}/{k}", g)
    table = "model/embed_tokens/weight"
    grads[table] = _embed_grad(grads.get(table, jnp.zeros(flat[table].shape, F32)), ids, dx)
    return loss, grads


def sft_reference(flat: dict, cfg: dict, recipe: dict, batches, fresh_leaves, keep_first_grad=False) -> dict:
    """``reference.sft_reference`` for this architecture with every leaf
    trainable: each step's loss, the first gradient's norm before the clip,
    its norm by leaf after the clip, and the norm by leaf of the parameters'
    change. ``batches``: one [accum, rows, seq] int array a step; rows go
    through one at a time (full rows of one length: the mean of the row means
    is the microbatch's token mean, and the mean of those the step's)."""
    if recipe.get("optimizer", "adamw") != "adamw" or recipe.get("weight_decay", 0.0):
        raise ValueError("the reference knows AdamW without weight decay")
    flat = dict(flat)
    train = sorted(trainable_paths(flat))
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
