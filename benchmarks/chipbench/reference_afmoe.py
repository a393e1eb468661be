"""The plain reference of an ``afmoe``-shaped decoder (arcee-ai Trinity-Mini):
gated grouped-query attention with per-head q/k norms on window layers (rope)
and global layers (no rope) side by side, four norms a block, leading dense
layers, then routed experts behind a sigmoid router with a selection bias
beside one shared expert; token-mean cross-entropy, the gradients of every
trainable leaf, AdamW behind a global-norm clip (``reference.py``'s optimizer
functions, by import).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, one layer and one row of the batch at
a time, over the bfloat16 weights ``weights_afmoe.py`` made from the seed.
Attention is an explicit masked softmax over the whole row's keys; every held
expert is applied to ALL tokens and its result kept under the expert's weight:
no band, no sort, no grouping, no kernel, no remat of a block. It imports
nothing of the program.

The layer equations (RMS norms with eps 1e-5 and plain weights; no bias
anywhere; ``x`` the stream ``[rows, seq, 2048]``):

  embedding   x0 = embed[ids] * sqrt(hidden) (``mup_enabled``)
  block i     x += post_attention_layernorm(mixer_i(input_layernorm(x)))
              x += post_mlp_layernorm(ff_i(pre_mlp_layernorm(x)))      (the tree's pre/post_feedforward_layernorm)
  mixer i     on u: q = q_norm(reshape(u W_q, 32 x 128)), k = k_norm(reshape(u W_k, 4 x 128)) (a norm over each
              head's 128), v = reshape(u W_v, 4 x 128), g = u W_g (``q_proj``'s leaf holds [q | gate] by head);
    window layer (``layer_types[i] == "sliding_attention"``): q and k rotated (default rope, ``rope_theta``, the
              whole head, halves rotated); key s is seen by query t iff t - window < s <= t
    global layer (``"full_attention"``): NO rope; key s is seen iff s <= t
              P = softmax over the keys seen of q k^T / sqrt(128), 8 queries a kv head;
              out = (concat(P v) * sigmoid(g)) W_o
  ff i        i < ``num_dense_layers``: W_down (silu(W_gate t) * W_up t) at ``intermediate_size``
              else: s = sigmoid(t W_r) over ALL ``router_experts``; S = the top k of s + expert_bias (the bias
              selects only); w_e = s_e / (sum_{j in S} s_j + 1e-20) * ``route_scale`` for e in S;
              y = sum_{e in S, e held here} w_e SwiGLU_e(t) + SwiGLU_shared(t)
  head        logits = final_norm(x) W_head (untied)

Departures from the published model, each on purpose:

- The chip's share (the configuration file states it): only ``held_experts``
  of the ``router_experts`` are here; what the absent ones would add is left
  out BEFORE ``post_mlp_layernorm``, so the norm is taken of the partial sum
  (held experts + the shared expert) and that goes on. The normaliser of w runs
  over all k selected experts, held or not. The vocabulary is a slice: logits,
  loss and ids are over ``vocab_size`` rows. ``layer_types`` is read up to
  ``num_hidden_layers``.
- What the config has no key for is HF ``transformers``
  ``models/afmoe/modeling_afmoe.py``'s (``assumed`` in the configuration file):
  four norms a block, per-head q/k norms, the gate's projection, rope on the
  window layers only, the sqrt(hidden) multiplier, the bias a buffer of zeros
  that selects only and takes no gradient and no update, no auxiliary loss.
- HF makes the router's product in the activations' bfloat16 and the sigmoid
  in float32; here, as everything, in float32.
- Computed in blocks so that it fits: attention goes ``QUERIES_A_BLOCK``
  queries at a time, every head of them against the whole row's keys, one block
  after another (``lax.map``), and a block's scores are made a second time in
  the backward pass instead of being held (``jax.checkpoint`` around one block:
  32 heads' probabilities of one 8192-token row are 8.6 GB). The held experts
  are a ``lax.scan`` over their stacked matrices (written out one after the
  other, 32 of them made a layer's program a minute to compile: PERF.md, PR 32).
  The same float32 arithmetic; nothing is left out and nothing approximated.
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
from benchmarks.chipbench.reference_mla_moe import BUFFER, _head_loss_grad, _logits, swiglu, trainable_paths

CFG_KEYS = (
    "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "num_experts_per_tok", "rms_norm_eps",
    "router_experts", "rope_theta", "sliding_window", "route_scale",
)
QUERIES_A_BLOCK = 1024
WINDOW = "sliding_attention"


def cfg_items(cfg: dict):
    """What a layer's function reads of the configuration, hashable."""
    return tuple((k, cfg[k]) for k in CFG_KEYS) + (("held_experts", tuple(cfg["held_experts"])),)


def layer_kind(cfg: dict, layer: int) -> tuple:
    """(the layer's ``layer_types`` entry, whether its feed-forward is dense)."""
    return cfg["layer_types"][layer], layer < cfg["num_dense_layers"]


def embed_scale(cfg: dict) -> float:
    return math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0


def attention(w, u, cfg, kind: str):
    """``u [rows, seq, hidden]`` (normed) -> the gated mixer's output of a
    layer of ``kind`` (``sliding_attention`` | ``full_attention``)."""
    b, t, _ = u.shape
    nh, nkv, d, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    qg = (u @ w["self_attn/q_proj/kernel"]).reshape(b, t, nh, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, t, nh * d)
    k = (u @ w["self_attn/k_proj/kernel"]).reshape(b, t, nkv, d)
    v = (u @ w["self_attn/v_proj/kernel"]).reshape(b, t, nkv, d)
    q, k = rms_norm(q, w["self_attn/q_norm/weight"], eps), rms_norm(k, w["self_attn/k_norm/weight"], eps)
    window = None
    if kind == WINDOW:  # the global layers rotate nothing
        cos, sin = rope_tables(jnp.arange(t), d, float(cfg["rope_theta"]))
        q, k, window = _rotate(q, cos, sin), _rotate(k, cos, sin), cfg["sliding_window"]
    blk = min(QUERIES_A_BLOCK, t)
    if t % blk:
        raise ValueError(f"rows of {t} tokens are no whole blocks of {blk} queries")

    @jax.checkpoint
    def block(q_blk, start):
        """[rows, block, kv heads, queries a kv head, d] queries from position ``start`` on, against every key."""
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k) / math.sqrt(d)
        gap = (start + jnp.arange(blk))[:, None] - jnp.arange(t)[None, :]
        seen = (gap >= 0) if window is None else (gap >= 0) & (gap < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)

    q_blocks = q.reshape(b, t // blk, blk, nkv, nh // nkv, d).transpose(1, 0, 2, 3, 4, 5)
    out = jax.lax.map(lambda a: block(*a), (q_blocks, jnp.arange(t // blk) * blk))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, nh * d)
    return (out * jax.nn.sigmoid(gate)) @ w["self_attn/o_proj/kernel"]


def router(w, t, cfg):
    """Scores, the 0/1 selection and the combine weights, each
    ``[..., router_experts]``: the weights are zero outside the selection."""
    s = jax.nn.sigmoid(t @ w["mlp/gate/kernel"])
    _, chosen = jax.lax.top_k(s + w["mlp/gate/" + BUFFER], cfg["num_experts_per_tok"])
    selected = jax.nn.one_hot(chosen, cfg["router_experts"], dtype=F32).sum(-2)
    return s, selected, cfg["route_scale"] * s * selected / ((s * selected).sum(-1, keepdims=True) + 1e-20)


def experts(w, t, cfg, held=None, shared: bool = True):
    """An expert layer's output BEFORE ``post_mlp_layernorm``: the routed part
    for the experts ``held`` (default: the configuration's share), each applied
    to every token and kept under its weight (zero where it was not selected),
    and with ``shared`` the shared expert, ungated."""
    _, _, g = router(w, t, cfg)
    ids = jnp.asarray(cfg["held_experts"], jnp.int32)
    on = jnp.asarray([held is None or e in held for e in cfg["held_experts"]], F32)

    def add_one(y, e):
        w1, w3, w2, expert, counted = e
        return y + jnp.take(g, expert, axis=-1)[..., None] * counted * swiglu(t, w1, w3, w2), None

    y, _ = jax.lax.scan(add_one, jnp.zeros_like(t), (w["mlp/experts/w1"], w["mlp/experts/w3"], w["mlp/experts/w2"], ids, on))
    if shared:
        y = y + swiglu(t, w["mlp/shared_experts/gate_proj/kernel"], w["mlp/shared_experts/up_proj/kernel"],
                       w["mlp/shared_experts/down_proj/kernel"])
    return y


def _before_ff(lp, x, cfg, kind: str):
    """The layer's float32 leaves, the stream after its mixer, and the normed input of its feed-forward."""
    w = {k: v.astype(F32) for k, v in lp.items()}
    eps = cfg["rms_norm_eps"]
    a = attention(w, rms_norm(x, w["input_layernorm/weight"], eps), cfg, kind)
    x = x + rms_norm(a, w["post_attention_layernorm/weight"], eps)
    return w, x, rms_norm(x, w["pre_feedforward_layernorm/weight"], eps)


def layer_fn(lp, x, cfg, kind: str, dense: bool):
    """One block. ``lp``: the layer's leaves by their path below the layer."""
    w, x, t = _before_ff(lp, x, cfg, kind)
    if dense:
        m = swiglu(t, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"], w["mlp/down_proj/kernel"])
    else:
        m = experts(w, t, cfg)
    return x + rms_norm(m, w["post_feedforward_layernorm/weight"], cfg["rms_norm_eps"])


@partial(jax.jit, static_argnums=(2, 3, 4))
@_highest
def _layer_fwd(lp, x, items, kind, dense):
    return layer_fn(lp, x, dict(items), kind, dense)


@partial(jax.jit, static_argnums=(3, 4, 5))
@_highest
def _layer_bwd(lp, x, dy, items, kind, dense):
    """Gradients to the block's leaves (float32, taken at the bfloat16
    values) and to its input."""
    lp32 = {k: v.astype(F32) for k, v in lp.items()}
    _, vjp = jax.vjp(lambda ww, xx: layer_fn(ww, xx, dict(items), kind, dense), lp32, x)
    return vjp(dy)


@partial(jax.jit, static_argnums=(2, 3))
@_highest
def _selection(lp, x, items, kind):
    """An expert layer's 0/1 selection ``[rows, seq, router_experts]``."""
    cfg = dict(items)
    w, _, t = _before_ff(lp, x, cfg, kind)
    return router(w, t, cfg)[1]


def forward_hidden(flat: dict, cfg: dict, ids):
    """Final hidden states (before the final norm) and every block's input."""
    items = cfg_items(cfg)
    x = _embed(flat["model/embed_tokens/weight"], jnp.asarray(ids, jnp.int32)) * embed_scale(cfg)
    inputs = []
    for i in range(cfg["num_hidden_layers"]):
        inputs.append(x)
        x = _layer_fwd(layer_leaves(flat, i), x, items, *layer_kind(cfg, i))
    return x, inputs


def logits(flat: dict, cfg: dict, ids):
    x, _ = forward_hidden(flat, cfg, ids)
    return _logits(x, flat["model/norm/weight"], flat["lm_head/kernel"], cfg["rms_norm_eps"])


def selections(flat: dict, cfg: dict, ids) -> dict:
    """{expert layer: 0/1 selection [rows, seq, router_experts]}."""
    _, inputs = forward_hidden(flat, cfg, ids)
    items = cfg_items(cfg)
    return {i: _selection(layer_leaves(flat, i), inputs[i], items, layer_kind(cfg, i)[0])
            for i in range(cfg["num_dense_layers"], cfg["num_hidden_layers"])}


def rows_grads(flat: dict, cfg: dict, ids, scale: float, into=None):
    """``scale`` x the token-mean loss of ``ids [rows, seq]`` and its
    gradients of every trainable leaf (float32), added to ``into``."""
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
        dlp, dx = _layer_bwd(layer_leaves(flat, i), inputs[i], dx, items, *layer_kind(cfg, i))
        inputs[i] = None
        for k, g in dlp.items():
            if not k.endswith(BUFFER):
                give(f"model/layers/{i}/{k}", g)
    table = "model/embed_tokens/weight"
    grads[table] = _embed_grad(grads.get(table, jnp.zeros(flat[table].shape, F32)), ids, _scale(dx, embed_scale(cfg)))
    return loss, grads


def sft_reference(flat: dict, cfg: dict, recipe: dict, batches, fresh_leaves, keep_first_grad=False) -> dict:
    """``reference_mla_moe.sft_reference`` for this architecture (copied: it
    names its own ``rows_grads`` inside), every leaf trainable but the router's
    selection bias, a buffer: each step's loss, the first gradient's norm
    before the clip, its norm by leaf after the clip, and the norm by leaf of
    the parameters' change. ``batches``: one [accum, rows, seq] int array a
    step; rows go through one at a time (full rows of one length: the mean of
    the row means is the step's token mean)."""
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
