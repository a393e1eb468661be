"""The plain reference of a Qwen3-Next-shaped decoder (Qwen3-Next-80B-A3B):
Gated DeltaNet layers and gated softmax-attention layers side by side, and in
every layer routed experts behind a softmax router beside one gated shared
expert; token-mean cross-entropy, the gradients of every leaf, AdamW behind a
global-norm clip (``reference.py``'s optimizer functions, by import).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, one layer and one row of the batch at
a time, over the bfloat16 weights ``weights_gdn_moe.py`` made from the seed.
The gated delta rule runs TOKEN BY TOKEN (``lax.scan`` over the row, one
rank-one update a step); softmax attention is a mask on the whole ``[seq,
seq]`` scores; every held expert is applied to ALL tokens and its result kept
under the expert's weight (a ``lax.scan`` over the held ids): no chunk, no
triangular inverse, no sort, no grouping, no kernel. It imports nothing of the program.

The layer equations (HF ``modeling_qwen3_next.py``). ``N(x) = x / rms(x) (1 +
w)``, eps 1e-6, the zero-centred norm (w is drawn at 0); no bias anywhere.

  block       x += mixer(N(x)); x += moe(N(x)); the final norm is N too.
              ``layer_types[l]`` names the mixer.
  full layer  (``"full_attention"``) ``q_proj`` gives 16 heads of [query 256 | gate 256]; k, v 2 heads of 256
              (8 queries a kv head); q = N_q(query), k = N_k(k) per head over 256 (zero-centred);
              rope (theta 1e7) on dimensions 0..63 of q and k, angle = position * theta^(-2i/64), halves of the 64
              rotated, 64..255 pass; P = softmax over keys s <= t of q k^T / sqrt(256);
              out = (concat(P v) * sigmoid(gate)) W_o
  linear      (``"linear_attention"``; Hk = 16 key heads, Hv = 32 value heads, both 128 wide)
  layer       ``in_proj_qkvz`` gives [q | k | v | z], ``in_proj_ba`` [b | a] (the tree's layout: each part by
              head; HF stores both interleaved by key head); [q | k | v] (8192 channels) pass a causal
              depthwise convolution of 4 taps (y_t = sum_j c_j x_{t-3+j}, zeros left of the row) and silu;
              beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias) (a log decay, <= 0);
              q, k l2-normed over 128 (x rsqrt(sum x^2 + 1e-6)), q *= 128^-1/2; key head i serves value
              heads 2i and 2i + 1; then for each value head, S [128, 128] from zero, t = 0, 1, ...:
                S *= exp(g_t);  d = beta_t (v_t - S^T k_t);  S += k_t d^T;  o_t = S^T q_t
              o = o / rms(o) * w * silu(z) per head over 128 (plain w, drawn at 1); out = o W_out
  experts     p = softmax(h W_g) over ALL router_experts; S = the top k of p;
              g_e = p_e / sum_{j in S} p_j for e in S (``norm_topk_prob``);
              x += sum_{e in S, e held here} g_e W2_e (silu(W1_e h) * W3_e h) + sigmoid(h w_s) SwiGLU_shared(h)

Departures from the published model, each on purpose:

- The chip's share (the configuration file states it): only ``held_experts``
  of the ``router_experts`` are here; what the absent ones would add is left
  out and the partial result goes on. The normaliser of g runs over all k
  selected experts, held or not. The shared expert is whole on every chip.
  The vocabulary is a slice: logits, loss and ids are over ``vocab_size``
  rows. ``layer_types`` is read up to ``num_hidden_layers``.
- Not here (``assumed`` in the configuration file): no auxiliary router loss
  (HF's ``output_router_logits`` defaults to false), no multi-token-prediction
  layer (the config has no key for one).
- Computed in blocks so that it fits: the query heads go through attention
  ``HEADS_A_BLOCK`` at a time and a block's scores are made a second time in
  the backward pass instead of being held (as ``reference_swa_moe.py``); the
  recurrence is walked in segments of ``STEPS_A_SEGMENT`` tokens and a
  segment's states are made a second time in the backward pass (a state is 2
  MiB a token and row at the published widths, 16 GiB a row of 8192). The
  same float32 arithmetic, twice; nothing is left out and nothing approximated.
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
    layer_leaves, lr_at, rope_tables,
)
from benchmarks.chipbench.reference_mla_moe import _head_loss_grad, _logits, swiglu

CFG_KEYS = (
    "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
    "rms_norm_eps", "router_experts", "rope_theta", "partial_rotary_factor", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
)
HEADS_A_BLOCK = 4
STEPS_A_SEGMENT = 128
LINEAR = "linear_attention"


def cfg_items(cfg: dict):
    """What a layer's function reads of the configuration, hashable."""
    return tuple((k, cfg[k]) for k in CFG_KEYS) + (("held_experts", tuple(cfg["held_experts"])),)


def layer_kind(cfg: dict, layer: int) -> str:
    return cfg["layer_types"][layer]


def norm(x, w, eps):
    """The zero-centred norm: ``x / rms(x) (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def full_attention(w, h, cfg):
    """``h [rows, seq, hidden]`` (normed) -> the gated softmax-attention mixer's output."""
    b, t, _ = h.shape
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, rot = cfg["rms_norm_eps"], int(d * cfg["partial_rotary_factor"])
    qg = (h @ w["self_attn/q_proj/kernel"]).reshape(b, t, nh, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, t, nh * d)
    k = (h @ w["self_attn/k_proj/kernel"]).reshape(b, t, nkv, d)
    v = (h @ w["self_attn/v_proj/kernel"]).reshape(b, t, nkv, d)
    q, k = norm(q, w["self_attn/q_norm/weight"], eps), norm(k, w["self_attn/k_norm/weight"], eps)
    cos, sin = rope_tables(jnp.arange(t), rot, float(cfg["rope_theta"]))
    q = jnp.concatenate([_rotate(q[..., :rot], cos, sin), q[..., rot:]], axis=-1)
    k = jnp.concatenate([_rotate(k[..., :rot], cos, sin), k[..., rot:]], axis=-1)
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

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
    return (out * jax.nn.sigmoid(gate)) @ w["self_attn/o_proj/kernel"]


def causal_conv(x, taps):
    """``x [rows, seq, channels]``, ``taps [K, channels]``: y_t = sum_j taps[j] x_{t - (K - 1) + j}."""
    k, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * taps[j] for j in range(k))


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, segment: int = STEPS_A_SEGMENT):
    """The gated delta rule, token by token. ``q``, ``k`` ``[rows, seq, heads,
    d_k]``, ``v`` ``[rows, seq, heads, d_v]``, ``g``, ``beta`` ``[rows, seq,
    heads]`` -> ``o [rows, seq, heads, d_v]``. Walked in segments of
    ``segment`` steps whose states are made again in the backward pass."""
    b, t, nh, dk = q.shape
    dv = v.shape[-1]

    def step(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        d = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * d[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    pad = -t % segment  # steps that change nothing: k = 0, beta = 0, g = 0
    xs = tuple(
        jnp.moveaxis(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)), 1, 0).reshape(
            (-1, segment) + x.shape[:1] + x.shape[2:]
        )
        for x in (q, k, v, g, beta)
    )
    walk = jax.checkpoint(lambda state, seg: jax.lax.scan(step, state, seg))
    _, o = jax.lax.scan(walk, jnp.zeros((b, nh, dk, dv), F32), xs)
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)[:, :t]


def linear_attention(w, h, cfg):
    """``h [rows, seq, hidden]`` (normed) -> the Gated DeltaNet mixer's output."""
    b, t, _ = h.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kd, vd = hk * dk, hv * dv
    qkvz = h @ w["linear_attn/in_proj_qkvz/kernel"]
    ba = h @ w["linear_attn/in_proj_ba/kernel"]
    qkv = jax.nn.silu(causal_conv(qkvz[..., : 2 * kd + vd], w["linear_attn/conv1d/weight"]))
    z = qkvz[..., 2 * kd + vd:].reshape(b, t, hv, dv)
    q = l2_norm(qkv[..., :kd].reshape(b, t, hk, dk)) / math.sqrt(dk)
    k = l2_norm(qkv[..., kd: 2 * kd].reshape(b, t, hk, dk))
    v = qkv[..., 2 * kd:].reshape(b, t, hv, dv)
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))  # key head i: value heads i r .. i r + r - 1
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(w["linear_attn/A_log"]) * jax.nn.softplus(ba[..., hv:] + w["linear_attn/dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    o = o * w["linear_attn/norm/weight"] * jax.nn.silu(z)
    return o.reshape(b, t, vd) @ w["linear_attn/out_proj/kernel"]


def router(w, h, cfg):
    """Probabilities, the 0/1 selection and the combine weights, each
    ``[..., router_experts]``: ``g`` is zero outside the selection."""
    p = jax.nn.softmax(h @ w["mlp/gate/kernel"], axis=-1)
    _, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    selected = jax.nn.one_hot(chosen, cfg["router_experts"], dtype=F32).sum(-2)
    return p, selected, p * selected / (p * selected).sum(-1, keepdims=True)


def experts(w, h, cfg, held=None, shared: bool = True):
    """The feed-forward: the routed part for the experts ``held`` (default:
    the configuration's share), each applied to every token and kept under its
    weight (zero where it was not selected), and with ``shared`` the shared
    expert behind its sigmoid gate. The loop over the held ids is a
    ``lax.scan`` over their stacked matrices (32 experts written out one after
    the other made each layer's program a minute to compile)."""
    _, _, g = router(w, h, cfg)
    ids = jnp.asarray(cfg["held_experts"], jnp.int32)
    on = jnp.asarray([held is None or e in held for e in cfg["held_experts"]], F32)

    def add_one(y, e):
        w1, w3, w2, expert, counted = e
        weight = jnp.take(g, expert, axis=-1)[..., None] * counted
        return y + weight * swiglu(h, w1, w3, w2), None

    y, _ = jax.lax.scan(add_one, jnp.zeros_like(h), (w["mlp/experts/w1"], w["mlp/experts/w3"], w["mlp/experts/w2"], ids, on))
    if shared:
        y = y + jax.nn.sigmoid(h @ w["mlp/shared_expert_gate/kernel"]) * swiglu(
            h, w["mlp/shared_experts/gate_proj/kernel"], w["mlp/shared_experts/up_proj/kernel"],
            w["mlp/shared_experts/down_proj/kernel"],
        )
    return y


def _after_mixer(lp, x, cfg, kind: str):
    """The layer's float32 leaves, the stream after its mixer, and the normed input of its feed-forward."""
    w = {k: v.astype(F32) for k, v in lp.items()}
    eps = cfg["rms_norm_eps"]
    mixer = linear_attention if kind == LINEAR else full_attention
    x = x + mixer(w, norm(x, w["input_layernorm/weight"], eps), cfg)
    return w, x, norm(x, w["post_attention_layernorm/weight"], eps)


def layer_fn(lp, x, cfg, kind: str):
    """One block. ``lp``: the layer's leaves by their path below the layer."""
    w, x, h = _after_mixer(lp, x, cfg, kind)
    return x + experts(w, h, cfg)


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
    w, _, h = _after_mixer(lp, x, cfg, kind)
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


def _final_norm_weight(flat):
    """The zero-centred final norm as the plain weight the imported head functions multiply by."""
    return flat["model/norm/weight"].astype(F32) + 1.0


def logits(flat: dict, cfg: dict, ids):
    x, _ = forward_hidden(flat, cfg, ids)
    return _logits(x, _final_norm_weight(flat), flat["lm_head/kernel"], cfg["rms_norm_eps"])


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
        x, _final_norm_weight(flat), flat["lm_head/kernel"], ids, scale, cfg["rms_norm_eps"]
    )  # (the gradient to 1 + w is the gradient to w)
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
    """``reference_swa_moe.sft_reference`` for this architecture (every leaf
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
