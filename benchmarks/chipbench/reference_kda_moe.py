"""The plain reference of a Kimi-Linear-shaped decoder (moonshotai
Kimi-Linear-48B-A3B-Instruct): Kimi Delta Attention layers beside
latent-attention layers without rope, one leading dense layer, then routed
experts behind a sigmoid router with a selection bias beside one shared
expert; token-mean cross-entropy, the gradients of every trainable leaf, AdamW
behind a global-norm clip (``reference.py``'s optimizer functions, by import).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, one layer and one row of the batch at
a time, over the bfloat16 weights ``weights_kda_moe.py`` made from the seed.
The delta rule runs TOKEN BY TOKEN (``lax.scan`` over the row, the state's
decay by channel and one rank-one update a step); softmax attention is a mask
on the whole ``[seq, seq]`` scores; every held expert is applied to ALL tokens
and its result kept under the expert's weight (a ``lax.scan`` over the held
ids): no chunk, no sub-block, no triangular inverse, no sort, no grouping, no
kernel. It imports nothing of the program.

The layer equations (the config's keys and the family's published description;
what is marked + is HF ``modeling_kimi.py`` as remembered, there is no network
here, and is listed under ``assumed`` in the configuration file). ``N(x) = x /
rms(x) * w``, eps 1e-5, plain weight (drawn at 1); no bias anywhere.

  block       x += mixer(N(x)); x += ffn(N(x)); the final norm is N too; untied head.
              ``linear_attn_config.kda_layers`` / ``full_attn_layers`` (1-based) name the mixer.
  KDA layer   (H = 32 heads, d_k = d_v = 128, 4 taps)
              q = l2(silu(conv_q(x W_q))) * 128^-1/2, k = l2(silu(conv_k(x W_k))), v = silu(conv_v(x W_v)): each
              projection 2304 -> 4096, each convolution causal and depthwise (y_t = sum_j c_j x_{t-3+j}, zeros
              left of the row; the tree keeps the three as ONE [taps, q | k | v] leaf), l2 per head over 128
              (x rsqrt(sum x^2 + 1e-6));
              beta = sigmoid(x W_b) (2304 -> 32);
              g = -exp(A_log[h]) * softplus((x W_fa) W_fb + dt_bias), W_fa 2304 -> 128+, W_fb 128 -> 4096+, A_log
              one a head, dt_bias one a channel+: a log decay <= 0 for EVERY channel of every head;
              for each head, S [128, 128] from zero, t = 0, 1, ...:
                S = diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S += k_t d^T;  o_t = S^T q_t
              out = (N_head(o) * sigmoid((x W_ga) W_gb)) W_o, W_ga 2304 -> 128+, W_gb 128 -> 4096+, the norm per
              head over 128+ (plain w, drawn at 1)
  MLA layer   Moonlight's training form WITHOUT rotation (``mla_use_nope``): q_proj 2304 -> 32 x 192 (q_lora_rank
              null); kv_a_proj_with_mqa 2304 -> 512 + 64; the latent normed (N over 512); kv_b_proj 512 -> 32 x
              (128 + 128); the 64 further columns of the key one a token, shared by the heads, taken as they
              come; scores q k^T / sqrt(192) over all 192 columns, causal softmax, v of 128, o_proj 4096 -> 2304
  feed-forward  layer 0 a dense SwiGLU of 9216; every other layer s = sigmoid(h W_g) over ALL router_experts, S =
              the top 8 of s + bias (the bias a buffer: it selects and does not weigh), g_e = s_e / (sum_{j in S}
              s_j + 1e-20) * 2.446 for e in S;
              x += sum_{e in S, e held here} g_e W2_e (silu(W1_e h) * W3_e h) + SwiGLU_shared(h)

Departures from the published model, each on purpose:

- The chip's share (the configuration file states it): only ``held_experts``
  of the ``router_experts`` are here; what the absent ones would add is left
  out and the partial result goes on. The normaliser of g runs over all 8
  selected experts, held or not. The shared expert is whole on every chip.
  The vocabulary is a slice: logits, loss and ids are over ``vocab_size``
  rows. The layer lists are read up to ``num_hidden_layers``.
- Not here (``assumed`` in the configuration file): no auxiliary router loss,
  no multi-token-prediction layer (``num_nextn_predict_layers`` 0); the
  selection bias gets no gradient and no update.
- Computed in blocks so that it fits: the heads go through attention
  ``HEADS_A_BLOCK`` at a time and a block's scores are made a second time in
  the backward pass instead of being held; the recurrence is walked in segments
  of ``STEPS_A_SEGMENT`` tokens and a segment's states are made a second time
  in the backward pass (a state is 2 MiB a token and row at the published
  widths). The same float32 arithmetic, twice; nothing is left out and nothing
  approximated.
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
    F32, _adam_apply, _add, _delta_sq, _embed, _embed_grad, _highest, _scale, _sq_norm, layer_leaves, lr_at, rms_norm,
)
from benchmarks.chipbench.reference_gdn_moe import causal_conv, l2_norm
from benchmarks.chipbench.reference_mla_moe import BUFFER, _head_loss_grad, _logits, swiglu, trainable_paths

CFG_KEYS = (
    "hidden_size", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "num_experts_per_token", "routed_scaling_factor", "rms_norm_eps", "router_experts", "mla_use_nope",
)
HEADS_A_BLOCK = 4
STEPS_A_SEGMENT = 128
KDA, MLA = "kda", "mla"


def cfg_items(cfg: dict):
    """What a layer's function reads of the configuration, hashable."""
    lin = cfg["linear_attn_config"]
    return tuple((k, cfg[k]) for k in CFG_KEYS) + (
        ("held_experts", tuple(cfg["held_experts"])), ("kda_heads", lin["num_heads"]), ("kda_head_dim", lin["head_dim"]),
    )


def layer_kind(cfg: dict, layer: int):
    """``(the mixer's kind, whether the feed-forward is the dense MLP)`` of a 0-based layer; the config's lists are 1-based."""
    lin = cfg["linear_attn_config"]
    if (layer + 1 in lin["kda_layers"]) == (layer + 1 in lin["full_attn_layers"]):
        raise ValueError(f"layer {layer + 1} is in both or neither of kda_layers and full_attn_layers")
    return (KDA if layer + 1 in lin["kda_layers"] else MLA), layer < cfg["first_k_dense_replace"]


def latent_attention(w, u, cfg):
    """``u [rows, seq, hidden]`` (normed) -> the latent-attention mixer's output, no rotation."""
    if not cfg["mla_use_nope"]:
        raise ValueError("this reference knows latent attention without rope (mla_use_nope)")
    b, t, _ = u.shape
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q = (u @ w["self_attn/q_proj/kernel"]).reshape(b, t, nh, dn + dr)
    c = u @ w["self_attn/kv_a_proj_with_mqa/kernel"]
    c_kv = rms_norm(c[..., :r], w["self_attn/kv_a_layernorm/weight"], cfg["rms_norm_eps"])
    kv = (c_kv @ w["self_attn/kv_b_proj/kernel"]).reshape(b, t, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(c[..., r:].reshape(b, t, 1, dr), (b, t, nh, dr))], axis=-1)
    v = kv[..., dn:]
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def heads(q_blk, k_blk, v_blk):
        """[rows, seq, block, .] queries, keys and values of a block of heads."""
        scores = jnp.einsum("bthd,bshd->bhts", q_blk, k_blk) / math.sqrt(dn + dr)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", probs, v_blk)

    blk = min(HEADS_A_BLOCK, nh)
    blocks = lambda x: x.reshape(b, t, nh // blk, blk, x.shape[-1]).transpose(2, 0, 1, 3, 4)  # noqa: E731
    out = jax.lax.map(lambda a: heads(*a), (blocks(q), blocks(k), blocks(v)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, t, nh * dv) @ w["self_attn/o_proj/kernel"]


def delta_rule(q, k, v, g, beta, segment: int = STEPS_A_SEGMENT):
    """The delta rule with a decay a channel, token by token. ``q``, ``k``, ``g`` ``[rows, seq, heads, d_k]``, ``v``
    ``[rows, seq, heads, d_v]``, ``beta`` ``[rows, seq, heads]`` -> ``o [rows, seq, heads, d_v]``. Walked in
    segments of ``segment`` steps whose states are made again in the backward pass."""
    b, t, nh, dk = q.shape
    dv = v.shape[-1]

    def step(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]                       # diag(exp(g_t)) S
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


def kda_decay(w, u, cfg):
    """The log decay ``[rows, seq, heads, d_k]``, <= 0 on every channel."""
    b, t, _ = u.shape
    nh, d = cfg["kda_heads"], cfg["kda_head_dim"]
    pre = (u @ w["linear_attn/f_a_proj/kernel"]) @ w["linear_attn/f_b_proj/kernel"] + w["linear_attn/dt_bias"]
    return -jnp.exp(w["linear_attn/A_log"])[:, None] * jax.nn.softplus(pre).reshape(b, t, nh, d)


def kda_attention(w, u, cfg, decay=kda_decay):
    """``u [rows, seq, hidden]`` (normed) -> the Kimi Delta Attention mixer's output."""
    b, t, _ = u.shape
    nh, d = cfg["kda_heads"], cfg["kda_head_dim"]
    wide = nh * d
    taps = w["linear_attn/conv1d/weight"]
    through = lambda x, lo: jax.nn.silu(causal_conv(x, taps[:, lo:lo + wide])).reshape(b, t, nh, d)  # noqa: E731
    q = l2_norm(through(u @ w["linear_attn/q_proj/kernel"], 0)) / math.sqrt(d)
    k = l2_norm(through(u @ w["linear_attn/k_proj/kernel"], wide))
    v = through(u @ w["linear_attn/v_proj/kernel"], 2 * wide)
    beta = jax.nn.sigmoid(u @ w["linear_attn/b_proj/kernel"])
    o = delta_rule(q, k, v, decay(w, u, cfg), beta)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg["rms_norm_eps"]) * w["linear_attn/norm/weight"]
    gate = jax.nn.sigmoid((u @ w["linear_attn/g_a_proj/kernel"]) @ w["linear_attn/g_b_proj/kernel"])
    return (o.reshape(b, t, wide) * gate) @ w["linear_attn/out_proj/kernel"]


def router(w, h, cfg):
    """Scores, the 0/1 selection and the combine weights, each ``[..., router_experts]``: the weights are zero
    outside the selection."""
    s = jax.nn.sigmoid(h @ w["mlp/gate/kernel"])
    _, chosen = jax.lax.top_k(s + w["mlp/gate/" + BUFFER], cfg["num_experts_per_token"])
    selected = jax.nn.one_hot(chosen, cfg["router_experts"], dtype=F32).sum(-2)
    return s, selected, cfg["routed_scaling_factor"] * s * selected / ((s * selected).sum(-1, keepdims=True) + 1e-20)


def experts(w, h, cfg, held=None, shared: bool = True):
    """An expert layer's feed-forward: the routed part for the experts ``held`` (default: the configuration's
    share), each applied to every token and kept under its weight (zero where it was not selected), and with
    ``shared`` the shared expert, whole."""
    _, _, g = router(w, h, cfg)
    ids = jnp.asarray(cfg["held_experts"], jnp.int32)
    on = jnp.asarray([held is None or e in held for e in cfg["held_experts"]], F32)

    def add_one(y, e):
        w1, w3, w2, expert, counted = e
        return y + jnp.take(g, expert, axis=-1)[..., None] * counted * swiglu(h, w1, w3, w2), None

    y, _ = jax.lax.scan(add_one, jnp.zeros_like(h), (w["mlp/experts/w1"], w["mlp/experts/w3"], w["mlp/experts/w2"], ids, on))
    if shared:
        y = y + swiglu(h, w["mlp/shared_experts/gate_proj/kernel"], w["mlp/shared_experts/up_proj/kernel"],
                       w["mlp/shared_experts/down_proj/kernel"])
    return y


def _after_mixer(lp, x, cfg, kind: str):
    """The layer's float32 leaves, the stream after its mixer, and the normed input of its feed-forward."""
    w = {k: v.astype(F32) for k, v in lp.items()}
    eps = cfg["rms_norm_eps"]
    mixer = kda_attention if kind == KDA else latent_attention
    x = x + mixer(w, rms_norm(x, w["input_layernorm/weight"], eps), cfg)
    return w, x, rms_norm(x, w["post_attention_layernorm/weight"], eps)


def layer_fn(lp, x, cfg, kind: str, dense: bool):
    """One block. ``lp``: the layer's leaves by their path below the layer."""
    w, x, h = _after_mixer(lp, x, cfg, kind)
    if dense:
        return x + swiglu(h, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"], w["mlp/down_proj/kernel"])
    return x + experts(w, h, cfg)


@partial(jax.jit, static_argnums=(2, 3, 4))
@_highest
def _layer_fwd(lp, x, items, kind, dense):
    return layer_fn(lp, x, dict(items), kind, dense)


@partial(jax.jit, static_argnums=(3, 4, 5))
@_highest
def _layer_bwd(lp, x, dy, items, kind, dense):
    """Gradients to the block's leaves (float32, taken at the bfloat16 values; the selection bias gets none) and to
    its input."""
    lp32 = {k: v.astype(F32) for k, v in lp.items()}
    _, vjp = jax.vjp(lambda ww, xx: layer_fn(ww, xx, dict(items), kind, dense), lp32, x)
    return vjp(dy)


@partial(jax.jit, static_argnums=(2, 3))
@_highest
def _selection(lp, x, items, kind):
    """An expert layer's 0/1 selection ``[rows, seq, router_experts]``."""
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
            for i in range(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])}


def rows_grads(flat: dict, cfg: dict, ids, scale: float, into=None):
    """``scale`` x the token-mean loss of ``ids [rows, seq]`` and its gradients of every trainable leaf (float32),
    added to ``into``."""
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
    grads[table] = _embed_grad(grads.get(table, jnp.zeros(flat[table].shape, F32)), ids, dx)
    return loss, grads


def sft_reference(flat: dict, cfg: dict, recipe: dict, batches, fresh_leaves, keep_first_grad=False) -> dict:
    """``reference_afmoe.sft_reference`` for this architecture (copied: it names its own ``rows_grads`` inside),
    every leaf trainable but the router's selection bias, a buffer: each step's loss, the first gradient's norm
    before the clip, its norm by leaf after the clip, and the norm by leaf of the parameters' change. ``batches``:
    one [accum, rows, seq] int array a step; rows go through one at a time (full rows of one length: the mean of the
    row means is the step's token mean)."""
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
