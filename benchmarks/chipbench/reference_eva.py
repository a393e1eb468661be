"""The plain reference of an ``evabyte`` configuration (EvaByte/EvaByte, 6.5B,
byte-level): straightforward ``jax.numpy``, float32 under
``default_matmul_precision("highest")``, gradients by ``jax.grad``. It imports
nothing of the program and is handed nothing the program made.

**The layers**, from the published ``config.json`` where it pins them and from
the family's published description and its HF modeling code (``eva.py``,
``modeling_evabyte.py``) AS REMEMBERED where it does not (each such point is
marked † and listed under ``assumed`` in the configuration file; there is no
network here to read the code again):

``N(x) = x / rms(x) * (1 + w)``, eps 1e-5 (``norm_add_unit_offset``); no bias
anywhere; ``x += mixer(N(x)); x += mlp(N(x))`` with the residual stream float32
(``fp32_skip_add``: here everything is); ``mlp(u) = (silu(u W_gate) * (u W_up))
W_down``; an untied embedding.

*EVA mixer*, every layer; H heads of d, scale ``s = d ** -0.5``, window W,
chunk C: ``q, k, v = u W_q, u W_k, u W_v``; rope (theta 1e5, rotate-half over the
whole head, absolute positions) on q and k BEFORE anything else†.
Pooling, for head h and chunk c of C consecutive tokens j:
``a_cj = softmax_j(s * (k_cj . phi_h))``†, ``K_c = sum_j a_cj k_cj + mu_h``†,
``V_c = sum_j a_cj v_cj``†, with ``phi``, ``mu`` the checkpoint's
``adaptive_phi``, ``adaptive_mu_k`` ``[H, d]``.
Attention, for token n of window ``w = n // W``: its own window up to itself,
``L_n = {m : m // W = w, m <= n}``, and every chunk of every EARLIER window,
``R_n = {c : c < (W / C) w}`` (never a chunk of its own window†), under one
softmax:
``o_n = (sum_L e^{s q_n.k_m} v_m + sum_R e^{s q_n.K_c} V_c) / (sum_L e^{s q_n.k_m} + sum_R e^{s q_n.K_c})``;
``out = o W_o``. Written here as ONE masked softmax over the scores against
``[tokens of the window | all summaries]``, a window and a block of heads at a
time (the score rows of a whole row would not fit beside the weights).

*Heads*: ``logits = N(x) W_head``, ``W_head [hidden, P x vocab]``†, head i's
vocabulary at columns ``[i x vocab, (i + 1) x vocab)``†; head i (0..P-1) at
position t answers byte ``t + 1 + i``†. Loss ``(1 / P) sum_i CE_i``†, ``CE_i``
the mean over the positions whose target lies inside the row (every position
counts: the traffic's masks are all ones), computed head by head.

Departures from the published model: none in the mathematics beyond the †
points. The masters of the trainable leaves are kept in bfloat16 between steps
because the recipe under test states bfloat16 masters; the MLP goes through its
rows in blocks, each (window, block of heads) of the mixer is recomputed in the
backward pass and a block's backward runs a half at a time, which changes what
is held, not what is computed.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chipbench.reference import (
    F32, _adam_apply, _add, _delta_sq, _embed, _embed_grad, _highest, _rotate, _scale, _sq_norm, layer_leaves, lr_at,
    rope_tables, trainable_paths,
)

HEAD, EMBED, FINAL_NORM = "lm_head/kernel", "model/embed_tokens/weight", "model/norm/weight"
ROWS_A_BLOCK = 4096  # of the MLP
HEADS_A_BLOCK = 8  # of the mixer


def cfg_items(cfg: dict):
    keys = ("hidden_size", "head_dim", "num_attention_heads", "intermediate_size", "vocab_size", "num_hidden_layers",
            "rope_theta", "rms_norm_eps", "window_size", "chunk_size", "num_pred_heads")
    return tuple((k, cfg[k]) for k in keys)


def norm(x, w, eps):
    """RMSNorm with a unit offset: the stored weight is the multiplier's distance from 1."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def pool(k, v, phi, mu, chunk: int, scale: float):
    """k, v ``[b, t, H, d]`` -> the summaries K, V ``[b, t / chunk, H, d]``."""
    b, t, h, d = k.shape
    kc, vc = k.reshape(b, t // chunk, chunk, h, d), v.reshape(b, t // chunk, chunk, h, d)
    a = jax.nn.softmax(scale * jnp.einsum("bcjhd,hd->bcjh", kc, phi), axis=2)
    return jnp.einsum("bcjh,bcjhd->bchd", a, kc) + mu, jnp.einsum("bcjh,bcjhd->bchd", a, vc)


def eva_attention(q, k, v, phi, mu, window: int, chunk: int):
    """q, k, v ``[b, t, H, d]``, rotated -> o ``[b, t, H, d]``."""
    b, t, h, d = q.shape
    window = min(window, t)
    if t % window or window % chunk:
        raise ValueError(f"the reference takes whole windows of whole chunks: {t} tokens, window {window}, chunk {chunk}")
    scale, per, nw, n = d ** -0.5, window // chunk, t // window, t // chunk
    hb = math.gcd(h, HEADS_A_BLOCK)
    ks, vs = pool(k, v, phi, mu, chunk, scale)
    by_window = lambda x: x.reshape(b, nw, window, h // hb, hb, d).transpose(1, 3, 0, 2, 4, 5)  # noqa: E731  [nw, h/hb, b, W, hb, d]
    by_heads = lambda x: x.reshape(b, n, h // hb, hb, d).transpose(2, 0, 1, 3, 4)  # noqa: E731  [h/hb, b, n, hb, d]
    causal = jnp.tril(jnp.ones((window, window), bool))

    def one_window(args):
        w, qw, kw, vw = args
        seen = jnp.arange(n) < per * w
        mask = jnp.concatenate([causal, jnp.broadcast_to(seen, (window, n))], axis=1)

        @jax.checkpoint
        def one_block(block):
            qb, kb, vb, ksb, vsb = block
            scores = scale * jnp.einsum("bqhd,bkhd->bhqk", qb, jnp.concatenate([kb, ksb], axis=1))
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.concatenate([vb, vsb], axis=1))

        return jax.lax.map(one_block, (qw, kw, vw, by_heads(ks), by_heads(vs)))

    o = jax.lax.map(one_window, (jnp.arange(nw), by_window(q), by_window(k), by_window(v)))
    return o.transpose(2, 0, 3, 1, 4, 5).reshape(b, t, h, d)


def mlp(w, u):
    rows = u.reshape(-1, u.shape[-1])
    block = math.gcd(rows.shape[0], ROWS_A_BLOCK)

    @jax.checkpoint
    def some_rows(r):
        return (jax.nn.silu(r @ w["mlp/gate_proj/kernel"]) * (r @ w["mlp/up_proj/kernel"])) @ w["mlp/down_proj/kernel"]

    return jax.lax.map(some_rows, rows.reshape(-1, block, rows.shape[-1])).reshape(u.shape)


def mixer_half(lp, x, cfg):
    """``x + mixer(N(x))``. ``lp``: the layer's leaves by their path below the layer; ``x``: ``[rows, seq, hidden]`` float32."""
    b, t, _ = x.shape
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    w = {k: v.astype(F32) for k, v in lp.items()}
    u = norm(x, w["input_layernorm/weight"], cfg["rms_norm_eps"])
    q, k, v = ((u @ w[f"self_attn/{name}_proj/kernel"]).reshape(b, t, nh, d) for name in "qkv")
    cos, sin = rope_tables(jnp.arange(t), d, cfg["rope_theta"])
    o = eva_attention(_rotate(q, cos, sin), _rotate(k, cos, sin), v, w["self_attn/adaptive_phi"],
                      w["self_attn/adaptive_mu_k"], cfg["window_size"], cfg["chunk_size"])
    return x + o.reshape(b, t, nh * d) @ w["self_attn/o_proj/kernel"]


def mlp_half(lp, x, cfg):
    """``x + mlp(N(x))``."""
    w = {k: v.astype(F32) for k, v in lp.items()}
    return x + mlp(w, norm(x, w["post_attention_layernorm/weight"], cfg["rms_norm_eps"]))


def layer_fn(lp, x, cfg):
    """One block: ``x += mixer(N(x)); x += mlp(N(x))``."""
    return mlp_half(lp, mixer_half(lp, x, cfg), cfg)


HALVES = ((mixer_half, ("input_layernorm/", "self_attn/")), (mlp_half, ("post_attention_layernorm/", "mlp/")))


@partial(jax.jit, static_argnums=(2, 3))
@_highest
def _half_fwd(lp, x, items, half):
    return HALVES[half][0](lp, x, dict(items))


@partial(jax.jit, static_argnums=(3, 4))
@_highest
def _half_bwd(lp, x, dy, items, half):
    """Gradients to one half's leaves (float32, taken at the bfloat16 values) and to its input."""
    lp32 = {k: v.astype(F32) for k, v in lp.items()}
    return jax.vjp(lambda ww, xx: HALVES[half][0](ww, xx, dict(items)), lp32, x)[1](dy)


def _layer_fwd(lp, x, items):
    return _half_fwd(lp, _half_fwd(lp, x, items, 0), items, 1)


def _layer_bwd(lp, x, dy, items):
    """Gradients to the block's leaves and to its input, a half at a time (at rows of 32,768 a whole block's
    residuals, a dozen float32 arrays of the row, do not fit beside the weights): the MLP half from the mixer half's
    output, made again, then the mixer half."""
    leaves = [{k: v for k, v in lp.items() if k.startswith(prefixes)} for _, prefixes in HALVES]
    d_mlp, dy = _half_bwd(leaves[1], _half_fwd(leaves[0], x, items, 0), dy, items, 1)
    d_mixer, dx = _half_bwd(leaves[0], x, dy, items, 0)
    return {**d_mixer, **d_mlp}, dx


def heads_loss(x, norm_w, table, ids, cfg):
    """``(1 / P) sum_i CE_i`` of final hidden states ``x [rows, seq, hidden]``, head by head."""
    vocab, heads = cfg["vocab_size"], cfg["num_pred_heads"]
    logits = norm(x, norm_w, cfg["rms_norm_eps"]) @ table
    total = 0.0
    for i in range(heads):
        last = ids.shape[1] - 1 - i  # positions 0 .. last - 1 have their target t + 1 + i inside the row
        logp = jax.nn.log_softmax(logits[:, :last, i * vocab:(i + 1) * vocab], axis=-1)
        total = total - jnp.mean(jnp.take_along_axis(logp, ids[:, 1 + i:, None], axis=-1))
    return total / heads


@partial(jax.jit, static_argnums=(4,))
@_highest
def _head_loss_grad(x, norm_w, table, ids, items):
    """The loss and its gradients to the final hidden states, the final norm and the heads."""
    loss_of = lambda xx, nw, tab: heads_loss(xx, nw, tab, ids, dict(items))  # noqa: E731
    return jax.value_and_grad(loss_of, argnums=(0, 1, 2))(x, norm_w.astype(F32), table.astype(F32))


def forward_hidden(flat: dict, cfg: dict, ids, keep_from: int = 0):
    """The final hidden states (before the last norm) and the input of each block from ``keep_from`` on (None below)."""
    items = cfg_items(cfg)
    x = _embed(flat[EMBED], jnp.asarray(ids, jnp.int32))
    inputs = []
    for i in range(cfg["num_hidden_layers"]):
        inputs.append(x if i >= keep_from else None)
        x = _layer_fwd(layer_leaves(flat, i), x, items)
    return x, inputs


@partial(jax.jit, static_argnums=(3,))
@_highest
def _logits(x, norm_w, table, items):
    cfg = dict(items)
    return norm(x, norm_w.astype(F32), cfg["rms_norm_eps"]) @ table.astype(F32)


def logits(flat: dict, cfg: dict, ids):
    """``[rows, seq, P x vocab]`` float32."""
    return _logits(forward_hidden(flat, cfg, ids)[0], flat["model/norm/weight"], flat[HEAD], cfg_items(cfg))


def microbatch_grads(flat, cfg, ids, trainable: set):
    """Loss of one microbatch ``ids [rows, seq]`` and the float32 gradients of the trainable leaves: one forward that
    keeps each block's input, one backward that stops below the lowest trainable leaf."""
    items = cfg_items(cfg)
    n = cfg["num_hidden_layers"]
    ids = jnp.asarray(ids, jnp.int32)
    lowest = 0 if EMBED in trainable else min([int(p.split("/")[2]) for p in trainable if p.startswith("model/layers/")] or [n])
    x, inputs = forward_hidden(flat, cfg, ids, keep_from=lowest)
    loss, (dx, dnorm, dtab) = _head_loss_grad(x, flat[FINAL_NORM], flat[HEAD], ids, items)
    grads = {path: g for path, g in ((HEAD, dtab), (FINAL_NORM, dnorm)) if path in trainable}
    for i in range(n - 1, lowest - 1, -1):
        dlp, dx = _layer_bwd(layer_leaves(flat, i), inputs[i], dx, items)
        inputs[i] = None
        grads.update({f"model/layers/{i}/{k}": g for k, g in dlp.items() if f"model/layers/{i}/{k}" in trainable})
    if EMBED in trainable:
        grads[EMBED] = _embed_grad(jnp.zeros(flat[EMBED].shape, F32), ids, dx)
    return loss, grads


def sft_reference(flat: dict, cfg: dict, recipe: dict, batches, fresh_leaves, keep_first_grad=False) -> dict:
    """``reference.sft_reference`` for this architecture (copied: it names its own ``microbatch_grads`` inside): each
    step's loss, the first gradient's norm before the clip, its norm by leaf after the clip, and the norm by leaf of
    the parameters' change over the steps. ``batches``: one ``[accum, rows, seq]`` int array a step."""
    if recipe.get("optimizer", "adamw") != "adamw" or recipe.get("weight_decay", 0.0):
        raise ValueError("the reference knows AdamW without weight decay")
    flat = dict(flat)
    # the recipe's last layers and heads; a recipe that names no such split trains every leaf (the tests' float32 steps)
    train = set(trainable_paths(cfg, recipe, flat) if "unfreeze_last_n_layers" in recipe else flat)
    b1, b2, eps = float(recipe["adam_b1"]), float(recipe["adam_b2"]), float(recipe["adam_eps"])
    max_norm = float(recipe["max_grad_norm"])
    history = []
    out = {"losses": []}
    for step, batch in enumerate(batches):
        accum = len(batch)
        total, loss_sum = None, 0.0
        for micro in batch:
            loss, grads = microbatch_grads(flat, cfg, micro, train)
            loss_sum += float(loss)
            total = grads if total is None else {k: _add(total[k], g) for k, g in grads.items()}
            del grads
        out["losses"].append(loss_sum / accum)
        total = {k: _scale(g, 1.0 / accum) for k, g in total.items()}
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
        for k in sorted(train):
            flat[k] = _adam_apply(flat[k], [h[k] for h in history], b1, b2, eps, lr_t)
    del history, total
    out["delta_norms"] = {}
    for k in sorted(train):  # one leaf of the seed's weights at a time
        p0 = fresh_leaves([k])[k]
        out["delta_norms"][k] = math.sqrt(float(_delta_sq(flat.pop(k), p0)))
    return out
