"""The plain reference of a ``granitemoehybrid`` configuration (IBM Granite 4.0-H: Mamba-2 state-space layers beside
a few GQA layers without rope): straightforward ``jax.numpy``, float32 under ``default_matmul_precision("highest")``,
gradients by ``jax.vjp`` of these functions, a layer at a time over bfloat16 weights that ``weights_ssd.py`` made
from the seed. It imports nothing of the program and is handed nothing the program made.

**The layers**, from the published ``config.json`` where it pins them and from the family's published description
(Dao and Gu, "Transformers are SSMs", ICML 2024; the Granite 4.0 release notes) and HF
``modeling_granitemoehybrid.py`` AS REMEMBERED where it does not (each such point is marked † and listed under
``assumed`` in the configuration file; there is no network here to read the code again).
``N(x; w) = x / rms(x) * w``, eps 1e-5; no bias on any projection.

    x = 12 * E[ids]                                                   (embedding_multiplier; E [vocab, hidden])
    for each layer:  x = x + 0.22 * mixer(N(x; w_in));  x = x + 0.22 * mlp(N(x; w_post))      (residual_multiplier)
    mlp(u) = (silu(u W_gate) * (u W_up)) W_down                       (HF's shared_mlp: input_linear = [gate | up]†)
    logits = N(x; w_f) E^T / 8                                        (tied; logits_scaling)

*Attention layers* (``layer_types`` ``attention``): ``q = u W_q`` (heads of ``head_dim`` = hidden / heads†), ``k, v
= u W_k, u W_v`` (kv heads); NO rope (``position_embedding_type`` ``nope``); ``o = softmax_causal(m * q k^T) v`` with
``m`` the config's ``attention_multiplier`` (1/64 where ``d ** -0.5`` would be 1/8); ``out = o W_o``. One query head
at a time (the scores of a whole row of 8192 would not fit beside the weights).

*Mamba-2 layers* (``mamba``; H heads of P channels, a state of N, ``G`` groups, inner = H P):
``[z | xBC | dt] = u W_in``, the columns in that order†, ``xBC`` inner + 2 G N wide;
``xBC = silu(conv(xBC) + b_conv)``: depthwise, causal, ``taps - 1`` zeros left of a row, ``conv(x)_t = sum_j w_j
x_{t - (taps - 1) + j}``; ``[x | B | C] = xBC`` cut at inner and inner + G N†; ``B_t``, ``C_t`` in R^N, one pair a
group of H / G heads;
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head (HF's ``time_step_limit`` (0, inf) clamps nothing†);
for head h with a state ``S [P, N]`` from zero: ``S = exp(dt_t A) S + dt_t x_t B_t^T;  y_t = S C_t + D x_t``.
Written here NOT in chunks and with no carried state, as the recurrence's dual form over the whole row: with ``G_i``
the running sum of ``dt A`` over the row,

    y_i = sum_{j <= i} exp(G_i - G_j) (C_i . B_j) dt_j x_j + D x_i,

the difference masked BEFORE the ``exp`` (``exp(-G_j)`` alone overflows), one head at a time so that the ``[T, T]``
float32 matrix of a row of 8192 fits (``tests/test_ssd.py`` holds this form to the token-by-token walk);
``y = N(y * silu(z); w_norm)``: the gate FIRST, then one norm over each group's inner / G channels†;
``out = y W_out``.

Loss: the token-mean next-token cross-entropy (every position counts: the traffic's masks are all ones), the head a
block of rows at a time (8192 x 100,352 float32 logits and their softmax would not fit beside the weights).

Departures from the published model: none in the mathematics beyond the † points. The masters of the trainable leaves
are kept in bfloat16 between steps because the recipe under test states bfloat16 masters; the MLP, the head and the
mixers go through their rows or heads in blocks that are recomputed in the backward pass, which changes what is held,
not what is computed.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chipbench.reference import (
    F32, _adam_apply, _add, _delta_sq, _embed, _embed_grad, _highest, _scale, _sq_norm, layer_leaves, lr_at, trainable_paths,
)

EMBED, FINAL_NORM = "model/embed_tokens/weight", "model/norm/weight"
ROWS_A_BLOCK = 4096  # of the MLP
HEAD_ROWS = 1024     # of the loss
HELD_ON_DEVICE = 1 << 30  # bytes of blocks' inputs a microbatch may keep on the device; more waits on the host


def cfg_items(cfg: dict):
    keys = ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "vocab_size", "num_hidden_layers",
            "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
            "attention_multiplier", "embedding_multiplier", "residual_multiplier", "logits_scaling")
    return tuple((k, cfg[k]) for k in keys)


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def mlp(w, u):
    rows = u.reshape(-1, u.shape[-1])
    block = math.gcd(rows.shape[0], ROWS_A_BLOCK)

    @jax.checkpoint
    def some_rows(r):
        return (jax.nn.silu(r @ w["mlp/gate_proj/kernel"]) * (r @ w["mlp/up_proj/kernel"])) @ w["mlp/down_proj/kernel"]

    return jax.lax.map(some_rows, rows.reshape(-1, block, rows.shape[-1])).reshape(u.shape)


def attention(q, k, v, scale):
    """q ``[b, t, heads, d]``, k and v ``[b, t, kv heads, d]`` -> o like q: causal softmax of ``scale * q k^T``."""
    b, t, heads, d = q.shape
    per_kv = heads // k.shape[2]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(block):
        qh, kh, vh = block                                           # [b, t, d] each
        scores = scale * jnp.einsum("bqd,bkd->bqk", qh, kh)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1), vh)

    by_head = lambda x, r: jnp.moveaxis(jnp.repeat(x, r, axis=2), 2, 0)  # noqa: E731  (query head i reads kv head i // per_kv)
    return jnp.moveaxis(jax.lax.map(one_head, (by_head(q, 1), by_head(k, per_kv), by_head(v, per_kv))), 0, 2)


def scan_dual(x, dt, a, bm, cm, d):
    """The recurrence's dual form over the whole row (module docstring). ``x [b, t, H, P]``, ``dt [b, t, H]`` (after
    the softplus), ``a [H]`` (negative), ``bm`` and ``cm`` ``[b, t, G, N]``, ``d [H]`` -> ``y [b, t, H, P]``."""
    b, t, heads, p = x.shape
    per_group = heads // bm.shape[2]
    run = jnp.cumsum(dt * a, axis=1)                                  # G_i  [b, t, H]
    cb = jnp.einsum("bign,bjgn->gbij", cm, bm)                        # C_i . B_j  [G, b, t, t]
    lower = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(head):
        x_h, dt_h, run_h, d_h, group = head                          # [b, t, P], [b, t], [b, t], [], []
        decay = jnp.exp(jnp.where(lower, run_h[:, :, None] - run_h[:, None, :], -jnp.inf))
        m = decay * jnp.take(cb, group, axis=0) * dt_h[:, None, :]
        return jnp.einsum("bij,bjp->bip", m, x_h) + d_h * x_h

    y = jax.lax.map(one_head, (jnp.moveaxis(x, 2, 0), jnp.moveaxis(dt, 2, 0), jnp.moveaxis(run, 2, 0), d, jnp.arange(heads) // per_group))
    return jnp.moveaxis(y, 0, 2)


def causal_conv(x, w, bias):
    """``x [b, t, channels]``, ``w [taps, channels]``: ``sum_j w_j x_{t - (taps - 1) + j} + bias``, zeros left of the row."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[j] * padded[:, j:j + t] for j in range(taps)) + bias


def mamba_mixer(w, u, cfg):
    b, t, _ = u.shape
    heads, p, n, groups = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_n_groups"]
    inner, gn = heads * p, groups * n
    zxbcdt = u @ w["mamba/in_proj/kernel"]
    z, xbc, dt = zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * gn], zxbcdt[..., 2 * inner + 2 * gn:]
    xbc = jax.nn.silu(causal_conv(xbc, w["mamba/conv1d/weight"], w["mamba/conv1d/bias"]))
    x, bm, cm = xbc[..., :inner], xbc[..., inner:inner + gn], xbc[..., inner + gn:]
    dt = jax.nn.softplus(dt + w["mamba/dt_bias"])
    y = scan_dual(x.reshape(b, t, heads, p), dt, -jnp.exp(w["mamba/A_log"]), bm.reshape(b, t, groups, n),
                  cm.reshape(b, t, groups, n), w["mamba/D"])
    gated = (y.reshape(b, t, inner) * jax.nn.silu(z)).reshape(b, t, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return (normed.reshape(b, t, inner) * w["mamba/norm/weight"]) @ w["mamba/out_proj/kernel"]


def attention_mixer(w, u, cfg):
    b, t, _ = u.shape
    nh, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (u @ w["self_attn/q_proj/kernel"]).reshape(b, t, nh, d)
    k, v = ((u @ w[f"self_attn/{name}_proj/kernel"]).reshape(b, t, kv, d) for name in "kv")
    return attention(q, k, v, cfg["attention_multiplier"]).reshape(b, t, nh * d) @ w["self_attn/o_proj/kernel"]


def layer_fn(lp, x, cfg):
    """One block: ``x += r * mixer(N(x)); x += r * mlp(N(x))``; which mixer is read from the layer's own leaves."""
    w = {k: v.astype(F32) for k, v in lp.items()}
    mixer = mamba_mixer if "mamba/in_proj/kernel" in w else attention_mixer
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    x = x + r * mixer(w, norm(x, w["input_layernorm/weight"], eps), cfg)
    return x + r * mlp(w, norm(x, w["post_attention_layernorm/weight"], eps))


@partial(jax.jit, static_argnums=(2,))
@_highest
def _layer_fwd(lp, x, items):
    return layer_fn(lp, x, dict(items))


@partial(jax.jit, static_argnums=(3,))
@_highest
def _layer_bwd_x(lp, x, dy, items):
    """Gradient to the block's input alone (a frozen block on the way down to the tied table's lookup)."""
    return jax.vjp(lambda xx: layer_fn(lp, xx, dict(items)), x)[1](dy)[0]


@partial(jax.jit, static_argnums=(3,))
@_highest
def _layer_bwd_all(lp, x, dy, items):
    """Gradients to the block's leaves (float32, taken at the bfloat16 values) and to its input."""
    lp32 = {k: v.astype(F32) for k, v in lp.items()}
    return jax.vjp(lambda ww, xx: layer_fn(ww, xx, dict(items)), lp32, x)[1](dy)


def head_loss(x, norm_w, table, ids, cfg):
    """Token-mean next-token cross-entropy of final hidden states ``x [rows, seq, hidden]``, ``HEAD_ROWS`` positions at
    a time: ``logits = N(x) E^T / logits_scaling``."""
    rows, seq, h = x.shape
    hid = norm(x, norm_w, cfg["rms_norm_eps"])[:, :-1].reshape(-1, h)
    gold = ids[:, 1:].reshape(-1)
    count = hid.shape[0]
    pad = -count % HEAD_ROWS
    hid, gold = jnp.pad(hid, ((0, pad), (0, 0))), jnp.pad(gold, (0, pad))
    counts = (jnp.arange(count + pad) < count).astype(F32)

    @jax.checkpoint
    def some_rows(block):
        hb, gb, cb = block
        logp = jax.nn.log_softmax((hb @ table.T) / cfg["logits_scaling"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, gb[:, None], axis=-1)[:, 0] * cb)

    blocks = lambda z: z.reshape(-1, HEAD_ROWS, *z.shape[1:])  # noqa: E731
    return jnp.sum(jax.lax.map(some_rows, (blocks(hid), blocks(gold), blocks(counts)))) / count


@partial(jax.jit, static_argnums=(4,))
@_highest
def _head_loss_grad(x, norm_w, table, ids, items):
    """The loss and its gradients to the final hidden states, the final norm and the tied table (the head's part)."""
    loss_of = lambda xx, nw, tab: head_loss(xx, nw, tab, ids, dict(items))  # noqa: E731
    return jax.value_and_grad(loss_of, argnums=(0, 1, 2))(x, norm_w.astype(F32), table.astype(F32))


def forward_hidden(flat: dict, cfg: dict, ids):
    """The final hidden states (before the last norm) and the input of every block (on the host where all of them
    together would crowd the device: 40 x 64 MiB at a row of 8192)."""
    items = cfg_items(cfg)
    x = cfg["embedding_multiplier"] * _embed(flat[EMBED], jnp.asarray(ids, jnp.int32))
    on_host = x.nbytes * cfg["num_hidden_layers"] > HELD_ON_DEVICE
    inputs = []
    for i in range(cfg["num_hidden_layers"]):
        inputs.append(np.asarray(x) if on_host else x)
        x = _layer_fwd(layer_leaves(flat, i), x, items)
    return x, inputs


@partial(jax.jit, static_argnums=(3,))
@_highest
def _logits(x, norm_w, table, items):
    cfg = dict(items)
    return norm(x, norm_w.astype(F32), cfg["rms_norm_eps"]) @ table.astype(F32).T / cfg["logits_scaling"]


def logits(flat: dict, cfg: dict, ids):
    """``[rows, seq, vocab]`` float32 (the tests' sizes)."""
    return _logits(forward_hidden(flat, cfg, ids)[0], flat[FINAL_NORM], flat[EMBED], cfg_items(cfg))


def microbatch_grads(flat, cfg, ids, trainable: set):
    """Loss of one microbatch ``ids [rows, seq]`` and the float32 gradients of the trainable leaves: one forward that
    keeps each block's input, one backward through EVERY block where the tied table is trainable (its lookup lies
    below them all), leaves' gradients where the block has trainable leaves."""
    items = cfg_items(cfg)
    n = cfg["num_hidden_layers"]
    ids = jnp.asarray(ids, jnp.int32)
    x, inputs = forward_hidden(flat, cfg, ids)
    loss, (dx, dnorm, dtab) = _head_loss_grad(x, flat[FINAL_NORM], flat[EMBED], ids, items)
    grads = {path: g for path, g in ((EMBED, dtab), (FINAL_NORM, dnorm)) if path in trainable}
    lowest = min([int(p.split("/")[2]) for p in trainable if p.startswith("model/layers/")] or [n])
    for i in range(n - 1, (0 if EMBED in trainable else lowest) - 1, -1):
        if i >= lowest:
            dlp, dx = _layer_bwd_all(layer_leaves(flat, i), jnp.asarray(inputs[i]), dx, items)
            grads.update({f"model/layers/{i}/{k}": g for k, g in dlp.items() if f"model/layers/{i}/{k}" in trainable})
        else:
            dx = _layer_bwd_x(layer_leaves(flat, i), jnp.asarray(inputs[i]), dx, items)
        inputs[i] = None
    if EMBED in trainable:  # the lookup's part of the tied table's gradient
        grads[EMBED] = _embed_grad(grads[EMBED], ids, cfg["embedding_multiplier"] * dx)
    return loss, grads


def sft_reference(flat: dict, cfg: dict, recipe: dict, batches, fresh_leaves, keep_first_grad=False) -> dict:
    """``reference.sft_reference`` for this architecture (copied: it names its own ``microbatch_grads`` inside): each
    step's loss, the first gradient's norm before the clip, its norm by leaf after the clip, and the norm by leaf of
    the parameters' change over the steps. ``batches``: one ``[accum, rows, seq]`` int array a step."""
    if recipe.get("optimizer", "adamw") != "adamw" or recipe.get("weight_decay", 0.0):
        raise ValueError("the reference knows AdamW without weight decay")
    flat = dict(flat)
    # the recipe's last layers and the tied table; a recipe that names no such split trains every leaf (the tests' float32 steps)
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
