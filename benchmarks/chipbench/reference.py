"""The plain reference: a dense decoder in straightforward ``jax.numpy``.

RMSNorm, grouped-query attention, rotary embeddings in the HF rotate-half
convention with SmolLM3's NoPE layers (every ``no_rope_layer_interval``-th
layer applies none), SwiGLU, a tied or untied output head, the token-mean
cross-entropy of SFT, its gradients for a trainable subset, and AdamW behind
a global-norm clip. Everything is float32 under
``default_matmul_precision("highest")``, one layer at a time over bfloat16
weights that ``weights.py`` made from the seed, so that it fits beside them.
It imports nothing of the program and is handed nothing the program made.

Departures from the published models: none in the mathematics. Masters of the
trainable leaves are kept in bfloat16 between steps because the recipe under
test states bfloat16 masters (``param_dtype``): the update is computed in
float32 and the sum is rounded once, as the recipe says.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _highest(fn):
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


def cfg_items(cfg: dict):
    keys = (
        "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "vocab_size", "num_hidden_layers", "rope_theta",
        "rms_norm_eps", "tie_word_embeddings", "no_rope_layer_interval",
    )
    return tuple((k, cfg.get(k, 0)) for k in keys)


def uses_rope(cfg: dict, layer: int) -> bool:
    interval = cfg.get("no_rope_layer_interval") or 0
    return not (interval and (layer + 1) % interval == 0)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope_tables(positions, head_dim, theta):
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def layer_fn(lp, x, cfg, rope):
    """One block. ``lp``: the layer's leaves by their path below the layer;
    ``x``: [rows, seq, hidden] float32."""
    b, t, _ = x.shape
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    w = {k: v.astype(F32) for k, v in lp.items()}
    hid = rms_norm(x, w["input_layernorm/weight"], eps)
    q = (hid @ w["self_attn/q_proj/kernel"]).reshape(b, t, nh, d)
    k = (hid @ w["self_attn/k_proj/kernel"]).reshape(b, t, nkv, d)
    v = (hid @ w["self_attn/v_proj/kernel"]).reshape(b, t, nkv, d)
    if rope:
        cos, sin = rope_tables(jnp.arange(t), d, cfg["rope_theta"])
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    q = q.reshape(b, t, nkv, nh // nkv, d)
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k) / math.sqrt(d)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v).reshape(b, t, nh * d)
    x = x + out @ w["self_attn/o_proj/kernel"]
    hid = rms_norm(x, w["post_attention_layernorm/weight"], eps)
    gate = hid @ w["mlp/gate_proj/kernel"]
    up = hid @ w["mlp/up_proj/kernel"]
    return x + (jax.nn.silu(gate) * up) @ w["mlp/down_proj/kernel"]


@partial(jax.jit, static_argnums=(2, 3))
@_highest
def _layer_fwd(lp, x, items, rope):
    return layer_fn(lp, x, dict(items), rope)


@partial(jax.jit, static_argnums=(3, 4))
@_highest
def _layer_bwd_x(lp, x, dy, items, rope):
    """Gradient to the block's input alone (a frozen block on the way down
    to a trainable embedding)."""
    _, vjp = jax.vjp(lambda xx: layer_fn(lp, xx, dict(items), rope), x)
    return vjp(dy)[0]


@partial(jax.jit, static_argnums=(3, 4))
@_highest
def _layer_bwd_all(lp, x, dy, items, rope):
    """Gradients to the block's leaves (as float32, taken at the bfloat16
    values) and to its input."""
    lp32 = {k: v.astype(F32) for k, v in lp.items()}
    _, vjp = jax.vjp(lambda ww, xx: layer_fn(ww, xx, dict(items), rope), lp32, x)
    return vjp(dy)


def _head_logits(x, norm_w, table, cfg):
    hid = rms_norm(x, norm_w.astype(F32), cfg["rms_norm_eps"])
    tab = table.astype(F32)
    if cfg["tie_word_embeddings"]:
        return hid @ tab.T
    return hid @ tab


@partial(jax.jit, static_argnums=(4,))
@_highest
def _head_loss_grad(x, norm_w, table, ids, items):
    """Token-mean next-token cross-entropy of one microbatch (every position
    counts: the traffic's masks are all ones), and its gradients to the final
    hidden states and to the head's table."""
    cfg = dict(items)

    def loss_of(xx, tab):
        logits = _head_logits(xx[:, :-1], norm_w, tab, cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        gold = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
        return -jnp.mean(gold)

    loss, (dx, dtab) = jax.value_and_grad(loss_of, argnums=(0, 1))(x, table.astype(F32))
    return loss, dx, dtab


@jax.jit
def _embed(table, ids):
    return table.astype(F32)[ids]


@jax.jit
def _embed_grad(acc, ids, dx):
    return acc.at[ids.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))


def layer_leaves(flat: dict, layer: int) -> dict:
    prefix = f"model/layers/{layer}/"
    return {k[len(prefix):]: v for k, v in flat.items() if k.startswith(prefix)}


def head_path(cfg: dict) -> str:
    return "model/embed_tokens/weight" if cfg["tie_word_embeddings"] else "lm_head/kernel"


def trainable_paths(cfg: dict, recipe: dict, all_paths) -> list:
    """The recipe's trainable subset of ``all_paths``: the last
    ``unfreeze_last_n_layers`` blocks and the output head, which is the
    embedding table where they are tied. The final norm and an untied input
    embedding stay frozen."""
    n = cfg["num_hidden_layers"]
    first = n - int(recipe["unfreeze_last_n_layers"])
    paths = []
    for path in all_paths:
        if path.startswith("model/layers/"):
            if int(path.split("/")[2]) >= first:
                paths.append(path)
        elif path == head_path(cfg):
            paths.append(path)
    return paths


def microbatch_grads(flat, cfg, ids, trainable: set):
    """Loss of one microbatch ``ids`` [rows, seq] and the gradients of the
    trainable leaves, float32, by one forward that keeps each block's input
    and one backward that stops below the lowest trainable leaf."""
    items = cfg_items(cfg)
    n = cfg["num_hidden_layers"]
    embed_path = "model/embed_tokens/weight"
    ids = jnp.asarray(ids, jnp.int32)
    x = _embed(flat[embed_path], ids)
    inputs = []
    for i in range(n):
        inputs.append(x)
        x = _layer_fwd(layer_leaves(flat, i), x, items, uses_rope(cfg, i))
    hp = head_path(cfg)
    loss, dx, dtab = _head_loss_grad(x, flat["model/norm/weight"], flat[hp], ids, items)
    grads = {}
    if hp in trainable:
        grads[hp] = dtab
    lowest = min(
        [int(p.split("/")[2]) for p in trainable if p.startswith("model/layers/")] or [n]
    )
    stop = 0 if embed_path in trainable else lowest
    for i in range(n - 1, stop - 1, -1):
        lp = layer_leaves(flat, i)
        if i >= lowest:
            dlp, dx = _layer_bwd_all(lp, inputs[i], dx, items, uses_rope(cfg, i))
            for k, g in dlp.items():
                path = f"model/layers/{i}/{k}"
                if path in trainable:
                    grads[path] = g
        else:
            dx = _layer_bwd_x(lp, inputs[i], dx, items, uses_rope(cfg, i))
        inputs[i] = None
    if embed_path in trainable:
        grads[embed_path] = _embed_grad(
            grads.get(embed_path, jnp.zeros(flat[embed_path].shape, F32)), ids, dx
        )
    return loss, grads


@jax.jit
def _sq_norm(x):
    return jnp.sum(jnp.square(x.astype(F32)))


@partial(jax.jit, static_argnums=(2, 3, 4), donate_argnums=(0,))
def _adam_apply(p, gs, b1, b2, eps, lr_t):
    """AdamW's step ``len(gs)`` for one leaf from its clipped gradients so
    far (moments written out as their sums: the same mathematics, and no
    moment arrays held between steps), added to the bfloat16 master."""
    t = len(gs)
    mu = sum((1 - b1) * b1 ** (t - 1 - k) * g for k, g in enumerate(gs))
    nu = sum((1 - b2) * b2 ** (t - 1 - k) * jnp.square(g) for k, g in enumerate(gs))
    m_hat = mu / (1 - b1 ** t)
    v_hat = nu / (1 - b2 ** t)
    update = -lr_t * m_hat / (jnp.sqrt(v_hat) + eps)
    return (p.astype(F32) + update).astype(p.dtype)


@jax.jit
def _scale(g, s):
    return g * s


@jax.jit
def _add(a, b):
    return a + b


@partial(jax.jit, donate_argnums=(0,))
def _delta_sq(p, p0):
    return jnp.sum(jnp.square(p.astype(F32) - p0.astype(F32)))


def lr_at(recipe: dict, count: int) -> float:
    """The recipe's schedule at optimizer count ``count`` (0 for step 1)."""
    lr = float(recipe["learning_rate"])
    if recipe.get("lr_schedule", "linear") == "constant":
        return lr
    if recipe["lr_schedule"] != "linear" or recipe.get("warmup_ratio", 0.0):
        raise ValueError("the reference knows the linear schedule without warm-up")
    total = int(recipe["total_steps"])
    return lr * (1.0 - min(count, total) / total)


def sft_reference(flat: dict, cfg: dict, recipe: dict, batches, fresh_leaves, keep_first_grad=False) -> dict:
    """Follow the first ``len(batches)`` optimizer steps. ``flat``: the
    seed's weights (the trainable leaves are replaced as the steps go);
    ``batches``: one [accum, rows, seq] int array a step; ``fresh_leaves``:
    a callable that makes the named leaves again from the seed. Returns each
    step's loss, the first gradient's norm before the clip, the norm by leaf
    of the first gradient as the optimizer gets it (after the clip), and the
    norm by leaf of the parameters' change over the steps."""
    if recipe.get("optimizer", "adamw") != "adamw" or recipe.get("weight_decay", 0.0):
        raise ValueError("the reference knows AdamW without weight decay")
    flat = dict(flat)
    train = set(trainable_paths(cfg, recipe, flat))
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
    names = sorted(train)
    out["delta_norms"] = {}
    for k in names:  # one leaf of the seed's weights at a time
        p0 = fresh_leaves([k])[k]
        out["delta_norms"][k] = math.sqrt(float(_delta_sq(flat.pop(k), p0)))
    return out


@partial(jax.jit, static_argnums=(4,))
@_highest
def _gap_rows(x, norm_w, table, served, items):
    logits = _head_logits(x, norm_w, table, dict(items))
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return best - got


def served_token_gaps(flat: dict, cfg: dict, prompt, served, rows: int = 256) -> np.ndarray:
    """One forward over ``prompt`` followed by the tokens that were served for
    it; for each served token, how far its logit lies below the best logit at
    its position (0 where the served token is the reference's own choice).
    The sequence is padded at its end to a multiple of ``rows`` (attention is
    causal, so no real position sees the pad) to keep the compiled shapes few."""
    items = cfg_items(cfg)
    tokens = np.asarray(list(prompt) + list(served), np.int32)[:-1]
    n_out, first = len(served), len(prompt) - 1
    padded = np.zeros((-(-(first + -(-n_out // rows) * rows) // rows) * rows,), np.int32)
    padded[: len(tokens)] = tokens
    x = _embed(flat["model/embed_tokens/weight"], jnp.asarray(padded)[None, :])
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_fwd(layer_leaves(flat, i), x, items, uses_rope(cfg, i))
    want = np.zeros((-(-n_out // rows) * rows,), np.int32)
    want[:n_out] = served
    gaps = []
    for lo in range(0, len(want), rows):
        gaps.append(np.asarray(_gap_rows(
            x[0, first + lo:first + lo + rows], flat["model/norm/weight"],
            flat[head_path(cfg)], jnp.asarray(want[lo:lo + rows]), items,
        )))
    return np.concatenate(gaps)[:n_out]
