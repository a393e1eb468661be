"""The two operations of a Gated DeltaNet mixer that are not projections
(Qwen3-Next's ``linear_attention`` layers, HF ``Qwen3NextGatedDeltaNet``): a
causal depthwise convolution of a few taps, and the gated delta rule, a
recurrence over time that stands where softmax attention stands elsewhere.

The rule, for one row and one value head, with a state ``S [d_k, d_v]`` from
zero and for t = 0, 1, ...:

    S *= exp(g_t);  d = beta_t (v_t - S^T k_t);  S += k_t d^T;  o_t = S^T q_t

(``g_t <= 0`` a log decay, ``beta_t`` in (0, 1), q and k l2-normed by the
caller). Token by token that is a chain of ``seq`` dependent steps of rank-one
updates; it stays in the reference (``benchmarks/chipbench/reference_gdn_moe.py``).
Here the rule runs in CHUNKED form, the one implementation on every backend:

- a row is cut into chunks of ``CHUNK`` tokens; inside a chunk, with ``G_i``
  the running sum of g from the chunk's start, the updates ``d_i`` of all its
  tokens solve one unit lower-triangular system,
  ``(I + A) D = beta (V - exp(G) K S_0)`` with
  ``A_ij = beta_i exp(G_i - G_j) k_i.k_j`` for j < i, so with
  ``T = (I + A)^-1``, ``U = T (beta V)`` and ``W = T (beta exp(G) K)``:
  ``D = U - W S_0``. T, U, W and the decayed ``q k^T`` are made for all chunks
  at once, as batched matrix products;
- across chunks a ``lax.scan`` carries ``S`` in float32 (``STATE_DTYPE``):
  ``D = U - W S``, ``O = exp(G) (Q S) + (decay * Q K^T) D``,
  ``S = exp(G_C) S + K^T (exp(G_C - G) D)``: ``seq / CHUNK`` dependent steps of
  full matrix products instead of ``seq`` rank-one ones.

A decay is only ever formed as ``exp(G_i - G_j)`` with ``i >= j`` (at most 1),
masked BEFORE the ``exp``: ``exp(-G_j)`` alone overflows float32 at the decays
``A_log`` allows (a head with ``A = 16`` loses ``exp(-16 softplus(.))`` a token).

``T``: a unit lower-triangular ``C x C`` matrix, inverted by XLA's triangular
solve against the identity (``unit_lower_inverse``), in float32. Measured on a
v5e at the Qwen3-Next cell's shapes (``benchmarks/gdn_kernels.py``, PERF.md, PR
32: 2 rows of 8192, forward / forward + backward of the rule): the solve 13.9 /
34.1 ms at chunks of 64, against 19.3 / 48.9 for doublings ``(I - A)(I +
A^2)(I + A^4)...`` at HIGHEST precision (15.1 / 41.0 at the default one); at
chunks of 128 the solve is the slowest (50.7 / 69.1) and doublings read 23.7 /
41.0. So: chunks of 64 and the solve; the doublings live on in that tool.

The backward pass is autodiff of the scan with its body rematerialized: what
it holds of a layer is the state at every chunk boundary (``rows x seq / CHUNK
x value heads x d_k x d_v`` float32) and the per-chunk inputs, never a state a
token.

Rows whose length is no multiple of the chunk are padded with tokens that
change nothing (k = 0, beta = 0, g = 0) and the padding's outputs dropped.
Right-padded rows of a batch need nothing: the rule is causal. Packed rows
(``segment_ids``) are NOT supported: state and convolution would have to
restart at a boundary (the model refuses them, ROADMAP.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Tokens a chunk. HF's torch fallback and the flash-linear-attention kernels
# use 64. (What 128 costs on a v5e: PERF.md, PR 32.)
CHUNK = 64
# The carried state's dtype (a test lowers it to show that the comparison with
# the reference sees it; nothing else sets it).
STATE_DTYPE = jnp.float32

# {(rows, seq, key heads, value heads, d_k, d_v): [calls traced, form]} of every
# ``gated_delta_rule`` traced in this process (as ``flash_attention.GRID_TILES``
# says what a grid was built to visit): which form the linear layers' rule took.
CALLS: dict = {}


def calls_summary() -> str:
    """One line for entry points to print beside ``dispatch_summary()``."""
    said = "; ".join(f"{list(shape)}: {form} x {n}" for shape, (n, form) in sorted(CALLS.items()))
    return f"gated delta rule traced as: {said or 'nothing traced'}"


def _shifted_sum(padded, w, offsets, length, dtype):
    """``sum_j w[j] padded[:, offsets[j]:offsets[j] + length]`` in float32, out in
    ``dtype``: each tap's slice is cast on its own, inside one fusion, so no
    float32 copy of the padded activation exists."""
    return sum(padded[:, o:o + length].astype(jnp.float32) * w[j] for j, o in enumerate(offsets)).astype(dtype)


@jax.custom_vjp
def causal_conv(x, weight):
    """Causal depthwise convolution: ``x [b, s, channels]``, ``weight [taps,
    channels]`` -> ``y_t = sum_j weight[j] x_{t - (taps - 1) + j}`` with zeros
    left of the row (torch ``Conv1d(groups=channels, padding=taps - 1)`` cut
    to the row's length; ``weight[j]`` is torch's ``weight[:, 0, j]``). No
    bias. Float32 inside, the input's dtype out.

    The taps are shifted multiply-adds, and the backward pass is written out:
    the input's cotangent is the same sum run against time, the taps' are
    ``taps`` reductions. (Autodiff of the slices pads each tap's cotangent to
    the whole row and adds them. On a v5e, convolution and silu, forward and
    backward, on 2 rows of 8192 x 8192 channels: 5.3 ms like this, 14.9 by
    autodiff over a float32 padded copy, 20.9 as ``lax.conv_general_dilated``
    with a group a channel: ``benchmarks/gdn_kernels.py``, PERF.md, PR 32.)"""
    taps, s = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return _shifted_sum(padded, weight.astype(jnp.float32), range(taps), s, x.dtype)


def _causal_conv_fwd(x, weight):
    return causal_conv(x, weight), (x, weight)


def _causal_conv_bwd(kept, dy):
    x, weight = kept
    taps, s = weight.shape[0], x.shape[1]
    ahead = jnp.pad(dy, ((0, 0), (0, taps - 1), (0, 0)))  # dx_t = sum_j weight[j] dy_{t + (taps - 1) - j}
    dx = _shifted_sum(ahead, weight.astype(jnp.float32), range(taps - 1, -1, -1), s, x.dtype)
    behind = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    dy32 = dy.astype(jnp.float32)
    dw = jnp.stack([jnp.sum(behind[:, j:j + s].astype(jnp.float32) * dy32, axis=(0, 1)) for j in range(taps)])
    return dx, dw.astype(weight.dtype)


causal_conv.defvjp(_causal_conv_fwd, _causal_conv_bwd)


def l2_norm(x, eps: float = 1e-6):
    """``x * rsqrt(sum x^2 + eps)`` over the last axis, float32 inside."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + eps)).astype(x.dtype)


def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a [..., C, C]`` strictly lower triangular: a
    triangular solve against the identity (module docstring: measured)."""
    eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
    return jax.scipy.linalg.solve_triangular(eye + a, eye, lower=True, unit_diagonal=True)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK):
    """The gated delta rule over whole rows, chunked (module docstring).

    ``q``, ``k`` ``[b, s, key heads, d_k]`` (l2-normed, q scaled by the
    caller), ``v`` ``[b, s, value heads, d_v]``, ``g`` and ``beta`` ``[b, s,
    value heads]`` float32; key head ``i`` serves value heads ``i r .. i r + r
    - 1`` (``repeat_interleave``). Returns ``o [b, s, value heads, d_v]`` in
    ``v``'s dtype. Matrix products take their operands in ``v``'s dtype and
    add up in float32; decays, the triangular inverse and the carried state
    are float32."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    cd, f32 = v.dtype, jnp.float32
    entry = CALLS.setdefault((b, s, hk, hv, dk, dv), [0, f"chunked {chunk}"])
    entry[0] += 1

    pad = -s % chunk
    if pad:  # tokens that change nothing: k = 0, beta = 0, g = 0
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta))
    n = (s + pad) // chunk
    qc = q.reshape(b, n, chunk, hk, dk)
    kc = k.reshape(b, n, chunk, hk, dk)
    vc = v.reshape(b, n, chunk, hk, r, dv)
    gc = g.astype(f32).reshape(b, n, chunk, hk, r)
    bc = beta.astype(f32).reshape(b, n, chunk, hk, r)

    cum = jnp.cumsum(gc, axis=2)                                   # G_i, from the chunk's start
    cum_t = jnp.moveaxis(cum, 2, -1)                               # [b, n, hk, r, C]
    rows, cols = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(rows >= cols, cum_t[..., :, None] - cum_t[..., None, :], -jnp.inf))  # [.., C, C]; 0 above
    kk = jnp.einsum("bnigd,bnjgd->bngij", kc, kc, preferred_element_type=f32)
    qk = jnp.einsum("bnigd,bnjgd->bngij", qc, kc, preferred_element_type=f32)
    beta_t = jnp.moveaxis(bc, 2, -1)
    a = jnp.where(rows > cols, beta_t[..., :, None] * decay * kk[:, :, :, None], 0.0)
    t = unit_lower_inverse(a).astype(cd)                           # [b, n, hk, r, C, C]
    qk_decayed = (decay * qk[:, :, :, None]).astype(cd)            # diagonal included

    v_beta = (vc * bc[..., None].astype(cd))
    k_beta = kc[:, :, :, :, None, :] * (bc * jnp.exp(cum))[..., None].astype(cd)
    # (chunks lead what the scan walks)
    u = jnp.einsum("bngrij,bnjgrv->nbgriv", t, v_beta, preferred_element_type=f32).astype(cd)
    w = jnp.einsum("bngrij,bnjgrd->nbgrid", t, k_beta, preferred_element_type=f32).astype(cd)
    last = cum_t[..., -1:]
    xs = (
        u, w, jnp.moveaxis(qk_decayed, 1, 0),
        jnp.transpose(qc, (1, 0, 3, 2, 4)), jnp.transpose(kc, (1, 0, 3, 2, 4)),  # [n, b, hk, C, dk]
        jnp.moveaxis(jnp.exp(cum_t), 1, 0),                                     # exp(G_i)        [n, b, hk, r, C]
        jnp.moveaxis(jnp.exp(last - cum_t), 1, 0),                              # exp(G_C - G_i)
        jnp.moveaxis(jnp.exp(last[..., 0]), 1, 0),                              # exp(G_C)        [n, b, hk, r]
    )

    @jax.checkpoint  # the backward pass holds the state at each boundary and makes d again
    def step(state, x):
        u_c, w_c, qk_c, q_c, k_c, e_g, e_rest, e_all = x
        s_c = state.astype(cd)
        d = u_c.astype(f32) - jnp.einsum("bgrid,bgrdv->bgriv", w_c, s_c, preferred_element_type=f32)
        o = e_g[..., None] * jnp.einsum("bgid,bgrdv->bgriv", q_c, s_c, preferred_element_type=f32)
        o = o + jnp.einsum("bgrij,bgrjv->bgriv", qk_c, d.astype(cd), preferred_element_type=f32)
        grown = jnp.einsum("bgid,bgriv->bgrdv", k_c, (e_rest[..., None] * d).astype(cd), preferred_element_type=f32)
        state = (e_all[..., None, None] * state.astype(f32) + grown).astype(STATE_DTYPE)
        return state, o.astype(cd)

    _, o = jax.lax.scan(step, jnp.zeros((b, hk, r, dk, dv), STATE_DTYPE), xs)
    o = jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(b, n * chunk, hv, dv)     # [n, b, hk, r, C, dv] -> rows
    return o[:, :s]
