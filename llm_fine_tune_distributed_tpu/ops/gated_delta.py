"""The operations of a Gated DeltaNet mixer that are not projections
(Qwen3-Next's ``linear_attention`` layers, HF ``Qwen3NextGatedDeltaNet``): the
gated delta rule, a recurrence over time that stands where softmax attention
stands elsewhere, and the elementwise work on either side of it: on the way IN
a causal depthwise convolution of a few taps, silu, the l2 norms of q and k
and q's scale (``mixer_in``), on the way OUT a norm gated by ``silu(z)``
(``gated_norm``).

Each of the three runs as one of two programs that compute the same thing.
Which one takes a call is read from the input and the backend
(``gated_delta_rule``, ``mixer_in``, ``gated_norm``; ``_program``; no knob):
Pallas kernels on a TPU where a head is whole lanes (``d_k``, ``d_v``
multiples of 128; the rule also wants chunks of 64), XLA operations everywhere
else (a CPU, a GPU, the ``tiny_*`` presets' heads of 16 on any backend), which
is also what the tests hold the kernels to. ``CALLS`` says which the rule
took and ``PASSES`` which the two passes took, and on a TPU why not the
kernels; ``calls_summary()`` is the line entry points print.

The two passes (the section at the end of the file). As XLA operations:
``causal_conv`` (a ``custom_vjp``: shifted multiply-adds over a padded copy),
``jax.nn.silu``, ``l2_norm``, ``ops/norms.rms_norm``; float32 inside each,
rounded to the activation's dtype between them. As kernels
(``gdn_in_fwd``/``gdn_in_bwd``, ``gdn_out_fwd``/``gdn_out_bwd``, each pair
behind a ``custom_vjp``): every activation is read once where it lies, flat
``[b, s, heads x d]``, and written once in the layout the next consumer reads
(the rule's kernels, ``out_proj``); float32 inside and ONE rounding, at the
output, so never fewer bits than the XLA form (which rounds after the
convolution, after the norm, after the scale, and after ``rms_norm``); the
same ``eps``, the same zeros left of a row, the weight broadcast the same way.
Each ``custom_vjp`` keeps its inputs and nothing else (the in pass: the
projection's q, k, v columns and the taps; the out pass: ``o``, ``z`` and the
norm's weight): the backward kernels make the convolution's output, the norms
and the gate again in VMEM.

The rule, for one row and one value head, with a state ``S [d_k, d_v]`` from
zero and for t = 0, 1, ...:

    S *= exp(g_t);  d = beta_t (v_t - S^T k_t);  S += k_t d^T;  o_t = S^T q_t

(``g_t <= 0`` a log decay, ``beta_t`` in (0, 1), q and k l2-normed by the
caller). Token by token that is a chain of ``seq`` dependent steps of rank-one
updates; it stays in the reference (``benchmarks/chipbench/reference_gdn_moe.py``).
Here the rule runs in CHUNKED form, as one of two programs that compute the
same thing in the same dtypes:

- on a TPU where a head is whole lanes (``d_k`` and ``d_v`` multiples of 128)
  and the chunk is 64: two Pallas kernels (``gdn_rule_fwd``, ``gdn_rule_bwd``,
  behind a ``custom_vjp``) that keep a chunk's matrices and the state in VMEM;
- everywhere else (a CPU, a GPU, the ``tiny_*`` presets' heads of 16 on any
  backend, another chunk): XLA operations (``_rule_xla``), which is also what
  the tests hold the kernels to.

The decay is a scalar a head and token there (``g [b, s, value heads]``, Gated
DeltaNet). Kimi Delta Attention decays every CHANNEL of a head apart (``g [b, s,
value heads, d_k]``: ``S = diag(exp(g_t)) S``), which ``gated_delta_rule`` reads
from ``g``'s rank; that rule has the same two forms under the same conditions,
``kda_rule_fwd`` / ``kda_rule_bwd`` and ``_rule_xla_by_channel`` (their sections'
comments: what a vector decay does to a chunk), and shares the walk, the inverse
and the ``custom_vjp`` with the scalar one.

The chunked form:

- a row is cut into chunks of ``CHUNK`` tokens; inside a chunk, with ``G_i``
  the running sum of g from the chunk's start, the updates ``d_i`` of all its
  tokens solve one unit lower-triangular system,
  ``(I + A) D = beta (V - exp(G) K S_0)`` with
  ``A_ij = beta_i exp(G_i - G_j) k_i.k_j`` for j < i, so with
  ``T = (I + A)^-1``, ``U = T (beta V)`` and ``W = T (beta exp(G) K)``:
  ``D = U - W S_0``. The XLA form makes T, U, W and the decayed ``q k^T`` for
  all chunks at once, as batched matrix products (through HBM); the kernels
  make them a few chunks at a time and write none of them;
- across chunks the state ``S`` is carried in float32 (``STATE_DTYPE``), by a
  ``lax.scan`` or in the kernels' VMEM scratch along a sequential grid axis:
  ``D = U - W S``, ``O = exp(G) (Q S) + (decay * Q K^T) D``,
  ``S = exp(G_C) S + K^T (exp(G_C - G) D)``: ``seq / CHUNK`` dependent steps of
  full matrix products instead of ``seq`` rank-one ones.

A decay is only ever formed as ``exp(G_i - G_j)`` with ``i >= j`` (at most 1),
masked BEFORE the ``exp``: ``exp(-G_j)`` alone overflows float32 at the decays
``A_log`` allows (a head with ``A = 16`` loses ``exp(-16 softplus(.))`` a token).

``T``: a unit lower-triangular ``C x C`` matrix in float32. The XLA form
inverts it by XLA's triangular solve against the identity
(``unit_lower_inverse``). Measured on a v5e at the Qwen3-Next cell's shapes
(``benchmarks/gdn_kernels.py``, PERF.md, PR 32: 2 rows of 8192, forward /
forward + backward of the rule): the solve 13.9 / 34.1 ms at chunks of 64,
against 19.3 / 48.9 for doublings ``(I - A)(I + A^2)(I + A^4)...`` at HIGHEST
precision (15.1 / 41.0 at the default one); at chunks of 128 the solve is the
slowest (50.7 / 69.1) and doublings read 23.7 / 41.0. So: chunks of 64 and the
solve; the doublings live on in that tool. The kernels invert by levels of
diagonal blocks (``_inverse_in_vmem``: why, and in which precision).

The backward pass. Of the XLA form: autodiff of the scan with its body
rematerialized: what it holds of a layer is the state at every chunk boundary
(``rows x seq / CHUNK x value heads x d_k x d_v`` float32), T, U, W and the
per-chunk inputs, never a state a token. Of the kernels: the ``custom_vjp``
keeps the inputs and the state at every EIGHTH chunk boundary (the forward
sweep always writes it, 67 MB a call of the Qwen3-Next cell, so that the sweep
is ONE program whether a gradient follows or not); the backward sweep walks a
row's steps last to first, makes a step's ``T``, ``U``, ``W``, ``D`` and
states again in VMEM, then the chunks against time with the state's cotangent
carried in VMEM. With a decay a channel ``T`` and the two decayed products are
the dear half of that (three float32 sub-block products at HIGHEST, the
pairwise ``exp`` blocks, the inverse), so ``kda_rule_fwd`` writes them beside
the states, 40 KiB a chunk and head, and ``kda_rule_bwd`` reads them and makes
only ``U``, ``W``, ``D`` and the states again (PERF.md, PR 48).

Rows whose length is no multiple of the chunk are padded with tokens that
change nothing (k = 0, beta = 0, g = 0) and the padding's outputs dropped.
Right-padded rows of a batch need nothing: the rule is causal. Packed rows
(``segment_ids``) are NOT supported: state and convolution would have to
restart at a boundary (the model refuses them, ROADMAP.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_fine_tune_distributed_tpu.ops.tiling import by_eights

# Tokens a chunk. HF's torch fallback and the flash-linear-attention kernels
# use 64. (What 128 costs on a v5e: PERF.md, PR 32.)
CHUNK = 64
# The carried state's dtype (a test lowers it to show that the comparison with
# the reference sees it; nothing else sets it).
STATE_DTYPE = jnp.float32

# What the rule names (``checkpoint_name``) for a rematerialized block to keep (``models/transformer._remat_policy``, as
# the flash kernels' ``o`` and ``lse`` and the expert layer's routing are): its output ``o`` and, where the kernels run,
# what the backward sweep reads of the forward one: the state each step starts from and, of the rule with a decay a
# channel, each chunk's ``T`` beside its decayed ``k k^T`` and its ``P`` (``KEPT_BY_CHANNEL``, the names of
# ``kda_rule_fwd``'s four outputs in order). Under any policy that does not save them the names are inert. A sweep's
# names go together: with one of them missing, the recomputed pass still runs the whole forward sweep for it
# (``_flat_rule``). The XLA forms carry their state through their own scan and name ``o`` only. By the chip (PERF.md,
# PRs 44 and 48; a microbatch of 2 rows of 8192 at 32 value heads of 128): ``o`` 128 MiB and the states 64 MiB a layer
# buy the forward sweep's second run (4.78 ms a call of the scalar rule, 9.0 with a decay a channel: 0.050 and 0.094 ms
# a MiB), the three matrices' 320 MiB a layer 6.7 ms of the backward sweep less 0.15 of the forward (0.041 ms a MiB).
KEPT_ACROSS_REMAT = ("gdn_o", "gdn_states")
KEPT_BY_CHANNEL = KEPT_ACROSS_REMAT + ("kda_t_kk", "kda_p")

# {(rows, seq, key heads, value heads, d_k, d_v): [calls traced, form]} of every
# ``gated_delta_rule`` traced in this process (as ``flash_attention.GRID_TILES``
# says what a grid was built to visit): which form the linear layers' rule took.
CALLS: dict = {}
# {(pass, rows, seq, channels): [calls traced, form]} of every ``mixer_in`` ("in") and ``gated_norm`` ("out") traced in
# this process; a dict of its own because ``CALLS`` is the rule's (``gdn_chunked_calls_pct`` reads all of it).
PASSES: dict = {}


def calls_summary() -> str:
    """One line for entry points to print beside ``dispatch_summary()``."""
    said = "; ".join(f"{list(shape)}: {form} x {n}" for shape, (n, form) in sorted(CALLS.items()))
    passes = "; ".join(f"{which} {list(shape)}: {form} x {n}" for (which, *shape), (n, form) in sorted(PASSES.items()))
    return f"gated delta rule traced as: {said or 'nothing traced'}" + (f"; mixer passes: {passes}" if passes else "")


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


L2_EPS = 1e-6


def l2_norm(x, eps: float = L2_EPS):
    """``x * rsqrt(sum x^2 + eps)`` over the last axis, float32 inside."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + eps)).astype(x.dtype)


def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a [..., C, C]`` strictly lower triangular: a
    triangular solve against the identity (module docstring: measured)."""
    eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
    return jax.scipy.linalg.solve_triangular(eye + a, eye, lower=True, unit_diagonal=True)


def _padded_rows(arrays, multiple):
    """``arrays [b, s, ...]`` padded with zeros along the row to whole multiples, and the rows' own length. What a pad
    computes reaches no real token (rule and passes are causal), and to the rule a zero token changes nothing (k = 0,
    beta = 0, g = 0)."""
    s = arrays[0].shape[1]
    pad = -s % multiple
    return [jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) if pad else x for x in arrays], s


def _rule_xla(q, k, v, g, beta, *, chunk: int = CHUNK):
    """The chunked rule as XLA operations (module docstring): it runs on any backend, and the kernels are held to it.

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

    (q, k, v, g, beta), s = _padded_rows((q, k, v, g, beta), chunk)
    n = q.shape[1] // chunk
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


# -- the rule with a decay a CHANNEL (Kimi Delta Attention) -----------------------
#
# ``g [b, s, value heads, d_k]``: ``S = diag(exp(g_t)) S`` in place of ``S *= exp(g_t)``; the rest of the rule as it
# stands. The chunked form is the same algebra with the decays inside the products: with ``G_i`` (a vector now) the
# running sum from the chunk's start,
#
#     A_ij = beta_i sum_d k_id k_jd exp(G_id - G_jd)  (j < i),   P_ij = sum_d q_id k_jd exp(G_id - G_jd)  (j <= i),
#     W = T (beta (K * exp(G))),   O = (Q * exp(G)) S + P D,   S' = diag(exp(G_C)) S + (K * exp(G_C - G))^T D.
#
# With a scalar decay ``exp(G_i - G_j)`` leaves the sum and one product ``k k^T`` serves a chunk. With a vector it
# does not, and the split ``(k_i * exp(G_i)) . (k_j * exp(-G_j))`` overflows: ``exp(A_log)`` reaches 16 a token, ``-G``
# passes 1000 inside one chunk of 64, float32 ends at ``exp(88)``. So a chunk is cut a second time, into sub-blocks of
# ``SUB`` tokens:
#
# - a sub-block against an EARLIER one, relative to the boundary between them: ``R_I`` the running sum at the last
#   token before sub-block I, ``(k_i * exp(G_i - R_I)) . (k_j * exp(R_I - G_j))`` for i in I and every j before I.
#   Both exponents are sums of g over whole runs of tokens, so <= 0 whatever g is: a factor can underflow (to a
#   product that is below float32 anyway), none can overflow. One product a sub-block I, against all tokens before it;
# - a sub-block against ITSELF from pairwise differences, ``exp(G_i - G_j)`` for j <= i and masked before the ``exp``
#   as everywhere in this file: ``[SUB, SUB, d_k]`` elementwise work and a sum over the channels, no product.
#
# Both in float32 (the products at HIGHEST: their operands are k times a decay, which bfloat16 would round a second
# time before the triangular inverse sees them), inside ONE ``jax.checkpoint``: its backward pass makes the decayed
# operands again, so none of them outlives the few operations that read it, and the pairwise block is never written
# at all (``_own_products``: of all chunks at once it would be 4 GiB a call at the Kimi cell's shapes). This is the
# form of every backend but a TPU at heads of whole lanes, and what the kernels further down are held to.

SUB = 16


def _running_sums(local):
    """``(R_I, G_i)`` from ``local [..., m, SUB, d_k]``, g's running sum from each sub-block's start: the running sum at
    the last token before sub-block I ``[..., m, d_k]``, and at every token, both from the CHUNK's start."""
    totals = local[..., -1, :]
    before = jnp.cumsum(totals, axis=-2) - totals
    return before, before[..., None, :] + local


def _pairwise_decay(local):
    """``exp(G_i - G_j)`` for ``j <= i`` inside each sub-block, 0 above: ``[..., m, SUB, SUB, d_k]``, masked before
    the ``exp``. Never meant to reach memory: every use below is one fusion that sums it away."""
    c = local.shape[-2]
    rows, cols = jnp.arange(c)[:, None, None], jnp.arange(c)[None, :, None]
    return jnp.exp(jnp.where(rows >= cols, local[..., :, None, :] - local[..., None, :, :], -jnp.inf))


@jax.custom_vjp
def _own_products(x, k, local):
    """``sum_d x_id k_jd exp(G_id - G_jd)`` for ``j <= i`` of one sub-block (0 above): ``x [2, ..., m, SUB, d_k]``
    (k and q stacked, so that the pairwise decay has ONE reader), ``k``, ``local`` ``[..., m, SUB, d_k]``, all float32
    -> ``[2, ..., m, SUB, SUB]``. The backward pass is written out because autodiff's is not affordable: it reads the
    pairwise block (``SUB`` times the inputs' size, 4 GiB a call at the Kimi cell's shapes) from several fusions, and
    XLA then writes it out rather than take an ``exp`` twice. Here it has two readers, the sum over j and the sum
    over i, each handed its own copy of ``local`` behind an optimization barrier so that each takes its own ``exp``;
    the decay's cotangent needs no third: ``dG_i = sum_z x_zi dx_zi`` and ``dG_j = -k_j dk_j`` follow from the other two."""
    return jnp.stack([jnp.sum(x_z[..., :, None, :] * k[..., None, :, :] * _pairwise_decay(local), axis=-1) for x_z in x])


def _own_products_bwd(kept, d):
    x, k, local = kept
    over_j, over_i = jax.lax.optimization_barrier((local, local))
    dx = jnp.stack([jnp.sum(d_z[..., None] * k[..., None, :, :] * _pairwise_decay(over_j), axis=-2) for d_z in d])  # [2, ..., i, d]
    through = sum(d_z[..., None] * x_z[..., :, None, :] for d_z, x_z in zip(d, x))                     # [..., i, j, d]
    dk = jnp.sum(through * _pairwise_decay(over_i), axis=-3)                                           # [..., j, d]
    return dx, dk, jnp.sum(x * dx, axis=0) - k * dk


_own_products.defvjp(lambda x, k, local: (_own_products(x, k, local), (x, k, local)), _own_products_bwd)


@jax.checkpoint
def _decayed_products(q, k, local):
    """``(sum_d k_id k_jd exp(G_id - G_jd), sum_d q_id k_jd exp(G_id - G_jd))`` for ``j <= i`` (anything above the
    diagonal), each ``[..., C, C]`` float32, of ``q``, ``k`` ``[..., m, SUB, d_k]`` (a chunk's sub-blocks) and
    ``local``, g's running sum from each SUB-BLOCK's start, float32 (the section's comment)."""
    f32 = jnp.float32
    m, c = q.shape[-3], q.shape[-2]
    q, k = q.astype(f32), k.astype(f32)
    before, cum = _running_sums(local)                               # R_I and G_i, from the chunk's start
    x = jnp.stack([k, q])
    own = _own_products(x, k, local)                                 # [2, ..., m, c, c]
    x_in = x * jnp.exp(local)                                        # relative to the boundary before the sub-block
    flat = lambda y: y.reshape(y.shape[:-3] + (m * c, y.shape[-1]))  # noqa: E731
    k_flat, cum_flat = flat(k), flat(cum)
    blocks = []
    for i in range(m):
        parts = []
        if i:
            k_out = k_flat[..., :i * c, :] * jnp.exp(before[..., i, None, :] - cum_flat[..., :i * c, :])
            parts.append(jnp.einsum("z...ad,...jd->z...aj", x_in[..., i, :, :], k_out,
                                    precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32))
        parts.append(own[..., i, :, :])
        if i < m - 1:
            parts.append(jnp.zeros(own.shape[:-3] + (c, (m - 1 - i) * c), f32))
        blocks.append(jnp.concatenate(parts, axis=-1))
    both = jnp.concatenate(blocks, axis=-2)
    return both[0], both[1]


@jax.checkpoint
def _decayed_operands(q, k, beta, local):
    """What the walk over chunks multiplies by, in ``q``'s dtype: ``beta K * exp(G)``, ``Q * exp(G)``, ``K * exp(G_C -
    G)`` (each ``[..., C, d_k]``) and ``exp(G_C)`` ``[..., d_k]`` float32, of ``q``, ``k`` ``[..., C, d_k]``, ``beta
    [..., C, 1]`` and ``local`` as ``_decayed_products`` takes it. Rematerialized: what it keeps is its arguments, not
    five float32 arrays a token and channel."""
    f32 = jnp.float32
    cum = _running_sums(local)[1].reshape(k.shape)                   # G_i, from the chunk's start
    e, last = jnp.exp(cum), cum[..., -1:, :]
    k32 = k.astype(f32)
    return ((k32 * (beta * e)).astype(k.dtype), (q.astype(f32) * e).astype(q.dtype),
            (k32 * jnp.exp(last - cum)).astype(k.dtype), jnp.exp(last[..., 0, :]))


def _chunk_matrices(q, k, beta, local):
    """``(T, the decayed k k^T, P)`` of chunks, float32 ``[..., C, C]`` each: ``T = (I + A)^-1`` with ``A`` beta times the
    decayed ``k k^T`` below the diagonal, ``P`` the decayed ``q k^T`` from the diagonal down; of ``q``, ``k`` and
    ``local`` as ``_decayed_products`` takes them and ``beta [..., C, 1]`` float32. What the kernels' forward sweep
    keeps of a chunk for its backward sweep (``kda_rule_fwd``)."""
    c = q.shape[-3] * q.shape[-2]
    rows, cols = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    kk, qk = _decayed_products(q, k, local)
    return unit_lower_inverse(jnp.where(rows > cols, beta * kk, 0.0)), kk, jnp.where(rows >= cols, qk, 0.0)


def _rule_xla_by_channel(q, k, v, g, beta, *, chunk: int = CHUNK):
    """``_rule_xla`` for ``g [b, s, value heads, d_k]`` (the section's comment): the same arguments otherwise, the
    same dtypes (products of bfloat16 operands added up in float32; decays, the two decayed products, the triangular
    inverse and the carried state float32), the same scan over chunks with its body rematerialized."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    cd, f32 = v.dtype, jnp.float32
    if hv != hk:  # a key head's value heads decay apart: each gets its own copy
        q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    sub = min(SUB, chunk)
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is no multiple of the sub-block {sub}")

    (q, k, v, g, beta), s = _padded_rows((q, k, v, g, beta), chunk)
    n, m = q.shape[1] // chunk, chunk // sub

    @jax.checkpoint
    def before_the_walk(q, k, v, g, beta):
        """What ONE row's chunks are before any state reaches them, chunks leading (what the scan walks): ``U``,
        ``W``, the decayed ``q k^T``, ``Q * exp(G)``, ``K * exp(G_C - G)``, ``exp(G_C)``. A row at a time and
        rematerialized: the float32 operands of the decayed products of ALL rows at once do not fit beside the
        Kimi cell's state (2 rows of 8192: 16.19 G of the chip's 15.75, my deviceless compile, PR 42), so the
        backward pass makes a row's again while it holds only that row's."""
        by_head = lambda x: jnp.moveaxis(x.reshape(n, chunk, hv, -1), 2, 1)  # noqa: E731  [n, h, C, .]
        qc, kc, vc, gc = by_head(q), by_head(k), by_head(v), by_head(g.astype(f32))
        bc = by_head(beta.astype(f32))                                       # [n, h, C, 1]
        blocks = lambda x: x.reshape(n, hv, m, sub, x.shape[-1])             # noqa: E731
        local = jnp.cumsum(blocks(gc), axis=-2)                              # from each sub-block's start
        t, _, p = _chunk_matrices(blocks(qc), blocks(kc), bc, local)         # [n, h, C, C]; P's diagonal included
        t, p = t.astype(cd), p.astype(cd)
        k_beta, q_e, k_rest, e_all = _decayed_operands(qc, kc, bc, local)
        u = jnp.einsum("nhij,nhjv->nhiv", t, vc * bc.astype(cd), preferred_element_type=f32).astype(cd)
        w = jnp.einsum("nhij,nhjd->nhid", t, k_beta, preferred_element_type=f32).astype(cd)
        return u, w, p, q_e, k_rest, e_all

    xs = jax.lax.map(lambda row: before_the_walk(*row), (q, k, v, g, beta))  # [b, n, h, ...]
    xs = tuple(jnp.moveaxis(x, 0, 1) for x in xs)                            # [n, b, h, ...]

    @jax.checkpoint  # as _rule_xla's: the backward pass holds the state at each boundary and makes d again
    def step(state, x):
        u_c, w_c, p_c, q_e, k_rest, e_all = x
        s_c = state.astype(cd)
        d = u_c.astype(f32) - jnp.einsum("bhid,bhdv->bhiv", w_c, s_c, preferred_element_type=f32)
        o = jnp.einsum("bhid,bhdv->bhiv", q_e, s_c, preferred_element_type=f32)
        o = o + jnp.einsum("bhij,bhjv->bhiv", p_c, d.astype(cd), preferred_element_type=f32)
        grown = jnp.einsum("bhid,bhiv->bhdv", k_rest, d.astype(cd), preferred_element_type=f32)
        state = (e_all[..., None] * state.astype(f32) + grown).astype(STATE_DTYPE)
        return state, o.astype(cd)

    _, o = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), STATE_DTYPE), xs)
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, n * chunk, hv, dv)     # [n, b, h, C, dv] -> rows
    return o[:, :s]


# -- the same chunked rule as Pallas kernels (TPU) ---------------------------------
#
# One grid step holds one row's ``STEP_CHUNKS`` chunks of ONE key head and ``rs``
# (two, or one where r is odd) of its value heads; the grid walks a row's steps in
# order with the state ``[d_k, rs d_v]`` (head j in lanes ``j d_v ..``) in VMEM
# scratch. The ``rs`` heads of a chunk are STACKED: their ``rs C`` tokens are the
# rows of one matrix, so decay, ``A``, ``T`` and ``q k^T`` are ``[rs C, rs C]``
# matrices of diagonal ``C x C`` blocks (128 x 128 at r = 2: whole vregs, one MXU
# tile) and ``k k^T``, ``q k^T`` and the q/k loads are shared. q, k, v and o are read
# and written where they lie, as blocks of ``[b, s, heads x d]``; g's running sum and
# beta come as rows ``[b, groups, chunks, rs C]`` (a chunk's tokens along lanes) and
# are turned into columns in VMEM (``_col``).
#
# Mosaic issues every product to one MXU in program order, and a product that the
# next one waits for costs its whole latency (about 90 cycles beside 16 to 32 of
# pushes; my chip runs, PR 36). A chunk's work until the state reaches it is a chain
# of 14 such products, so the chunks of a GROUP are written as GENERATORS that yield
# after each product something waits for, and ``_in_step`` runs them a stage at a
# time: in program order the group's chunks stand side by side.
#
# What that text costs is paid at every start of a process, warm cache or not: Python
# traces it and lowers it to a Mosaic module before the compile cache is even asked.
# PR 36 wrote all 8 chunks of a grid step side by side (126 KB of serialized modules in
# the Qwen3-Next cell's step) and every warm ``setup_s`` of the cell grew by 10.9 s, for
# which the driver refused it; the step's ``lower()`` follows that text and its
# ``.compile()`` on a cache hit does not move (PERF.md, PR 37, step 0). So a grid step is
# a loop over groups that is NOT unrolled (a group is traced, lowered and emitted once),
# and the forward sweep is one program whether a gradient follows or not.
# ``tests/test_tpu_compile.py`` holds the programs' count and bytes.

STEP_CHUNKS = 8
# Chunks whose state-free work stands side by side in the text: a grid step is a ``lax.fori_loop`` over
# ``STEP_CHUNKS / GROUP`` such groups, NOT unrolled, so a group is traced, lowered and emitted once (section's end).
GROUP = 4
# Newton steps that repair the triangular inverse after its one-pass levels (``_inverse_in_vmem``).
NEWTON_STEPS = 2
_F32 = jnp.float32
_SHIFT = CHUNK.bit_length() - 1


def _dot(x, y):
    return jnp.dot(x, y, preferred_element_type=_F32)


def _dot_nt(x, y):
    """``x y^T``: the MXU takes its right operand transposed as it is loaded."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())), preferred_element_type=_F32)


def _mm32(x, y):
    """A product of float32 matrices in float32: Mosaic rounds float32 operands to ONE
    bfloat16 pass unless the product states its precision (PERF.md, PR 25)."""
    return jnp.dot(x, y, preferred_element_type=_F32, precision=jax.lax.Precision.HIGHEST)


def _one_pass(x, y):
    """``_dot`` of float32 matrices, ONE bfloat16 pass: what the inverse's levels take, see there (a name of
    its own so that ``benchmarks/gdn_kernels.py`` can price the choice)."""
    return _dot(x, y)


def _iotas(n):
    return jax.lax.broadcasted_iota(jnp.int32, (n, n), 0), jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)


def _col(row, eye):
    """``[1, n]`` -> ``[n, 1]`` without a transpose: the diagonal of the row's broadcast."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _last_of(g_row, ci, rs):
    """A chunk's last running sum, a head: ``[1, 1]`` each, as a sum over lanes (Mosaic refuses a value's slice
    at a lane offset, and a ``[1, 1]`` read at one spreads over neither sublanes nor lanes)."""
    return [jnp.sum(jnp.where(ci[:1] == j * CHUNK + CHUNK - 1, g_row, 0.0), axis=1, keepdims=True) for j in range(rs)]


def _in_step(chunks):
    """Run the generators a stage at a time (the section's comment); their return values."""
    chunks = list(chunks)
    out, live = [None] * len(chunks), list(range(len(chunks)))
    while live:
        for i in list(live):
            try:
                next(chunks[i])
            except StopIteration as done:
                out[i] = done.value
                live.remove(i)
    return out


def _inverse_in_vmem(a, ri, ci):
    """``(I + a)^-1`` of ``a [n, n]`` float32, strictly lower triangular inside diagonal
    blocks of ``CHUNK``, in float32 (a generator, ``_in_step``).

    By levels: with ``T`` the inverse of the diagonal blocks of size s, the blocks of
    size 2 s are ``[[T11, 0], [-T22 A21 T11, T22]] = T - T (A's lower-left s x s blocks)
    T``. Every entry on the way is an entry of a true inverse or one product of three of
    them (the doublings ``(I - A)(I + A^2)...`` pass through powers of A whose entries
    grow like binomials and cancel); 2 products a level, 10 in all, as the doublings.

    Precision. At HIGHEST (six bfloat16 passes) those ten products are 60 of a chunk's 72
    passes through the one MXU Mosaic uses, 5.4 of the forward sweep's 9.2 ms (my chip
    runs, PR 36). So the levels multiply at ONE pass, which leaves ``T0`` right to about
    2^-8, and ``NEWTON_STEPS`` steps ``T <- T + T R`` with ``R = I - (I + A) T`` taken at
    HIGHEST repair it: ``(I + A) T`` stays unit lower triangular whatever the rounding, so
    R is strictly lower triangular and each step squares it (3e-3 -> 7e-5 -> 4e-8 at
    entries of A like the cell's); 24 passes for 60, the same float32 inverse
    (``benchmarks/gdn_kernels.py --only inverse``: against float64, beside HIGHEST at
    every level)."""
    lower_left = lambda s: ((ri ^ ci) < 2 * s) & ((ri & s) != 0) & ((ci & s) == 0)  # noqa: E731
    eye = jnp.where(ri == ci, 1.0, 0.0)
    t = eye - jnp.where(lower_left(1), a, 0.0)
    s = 2
    while s < CHUNK:
        y = _one_pass(t, jnp.where(lower_left(s), a, 0.0))
        yield
        t = t - _one_pass(y, t)
        yield
        s *= 2
    for _ in range(NEWTON_STEPS):
        r = eye - t - _mm32(a, t)
        yield
        t = t + _one_pass(t, r)
        yield
    return t


def _transposed(x):
    """``x^T`` of ``x [rows, d]`` in a matmul dtype, by the MXU: ``I x^T``, exact."""
    ri, ci = _iotas(x.shape[1])
    return _dot_nt(jnp.where(ri == ci, 1.0, 0.0).astype(x.dtype), x).astype(x.dtype)


def _stack(x, rs, width):
    """``[C, rs width]`` (heads side by side) -> ``[rs C, width]`` (heads one under the other)."""
    return x if rs == 1 else jnp.concatenate([x[:, j * width:(j + 1) * width] for j in range(rs)], axis=0)


def _beside(x, rs):
    """``[rs C, width]`` -> ``[C, rs width]``."""
    return x if rs == 1 else jnp.concatenate([x[j * CHUNK:(j + 1) * CHUNK] for j in range(rs)], axis=1)


def _decays(g_ref, b_ref, c, rs, dv):
    """A chunk's g and beta as rows and columns, and what hangs on them alone."""
    n = rs * CHUNK
    ri, ci = _iotas(n)
    eye, same = ri == ci, (ri >> _SHIFT) == (ci >> _SHIFT)
    g_row, b_row = g_ref[0, 0, pl.ds(c, 1), :], b_ref[0, 0, pl.ds(c, 1), :]
    gc, bc = _col(g_row, eye), _col(b_row, eye)
    last = _last_of(g_row, ci, rs)
    return dict(
        ri=ri, ci=ci, eye=eye, same=same, g_row=g_row, b_row=b_row, gc=gc, bc=bc, e=jnp.exp(gc), last=last,
        decay=jnp.exp(jnp.where(same & (ri >= ci), gc - g_row, -jnp.inf)),   # exp(G_i - G_j); 0 above and between heads
        rest=jnp.exp(jnp.concatenate([jnp.broadcast_to(x, (CHUNK, 1)) for x in last], axis=0) - gc),   # exp(G_C - G_i)
        whole=jnp.concatenate([jnp.broadcast_to(jnp.exp(x), (1, dv)) for x in last], axis=1),           # exp(G_C), a head's lanes
    )


def _chunk_alone(q, k, v2, g_ref, b_ref, c, rs, with_output=True):
    """What a chunk of ``rs`` stacked heads is before any state reaches it (a generator, ``_in_step``): ``T``,
    ``U``, ``W``, the decayed ``q k^T``, ``k^T`` and the decays the state's walk needs. Line by line what
    ``_rule_xla`` computes for all chunks at once, in its dtypes."""
    cd = k.dtype
    x = _decays(g_ref, b_ref, c, rs, v2.shape[1])
    k2 = jnp.concatenate([k] * rs, axis=0)
    kk = _dot_nt(k2, k2)
    p = (x["decay"] * _dot_nt(jnp.concatenate([q] * rs, axis=0), k2)).astype(cd) if with_output else None
    k_t = _transposed(k)
    yield
    t = yield from _inverse_in_vmem(jnp.where(x["same"] & (x["ri"] > x["ci"]), x["bc"] * x["decay"] * kk, 0.0), x["ri"], x["ci"])
    tc = t.astype(cd)
    return dict(t=t, u=_dot(tc, v2 * x["bc"].astype(cd)).astype(cd), w=_dot(tc, k2 * (x["bc"] * x["e"]).astype(cd)).astype(cd),
                p=p, k_t=k_t, e=x["e"], rest=x["rest"], whole=x["whole"])


def _chunk_through(alone, s, rs, state_dtype):
    """The chunk from the state ``s [d_k, rs d_v]`` it starts from: ``(the next state, D)``: two dependent
    products a chunk are all that the walk over chunks waits for."""
    cd, dv = alone["u"].dtype, alone["u"].shape[1]
    sc = s.astype(cd)
    d = jnp.concatenate([alone["u"][j * CHUNK:(j + 1) * CHUNK].astype(_F32)
                         - _dot(alone["w"][j * CHUNK:(j + 1) * CHUNK], sc[:, j * dv:(j + 1) * dv]) for j in range(rs)], axis=0)
    grown = _dot(alone["k_t"], _beside((alone["rest"] * d).astype(cd), rs))
    return (alone["whole"] * s.astype(_F32) + grown).astype(state_dtype), d


def _rows(c):
    """The tokens of chunk ``c`` of a grid step's block (``c`` may be a loop's index)."""
    return pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)


def _over_groups(body, carry, against_time=False):
    """``body(first chunk, carry) -> carry`` over a grid step's groups of ``GROUP`` chunks, in a loop that is not
    unrolled (one group alone is plain code: its indices are static)."""
    trips = STEP_CHUNKS // GROUP
    if trips == 1:
        return body(0, carry)
    return jax.lax.fori_loop(0, trips, lambda i, x: body((trips - 1 - i if against_time else i) * GROUP, x), carry)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s0_ref, state, *, rs):
    """A grid step of the forward sweep. It always writes the state the step starts from (the backward sweep's
    only residual besides the inputs; a call that no gradient follows drops it): ONE program for both."""
    dv, cd = v_ref.shape[2] // rs, v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    s0_ref[0, 0, 0] = state[...]

    def group(first, s):
        chunks = [first + j for j in range(GROUP)]
        q = [q_ref[0, _rows(c), :] for c in chunks]
        alone = _in_step(_chunk_alone(q[j], k_ref[0, _rows(c), :], _stack(v_ref[0, _rows(c), :], rs, dv), g_ref, b_ref, c, rs)
                         for j, c in enumerate(chunks))
        walked = []
        for j in range(GROUP):
            of_state = _dot(q[j], s.astype(cd))                      # q S, beside the walk
            s, d = _chunk_through(alone[j], s, rs, state.dtype)
            walked.append((of_state, d))
        for c, x, (of_state, d) in zip(chunks, alone, walked):       # o = exp(G) (q S) + P D
            o = x["e"] * _stack(of_state, rs, dv) + _dot(x["p"], d.astype(cd))
            o_ref[0, _rows(c), :] = _beside(o, rs).astype(o_ref.dtype)
        return s

    state[...] = _over_groups(group, state[...])


def _chunk_backward_alone(q, k, v2, do2, g_ref, b_ref, c, s, d, w, rs):
    """The backward sweep's chunk before the state's cotangent reaches it (a generator, ``_in_step``): what
    hangs on the chunk's inputs, its output's cotangent ``do2 [rs C, d_v]``, the state ``s`` it started from
    and its ``D`` and ``W`` alone. What stands transposed in the algebra is MADE transposed where that is a product
    or an ``exp`` (``exp(G_j - G_i)``, ``k q^T``, ``D dO^T``)."""
    cd, dv = k.dtype, v2.shape[1]
    x = _decays(g_ref, b_ref, c, rs, dv)
    ri, ci, same, e = x["ri"], x["ci"], x["same"], x["e"]
    decay_t = jnp.exp(jnp.where(same & (ri <= ci), x["g_row"] - x["gc"], -jnp.inf))      # the decay's transpose
    k2, q2 = jnp.concatenate([k] * rs, axis=0), jnp.concatenate([q] * rs, axis=0)
    sc, dc, do32 = s.astype(cd), d.astype(cd), do2.astype(_F32)
    do_e = (e * do32).astype(cd)
    kk, qk, kq = _dot_nt(k2, k2), _dot_nt(q2, k2), _dot_nt(k2, q2)
    q_t, w_t = _transposed(q), _transposed(w)
    of_state = _dot(q, sc)
    dp = jnp.where(same & (ri >= ci), _dot_nt(do2, dc), 0.0)
    dp_t = jnp.where(same & (ri <= ci), _dot_nt(dc, do2), 0.0)
    dq = _dot_nt(_beside(do_e, rs), sc)                                                   # through o = exp(G) (q S)
    yield
    return dict(
        x, decay_t=decay_t, k2=k2, q2=q2, sc=sc, kk=kk, w_t=w_t, p=x["decay"] * qk, dp=dp, dp_t=dp_t, dq=dq,
        dd=_dot((decay_t * kq).astype(cd), do2),                                          # P^T dO
        ds=_dot(q_t, _beside(do_e, rs)),
        dg=_row_sum(do32 * e * _stack(of_state, rs, dv)),
        to_state=_beside((x["rest"] * d).astype(cd), rs),
    )


def _chunk_backward_through(x, k, s, d, ds_next, rs):
    """The state's cotangent through the chunk: ``(its cotangent before the chunk, and what the rest of the
    chunk's backward pass needs of it)``. Two dependent products."""
    cd, dv = k.dtype, d.shape[1]
    dsc = ds_next.astype(cd)
    # S' = exp(G_C) S + k^T (exp(G_C - G) D)
    d_grown = _stack(_dot(k, dsc), rs, dv)
    dd = x["dd"] + x["rest"] * d_grown
    ddc = dd.astype(cd)
    by_head = jnp.concatenate([jnp.where((x["ri"][:, :1] >> _SHIFT) == j, ddc, 0) for j in range(rs)], axis=1)  # head j's rows in its lanes
    ds = x["whole"] * ds_next + x["ds"] - _dot(x["w_t"], by_head)                              # D = U - W S
    d_whole = [jnp.sum(ds_next[:, j * dv:(j + 1) * dv] * s[:, j * dv:(j + 1) * dv].astype(_F32), axis=(0, 1), keepdims=True)
               for j in range(rs)]
    return ds, dict(ddc=ddc, d_rest=_row_sum(d_grown * d), d_whole=d_whole, dk=_dot_nt(x["to_state"], dsc))


def _chunk_backward_rest(x, y, v2, t, rs):
    """The rest of the chunk's backward pass (a generator, ``_in_step``): ``(dq, dk [C, d_k] summed over the
    stacked heads, dv [rs C, d_v], the cotangents of g's running sum and of beta as rows [1, rs C])``, float32.
    Two ``[rs C, rs C]`` float32 matrices are transposed as such (``T``, ``dA``)."""
    cd, dv = v2.dtype, v2.shape[1]
    ri, ci, same, e, bc, k2, sc, ddc = x["ri"], x["ci"], x["same"], x["e"], x["bc"], x["k2"], x["sc"], y["ddc"]
    heads = [(slice(j * CHUNK, (j + 1) * CHUNK), slice(j * dv, (j + 1) * dv)) for j in range(rs)]
    summed = lambda z: sum(z[rows] for rows, _ in heads)  # noqa: E731  [rs C, .] -> [C, .] over the heads
    # D = U - W S;  U = T (beta v), W = T (beta exp(G) k)
    dwc = (-jnp.concatenate([_dot_nt(ddc[rows], sc[:, lanes]) for rows, lanes in heads], axis=0)).astype(cd)
    tt = t.T
    ttc = tt.astype(cd)
    v_beta, k_beta = v2 * bc.astype(cd), k2 * (bc * e).astype(cd)
    dt_t = _dot_nt(v_beta, ddc)
    d_vb = _dot(ttc, ddc)
    yield
    dt_t = dt_t + _dot_nt(k_beta, dwc)                                                    # dT^T
    d_kb = _dot(ttc, dwc)
    of_k = _row_sum(d_kb * k2.astype(_F32))
    db = _row_sum(d_vb * v2.astype(_F32)) + e * of_k
    dg = x["dg"] + bc * e * of_k
    dk = y["dk"] + summed(bc * e * d_kb)
    dq = x["dq"] + summed(_dot((x["dp"] * x["decay"]).astype(cd), k2))
    yield
    # T = (I + A)^-1:  dA = -T^T dT T^T, made transposed and turned
    da_t = _mm32(t, dt_t)
    yield
    da_t = jnp.where(same & (ri < ci), -_mm32(da_t, t), 0.0)
    yield
    da = da_t.T
    # A = beta decay k k^T below the diagonal, P = decay q k^T
    of_decay = da * x["decay"]
    db = db + _row_sum(of_decay * x["kk"])
    through_decay = of_decay * bc * x["kk"] + x["dp"] * x["p"]                            # d decay * decay
    dg = dg + _row_sum(through_decay) - x["rest"] * y["d_rest"]
    at_last = jnp.sum(jnp.where(same, x["rest"] * y["d_rest"], 0.0), axis=0, keepdims=True)   # a head's sum, in each of its lanes
    at_last = at_last + jnp.concatenate(
        [jnp.broadcast_to(jnp.exp(g) * z, (1, CHUNK)) for g, z in zip(x["last"], y["d_whole"])], axis=1)
    dg_row = _row(dg, x["eye"]) - jnp.sum(through_decay, axis=0, keepdims=True) + jnp.where(
        (ci[:1] & (CHUNK - 1)) == CHUNK - 1, at_last, 0.0)
    dkk_both = bc * of_decay + x["b_row"] * da_t * x["decay_t"]                           # d(k k^T) and its transpose
    dk = dk + summed(_dot((x["dp_t"] * x["decay_t"]).astype(cd), x["q2"]) + _dot(dkk_both.astype(cd), k2))
    return dq, dk, bc * d_vb, dg_row, _row(db, x["eye"])


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s0_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                ds_ref, s_at, d_at, t_at, w_at, *, rs):
    """A grid step of the backward sweep (the steps of a row come last first): the step's chunks forward once
    more from the state the forward sweep kept, group by group, each chunk's starting state, ``D``, ``T`` and ``W``
    left in VMEM scratch (``*_at``); then the groups against time with the state's cotangent carried
    (``ds_ref`` from step to step); in both directions a group's state-free work first, the walk, then what
    hangs on the walk."""
    dv = v_ref.shape[2] // rs

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def inputs(chunks):
        return ([q_ref[0, _rows(c), :] for c in chunks], [k_ref[0, _rows(c), :] for c in chunks],
                [_stack(v_ref[0, _rows(c), :], rs, dv) for c in chunks])

    def forward(first, s):
        chunks = [first + j for j in range(GROUP)]
        q, k, v2 = inputs(chunks)
        alone = _in_step(_chunk_alone(q[j], k[j], v2[j], g_ref, b_ref, c, rs, with_output=False) for j, c in enumerate(chunks))
        for c, x in zip(chunks, alone):
            s_at[c] = s
            s, d = _chunk_through(x, s, rs, s.dtype)
            d_at[c], t_at[c], w_at[c] = d, x["t"], x["w"]
        return s

    _over_groups(forward, s0_ref[0, 0, 0])

    def backward(first, ds):
        chunks = [first + j for j in range(GROUP)]
        q, k, v2 = inputs(chunks)
        back = _in_step(_chunk_backward_alone(q[j], k[j], v2[j], _stack(do_ref[0, _rows(c), :], rs, dv), g_ref, b_ref, c,
                                              s_at[c], d_at[c], w_at[c], rs) for j, c in enumerate(chunks))
        through = [None] * GROUP
        for j in reversed(range(GROUP)):
            ds, through[j] = _chunk_backward_through(back[j], k[j], s_at[chunks[j]], d_at[chunks[j]], ds, rs)
        out = _in_step(_chunk_backward_rest(back[j], through[j], v2[j], t_at[c], rs) for j, c in enumerate(chunks))
        for c, (dq, dk, dv2, dg, db) in zip(chunks, out):
            dq_ref[0, _rows(c), :] = dq.astype(dq_ref.dtype)
            dk_ref[0, _rows(c), :] = dk.astype(dk_ref.dtype)
            dv_ref[0, _rows(c), :] = _beside(dv2, rs).astype(dv_ref.dtype)
            dg_ref[0, 0, pl.ds(c, 1), :] = dg
            db_ref[0, 0, pl.ds(c, 1), :] = db
        return ds

    ds_ref[...] = _over_groups(backward, ds_ref[...], against_time=True)


def _specs(rs, r, dk, dv):
    """Block specs over ``(row, group of rs value heads, step)`` for q or k, v or o, and g or beta."""
    tokens = STEP_CHUNKS * CHUNK
    return (
        lambda at: pl.BlockSpec((1, tokens, dk), lambda i, j, t: (i, at(t), (j * rs) // r)),
        lambda at: pl.BlockSpec((1, tokens, rs * dv), lambda i, j, t: (i, at(t), j)),
        lambda at: pl.BlockSpec((1, 1, STEP_CHUNKS, rs * CHUNK), lambda i, j, t: (i, j, at(t), 0)),
    )


def _params(interpret, semantics=("parallel", "parallel", "arbitrary")):
    return {} if interpret else dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=64 * 2**20))


@functools.partial(jax.jit, static_argnames=("hk", "rs", "state_dtype", "interpret"))
def gdn_rule_fwd(q, k, v, cum, beta, *, hk, rs, state_dtype, interpret):
    """The forward sweep. ``q``, ``k`` ``[b, s, hk d_k]``, ``v`` ``[b, s, hv d_v]``, ``cum`` (g's running sum
    from each chunk's start) and ``beta`` ``[b, hv / rs, s / C, rs C]`` -> ``o`` like v and the state each step
    starts from ``[b, hv / rs, steps, d_k, rs d_v]``."""
    b, s, _ = q.shape
    groups, dk = cum.shape[1], q.shape[2] // hk
    dv, r, steps = v.shape[2] // (groups * rs), groups * rs // hk, s // (STEP_CHUNKS * CHUNK)
    qk, vo, gb = (spec(lambda t: t) for spec in _specs(rs, r, dk, dv))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rs=rs),
        grid=(b, groups, steps), in_specs=[qk, qk, vo, gb, gb],
        out_specs=[vo, pl.BlockSpec((1, 1, 1, dk, rs * dv), lambda i, j, t: (i, j, t, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct((b, groups, steps, dk, rs * dv), state_dtype)],
        scratch_shapes=[pltpu.VMEM((dk, rs * dv), state_dtype)], name="gdn_rule_fwd", interpret=interpret, **_params(interpret),
    )(q, k, v, cum, beta)


@functools.partial(jax.jit, static_argnames=("hk", "rs", "interpret"))
def gdn_rule_bwd(q, k, v, cum, beta, do, states, *, hk, rs, interpret):
    """The backward sweep: ``gdn_rule_fwd``'s inputs, ``do`` like v and the kept states -> cotangents of q and k
    ``[b, s, (hv / rs) d_k]`` (a group of value heads each: the caller adds a key head's groups), of v, and of
    ``cum`` and ``beta`` in their row form."""
    b, s, _ = q.shape
    groups, dk = cum.shape[1], q.shape[2] // hk
    dv, r, steps = v.shape[2] // (groups * rs), groups * rs // hk, s // (STEP_CHUNKS * CHUNK)
    qk, vo, gb = (spec(lambda t: steps - 1 - t) for spec in _specs(rs, r, dk, dv))
    per_group = pl.BlockSpec((1, STEP_CHUNKS * CHUNK, dk), lambda i, j, t: (i, steps - 1 - t, j))
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, rs=rs),
        grid=(b, groups, steps),
        in_specs=[qk, qk, vo, gb, gb, vo, pl.BlockSpec((1, 1, 1, dk, rs * dv), lambda i, j, t: (i, j, steps - 1 - t, 0, 0))],
        out_specs=[per_group, per_group, vo, gb, gb],
        out_shape=[like((b, s, groups * dk), q.dtype), like((b, s, groups * dk), k.dtype), like(v.shape, v.dtype),
                   like(cum.shape, _F32), like(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((dk, rs * dv), _F32), pltpu.VMEM((STEP_CHUNKS, dk, rs * dv), states.dtype),
                        pltpu.VMEM((STEP_CHUNKS, rs * CHUNK, dv), _F32), pltpu.VMEM((STEP_CHUNKS, rs * CHUNK, rs * CHUNK), _F32),
                        pltpu.VMEM((STEP_CHUNKS, rs * CHUNK, dk), k.dtype)],
        name="gdn_rule_bwd", interpret=interpret, **_params(interpret),
    )(q, k, v, cum, beta, do, states)


# -- the rule with a decay a channel as Pallas kernels (TPU) ------------------------
#
# ``_rule_xla_by_channel`` line by line, a chunk at a time in VMEM: the same two sweeps behind one ``custom_vjp`` as
# the scalar rule's, the same walk (``_in_step`` over a group's chunks, ``_over_groups`` over a grid step's groups,
# the state ``[d_k, d_v]`` in scratch along the sequential grid axis, the state each step starts from kept for the
# backward sweep), the same inverse (``_inverse_in_vmem``). What differs is what a chunk is before the state reaches
# it, ``_kda_alone``: the decay does not leave the products, so ``k k^T`` and ``q k^T`` are made from sub-blocks of
# ``SUB`` tokens as ``_decayed_products`` makes them (against an earlier sub-block a float32 product at HIGHEST of
# operands decayed towards the boundary between them; against itself the ``[SUB, SUB, d_k]`` pairwise block, which
# lives in vector registers and is summed over its channels at once), the walk's operands ``Q exp(G)``, ``K exp(G_C -
# G)``, ``beta K exp(G)`` are decayed a channel, and ``exp(G_C)`` scales the state's ROWS. Nothing is shared between
# heads (each value head decays apart), so a grid step holds ONE value head and every matrix of a chunk is ``[C, C]``:
# two heads stacked into ``[2 C, 2 C]`` matrices of diagonal blocks, as the scalar kernels stack them, would double the
# float32 sub-block products for blocks that are zero. q, k, v, o and g (float32, ``[b, s, value heads x d_k]``, as
# ``kda_gates`` makes it) are read and written where they lie; g is summed from each sub-block's start in VMEM (four
# shifted adds along sublanes), beta comes as rows like the scalar kernels'. The forward sweep also WRITES each chunk's
# ``T``, decayed ``k k^T`` and ``P``, which the backward sweep reads where the scalar rule's makes its own again
# (``_kda_fwd_kernel``: the layout; ``_kda_alone_again``: what is left to make).

_SUBS = CHUNK // SUB


def _mm32_over(x, y, axis_x, axis_y):
    """``_mm32`` with the contraction over ``axis_x`` of x and ``axis_y`` of y: ``x y^T`` at (1, 1), ``x^T y`` at (0, 0)."""
    return jax.lax.dot_general(x, y, (((axis_x,), (axis_y,)), ((), ())), preferred_element_type=_F32, precision=jax.lax.Precision.HIGHEST)


def _from_sub_block_start(g, against_time=False):
    """``g [C, d_k]`` summed along the tokens of each sub-block from its start (``against_time``: to its end, what the
    sum's cotangent takes): ``log2(SUB)`` shifted adds."""
    at = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) & (SUB - 1)
    s = 1
    while s < SUB:
        g = g + (jnp.where(at < SUB - s, pltpu.roll(g, CHUNK - s, 0), 0.0) if against_time
                 else jnp.where(at >= s, pltpu.roll(g, s, 0), 0.0))
        s *= 2
    return g


def _sub(x, i):
    return x[i * SUB:(i + 1) * SUB]


def _kda_decays(g, q, k):
    """What hangs on a chunk's g alone, and q and k in float32: ``local`` (the running sum from each sub-block's
    start), ``before`` (at the last token before each sub-block, from the chunk's start: ``[1, d_k]`` each), ``cum``
    (at every token), ``last`` (at the chunk's end), their ``exp``s (``whole``, ``exp(G_C)``, as a row and as the column
    that scales the state's rows), and the pairwise block's mask."""
    local = _from_sub_block_start(g)
    before = [jnp.zeros_like(local[:1])]
    for i in range(_SUBS):
        before.append(before[-1] + _sub(local, i)[SUB - 1:])
    cum = jnp.concatenate([_sub(local, i) + before[i] for i in range(_SUBS)], axis=0)
    last = before.pop()
    shape = (SUB, SUB, g.shape[1])
    rk, ck = _iotas(g.shape[1])
    whole = jnp.exp(last)
    return dict(local=local, before=before, cum=cum, last=last, e=jnp.exp(cum), rest=jnp.exp(last - cum), whole_row=whole,
                eye_k=rk == ck, whole=_col(whole, rk == ck), q32=q.astype(_F32),
                k32=k.astype(_F32), at=jax.lax.broadcasted_iota(jnp.int32, g.shape, 0),
                earlier=jax.lax.broadcasted_iota(jnp.int32, shape, 1) <= jax.lax.broadcasted_iota(jnp.int32, shape, 0))


def _pairwise(x, i):
    """``exp(G_a - G_b)`` for ``b <= a`` inside sub-block ``i``, 0 above: ``[SUB (a), SUB (b), d_k]``, masked before the
    ``exp`` (``_pairwise_decay``)."""
    local = _sub(x["local"], i)
    return jnp.exp(jnp.where(x["earlier"], local[:, None, :] - local[None, :, :], -jnp.inf))


def _towards_boundary(x, i):
    """Sub-block ``i``'s two operands of its product against every earlier token (``_decayed_products``): k and q of
    the sub-block decayed from the boundary before it ``[2 SUB, d_k]``, and every EARLIER token's k decayed up to that
    boundary ``[C, d_k]`` (0 from the sub-block on), with the two decays."""
    e_in = jnp.exp(_sub(x["local"], i))
    f = jnp.exp(jnp.where(x["at"] < i * SUB, x["before"][i] - x["cum"], -jnp.inf))
    return jnp.concatenate([_sub(x["k32"], i) * e_in, _sub(x["q32"], i) * e_in], axis=0), x["k32"] * f, e_in, f


def _kda_products(x):
    """The decayed ``k k^T`` and ``q k^T`` of a chunk, ``[C, C]`` float32 each, ``j <= i`` (0 above): what
    ``_decayed_products`` computes (a generator, ``_in_step``)."""
    rows = []
    zeros = lambda n: [jnp.zeros((SUB, n * SUB), _F32)] if n else []  # noqa: E731
    for i in range(_SUBS):
        k_i, q_i = _sub(x["k32"], i), _sub(x["q32"], i)
        k_decayed = k_i[None, :, :] * _pairwise(x, i)
        own = [jnp.sum(z[:, None, :] * k_decayed, axis=2) for z in (k_i, q_i)]                     # [SUB, SUB] each
        own = jnp.concatenate([jnp.concatenate(zeros(i) + [z] + zeros(_SUBS - 1 - i), axis=1) for z in own], axis=0)  # [2 SUB, C]: in its columns
        if i:
            x_in, k_out, _, _ = _towards_boundary(x, i)
            own = own + _mm32_over(x_in, k_out, 1, 1)
            yield
        rows.append(own)
    return jnp.concatenate([z[:SUB] for z in rows], axis=0), jnp.concatenate([z[SUB:] for z in rows], axis=0)


def _kda_products_back(x, dkk, dqk):
    """``_kda_products``'s backward pass from the cotangents of both products (0 above the diagonal): ``(dq, dk, the
    cotangents of local and of cum [C, d_k], of before [1, d_k] a sub-block)``, float32. The pairwise block is made
    again and its cotangent never: ``_own_products_bwd``'s identities (a generator, ``_in_step``)."""
    dq, dk, dlocal, dbefore = [], [], [], []
    dk_earlier = dcum = jnp.zeros_like(x["k32"])
    for i in range(_SUBS):
        k_i, q_i = _sub(x["k32"], i), _sub(x["q32"], i)
        d_k, d_q = (_sub(z, i)[:, i * SUB:(i + 1) * SUB][:, :, None] for z in (dkk, dqk))         # [SUB (a), SUB (b), 1]
        decay = _pairwise(x, i)
        k_decayed = k_i[None, :, :] * decay
        dx_k, dx_q = jnp.sum(d_k * k_decayed, axis=1), jnp.sum(d_q * k_decayed, axis=1)            # [SUB (a), d_k]
        dk_b = jnp.sum((d_k * k_i[:, None, :] + d_q * q_i[:, None, :]) * decay, axis=0)            # [SUB (b), d_k]
        dl_i = k_i * dx_k + q_i * dx_q - k_i * dk_b
        dk_i, dq_i = dx_k + dk_b, dx_q
        if i:
            x_in, k_out, e_in, f = _towards_boundary(x, i)
            d_both = jnp.concatenate([_sub(dkk, i), _sub(dqk, i)], axis=0)                         # [2 SUB, C]
            dx_in = _mm32(d_both, k_out)
            dk_out = _mm32_over(d_both, x_in, 0, 0)
            yield
            dl_i = dl_i + x_in[:SUB] * dx_in[:SUB] + x_in[SUB:] * dx_in[SUB:]
            dk_i, dq_i = dk_i + dx_in[:SUB] * e_in, dq_i + dx_in[SUB:] * e_in
            dk_earlier = dk_earlier + dk_out * f
            through = k_out * dk_out
            dcum = dcum - through
            dbefore.append(jnp.sum(through, axis=0, keepdims=True))
        else:
            dbefore.append(jnp.zeros_like(x["last"]))
        dq.append(dq_i)
        dk.append(dk_i)
        dlocal.append(dl_i)
    return jnp.concatenate(dq, axis=0), jnp.concatenate(dk, axis=0) + dk_earlier, jnp.concatenate(dlocal, axis=0), dcum, dbefore


def _kda_solved(x, v, bc, t):
    """``U = T (beta V)`` and ``W = T (beta exp(G) K)`` of a chunk in the compute dtype, two one-pass products."""
    cd = v.dtype
    tc = t.astype(cd)
    return _dot(tc, v * bc.astype(cd)).astype(cd), _dot(tc, (x["k32"] * (bc * x["e"])).astype(cd)).astype(cd)


def _kda_alone(q, k, v, g, b_ref, c):
    """What a chunk of ONE head is before any state reaches it (a generator, ``_in_step``): ``T``, ``U``, ``W``, the
    decayed ``k k^T`` and ``q k^T``, ``(K exp(G_C - G))^T``, ``Q exp(G)`` and ``exp(G_C)`` as a column. Line by line
    what ``_rule_xla_by_channel`` computes for a row's chunks at once, in its dtypes."""
    cd = k.dtype
    ri, ci = _iotas(CHUNK)
    bc = _col(b_ref[0, 0, pl.ds(c, 1), :], ri == ci)
    x = _kda_decays(g, q, k)
    kk, qk = yield from _kda_products(x)
    k_t = _transposed((x["k32"] * x["rest"]).astype(cd))
    yield
    t = yield from _inverse_in_vmem(jnp.where(ri > ci, bc * kk, 0.0), ri, ci)
    u, w = _kda_solved(x, v, bc, t)
    return dict(x, t=t, kk=kk, u=u, w=w, p=jnp.where(ri >= ci, qk, 0.0).astype(cd), k_t=k_t, q_e=(x["q32"] * x["e"]).astype(cd))


def _kda_alone_again(q, k, v, g, b_ref, c, t):
    """What the walk needs of ``_kda_alone`` for the backward sweep, from the ``T`` the forward sweep kept: the decays,
    ``U``, ``W`` and ``(K exp(G_C - G))^T``, three products that wait for nothing. No decayed product and no inverse."""
    ri, ci = _iotas(CHUNK)
    x = _kda_decays(g, q, k)
    u, w = _kda_solved(x, v, _col(b_ref[0, 0, pl.ds(c, 1), :], ri == ci), t)
    return dict(x, u=u, w=w, k_t=_transposed((x["k32"] * x["rest"]).astype(k.dtype)))


def _kda_through(alone, s, state_dtype):
    """``_chunk_through`` for a decay a channel: ``exp(G_C)`` scales the state's rows, and k comes decayed."""
    cd = alone["u"].dtype
    d = alone["u"].astype(_F32) - _dot(alone["w"], s.astype(cd))
    return (alone["whole"] * s.astype(_F32) + _dot(alone["k_t"], d.astype(cd))).astype(state_dtype), d


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s0_ref, tk_ref, p_ref, state):
    """A grid step of the forward sweep, as ``_fwd_kernel``: one value head's ``STEP_CHUNKS`` chunks. Beside ``o`` and
    the step's starting state it writes what the backward sweep would otherwise make a second time, lane-dense (a
    float32 array 64 wide would hold twice its count): each chunk's ``T`` and decayed ``k k^T`` side by side
    (``tk_ref``, float32 ``[C, 2 C]`` a chunk) and two chunks' ``P`` side by side (``p_ref``, the compute dtype)."""
    cd = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    s0_ref[0, 0, 0] = state[...]

    def group(first, s):
        chunks = [first + j for j in range(GROUP)]
        alone = _in_step(_kda_alone(q_ref[0, _rows(c), :], k_ref[0, _rows(c), :], v_ref[0, _rows(c), :], g_ref[0, _rows(c), :], b_ref, c)
                         for c in chunks)
        for c, x in zip(chunks, alone):
            tk_ref[0, 0, _rows(c), :] = jnp.concatenate([x["t"], x["kk"]], axis=1)
        for j in range(0, GROUP, 2):
            p_ref[0, 0, _rows(chunks[j] // 2), :] = jnp.concatenate([alone[j]["p"], alone[j + 1]["p"]], axis=1)
        walked = []
        for x in alone:
            of_state = _dot(x["q_e"], s.astype(cd))                   # (Q exp(G)) S, beside the walk
            s, d = _kda_through(x, s, state.dtype)
            walked.append((of_state, d))
        for c, x, (of_state, d) in zip(chunks, alone, walked):
            o_ref[0, _rows(c), :] = (of_state + _dot(x["p"], d.astype(cd))).astype(o_ref.dtype)
        return s

    state[...] = _over_groups(group, state[...])


def _kda_backward_alone(q, k, v, do, g, b_ref, c, s, d, w, p):
    """The backward sweep's chunk before the state's cotangent reaches it (a generator, ``_in_step``)."""
    cd = k.dtype
    ri, ci = _iotas(CHUNK)
    x = _kda_decays(g, q, k)
    sc, dc = s.astype(cd), d.astype(cd)
    q_e, k_rest = (x["q32"] * x["e"]).astype(cd), (x["k32"] * x["rest"]).astype(cd)
    yield
    return dict(
        x, ri=ri, ci=ci, eye=ri == ci, bc=_col(b_ref[0, 0, pl.ds(c, 1), :], ri == ci), sc=sc, k_rest=k_rest, w_t=_transposed(w),
        dq_e=_dot_nt(do, sc),                                          # through O = (Q exp(G)) S + P D
        ds=_dot(_transposed(q_e), do),
        dp=jnp.where(ri >= ci, _dot_nt(do, dc), 0.0),
        dd=_dot(_transposed(p), do),
        to_state=dc,
    )


def _kda_backward_through(x, s, ds_next):
    """The state's cotangent through the chunk (``_chunk_backward_through``): two dependent products."""
    cd = x["sc"].dtype
    dsc = ds_next.astype(cd)
    dd = x["dd"] + _dot(x["k_rest"], dsc)                               # S' = exp(G_C) S + (K exp(G_C - G))^T D
    ddc = dd.astype(cd)
    ds = x["whole"] * ds_next + x["ds"] - _dot(x["w_t"], ddc)           # D = U - W S
    d_whole = _row(_row_sum(ds_next * s.astype(_F32)), x["eye_k"])      # [1, d_k]
    return ds, dict(ddc=ddc, d_whole=d_whole, dk_rest=_dot_nt(x["to_state"], dsc))


def _kda_backward_rest(x, y, v, t, kk):
    """The rest of the chunk's backward pass (a generator, ``_in_step``): ``(dq, dk [C, d_k], dv [C, d_v], dg [C,
    d_k], the cotangent of beta as a row [1, C])``, float32."""
    cd = v.dtype
    ri, ci, bc, e, rest, k32, q32, ddc = x["ri"], x["ci"], x["bc"], x["e"], x["rest"], x["k32"], x["q32"], y["ddc"]
    # D = U - W S;  U = T (beta v), W = T (beta exp(G) k)
    dwc = (-_dot_nt(ddc, x["sc"])).astype(cd)
    tt = t.T
    ttc = tt.astype(cd)
    v_beta, k_beta = v * bc.astype(cd), (k32 * (bc * e)).astype(cd)
    dt = _dot_nt(ddc, v_beta) + _dot_nt(dwc, k_beta)
    d_vb, d_kb = _dot(ttc, ddc), _dot(ttc, dwc)
    yield
    # T = (I + A)^-1:  dA = -T^T dT T^T
    da = _mm32(tt, dt)
    yield
    da = jnp.where(ri > ci, -_mm32(da, tt), 0.0)
    yield
    # A = beta (k k^T) below the diagonal, P = q k^T from the diagonal down
    dq, dk, dlocal, dcum, dbefore = yield from _kda_products_back(x, bc * da, x["dp"])
    db = _row_sum(da * kk) + _row_sum(d_vb * v.astype(_F32)) + _row_sum(d_kb * k32 * e)
    d_rest = k32 * y["dk_rest"]
    d_e = bc * k32 * d_kb + q32 * x["dq_e"]
    dq = dq + e * x["dq_e"]
    dk = dk + bc * e * d_kb + rest * y["dk_rest"]
    dcum = dcum + e * d_e - rest * d_rest
    d_last = jnp.sum(rest * d_rest, axis=0, keepdims=True) + x["whole_row"] * y["d_whole"]
    dcum = dcum + jnp.where(x["at"] == CHUNK - 1, d_last, 0.0)
    # cum = before + local, before a sub-block = the sum of the earlier sub-blocks' last local
    dlocal = dlocal + dcum
    later = jnp.zeros_like(d_last)
    for i in reversed(range(1, _SUBS)):
        later = later + dbefore[i] + jnp.sum(_sub(dcum, i), axis=0, keepdims=True)
        dlocal = dlocal + jnp.where(x["at"] == i * SUB - 1, later, 0.0)
    return dq, dk, bc * d_vb, _from_sub_block_start(dlocal, against_time=True), _row(db, x["eye"])


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s0_ref, tk_ref, p_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                    ds_ref, s_at, d_at, w_at):
    """A grid step of the backward sweep, as ``_bwd_kernel``: the step's chunks walked forward once more from the kept
    state (each chunk's starting state, ``D`` and ``W`` left in scratch), then the groups against time with the
    state's cotangent carried. ``T``, the decayed ``k k^T`` and ``P`` are READ as the forward sweep left them
    (``_kda_fwd_kernel``): no decayed product and no inverse is made here."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def inputs(chunks):
        return [[ref[0, _rows(c), :] for c in chunks] for ref in (q_ref, k_ref, v_ref, g_ref)]

    def forward(first, s):
        chunks = [first + j for j in range(GROUP)]
        q, k, v, g = inputs(chunks)
        for j, c in enumerate(chunks):
            x = _kda_alone_again(q[j], k[j], v[j], g[j], b_ref, c, tk_ref[0, 0, _rows(c), :][:, :CHUNK])
            s_at[c] = s
            s, d_at[c] = _kda_through(x, s, s.dtype)
            w_at[c] = x["w"]
        return s

    _over_groups(forward, s0_ref[0, 0, 0])

    def backward(first, ds):
        chunks = [first + j for j in range(GROUP)]
        q, k, v, g = inputs(chunks)
        tk = [tk_ref[0, 0, _rows(c), :] for c in chunks]                                                         # T beside the decayed k k^T
        p = [p_ref[0, 0, _rows(c // 2), :][:, j % 2 * CHUNK:(j % 2 + 1) * CHUNK] for j, c in enumerate(chunks)]  # a pair's left or right half
        back = _in_step(_kda_backward_alone(q[j], k[j], v[j], do_ref[0, _rows(c), :], g[j], b_ref, c, s_at[c], d_at[c], w_at[c], p[j])
                        for j, c in enumerate(chunks))
        through = [None] * GROUP
        for j in reversed(range(GROUP)):
            ds, through[j] = _kda_backward_through(back[j], s_at[chunks[j]], ds)
        out = _in_step(_kda_backward_rest(back[j], through[j], v[j], tk[j][:, :CHUNK], tk[j][:, CHUNK:]) for j in range(GROUP))
        for c, (dq, dk, dv, dg, db) in zip(chunks, out):
            dq_ref[0, _rows(c), :] = dq.astype(dq_ref.dtype)
            dk_ref[0, _rows(c), :] = dk.astype(dk_ref.dtype)
            dv_ref[0, _rows(c), :] = dv.astype(dv_ref.dtype)
            dg_ref[0, _rows(c), :] = dg
            db_ref[0, 0, pl.ds(c, 1), :] = db
        return ds

    ds_ref[...] = _over_groups(backward, ds_ref[...], against_time=True)


def _kda_specs(r, dk, dv, at):
    """Block specs over ``(row, value head, step)``, the step's block ``at(t)``: q or k (its key head's columns), v or
    o, g or a cotangent a value head ``[b, s, value heads x d_k]``, beta's rows, the kept state, and what the forward
    sweep keeps of each chunk for the backward one (``T`` beside the decayed ``k k^T``; two chunks' ``P``)."""
    tokens = STEP_CHUNKS * CHUNK
    return (pl.BlockSpec((1, tokens, dk), lambda i, j, t: (i, at(t), j // r)),
            pl.BlockSpec((1, tokens, dv), lambda i, j, t: (i, at(t), j)),
            pl.BlockSpec((1, tokens, dk), lambda i, j, t: (i, at(t), j)),
            pl.BlockSpec((1, 1, STEP_CHUNKS, CHUNK), lambda i, j, t: (i, j, at(t), 0)),
            pl.BlockSpec((1, 1, 1, dk, dv), lambda i, j, t: (i, j, at(t), 0, 0)),
            pl.BlockSpec((1, 1, tokens, 2 * CHUNK), lambda i, j, t: (i, j, at(t), 0)),
            pl.BlockSpec((1, 1, tokens // 2, 2 * CHUNK), lambda i, j, t: (i, j, at(t), 0)))


@functools.partial(jax.jit, static_argnames=("hk", "state_dtype", "interpret"))
def kda_rule_fwd(q, k, v, g, beta, *, hk, state_dtype, interpret):
    """The forward sweep with a decay a channel. ``q``, ``k`` ``[b, s, hk d_k]``, ``v`` ``[b, s, hv d_v]``, ``g [b, s,
    hv d_k]`` float32, ``beta [b, hv, s / C, C]`` -> ``o`` like v, the state each step starts from ``[b, hv, steps,
    d_k, d_v]``, and what the backward sweep reads of each chunk instead of making it again: ``T`` and the decayed ``k
    k^T`` side by side, float32 ``[b, hv, s, 2 C]`` (a chunk's ``[C, 2 C]`` at its tokens' rows), and the decayed ``q
    k^T`` from the diagonal down, two chunks side by side in k's dtype, ``[b, hv, s / 2, 2 C]``: 40 KiB a chunk and
    head in bfloat16, 320 MiB a call of the Kimi cell (2 rows of 8192, 32 heads), every lane used."""
    b, s, _ = q.shape
    hv, dk = beta.shape[1], q.shape[2] // hk
    dv, steps = v.shape[2] // hv, s // (STEP_CHUNKS * CHUNK)
    qk, vo, gs, bs, ss, tk, ps = _kda_specs(hv // hk, dk, dv, lambda t: t)
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        _kda_fwd_kernel, grid=(b, hv, steps), in_specs=[qk, qk, vo, gs, bs], out_specs=[vo, ss, tk, ps],
        out_shape=[like(v.shape, v.dtype), like((b, hv, steps, dk, dv), state_dtype), like((b, hv, s, 2 * CHUNK), _F32),
                   like((b, hv, s // 2, 2 * CHUNK), k.dtype)],
        scratch_shapes=[pltpu.VMEM((dk, dv), state_dtype)], name="kda_rule_fwd", interpret=interpret, **_params(interpret),
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("hk", "interpret"))
def kda_rule_bwd(q, k, v, g, beta, do, states, t_kk, p, *, hk, interpret):
    """The backward sweep: ``kda_rule_fwd``'s inputs, ``do`` like v and what the forward sweep kept (the states, ``T``
    beside the decayed ``k k^T``, ``P``) -> cotangents of q and k ``[b, s, hv d_k]`` (a value head each: the caller
    adds a key head's), of v, of g (by channel, like g) and of beta in its row form. By the chip (PERF.md, PR 48, a
    call of the Kimi cell: 2 rows of 8192, 32 heads): 17.8 ms where the sweep that made the three matrices again took
    24.5, every cotangent equal to that sweep's bit for bit, for the 320 MiB held from a microbatch's forward pass to
    its backward pass (the forward sweep 9.0 -> 9.15 ms for writing them)."""
    b, s, _ = q.shape
    hv, dk = beta.shape[1], q.shape[2] // hk
    dv, steps = v.shape[2] // hv, s // (STEP_CHUNKS * CHUNK)
    qk, vo, gs, bs, ss, tk, ps = _kda_specs(hv // hk, dk, dv, lambda t: steps - 1 - t)
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        _kda_bwd_kernel, grid=(b, hv, steps), in_specs=[qk, qk, vo, gs, bs, vo, ss, tk, ps], out_specs=[gs, gs, vo, gs, bs],
        out_shape=[like(g.shape, q.dtype), like(g.shape, k.dtype), like(v.shape, v.dtype), like(g.shape, _F32), like(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32), pltpu.VMEM((STEP_CHUNKS, dk, dv), states.dtype),
                        pltpu.VMEM((STEP_CHUNKS, CHUNK, dv), _F32), pltpu.VMEM((STEP_CHUNKS, CHUNK, dk), k.dtype)],
        name="kda_rule_bwd", interpret=interpret, **_params(interpret),
    )(q, k, v, g, beta, do, states, t_kk, p)


def _rows_of(x, b, n, groups, rs):
    """``[b, n C, hv]`` -> ``[b, groups, n, rs C]``: a chunk's tokens along lanes, a group's heads side by side."""
    return jnp.transpose(x.reshape(b, n, CHUNK, groups, rs), (0, 3, 1, 4, 2)).reshape(b, groups, n, rs * CHUNK)


@functools.lru_cache(maxsize=None)
def _flat_rule(forward, backward, hk, state_dtype, interpret, **static):
    """Two sweeps (the scalar decay's or the decay by channel's) as one differentiable function of the kernels' own
    layouts. What the backward sweep keeps besides the inputs is whatever the forward sweep returns beyond ``o``: the
    state each step starts from (``rows x seq / (8 C) x value heads x d_k x d_v``, an eighth of what the scan's
    autodiff held), which the forward sweep always writes, and of the rule with a decay a channel each chunk's ``T`` and
    its two decayed products too (``kda_rule_fwd``: 40 KiB a chunk and head). Every output is named, in the sweep's
    order (``KEPT_BY_CHANNEL``, of which the scalar rule's two are the first), and the NAMED values are the primal
    output and the residuals (autodiff would read an unnamed one past the name): a ``jax.checkpoint`` whose policy
    saves all of a sweep's names has no forward sweep left in its recomputed pass (q, k, v, the decay and beta are
    rebuilt from the block's input, the sweep itself is dead code and dropped); under a policy that misses ONE of them
    the sweep runs a second time there, and without ``jax.checkpoint`` the residuals are simply held (320 MiB a layer
    and microbatch more than before PR 48 at the Kimi cell's shapes). ``o`` is the only one the forward pass also reads,
    so the only one ``jax.checkpoint`` passes through a ``reduce_precision`` (0.4 ms a call)."""
    static = dict(static, hk=hk, interpret=interpret)

    def fwd(q, k, v, decay, beta):
        swept = forward(q, k, v, decay, beta, state_dtype=state_dtype, **static)
        o, *kept = (checkpoint_name(x, name) for x, name in zip(swept, KEPT_BY_CHANNEL))
        return o, (q, k, v, decay, beta, *kept)

    @jax.custom_vjp
    def rule(q, k, v, decay, beta):
        return fwd(q, k, v, decay, beta)[0]

    def bwd(kept, do):
        dq, dk, dv, ddecay, dbeta = backward(*kept[:5], do, *kept[5:], **static)
        b, s, _ = dq.shape
        of_key_head = lambda x: x.reshape(b, s, hk, -1, kept[0].shape[2] // hk).sum(axis=3).reshape(kept[0].shape)  # noqa: E731
        return of_key_head(dq), of_key_head(dk), dv, ddecay, dbeta

    rule.defvjp(fwd, bwd)
    return rule


def _laid_out(q, k, v, g, beta):
    """The kernels' own layouts of the rule's arguments: ``(the two sweeps, their static arguments besides hk, their five
    operands, the rows' unpadded length)``: rows padded to whole steps with tokens that change nothing, heads flattened
    into lanes, beta laid out as rows; a decay a head summed from each chunk's start and laid out like beta, a decay a
    channel (``g`` of rank 4) handed over flat as it is."""
    b, hk, hv = q.shape[0], q.shape[2], v.shape[2]
    (q, k, v, g, beta), s = _padded_rows((q, k, v, g, beta), STEP_CHUNKS * CHUNK)
    n = q.shape[1] // CHUNK
    flat = lambda x: x.reshape(b, n * CHUNK, -1)  # noqa: E731
    if g.ndim == 4:
        rs, sweeps, more, decay = 1, (kda_rule_fwd, kda_rule_bwd), {}, flat(g.astype(_F32))
    else:
        rs = 2 if (hv // hk) % 2 == 0 else 1
        cum = jnp.cumsum(g.astype(_F32).reshape(b, n, CHUNK, hv), axis=2).reshape(b, n * CHUNK, hv)
        sweeps, more, decay = (gdn_rule_fwd, gdn_rule_bwd), dict(rs=rs), _rows_of(cum, b, n, hv // rs, rs)
    return sweeps, more, (flat(q), flat(k), flat(v), decay, _rows_of(beta.astype(_F32), b, n, hv // rs, rs)), s


def _rule_kernels(q, k, v, g, beta, *, interpret=False):
    """The chunked rule through the kernels (``_rule_xla``'s and ``_rule_xla_by_channel``'s signature at
    ``chunk=CHUNK``) on their own layouts (``_laid_out``); JAX differentiates these layouts, the kernels'
    ``custom_vjp`` the rule."""
    sweeps, more, operands, s = _laid_out(q, k, v, g, beta)
    o = _flat_rule(*sweeps, q.shape[2], jnp.dtype(STATE_DTYPE), interpret, **more)(*operands)
    return o.reshape(o.shape[:2] + (v.shape[2], -1))[:, :s]


def _program(chunk=CHUNK, **head_sizes):
    """Which program takes a call of these head sizes (``d_k=``, ``d_v=``), as ``CALLS`` and ``PASSES`` word it:
    ``kernels``, or ``xla`` and, on a TPU, why."""
    if jax.default_backend() != "tpu":
        return "xla"
    for name, d in head_sizes.items():
        if d % 128:
            return f"xla ({name} {d} is no multiple of 128)"
    return "kernels" if chunk == CHUNK else f"xla (chunk {chunk} is not {CHUNK})"


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK, impl=None):
    """The gated delta rule over whole rows, chunked (module docstring; the
    arguments and the dtypes: ``_rule_xla``). Which program runs is read from
    the input: ``g [b, s, value heads]`` is a decay a head, and takes the
    kernels on a TPU where a head is whole lanes and the chunk is ``CHUNK``,
    the XLA form elsewhere; ``g [b, s, value heads, d_k]`` is a decay a
    channel, and takes its own two kernels under the same conditions and
    ``_rule_xla_by_channel`` elsewhere. ``CALLS`` says which form a call took
    and, on a TPU, why not the kernels. Either form names what a
    rematerialized block may keep of it (``KEPT_ACROSS_REMAT``). ``impl`` is the tests' and the tools' handle: ``"xla"``,
    ``"kernels"``, ``"kernels_interpret"`` (the kernels under the Pallas
    interpreter)."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    by_channel = g.ndim == 4
    program = _program(chunk, d_k=dk, d_v=dv) if impl is None else impl.split("_")[0]
    form = f"chunked {chunk}, a decay a channel in sub-blocks of {min(SUB, chunk)}: {program}" if by_channel else f"chunked {chunk}: {program}"
    entry = CALLS.setdefault((b, s, hk, hv, dk, dv) + ("by channel",) * by_channel, [0, form])
    entry[0] += 1
    if program == "kernels":
        return _rule_kernels(q, k, v, g, beta, interpret=impl == "kernels_interpret")
    return checkpoint_name((_rule_xla_by_channel if by_channel else _rule_xla)(q, k, v, g, beta, chunk=chunk), KEPT_ACROSS_REMAT[0])


# -- the mixer's elementwise work as two fused passes (TPU) -----------------------
#
# Between ``in_proj_qkvz`` and the rule stand a convolution of a few taps, silu, two l2 norms and a scale; between the
# rule and ``out_proj`` a norm gated by ``silu(z)``. As XLA operations each is a pass or two over a ``[b, s, .]``
# activation, with the pads, reshapes to heads and float32 copies that autodiff adds (PERF.md, PR 39, step 0). Here each
# way is ONE kernel forward and one backward over the flat ``[b, s, heads x d]`` layout the rule's kernels read: an
# activation is read once where it lies and written once. A grid step holds a block of ``_token_block`` tokens of one
# key head's columns (its q and k columns and its value heads' columns: three arrays, cut apart where the projection is
# made, ``models/transformer._linear_mixer``) or of one value head (the gated norm); inside it a loop over ``ROWS``
# tokens that is NOT unrolled, so that the text is one trip's (``setup_s``: the rule's section) and a trip's values stay
# in vector registers. The taps are sublane rotations of the trip's rows with the 8 rows before them (after them, in the
# backward pass, which walks a row against time).

ROWS = 256         # tokens a trip of a pass's inner loop (32: every kernel twice as slow, a trip waits out its own latencies)
HALO = 16          # rows fetched before a token block: one bfloat16 tile (the taps look back taps - 1 <= 8 tokens)


def _token_block(s):
    """Tokens a grid step of a pass holds, of a row of ``s`` (whole multiples of 512, as ``_rule_kernels`` pads)."""
    return next(t for t in (2048, 1024, 512) if s % t == 0)


def _shifted(ext, first, rows):
    """Rows ``first .. first + rows - 1`` of ``ext [rows + 8, width]`` float32: a rotation along sublanes where
    ``first`` is no whole tile."""
    n = ext.shape[0]
    return ext[first:first + rows] if first % 8 == 0 else pltpu.roll(ext, n - first, 0)[:rows]


def _sigmoid(x):
    """``1 / (1 + exp(-x))`` with a true division, as the XLA form's (a name of its own so that
    ``benchmarks/calls/pr39_sweep.py`` can price ``tanh`` and an approximate reciprocal: 0.2 ms of the in pass's 1.25)."""
    return jax.nn.sigmoid(x)


def _conv_silu(ext, w_ref):
    """A trip's convolution: ``ext`` its rows under the 8 before them. ``(the shifted rows a tap, c, sigmoid(c))``."""
    taps = w_ref.shape[0]
    shifted = [_shifted(ext, 8 - (taps - 1) + j, ROWS) for j in range(taps)]
    c = sum(w_ref[pl.ds(j, 1), :].astype(_F32) * x for j, x in enumerate(shifted))
    return shifted, c, _sigmoid(c)


def _in_part(t, x_ref, halo_ref, w_ref, y_ref, *, norm, scale):
    """One of a key head's three column blocks through convolution, silu and (``norm``) the l2 norm over the block's
    lanes times ``scale``."""
    before = jnp.where(t > 0, halo_ref[0].astype(_F32)[HALO - 8:], 0.0)      # zeros left of the row

    def trip(i, before):
        rows = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
        x = x_ref[0, rows, :].astype(_F32)
        _, c, sig = _conv_silu(jnp.concatenate([before, x], axis=0), w_ref)
        a = c * sig
        if norm:
            a = a * (jax.lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True) + L2_EPS) * scale)
        y_ref[0, rows, :] = a.astype(y_ref.dtype)
        return x[ROWS - 8:]

    jax.lax.fori_loop(0, x_ref.shape[1] // ROWS, trip, before)


# A key head's three column blocks, q, k and v: (l2-normed?, scaled by d_k ** -0.5?)
_PARTS = ((True, True), (True, False), (False, False))


def _in_kernel(*refs, scale):
    """``(block, halo, taps)`` for each of q, k, v, then the three outputs."""
    for i, (norm, scaled) in enumerate(_PARTS):
        _in_part(pl.program_id(2), *refs[3 * i:3 * i + 3], refs[9 + i], norm=norm, scale=scale if scaled else 1.0)


def _in_part_back(x_ref, halo_ref, w_ref, dy_ref, dx_ref, dw_ref, after_ref, *, norm, scale):
    """``_in_part``'s backward pass over a block, its trips last first: the convolution's output again from the
    kept input, the cotangent through scale, norm and silu, the taps against time (the 8 tokens AFTER a trip's rows are
    the carry, ``after_ref`` from block to block) and the taps' own cotangent added up in ``dw_ref [8 taps, width]``
    (8 partial sums a tap) over the steps of a key head."""
    i_row, t = pl.program_id(1), pl.program_id(2)                             # the grid walks t against time
    taps, trips = w_ref.shape[0], x_ref.shape[1] // ROWS

    @pl.when(t == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)                            # nothing right of the row

    @pl.when((i_row == 0) & (t == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    halo = jnp.where(t < pl.num_programs(2) - 1, halo_ref[0].astype(_F32)[HALO - 8:], 0.0)

    def trip(n, carry):
        after, acc = carry[0], carry[1:]
        i = trips - 1 - n
        first = pl.multiple_of(i * ROWS, ROWS)
        rows = pl.ds(first, ROWS)
        x = x_ref[0, rows, :].astype(_F32)
        lower = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(first - HALO, 0), HALO), HALO), :].astype(_F32)[HALO - 8:]
        shifted, c, sig = _conv_silu(jnp.concatenate([jnp.where(i == 0, halo, lower), x], axis=0), w_ref)
        da = dy_ref[0, rows, :].astype(_F32)
        if norm:    # y = scale a r, r = rsqrt(sum a^2 + eps):  da = scale r (dy - n sum(dy n)), n = a r
            a = c * sig
            r = jax.lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True) + L2_EPS)
            n_ = a * r
            da = (scale * r) * (da - n_ * jnp.sum(da * n_, axis=1, keepdims=True))
        dc = da * sig * (1.0 + c * (1.0 - sig))
        back = jnp.concatenate([dc, after], axis=0)
        dx = sum(w_ref[pl.ds(j, 1), :].astype(_F32) * _shifted(back, taps - 1 - j, ROWS) for j in range(taps))
        dx_ref[0, rows, :] = dx.astype(dx_ref.dtype)
        return (dc[:8],) + tuple(a_j + by_eights(x_j * dc) for a_j, x_j in zip(acc, shifted))

    zeros = jnp.zeros((8, x_ref.shape[2]), _F32)
    out = jax.lax.fori_loop(0, trips, trip, (after_ref[...],) + (zeros,) * taps)
    after_ref[...] = out[0]
    for j, a_j in enumerate(out[1:]):
        dw_ref[8 * j:8 * j + 8, :] += a_j


def _in_back_kernel(*refs, scale):
    """``(block, halo, taps, the output's cotangent)`` for each of q, k, v, then for each the input's cotangent, the
    taps' partial sums, and the scratch that carries a block's first 8 tokens to the block before it."""
    for i, (norm, scaled) in enumerate(_PARTS):
        _in_part_back(*refs[4 * i:4 * i + 4], *refs[12 + i::3], norm=norm, scale=scale if scaled else 1.0)


def _pass_specs(tokens, taps, where):
    """Spec makers for a pass whose grid indices ``where`` turns into ``(row, token block, head)``: a head's block of
    ``[b, s, heads x width]``, the ``HALO`` rows before it, its columns of the taps ``[taps, heads x width]`` and of
    the taps' partial sums ``[8 taps, heads x width]``."""
    return (
        lambda width: pl.BlockSpec((1, tokens, width), lambda *g: where(*g)),
        lambda width: pl.BlockSpec((1, HALO, width), lambda *g: (
            where(*g)[0], jnp.maximum(where(*g)[1] * (tokens // HALO) - 1, 0), where(*g)[2])),
        lambda width: pl.BlockSpec((taps, width), lambda *g: (0, where(*g)[2])),
        lambda width: pl.BlockSpec((8 * taps, width), lambda *g: (0, where(*g)[2])),
    )


def _parts(xq, xk, xv, weight):
    """The q, k and v columns' arrays, each beside its columns of the taps ``[taps, q | k | v channels]``."""
    cuts = (0, xq.shape[2], 2 * xq.shape[2], 2 * xq.shape[2] + xv.shape[2])
    return [(x, weight[:, lo:hi]) for x, lo, hi in zip((xq, xk, xv), cuts, cuts[1:])]


@functools.partial(jax.jit, static_argnames=("hk", "interpret"))
def gdn_in_fwd(xq, xk, xv, weight, *, hk, interpret):
    """The in pass. ``xq``, ``xk`` ``[b, s, hk d_k]`` and ``xv`` ``[b, s, hv d_v]`` (the projection's q, k and v
    columns, ``s`` whole token blocks), ``weight [taps, q | k | v channels]`` -> ``q`` (convolution, silu, l2 norm
    over a head's ``d_k`` lanes, times ``d_k ** -0.5``), ``k`` (the same without the scale) and ``v`` (convolution and
    silu), shaped and typed like their inputs. Float32 inside, ONE rounding at the output: the XLA form
    (``_mixer_in_xla``) rounds to the input's dtype after the convolution, after the norm and after the scale."""
    b, s, kd = xq.shape
    tokens, taps = _token_block(s), weight.shape[0]
    block, halo, taps_of, _ = _pass_specs(tokens, taps, lambda i, h, t: (i, t, h))
    operands, specs = [], []
    for x, w in _parts(xq, xk, xv, weight):
        operands += [x, x, w.astype(_F32)]              # (float32: a row of a packed dtype is no load)
        specs += [block(x.shape[2] // hk), halo(x.shape[2] // hk), taps_of(x.shape[2] // hk)]
    return pl.pallas_call(
        functools.partial(_in_kernel, scale=(kd // hk) ** -0.5),
        grid=(b, hk, s // tokens), in_specs=specs, out_specs=[block(x.shape[2] // hk) for x in (xq, xk, xv)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (xq, xk, xv)],
        name="gdn_in_fwd", interpret=interpret, **_params(interpret, ("parallel",) * 3),
    )(*operands)


@functools.partial(jax.jit, static_argnames=("hk", "interpret"))
def gdn_in_bwd(xq, xk, xv, weight, dq, dk, dv, *, hk, interpret):
    """The in pass's backward pass from its kept inputs: the cotangents of ``xq``, ``xk``, ``xv`` (like them) and of
    ``weight`` (float32). The grid walks a key head's rows and, in a row, the token blocks against time."""
    b, s, kd = xq.shape
    tokens, taps = _token_block(s), weight.shape[0]
    steps = s // tokens
    block, halo, taps_of, sums_of = _pass_specs(tokens, taps, lambda h, i, t: (i, steps - 1 - t, h))
    operands, specs, widths = [], [], []
    for (x, w), dy in zip(_parts(xq, xk, xv, weight), (dq, dk, dv)):
        width = x.shape[2] // hk
        operands += [x, x, w.astype(_F32), dy]
        specs += [block(width), halo(width), taps_of(width), block(width)]
        widths.append(width)
    *dx, dwq, dwk, dwv = pl.pallas_call(
        functools.partial(_in_back_kernel, scale=(kd // hk) ** -0.5),
        grid=(hk, b, steps), in_specs=specs, out_specs=[block(w) for w in widths] + [sums_of(w) for w in widths],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (xq, xk, xv)]
        + [jax.ShapeDtypeStruct((8 * taps, x.shape[2]), _F32) for x in (xq, xk, xv)],
        scratch_shapes=[pltpu.VMEM((8, w), _F32) for w in widths],
        name="gdn_in_bwd", interpret=interpret, **_params(interpret, ("arbitrary",) * 3),
    )(*operands)
    dw = jnp.concatenate([dwq, dwk, dwv], axis=1)
    return (*dx, dw.reshape(taps, 8, -1).sum(axis=1))


def _gate_of(z, sig, sigmoid_gate):
    """The gate from ``z`` and ``sigmoid(z)``: ``silu(z)`` (Gated DeltaNet), or the sigmoid itself (Kimi Delta Attention)."""
    return sig if sigmoid_gate else z * sig


def _gate_norm_kernel(o_ref, z_ref, w_ref, y_ref, *, eps, sigmoid_gate):
    w = w_ref[...]

    def trip(i, _):
        rows = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
        o, z = o_ref[0, rows, :].astype(_F32), z_ref[0, rows, :].astype(_F32)
        normed = o * jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
        y_ref[0, rows, :] = (normed * w * _gate_of(z, _sigmoid(z), sigmoid_gate)).astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, o_ref.shape[1] // ROWS, trip, 0)


def _gate_norm_back_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *, eps, sigmoid_gate):
    """y = n w gate(z), n = o r, r = rsqrt(mean o^2 + eps), gate silu or (``sigmoid_gate``) sigmoid. ``dw_ref [8, d_v]`` holds 8 partial sums of the weight's
    cotangent over the whole grid."""
    w = w_ref[...]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def trip(i, acc):
        rows = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
        o, z, dy = (ref[0, rows, :].astype(_F32) for ref in (o_ref, z_ref, dy_ref))
        r = jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
        normed, sig = o * r, _sigmoid(z)
        gate = _gate_of(z, sig, sigmoid_gate)
        dn = dy * w * gate
        do_ref[0, rows, :] = (r * (dn - normed * jnp.mean(dn * normed, axis=1, keepdims=True))).astype(do_ref.dtype)
        dz_ref[0, rows, :] = (dy * normed * w * sig * ((1.0 - sig) if sigmoid_gate else 1.0 + z * (1.0 - sig))).astype(dz_ref.dtype)
        return acc + by_eights(dy * gate * normed)

    dw_ref[...] += jax.lax.fori_loop(0, o_ref.shape[1] // ROWS, trip, jnp.zeros(dw_ref.shape, _F32))


@functools.partial(jax.jit, static_argnames=("eps", "interpret", "sigmoid_gate"))
def gdn_out_fwd(o, z, weight, *, eps, interpret, sigmoid_gate=False):
    """The out pass. ``o`` (as the rule's kernel wrote it) and ``z`` ``[b, s, hv d_v]``, ``weight [d_v]`` ->
    ``rms_norm(o) weight silu(z)`` (``sigmoid(z)`` with ``sigmoid_gate``) over each head's ``d_v`` lanes, like ``o``. Float32 inside, ONE rounding at the
    output: the XLA form (``_gated_norm_xla``) rounds the norm's output to ``o``'s dtype before the gate."""
    b, s, vd = o.shape
    dv, tokens = weight.shape[0], _token_block(s)
    block = pl.BlockSpec((1, tokens, dv), lambda i, t, h: (i, t, h))
    return pl.pallas_call(
        functools.partial(_gate_norm_kernel, eps=eps, sigmoid_gate=sigmoid_gate),
        grid=(b, s // tokens, vd // dv), in_specs=[block, block, pl.BlockSpec((1, dv), lambda i, t, h: (0, 0))],
        out_specs=block, out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        name="gdn_out_fwd", interpret=interpret, **_params(interpret, ("parallel",) * 3),
    )(o, z, weight.astype(_F32).reshape(1, dv))


@functools.partial(jax.jit, static_argnames=("eps", "interpret", "sigmoid_gate"))
def gdn_out_bwd(o, z, weight, dy, *, eps, interpret, sigmoid_gate=False):
    """The out pass's backward pass from its kept inputs: the cotangents of ``o`` and ``z`` (like them) and of
    ``weight`` (float32)."""
    b, s, vd = o.shape
    dv, tokens = weight.shape[0], _token_block(s)
    block = pl.BlockSpec((1, tokens, dv), lambda i, t, h: (i, t, h))
    do, dz, dw = pl.pallas_call(
        functools.partial(_gate_norm_back_kernel, eps=eps, sigmoid_gate=sigmoid_gate),
        grid=(b, s // tokens, vd // dv), in_specs=[block, block, pl.BlockSpec((1, dv), lambda i, t, h: (0, 0)), block],
        out_specs=[block, block, pl.BlockSpec((8, dv), lambda i, t, h: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype), jax.ShapeDtypeStruct(z.shape, z.dtype), jax.ShapeDtypeStruct((8, dv), _F32)],
        name="gdn_out_bwd", interpret=interpret, **_params(interpret, ("arbitrary",) * 3),
    )(o, z, weight.astype(_F32).reshape(1, dv), dy)
    return do, dz, dw.sum(axis=0)


@functools.lru_cache(maxsize=None)
def _in_pass(hk, interpret):
    """The in pass as one differentiable function; it keeps its inputs and nothing else."""

    @jax.custom_vjp
    def run(xq, xk, xv, weight):
        return tuple(gdn_in_fwd(xq, xk, xv, weight, hk=hk, interpret=interpret))

    def bwd(kept, cotangents):
        *dx, dw = gdn_in_bwd(*kept, *cotangents, hk=hk, interpret=interpret)
        return (*dx, dw.astype(kept[3].dtype))

    run.defvjp(lambda *kept: (run(*kept), kept), bwd)
    return run


@functools.lru_cache(maxsize=None)
def _out_pass(eps, interpret, sigmoid_gate=False):
    """The out pass as one differentiable function; it keeps its inputs and nothing else."""
    static = dict(eps=eps, interpret=interpret, **({"sigmoid_gate": True} if sigmoid_gate else {}))

    @jax.custom_vjp
    def run(o, z, weight):
        return gdn_out_fwd(o, z, weight, **static)

    def bwd(kept, dy):
        do, dz, dw = gdn_out_bwd(*kept, dy, **static)
        return do, dz, dw.astype(kept[2].dtype)

    run.defvjp(lambda *kept: (run(*kept), kept), bwd)
    return run


def _mixer_in_xla(xq, xk, xv, weight, hk):
    """The in pass as XLA operations (``causal_conv``, ``l2_norm``): any backend's path, and what the kernel is held
    to."""
    b, s, kd = xq.shape
    q, k, v = (jax.nn.silu(causal_conv(x, w)) for x, w in _parts(xq, xk, xv, weight))
    heads = lambda x: l2_norm(x.reshape(b, s, hk, kd // hk)).reshape(b, s, kd)  # noqa: E731
    return heads(q) * jnp.asarray((kd // hk) ** -0.5, q.dtype), heads(k), v


def _gated_norm_xla(o, z, weight, eps, activation="silu"):
    """The out pass as XLA operations (``ops/norms.rms_norm``)."""
    from llm_fine_tune_distributed_tpu.ops.norms import rms_norm

    b, s, vd = o.shape
    heads = lambda x: x.reshape(b, s, vd // weight.shape[0], weight.shape[0])  # noqa: E731
    gate = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}[activation]
    y = rms_norm(heads(o), weight, eps).astype(_F32) * gate(heads(z).astype(_F32))
    return y.astype(o.dtype).reshape(b, s, vd)


def _counted(which, shape, program):
    entry = PASSES.setdefault((which, *shape), [0, program])
    entry[0] += 1


def mixer_in(xq, xk, xv, weight, hk, *, impl=None):
    """From the projection's q, k and v columns (``xq``, ``xk`` ``[b, s, hk d_k]``, ``xv`` ``[b, s, hv d_v]``) and
    ``conv1d/weight [taps, q | k | v channels]`` to what the rule takes, flat: q (convolution, silu, l2 norm a head,
    times ``d_k ** -0.5``), k (the same without the scale), v (convolution, silu). Which program runs is read from the
    input as for the rule (``_program``: the kernels on a TPU where a key head's q, k and v columns, ``d_k`` and ``r
    d_v`` wide, are whole lanes; ``PASSES`` says which); ``impl`` as ``gated_delta_rule``'s."""
    b, s, kd = xq.shape
    program = _program(**{"d_k": kd // hk, "r d_v": xv.shape[2] // hk}) if impl is None else impl.split("_")[0]
    _counted("in", (b, s, 2 * kd + xv.shape[2]), program)
    if program != "kernels":
        return _mixer_in_xla(xq, xk, xv, weight, hk)
    padded, s = _padded_rows((xq, xk, xv), STEP_CHUNKS * CHUNK)
    return tuple(y[:, :s] for y in _in_pass(hk, impl == "kernels_interpret")(*padded, weight))


def gated_norm(o, z, weight, eps, *, activation="silu", impl=None):
    """``rms_norm(o) weight gate(z)`` over each value head's ``d_v`` lanes, the gate's ``activation`` ``"silu"`` (Gated
    DeltaNet) or ``"sigmoid"`` (Kimi Delta Attention): ``o`` (the rule's output) and ``z`` flat ``[b, s, hv d_v]``,
    ``weight [d_v]``. Which program: as ``mixer_in``."""
    program = _program(d_v=weight.shape[0]) if impl is None else impl.split("_")[0]
    _counted("out", o.shape, program)
    if program != "kernels":
        return _gated_norm_xla(o, z, weight, eps, activation)
    (o, z), s = _padded_rows((o, z), STEP_CHUNKS * CHUNK)
    return _out_pass(float(eps), impl == "kernels_interpret", *((True,) if activation == "sigmoid" else ()))(o, z, weight)[:, :s]
