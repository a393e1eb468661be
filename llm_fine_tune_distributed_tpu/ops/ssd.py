"""The operations of a Mamba-2 mixer that are not projections (Granite 4.0-H's ``mamba`` layers, HF
``GraniteMoeHybridMambaLayer``; Dao and Gu, "Transformers are SSMs", ICML 2024): the state-space scan (SSD), a
recurrence over time with ONE scalar decay a head and token, and the elementwise work on either side of it: on the way
IN a causal depthwise convolution of a few taps with a bias and silu over ``[x | B | C]`` and ``softplus(dt +
dt_bias)`` (``mixer_in``), on the way OUT the gate ``silu(z)`` and then ONE norm over a group's channels
(``gated_norm``: the gate first, the other Mamba-2 order is ``N(y) silu(z)``).

The scan, for one row and one head of ``P`` channels with a state ``S [P, N]`` from zero, for t = 0, 1, ...:

    a_t = exp(dt_t A);   S = a_t S + dt_t x_t B_t^T;   y_t = S C_t + D x_t

(``dt_t > 0`` after the softplus, ``A < 0`` a head, ``B_t``, ``C_t`` in R^N shared by every head of a group, ``D`` a
skip a head). No correction ``S^T k`` as in the delta rules of ``ops/gated_delta.py`` and so no triangular inverse: the
chunked form is products alone. With ``G_i`` the running sum of ``dt A`` from a chunk's start, inside a chunk

    Y = (L * (C B^T)) (dt X) + exp(G) (C S_0^T) + D X,    L_ij = exp(G_i - G_j) for i >= j, else 0

and across chunks ``S = exp(G_end) S_0 + sum_j exp(G_end - G_j) dt_j x_j B_j^T``, the state float32
(``STATE_DTYPE``). A decay is only ever formed as ``exp(G_i - G_j)`` with ``i >= j`` (at most 1), masked BEFORE the
``exp``, as ``ops/gated_delta.py`` does and says why. ``C B^T`` is one product a chunk for all the heads that share B
and C; only the decay mask differs by head.

Which program runs a call is read from the input, the backend and the mesh (``_program``; no knob), and ``CALLS`` /
``calls_summary()`` say which and, on a TPU, why not the kernels:

- on a TPU, one device's program, one B/C group, heads that divide a 128-lane register (Granite: 64, two heads a
  register) and a state of whole registers: two Pallas sweeps, ``ssd_scan_fwd`` and ``ssd_scan_bwd`` behind one
  ``custom_vjp`` (the section "kernels" below: the layout and why). They take x, B, C and dt where the projections
  and the IN pass leave them and make ``G`` and the row forms of the per-token scalars themselves, in VMEM;
- everywhere else (a CPU, other head sizes, several groups, a mesh of several devices): XLA operations, a
  ``lax.scan`` over chunks (``_scan_xla``), which is also what the tests hold the kernels to.

The two passes are XLA operations on every backend: ``causal_conv`` (``ops/gated_delta.py``'s ``custom_vjp``) with the
bias and silu in one fusion, ``softplus``; the gate and ``rms_norm`` in float32. (PASSES records them as ``xla``.)

Rows whose length is no multiple of the chunk are padded with tokens that change nothing (``dt = 0``: a decay of 1 and
no update) and the padding's outputs dropped. Right-padded rows of a batch need nothing: the scan is causal. Packed
rows (``segment_ids``) are NOT supported: state and convolution would have to restart at a boundary (the model refuses
them, ROADMAP.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_fine_tune_distributed_tpu.ops.gated_delta import _padded_rows, causal_conv

# Tokens a chunk. ``[CHUNK, CHUNK]`` float32 is 16 vector registers: a head's decay mask, its product with C B^T and
# the cast live in registers together. (The config's ``mamba_chunk_size`` 256 is HF's kernel's; any chunk gives one result.)
CHUNK = 128
# Chunks a grid step of the sweeps holds: the forward sweep keeps the state each step starts from, one a
# ``STEP_CHUNKS * CHUNK`` tokens, and the backward sweep makes the states inside a step again.
STEP_CHUNKS = 8
# The carried state's dtype (a test lowers it to show that the comparison with the reference sees it).
STATE_DTYPE = jnp.float32
_F32 = jnp.float32

# What the scan names (``checkpoint_name``) for a rematerialized block to keep: its output and, where the kernels run,
# the state each step of the forward sweep starts from. They go together: with one missing the recomputed pass runs the
# whole forward sweep for it. The XLA form names ``y`` only. No block keeps them today (``models/transformer._remat_policy``).
KEPT_ACROSS_REMAT = ("ssd_y", "ssd_states")

# {(rows, seq, heads, head width, state width, groups): [calls traced, form]} of every ``ssd_scan`` traced in this
# process, as ``ops/gated_delta.CALLS``; PASSES the same for ``mixer_in`` ("in") and ``gated_norm`` ("out").
CALLS: dict = {}
PASSES: dict = {}


def calls_summary() -> str:
    """One line for entry points to print beside ``dispatch_summary()``."""
    said = "; ".join(f"{list(shape)}: {form} x {n}" for shape, (n, form) in sorted(CALLS.items()))
    passes = "; ".join(f"{which} {list(shape)}: {form} x {n}" for (which, *shape), (n, form) in sorted(PASSES.items()))
    return f"state-space scan traced as: {said or 'nothing traced'}" + (f"; mixer passes: {passes}" if passes else "")


def _counted(table, key, form):
    entry = table.setdefault(key, [0, form])
    entry[0] += 1


# -- the scan as XLA operations ---------------------------------------------------


def _scan_xla(x, dt, a, b, c, d, *, chunk: int = CHUNK):
    """The chunked scan as XLA operations (module docstring): any backend's path, and what the kernels are held to.

    ``x [b, s, heads, P]``, ``dt [b, s, heads]`` float32 (after the softplus), ``a [heads]`` float32 (``-exp(A_log)``),
    ``b`` and ``c`` ``[b, s, groups, N]`` (group ``i`` serves heads ``i r .. i r + r - 1``), ``d [heads]``. Returns
    ``y [b, s, heads, P]`` in ``x``'s dtype. Matrix products take their operands in ``x``'s dtype and add up in
    float32; decays and the carried state are float32. One chunk a step of a ``lax.scan`` whose body is
    rematerialized: the backward pass holds the state at each chunk boundary and nothing of ``[chunk, chunk]`` a head
    beyond the step it is in."""
    rows, s, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r = heads // groups
    cd = x.dtype
    (x, dt, b, c), s = _padded_rows((x, dt.astype(_F32), b, c), chunk)
    chunks = x.shape[1] // chunk
    by_chunk = lambda z, *rest: jnp.moveaxis(z.reshape(rows, chunks, chunk, *rest), 1, 0)  # noqa: E731
    xs = (by_chunk(x, groups, r, p), by_chunk(dt, groups, r), by_chunk(b, groups, n), by_chunk(c, groups, n))
    a, d = a.astype(_F32).reshape(groups, r), d.astype(_F32).reshape(groups, r)
    lower = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]

    @jax.checkpoint
    def step(state, chunk_of):
        x_c, dt_c, b_c, c_c = chunk_of                               # [rows, C, groups, ...]
        cum = jnp.cumsum(dt_c * a, axis=1)                           # G_i  [rows, C, groups, r]
        cum_t = jnp.moveaxis(cum, 1, -1)                             # [rows, groups, r, C]
        decay = jnp.exp(jnp.where(lower, cum_t[..., :, None] - cum_t[..., None, :], -jnp.inf))
        cb = jnp.einsum("bigs,bjgs->bgij", c_c, b_c, preferred_element_type=_F32)
        m = (cb[:, :, None] * decay * jnp.moveaxis(dt_c, 1, -1)[..., None, :]).astype(cd)       # [rows, groups, r, C, C]
        y = jnp.einsum("bgrij,bjgrp->bigrp", m, x_c, preferred_element_type=_F32)
        of_state = jnp.einsum("bigs,bgrps->bigrp", c_c, state.astype(cd), preferred_element_type=_F32)
        y = y + jnp.exp(cum)[..., None] * of_state + d[..., None] * x_c.astype(_F32)
        last = cum[:, -1:]                                           # G_end  [rows, 1, groups, r]
        w = jnp.exp(last - cum) * dt_c                               # exp(G_end - G_j) dt_j
        grown = jnp.einsum("bjgrp,bjgs->bgrps", (w[..., None] * x_c.astype(_F32)).astype(cd), b_c, preferred_element_type=_F32)
        state = (jnp.exp(last[:, 0])[..., None, None] * state.astype(_F32) + grown).astype(STATE_DTYPE)
        return state, y.astype(cd)

    _, y = jax.lax.scan(step, jnp.zeros((rows, groups, r, p, n), STATE_DTYPE), xs)
    return jnp.moveaxis(y, 0, 1).reshape(rows, chunks * chunk, heads, p)[:, :s]


# -- the scan as Pallas kernels (TPU) ---------------------------------------------
#
# ``_scan_xla`` chunk by chunk in VMEM. A head of 64 channels is HALF a lane register, so the kernels stand to it
# token-major with TWO heads a 128-lane block, each under its own decay (``128 // P`` heads in general): x, y and
# their cotangents are read and written flat where the projections and the passes leave them, ``[b, s, heads x P]``,
# a block ``[tokens, 128]`` at lane offset ``128 j``; nothing is padded or transposed in HBM. (Channel-major, ``Y^T
# [P, C] = X^T (L * C B^T)^T``, would make every lane dimension the chunk, but the MXU holds its RIGHT operand and
# streams the left one's rows: with the mask by head on the right it would load a ``[C, C]`` tile to stream 64 rows.
# Token-major the masked ``[C, C]`` matrix streams its 128 rows against the resident ``[C, 128]`` block of x.) A
# block's two heads are told apart by LANE MASKS, never by a slice at lane 64: ``M_0 X_0 + M_1 X_1`` with ``X_j`` the
# block zeroed outside head j's lanes costs the two passes of ``[C, C] x [C, 128]`` that two ``[C, C] x [C, 64]``
# products would, and ``C S_0^T``, ``B^T (w X)`` and their transposes are ONE product for both heads, the decays
# applied by lane.
#
# The grid is ``(row, step of STEP_CHUNKS chunks, block of heads)`` with the heads INNERMOST: B and C ``[tokens, N]``
# keep their block index over the heads, so they are fetched once a step, and ``C B^T`` is made at the step's first
# block of heads into VMEM scratch ``[STEP_CHUNKS, C, C]`` and read by the other blocks: once a chunk, not once a head.
# The state of EVERY block of heads lives in scratch ``[blocks, N, 128]`` float32 (2 MiB at 64 heads of 64 x 128) along
# the sequential step axis.
#
# The per-token scalars: a head's work wants ``dt`` and ``G`` (``dt A`` summed from its chunk's start) as ROWS, a
# chunk's tokens along lanes (the row form is what the ``[C, C]`` matrices broadcast over sublanes; the column form is
# the diagonal of its broadcast, ``_col``). The sweeps read ``dt [b, s, heads]`` float32 AS ``mixer_in`` LEAVES IT, a
# step's tokens of all heads a block whose index is constant over the heads' axis (fetched once a step, as B and C),
# and ``a [1, heads]``; no XLA operation stands between the IN pass and a sweep but free reshapes. At the step's first
# block of heads (where ``C B^T`` is made) ``_make_rows`` makes, for ALL heads and the step's chunks, both row forms
# into scratch ``[STEP_CHUNKS, blocks, heads a block, C]``, which a later block of the same row and step reads by
# LEADING indices only (Mosaic loads no single sublane at a dynamic offset): the token-major block ``[C, heads]``
# times a 0/1 ``[C, C]`` matrix, contracted over the tokens, lands already turned, the identity for dt and ``U_ji =
# [j <= i]`` for the running sum. Those products run at ``Precision.HIGHEST``: a decay is ``exp(G_i - G_j)`` and
# ``|G|`` reaches the thousands for a fast head, the 0/1 matrix is exact in bfloat16 and ``dt A`` is not. (The other
# way, a log-step shifted add over the tokens and a ``[C, C]`` transpose, was timed alone and not in the cell, where all
# of this costs 0.01 to 0.02 ms a call: PERF.md, PR 50.)
#
# The forward sweep always writes the state each step starts from (``[b, steps, blocks, N, 128]`` float32, 16 MiB a
# row of 8192: the backward sweep's only residual besides the inputs). The backward sweep walks a row's steps last to
# first: inside a step the states forward once more into scratch, then the chunks against time with the state's
# cotangent carried in scratch; dB and dC, which every head adds to, are output blocks that stay resident over the
# heads' axis, and ``d(C B^T)`` adds up over the heads in scratch and becomes its two products at the last block.
# The cotangents of a head's dt and G are rows too: they wait in scratch of the rows' layout for the step's LAST block
# of heads, which turns them back with the same two 0/1 matrices (``U`` contracted over ``i`` is the running sum's
# transpose: ``dt_j`` takes ``A`` times G's cotangents from ``j`` on) and writes dt's whole cotangent as dt lies,
# ``[b, s, heads]``, an output block resident over the heads' axis as dB and dC are, and the step's part of ``a``'s
# cotangent ``[b, steps, 1, heads]`` (XLA adds the parts up: a reduction to ``[heads]``).

def _dot(x, y):
    return jnp.dot(x, y, preferred_element_type=_F32)


def _dot_nt(x, y):
    """``x y^T``: the MXU takes its right operand transposed as it is loaded."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())), preferred_element_type=_F32)


def _dot_tn(x, y):
    """``x^T y``."""
    return jax.lax.dot_general(x, y, (((0,), (0,)), ((), ())), preferred_element_type=_F32)


def _turned(x, y):
    """``x^T y`` in float32: tokens down ``[C, heads]`` times a 0/1 ``[C, C]`` matrix -> rows ``[heads, C]``, summed
    (or merely turned) in float32. At the default precision the MXU would round ``dt A`` to bfloat16 first."""
    return jax.lax.dot_general(x, y, (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST, preferred_element_type=_F32)


def _turned_back(x, y):
    """``x y^T`` in float32: a 0/1 ``[C, C]`` matrix times rows ``[heads, C]`` -> ``[C, heads]``, as dt lies."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST, preferred_element_type=_F32)


def _col(row, eye):
    """``[1, n]`` -> ``[n, 1]`` without a transpose: the diagonal of the row's broadcast."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _rows(c):
    """The tokens of chunk ``c`` of a grid step's block (``c`` a loop's index)."""
    return pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)


def _iotas():
    return jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0), jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)


def _masks():
    ri, ci = _iotas()
    return ri >= ci, ri == ci, ci[:1] == CHUNK - 1


def _zero_ones():
    """The 0/1 matrices of the products that turn, float32: ``U_ji = [j <= i]`` (contracted over ``j`` a sum up to
    ``i``, the running sum; contracted over ``i`` a sum from ``j`` on, its transpose) and the identity."""
    ri, ci = _iotas()
    return jnp.where(ri <= ci, 1.0, 0.0).astype(_F32), jnp.where(ri == ci, 1.0, 0.0).astype(_F32)


def _make_rows(dt_ref, a_ref, dtr_ref, g_ref):
    """At a step's first block of heads, for ALL heads and the step's chunks: dt and ``G``, the running sum of ``dt A``
    from each chunk's start, as rows ``[heads, C]`` into scratch ``[STEP_CHUNKS, blocks, heads a block, C]``. One product
    each with a 0/1 matrix, which lands turned: the identity for dt, ``U`` for the sum, float32 throughout."""
    upper, same = _zero_ones()

    def one(c, _):
        dt = dt_ref[0, _rows(c), :]                                  # [C, heads], tokens down: the column form as it lies
        dtr_ref[c] = _turned(dt, same).reshape(dtr_ref.shape[1:])
        g_ref[c] = _turned(dt * a_ref[...], upper).reshape(g_ref.shape[1:])
        return 0

    jax.lax.fori_loop(0, STEP_CHUNKS, one, 0)


def _head_scalars(dtr_ref, g_ref, c, block, j, lower, eye, at_last):
    """Head ``j`` of the block in chunk ``c``: dt and G as rows ``[1, C]``, G as a column, the decay mask ``L``, ``G_end``
    ``[1, 1]`` and ``exp(G_end - G_j)`` as a row."""
    dt_row, g_row = dtr_ref[c, block, j:j + 1, :], g_ref[c, block, j:j + 1, :]
    g_col = _col(g_row, eye)
    last = jnp.sum(jnp.where(at_last, g_row, 0.0), axis=1, keepdims=True)
    return dt_row, g_row, g_col, jnp.exp(jnp.where(lower, g_col - g_row, -jnp.inf)), last, jnp.exp(last - g_row)


def _make_cb(b_ref, c_ref, cb_ref):
    def one(c, _):
        cb_ref[c] = _dot_nt(c_ref[0, _rows(c), :], b_ref[0, _rows(c), :])
        return 0

    jax.lax.fori_loop(0, STEP_CHUNKS, one, 0)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, s0_ref, cb_ref, state_ref, dtr_ref, g_ref, *, heads, p):
    """A grid step of the forward sweep: ``heads`` heads of ``p`` lanes side by side, ``STEP_CHUNKS`` chunks."""
    step, block = pl.program_id(1), pl.program_id(2)
    cd = x_ref.dtype
    lower, eye, at_last = _masks()
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads * p), 1) // p

    @pl.when(block == 0)
    def _():
        _make_cb(b_ref, c_ref, cb_ref)
        _make_rows(dt_ref, a_ref, dtr_ref, g_ref)

    @pl.when(step == 0)
    def _():
        state_ref[block] = jnp.zeros(state_ref.shape[1:], state_ref.dtype)

    s0_ref[0, 0, 0] = state_ref[block]

    def chunk(c, s):
        rows = _rows(c)
        x = x_ref[0, rows, :]
        x32, cb = x.astype(_F32), cb_ref[c]
        y = d_ref[0] * x32
        e_l, w_l, whole = jnp.zeros_like(x32), jnp.zeros_like(x32), jnp.zeros((1, heads * p), _F32)
        for j in range(heads):
            dt_row, _, g_col, decay, last, rest = _head_scalars(dtr_ref, g_ref, c, block, j, lower, eye, at_last)
            mine = head_of_lane == j
            y = y + _dot((cb * decay * dt_row).astype(cd), jnp.where(mine, x, jnp.zeros_like(x)))
            e_l = jnp.where(mine, jnp.exp(g_col), e_l)
            w_l = jnp.where(mine, _col(rest * dt_row, eye), w_l)
            whole = jnp.where(mine, jnp.exp(last), whole)
        y = y + e_l * _dot(c_ref[0, rows, :], s.astype(cd))
        y_ref[0, rows, :] = y.astype(y_ref.dtype)
        return (whole * s.astype(_F32) + _dot_tn(b_ref[0, rows, :], (w_l * x32).astype(cd))).astype(s.dtype)

    state_ref[block] = jax.lax.fori_loop(0, STEP_CHUNKS, chunk, state_ref[block])


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, dy_ref, s0_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                cb_ref, dcb_ref, s_at, ds_ref, dtr_ref, g_ref, ddtr_ref, dgr_ref, *, heads, p):
    """A grid step of the backward sweep (a row's steps come last first): the step's states forward once more from the
    state the forward sweep kept, then its chunks against time with the state's cotangent carried (``ds_ref``). The
    cotangents of a head's dt and G are rows; they wait in scratch for the step's last block of heads, which writes
    dt's cotangent as dt lies, G's folded in through the running sum's transpose, and the step's part of ``a``'s."""
    step, block = pl.program_id(1), pl.program_id(2)
    cd = x_ref.dtype
    lower, eye, at_last = _masks()
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads * p), 1) // p

    @pl.when(block == 0)
    def _():
        _make_cb(b_ref, c_ref, cb_ref)
        _make_rows(dt_ref, a_ref, dtr_ref, g_ref)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)
        da_ref[...] = jnp.zeros_like(da_ref)

    @pl.when(step == 0)
    def _():
        ds_ref[block] = jnp.zeros(ds_ref.shape[1:], ds_ref.dtype)

    def by_lane(c):
        """``exp(G_i)``, ``exp(G_end - G_j) dt_j`` and ``exp(G_end)`` of chunk ``c``, each head's in its lanes."""
        e_l, w_l = jnp.zeros((CHUNK, heads * p), _F32), jnp.zeros((CHUNK, heads * p), _F32)
        whole = jnp.zeros((1, heads * p), _F32)
        for j in range(heads):
            dt_row, _, g_col, _, last, rest = _head_scalars(dtr_ref, g_ref, c, block, j, lower, eye, at_last)
            mine = head_of_lane == j
            e_l = jnp.where(mine, jnp.exp(g_col), e_l)
            w_l = jnp.where(mine, _col(rest * dt_row, eye), w_l)
            whole = jnp.where(mine, jnp.exp(last), whole)
        return e_l, w_l, whole

    def forward(c, s):
        s_at[c] = s
        _, w_l, whole = by_lane(c)
        x32 = x_ref[0, _rows(c), :].astype(_F32)
        return (whole * s.astype(_F32) + _dot_tn(b_ref[0, _rows(c), :], (w_l * x32).astype(cd))).astype(s.dtype)

    jax.lax.fori_loop(0, STEP_CHUNKS, forward, s0_ref[0, 0, 0])

    def backward(n, ds):
        c = STEP_CHUNKS - 1 - n
        rows = _rows(c)
        x, dy = x_ref[0, rows, :], dy_ref[0, rows, :]
        x32, dy32 = x.astype(_F32), dy.astype(_F32)
        b_c, c_c, cb = b_ref[0, rows, :], c_ref[0, rows, :], cb_ref[c]
        s = s_at[c]
        sc, dsc = s.astype(cd), ds.astype(cd)
        of_state = _dot(c_c, sc)                                     # C S_0^T
        to_state = _dot(b_c, dsc)                                    # B dS^T: the cotangent of w X
        dx = d_ref[0] * dy32
        dcb = dcb_ref[c]
        e_l, w_l, whole = jnp.zeros_like(x32), jnp.zeros_like(x32), jnp.zeros((1, heads * p), _F32)
        for j in range(heads):
            dt_row, g_row, g_col, decay, last, rest = _head_scalars(dtr_ref, g_ref, c, block, j, lower, eye, at_last)
            mine = head_of_lane == j
            x_j, dy_j = jnp.where(mine, x, jnp.zeros_like(x)), jnp.where(mine, dy, jnp.zeros_like(dy))
            masked = cb * decay                                      # (C B^T) * L
            dx = dx + _dot_tn((masked * dt_row).astype(cd), dy_j)    # M^T dY
            dm = _dot_nt(dy_j, x_j)                                  # dM = dY X^T, this head's lanes alone
            dcb = dcb + dm * decay * dt_row
            k = dm * masked                                          # d(dt_j) a pair; times dt_j: d(G_i - G_j)
            through = k * dt_row
            w_row, e_col = rest * dt_row, jnp.exp(g_col)
            dw_row = _row(jnp.sum(jnp.where(mine, to_state * x32, 0.0), axis=1, keepdims=True), eye)
            dg_col = jnp.sum(through, axis=1, keepdims=True) + e_col * jnp.sum(jnp.where(mine, dy32 * of_state, 0.0), axis=1, keepdims=True)
            at_end = jnp.sum(dw_row * w_row, axis=1, keepdims=True) + jnp.exp(last) * jnp.sum(
                jnp.where(mine, ds * s.astype(_F32), 0.0), axis=(0, 1), keepdims=True)
            ddtr_ref[c, block, j:j + 1, :] = jnp.sum(k, axis=0, keepdims=True) + dw_row * rest
            dgr_ref[c, block, j:j + 1, :] = (_row(dg_col, eye) - jnp.sum(through, axis=0, keepdims=True) - dw_row * w_row
                                             + jnp.where(at_last, at_end, 0.0))
            e_l = jnp.where(mine, e_col, e_l)
            w_l = jnp.where(mine, _col(w_row, eye), w_l)
            whole = jnp.where(mine, jnp.exp(last), whole)
        dcb_ref[c] = dcb
        dx_ref[0, rows, :] = (dx + w_l * to_state).astype(dx_ref.dtype)
        dy_e = (e_l * dy32).astype(cd)
        dc_ref[0, rows, :] += _dot_nt(dy_e, sc)
        db_ref[0, rows, :] += _dot_nt((w_l * x32).astype(cd), dsc)

        @pl.when(block == pl.num_programs(2) - 1)
        def _():
            dcbc = dcb.astype(cd)
            dc_ref[0, rows, :] += _dot(dcbc, b_c)
            db_ref[0, rows, :] += _dot_tn(dcbc, c_c)
            # every head's rows are in: G_i holds dt_j A for j <= i, so dt_j takes A times G's cotangents from j on
            upper, same = _zero_ones()
            flat = (ddtr_ref.shape[1] * ddtr_ref.shape[2], CHUNK)
            from_here = _turned_back(upper, dgr_ref[c].reshape(flat))                # [C, heads]
            ddt_ref[0, rows, :] = _turned_back(same, ddtr_ref[c].reshape(flat)) + a_ref[...] * from_here
            da_ref[0, 0] += jnp.sum(dt_ref[0, rows, :] * from_here, axis=0, keepdims=True)

        return whole * ds + _dot_tn(c_c, dy_e)

    ds_ref[block] = jax.lax.fori_loop(0, STEP_CHUNKS, backward, ds_ref[block])


def _params(interpret):
    return {} if interpret else dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=64 * 2**20))


def _block_specs(p, per_block, all_heads, n, at):
    """Block specs over ``(row, step, block of heads)``, a row's steps at ``at(t)``: (x, y and their cotangents) a
    block's lanes of ``[b, s, heads x P]``; (dt and its cotangent) a step's tokens of ``[b, s, heads]`` as they lie,
    ALL heads, the same for every block of heads, as (B, C) ``[b, s, N]`` are; ``a [1, heads]`` whole; the skip's lanes
    ``[blocks, 1, lanes]``; a step's state ``[b, steps, blocks, N, lanes]``; a step's part of ``a``'s cotangent
    ``[b, steps, 1, heads]``."""
    tokens, lanes = STEP_CHUNKS * CHUNK, per_block * p
    return (
        pl.BlockSpec((1, tokens, lanes), lambda i, t, j: (i, at(t), j)),
        pl.BlockSpec((1, tokens, all_heads), lambda i, t, j: (i, at(t), 0)),
        pl.BlockSpec((1, all_heads), lambda i, t, j: (0, 0)),
        pl.BlockSpec((1, tokens, n), lambda i, t, j: (i, at(t), 0)),
        pl.BlockSpec((1, 1, lanes), lambda i, t, j: (j, 0, 0)),
        pl.BlockSpec((1, 1, 1, n, lanes), lambda i, t, j: (i, at(t), j, 0, 0)),
        pl.BlockSpec((1, 1, 1, all_heads), lambda i, t, j: (i, at(t), 0, 0)),
    )


def _rows_scratch(blocks, per_block):
    """A step's per-token scalars of every head as rows: a later block reads its heads' by LEADING indices."""
    return pltpu.VMEM((STEP_CHUNKS, blocks, per_block, CHUNK), _F32)


@functools.partial(jax.jit, static_argnames=("p", "state_dtype", "interpret"))
def ssd_scan_fwd(x, dt, a, b, c, d, *, p, state_dtype, interpret):
    """The forward sweep. ``x [b, s, heads x P]`` (``s`` whole steps), ``dt [b, s, heads]`` float32 as ``mixer_in``
    leaves it, ``a [1, heads]`` float32, ``b`` and ``c`` ``[b, s, N]``, ``d [blocks, 1, lanes]`` float32 -> ``y`` like x
    and the state each step starts from ``[b, steps, blocks, N, lanes]``."""
    rows, s, _ = x.shape
    all_heads, n, per_block = dt.shape[2], b.shape[2], 128 // p
    blocks, steps = all_heads // per_block, s // (STEP_CHUNKS * CHUNK)
    xs, dts, whole, bc, skip, state, _ = _block_specs(p, per_block, all_heads, n, lambda t: t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=per_block, p=p),
        grid=(rows, steps, blocks), in_specs=[xs, dts, whole, bc, bc, skip], out_specs=[xs, state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((rows, steps, blocks, n, 128), state_dtype)],
        scratch_shapes=[pltpu.VMEM((STEP_CHUNKS, CHUNK, CHUNK), _F32), pltpu.VMEM((blocks, n, 128), state_dtype),
                        _rows_scratch(blocks, per_block), _rows_scratch(blocks, per_block)],
        name="ssd_scan_fwd", interpret=interpret, **_params(interpret),
    )(x, dt, a, b, c, d)


@functools.partial(jax.jit, static_argnames=("p", "interpret"))
def ssd_scan_bwd(x, dt, a, b, c, d, dy, states, *, p, interpret):
    """The backward sweep: ``ssd_scan_fwd``'s inputs, ``dy`` like x and the kept states -> the cotangents of x (like
    it), of ``dt`` as it lies (G's folded in), of ``a`` a step ``[b, steps, 1, heads]``, and of ``b`` and ``c`` (float32,
    summed over the heads)."""
    rows, s, _ = x.shape
    all_heads, n, per_block = dt.shape[2], b.shape[2], 128 // p
    blocks, steps = all_heads // per_block, s // (STEP_CHUNKS * CHUNK)
    xs, dts, whole, bc, skip, state, da = _block_specs(p, per_block, all_heads, n, lambda t: steps - 1 - t)
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=per_block, p=p),
        grid=(rows, steps, blocks), in_specs=[xs, dts, whole, bc, bc, skip, xs, state],
        out_specs=[xs, dts, da, bc, bc],
        out_shape=[like(x.shape, x.dtype), like(dt.shape, _F32), like((rows, steps, 1, all_heads), _F32), like(b.shape, _F32), like(c.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((STEP_CHUNKS, CHUNK, CHUNK), _F32), pltpu.VMEM((STEP_CHUNKS, CHUNK, CHUNK), _F32),
                        pltpu.VMEM((STEP_CHUNKS, n, 128), states.dtype), pltpu.VMEM((blocks, n, 128), _F32)]
                       + [_rows_scratch(blocks, per_block)] * 4,
        name="ssd_scan_bwd", interpret=interpret, **_params(interpret),
    )(x, dt, a, b, c, d, dy, states)


@functools.lru_cache(maxsize=None)
def _flat_scan(p, state_dtype, interpret):
    """The two sweeps as one differentiable function of the kernels' own layouts. It keeps its inputs and the states
    the forward sweep wrote; both outputs are named (``KEPT_ACROSS_REMAT``), and the NAMED values are the primal output
    and the residual, so a ``jax.checkpoint`` whose policy saves both names has no forward sweep in its recomputed
    pass. The skip's cotangent is one reduction in XLA, dead (and dropped) where ``D`` is frozen; ``a``'s the sum of
    the steps' parts."""

    def fwd(x, dt, a, b, c, d):
        y, states = ssd_scan_fwd(x, dt, a, b, c, d, p=p, state_dtype=state_dtype, interpret=interpret)
        y, states = checkpoint_name(y, KEPT_ACROSS_REMAT[0]), checkpoint_name(states, KEPT_ACROSS_REMAT[1])
        return y, (x, dt, a, b, c, d, states)

    @jax.custom_vjp
    def scan(x, dt, a, b, c, d):
        return fwd(x, dt, a, b, c, d)[0]

    def bwd(kept, dy):
        x, dt, a, b, c, d, states = kept
        dx, ddt, da, db, dc = ssd_scan_bwd(x, dt, a, b, c, d, dy, states, p=p, interpret=interpret)
        lanes = d.shape[2]
        dd = jnp.sum((dy.astype(_F32) * x.astype(_F32)).reshape(-1, d.shape[0], lanes), axis=0)[:, None]
        return dx, ddt, jnp.sum(da, axis=(0, 1)), db.astype(b.dtype), dc.astype(c.dtype), dd

    scan.defvjp(fwd, bwd)
    return scan


def _scan_kernels(x, dt, a, b, c, d, *, interpret=False):
    """The scan through the kernels (``_scan_xla``'s signature at ``chunk=CHUNK``, one group): rows padded to whole steps
    with tokens that change nothing, heads flat in lanes, dt as ``mixer_in`` leaves it; nothing is summed or turned
    here (the sweeps make each chunk's running sum and the row forms in VMEM). JAX differentiates the reshapes, the
    kernels' ``custom_vjp`` the scan."""
    rows, _, heads, p = x.shape
    per_block = 128 // p
    (x, dt, b, c), s = _padded_rows((x, dt.astype(_F32), b, c), STEP_CHUNKS * CHUNK)
    blocks = heads // per_block
    skip = jnp.broadcast_to(d.astype(_F32).reshape(blocks, per_block, 1), (blocks, per_block, p)).reshape(blocks, 1, 128)
    flat = lambda z: z.reshape(rows, x.shape[1], -1)  # noqa: E731
    y = _flat_scan(p, jnp.dtype(STATE_DTYPE), interpret)(flat(x), dt, a.astype(_F32).reshape(1, heads), flat(b), flat(c), skip)
    return y.reshape(rows, -1, heads, p)[:, :s]


def _program(*, p: int, n: int, groups: int, chunk: int = CHUNK, mesh=None) -> str:
    """Which program takes a scan of these sizes, as ``CALLS`` words it: ``kernels``, or ``xla`` and, on a TPU, why."""
    if jax.default_backend() != "tpu":
        return "xla"
    if mesh is not None and mesh.size > 1:
        return f"xla (a mesh of {mesh.size} devices: the sweeps are one device's program)"
    if groups != 1:
        return f"xla ({groups} groups of B and C: the kernels share one)"
    if p > 128 or 128 % p:
        return f"xla (heads of {p} do not divide the 128 lanes)"
    if n % 128:
        return f"xla (a state of {n} is no multiple of 128)"
    return "kernels" if chunk == CHUNK else f"xla (chunk {chunk} is not {CHUNK})"


def ssd_scan(x, dt, a, b, c, d, *, chunk: int = CHUNK, mesh=None, impl=None):
    """The state-space scan over whole rows, chunked (module docstring; the arguments and dtypes: ``_scan_xla``). Which
    program runs is read from the input, the backend and the mesh (``_program``); ``CALLS`` says which a call took.
    Either form names what a rematerialized block may keep of it (``KEPT_ACROSS_REMAT``). ``impl`` is the tests' and
    the tools' handle: ``"xla"``, ``"kernels"``, ``"kernels_interpret"``."""
    rows, s, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    program = _program(p=p, n=n, groups=groups, chunk=chunk, mesh=mesh) if impl is None else impl.split("_")[0]
    if program == "kernels" and heads % (128 // p):
        program = f"xla ({heads} heads are no whole blocks of {128 // p})"
    _counted(CALLS, (rows, s, heads, p, n, groups), f"chunked {chunk}: {program}")
    if program == "kernels":
        return _scan_kernels(x, dt, a, b, c, d, interpret=impl == "kernels_interpret")
    return checkpoint_name(_scan_xla(x, dt, a, b, c, d, chunk=chunk), KEPT_ACROSS_REMAT[0])


# -- the two passes ---------------------------------------------------------------


def mixer_in(x, bc, dt, weight, bias, dt_bias):
    """From the projection's columns (``x [b, s, inner]``, ``bc [b, s, 2 groups N]``, ``dt [b, s, heads]``) to what the
    scan takes: x and ``[B | C]`` through the causal depthwise convolution ``weight [taps, x | B | C channels]`` with
    its ``bias`` and silu (the convolution is a channel's own business, so the two arrays are convolved apart and no
    ``[b, s, x | B | C]`` activation is ever cut), and ``softplus(dt + dt_bias)`` float32. The convolution adds up in
    float32 and rounds once to the activation's dtype (``causal_conv``); bias and silu in float32, rounded once."""
    inner = x.shape[2]
    _counted(PASSES, ("in", x.shape[0], x.shape[1], inner + bc.shape[2]), "xla")

    def conv_silu(z, lo, hi):
        return jax.nn.silu(causal_conv(z, weight[:, lo:hi]).astype(_F32) + bias[lo:hi].astype(_F32)).astype(z.dtype)

    return (conv_silu(x, 0, inner), conv_silu(bc, inner, inner + bc.shape[2]),
            jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32)))


def gated_norm(y, z, weight, eps, *, groups: int = 1):
    """``N(y * silu(z); weight)``: the gate FIRST, then one rms norm over each group's ``inner / groups`` channels;
    ``y`` (the scan's output) and ``z`` flat ``[b, s, inner]``, ``weight [inner]``. Float32 inside, ``y``'s dtype out."""
    _counted(PASSES, ("out", *y.shape), "xla")
    rows, s, inner = y.shape
    gated = (y.astype(_F32) * jax.nn.silu(z.astype(_F32))).reshape(rows, s, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
    return (normed.reshape(rows, s, inner) * weight.astype(_F32)).astype(y.dtype)
