"""EVA attention (EvaByte): exact softmax inside a token's own aligned window,
every earlier window seen through one learned summary a chunk.

For head ``h``, scale ``s``, window ``W`` and chunk ``C`` (``W / C`` chunks a
window), on q, k, v that are already rotated:

  pooling    for chunk ``c`` of ``C`` consecutive tokens ``j``:
             ``a_cj = softmax_j(s * (k_cj . phi_h))``,
             ``K_c = sum_j a_cj k_cj + mu_h``, ``V_c = sum_j a_cj v_cj``
             (``phi``, ``mu``: ``[heads, d]`` leaves, ``adaptive_phi`` and
             ``adaptive_mu_k``);
  attention  token ``n`` of window ``w = n // W`` sees ``L_n = {m : m // W = w,
             m <= n}`` (its own window, causal) and ``R_n = {c : c < (W / C)
             w}`` (every chunk of every EARLIER window; never a chunk of its
             own, so nothing a query reads holds a later token), under ONE
             softmax: ``o_n = (sum_L e^{s q.k_m} v_m + sum_R e^{s q.K_c} V_c) /
             (sum_L e^{s q.k_m} + sum_R e^{s q.K_c})``, maximum and sums in
             float32. A row no longer than a window is plain causal attention.

Two forms, chosen from the call's shapes and the backend (``_program``), said
by ``CALLS`` / ``calls_summary()``:

**kernels** (a TPU, heads of whole lanes, a window that is one block of the
resident flash kernels, whole tiles of summaries). The two key sources are two
calls merged by the running statistics, not one new fused kernel: the LOCAL
source is ``ops/flash_attention``'s resident causal kernels as they stand, on
the row cut into windows (``[b, h, T, d]`` read as ``[b, h * T / W, W, d]``: a
free reshape, each window a row of one block, so the grid visits a window's own
diagonal tile and nothing else); the REMOTE source is three small mask-free
kernels here. The remote forward CONTINUES the local one's online softmax from
the state the local kernel ended in (``m = lse_local``, ``acc = o_local``; its
``l = 1`` joins at the end as ``exp(lse_local - m)``) over the ``w`` earlier
windows' summaries, four windows (``4 W / C`` keys) behind each new maximum
and what is left a window a trip, ``m`` and ``l`` carried ``[rows, 128]``
lane-dense; a head's summaries, ``T / C`` rows, are resident in VMEM, ``w`` is
the query block's window index, a dynamic trip count, no mask anywhere; the
merged ``o`` and ``lse`` are written over the local ones (aliased). So nothing
is merged in XLA and no second ``lse`` (512 MiB a layer at 32 heads of 32,768
in its padded layout) is ever held. The
backward is the flash backward of each source under the MERGED ``lse`` and
``delta = rowsum(do * o)``: the resident ``dq`` / ``dk, dv`` kernels on the
windows, then a remote ``dq`` kernel that adds to the local ``dq`` and a remote
``dK, dV`` kernel (summaries resident, query blocks on the grid, scores held
transposed as ``_dkv_kernel`` holds them). Why two calls and not one kernel:
the local half is then code that five cells already measure and no new masked
kernel exists; the cost is one more read of ``o`` and ``lse`` a layer.

**xla** (a CPU, heads of 16, any other window): one window at a time under
``lax.map``, a masked softmax over ``[tokens of the window | all summaries]``;
no ``[T, T]`` buffer either. This is the reference the kernels are held to.

The pooling is XLA in both forms (``T / C`` rows out, two reductions over a
chunk) and differentiable by ``jax.grad``: the summaries' ``dK``, ``dV`` come
back from the aggregate's backward and go through the pooling's own transpose
to ``dk``, ``dv``, ``dphi``, ``dmu``.
"""

from __future__ import annotations

import functools
from collections import Counter

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (every cell's step loads the flash kernels' module; this one adds a page of text to the package's import)
from llm_fine_tune_distributed_tpu.ops import flash_attention as flash
from llm_fine_tune_distributed_tpu.ops.flash_attention import _as_row, _block_specs, _operand, _vmem_budget
from llm_fine_tune_distributed_tpu.ops.tiling import tiled_bytes

_NEG_INF = -1.0e30

# {(q's shape [b, heads, T, d], window, chunk, form): calls} of every call traced in this process; form is "kernels"
# or "xla (<why>)"
CALLS: Counter = Counter()

# {(kernel name, (T, window, chunk)): (tiles its grid and loops visit in one head's row, tiles L and R need there)}
# of every kernel call built in this process, as ``ops/flash_attention.GRID_TILES`` keeps the streamed kernels': a
# local tile is a window's own [W, W] diagonal block, a remote tile one query block against one window's summaries.
# DISTINCT tiles, each counted once however a kernel's trips group them (the remote forward takes four windows'
# summaries a trip): 100% says "none outside the masks"; what a tile costs is the benchmark's ``eva_agg_fwd_roofline_pct``.
GRID_TILES: dict = {}


def _windows_seen(w):
    """Of how many windows a query of window ``w`` reads the summaries: the earlier ones, ``[0, w)``. THE rule of
    which summaries are visible; both forms count by it and nothing else decides."""
    return w


def pairs_a_row(seq: int, window: int, chunk: int) -> int:
    """(query, key) pairs one head's row of ``seq`` tokens holds under the two masks: each window's causal triangle,
    and every query against one summary a chunk of every earlier window (whole windows; a row no longer than one is
    the plain triangle)."""
    if seq <= window:
        return seq * (seq + 1) // 2
    windows = seq // window
    return windows * window * (window + 1) // 2 + window * (window // chunk) * windows * (windows - 1) // 2


def calls_summary() -> str:
    """One line for entry points: which form each traced call took and why."""
    if not CALLS:
        return "eva attention traced as: no call"
    said = "; ".join(f"{list(shape)} window {w} chunk {c}: {form} x {n}" for (shape, w, c, form), n in sorted(CALLS.items()))
    return f"eva attention traced as: {said}"


def pool(k, v, phi, mu, *, chunk: int, scale: float):
    """The summaries of head-major ``k``, ``v`` ``[b, heads, T, d]``: ``K``, ``V`` ``[b, heads, T / chunk, d]`` in
    their dtypes, the softmax and the sums in float32."""
    b, h, s, d = k.shape
    kc = k.reshape(b, h, s // chunk, chunk, d).astype(jnp.float32)
    vc = v.reshape(b, h, s // chunk, chunk, v.shape[-1]).astype(jnp.float32)
    a = jax.nn.softmax(jnp.einsum("bhcjd,hd->bhcj", kc, phi.astype(jnp.float32)) * scale, axis=-1)
    ks = jnp.einsum("bhcj,bhcjd->bhcd", a, kc) + mu.astype(jnp.float32)[None, :, None, :]
    vs = jnp.einsum("bhcj,bhcjd->bhcd", a, vc)
    return ks.astype(k.dtype), vs.astype(v.dtype)


# ---------------------------------------------------------------------------
# the XLA form
# ---------------------------------------------------------------------------


def _aggregate_xla(q, k, v, ks, vs, *, window: int, chunk: int, scale: float):
    """Head-major q, k, v ``[b, h, T, d]`` and summaries ``[b, h, T / chunk, d]`` -> o ``[b, h, T, d]``, a window at
    a time. A last window that the row does not fill is padded: its pad keys lie behind every real query."""
    b, h, s, d = q.shape
    pad = (-s) % window
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) for x in (q, k, v))
    nw, per = (s + pad) // window, window // chunk
    by_window = lambda x: jnp.moveaxis(x.reshape(b, h, nw, window, x.shape[-1]), 2, 0)  # noqa: E731
    ks32, vs32 = ks.astype(jnp.float32), vs.astype(jnp.float32)
    causal = jnp.tril(jnp.ones((window, window), bool))
    chunks = jnp.arange(ks.shape[2])

    def one(args):
        w, qw, kw, vw = args
        qw = qw.astype(jnp.float32)
        local = jnp.where(causal, jnp.einsum("bhqd,bhkd->bhqk", qw, kw.astype(jnp.float32)) * scale, _NEG_INF)
        seen = chunks < _windows_seen(w) * per
        remote = jnp.where(seen, jnp.einsum("bhqd,bhcd->bhqc", qw, ks32) * scale, _NEG_INF)
        m = jnp.maximum(local.max(-1, keepdims=True), remote.max(-1, keepdims=True))
        p_l, p_r = jnp.exp(local - m), jnp.where(seen, jnp.exp(remote - m), 0.0)
        norm = p_l.sum(-1, keepdims=True) + p_r.sum(-1, keepdims=True)
        out = jnp.einsum("bhqk,bhkd->bhqd", p_l, vw.astype(jnp.float32)) + jnp.einsum("bhqc,bhcd->bhqd", p_r, vs32)
        return (out / norm).astype(q.dtype)

    o = jax.lax.map(one, (jnp.arange(nw), by_window(q), by_window(k), by_window(v)))
    return jnp.moveaxis(o, 0, 2).reshape(b, h, nw * window, v.shape[-1])[:, :, :s]


# ---------------------------------------------------------------------------
# the remote source's kernels
# ---------------------------------------------------------------------------


def _rows(window: int) -> int:
    """Query rows a program of the remote kernels takes: a window, or half of one of 2048."""
    return 1024 if window % 1024 == 0 else window


# Earlier windows whose summaries one trip of the remote forward brings behind ONE new maximum (what is left, under
# that many, goes a window a trip). On a v5e at the EvaByte cell's shape: 4 reads 7.55 ms a call, 2 reads 8.28, 8 reads
# 8.54 (half of a row's query blocks see fewer than 8 windows and run the single trips alone), 1 throughout 10.55.
_WINDOWS_A_TRIP = 4
_LANES = 128


def _remote_fwd_kernel(q_ref, ks_ref, vs_ref, o_in_ref, lse_in_ref, o_ref, lse_ref, *, scale, per, blocks_a_window):
    """Grid (batch, head, query block). Continues the local source's online softmax over the ``w`` tiles of the
    earlier windows' summaries: no mask, ``w`` the block's window index. The row statistics are carried lane-dense
    (``m`` ``[rows, 128]`` with a row's maximum in every lane, ``l`` ``[rows, 128]`` sums by lane: a ``[rows, 1]``
    column fills as many registers, one lane in 128 used), and a trip pays ONE lane reduction, for its new maximum."""
    w = _windows_seen(pl.program_id(2) // blocks_a_window)
    q = _operand(q_ref[0, 0])
    rows, d = q.shape

    def trips(n, first, count, carry):
        """``count`` trips of ``n`` windows' summaries each, from window ``first`` on."""
        def tile(c, carry):
            m, l, acc = carry
            keys = pl.ds(pl.multiple_of((first + c * n) * per, per), n * per)
            v_blk = _operand(vs_ref[0, 0, keys, :])
            s = jax.lax.dot_general(
                q, _operand(ks_ref[0, 0, keys, :]), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale
            by_lanes = [s[:, at:at + _LANES] for at in range(0, n * per, _LANES)]
            highest = functools.reduce(jnp.maximum, by_lanes)
            m_new = jnp.maximum(m, jnp.broadcast_to(jnp.max(highest, axis=1, keepdims=True), m.shape))
            alpha = jnp.exp(m - m_new)
            p = [jnp.exp(x - m_new) for x in by_lanes]
            acc = acc * jnp.concatenate([alpha] * (d // _LANES), axis=1) + jax.lax.dot_general(
                jnp.concatenate(p, axis=1).astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, functools.reduce(jnp.add, p, l * alpha), acc

        return jax.lax.fori_loop(0, count, tile, carry)

    # the local kernel's final state, normalised: m = lse, acc = o; its l = 1 is rescaled with the rest, so it joins
    # at the end as exp(lse - m) and ``l`` starts empty
    lse_local = lse_in_ref[0, 0]
    carry = (jnp.broadcast_to(lse_local, (rows, _LANES)), jnp.zeros((rows, _LANES), jnp.float32), o_in_ref[0, 0].astype(jnp.float32))
    wide = 0
    if ks_ref.shape[2] >= _WINDOWS_A_TRIP * per:  # (a row of fewer windows has no block that sees that many)
        wide = w // _WINDOWS_A_TRIP
        carry = trips(_WINDOWS_A_TRIP, 0, wide, carry)
    m, l, acc = trips(1, wide * _WINDOWS_A_TRIP, w - wide * _WINDOWS_A_TRIP, carry)
    m = m[:, :1]
    l = jnp.exp(lse_local - m) + jnp.sum(l, axis=1, keepdims=True)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _remote_dq_kernel(q_ref, ks_ref, vs_ref, do_ref, lse_ref, delta_ref, dq_in_ref, dq_ref, *, scale, per, blocks_a_window):
    """Grid as the forward's; adds the summaries' share to the local ``dq``."""
    w = _windows_seen(pl.program_id(2) // blocks_a_window)
    q, do = _operand(q_ref[0, 0]), _operand(do_ref[0, 0])
    lse, delta = lse_ref[0, 0], delta_ref[0, 0]

    def tile(c, acc):
        keys = pl.ds(pl.multiple_of(c * per, per), per)
        k_blk, v_blk = _operand(ks_ref[0, 0, keys, :]), _operand(vs_ref[0, 0, keys, :])
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = jnp.exp(s - lse) * (dp - delta)
        return acc + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, w, tile, jnp.zeros(q.shape, jnp.float32))
    dq_ref[0, 0] = (dq_in_ref[0, 0].astype(jnp.float32) + acc * scale).astype(dq_ref.dtype)


def _remote_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, ks_ref, vs_ref, dks_ref, dvs_ref, dk_acc, dv_acc, *,
                       scale, per, blocks_a_window):
    """Grid (batch, head, query block), the last axis in order: a head's ``dK``, ``dV`` accumulate in scratch over
    its query blocks and leave on the last. Scores transposed, ``[summaries, queries]``, as ``_dkv_kernel`` holds
    them: the four products run as the MXU has them and only lse and delta go through the transpose unit."""
    i = pl.program_id(2)
    w = _windows_seen(i // blocks_a_window)

    @pl.when(i == 0)
    def _start():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    q, do = _operand(q_ref[0, 0]), _operand(do_ref[0, 0])
    lse, delta = _as_row(lse_ref[0, 0]), _as_row(delta_ref[0, 0])

    def tile(c, carry):
        keys = pl.ds(pl.multiple_of(c * per, per), per)
        s_t = jax.lax.dot_general(
            _operand(ks_ref[0, 0, keys, :]), q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        p_t = jnp.exp(s_t - lse)
        dv_acc[keys] = dv_acc[keys] + jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(
            _operand(vs_ref[0, 0, keys, :]), do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta)
        dk_acc[keys] = dk_acc[keys] + jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, w, tile, 0)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finish():
        dks_ref[0, 0] = (dk_acc[...] * scale).astype(dks_ref.dtype)
        dvs_ref[0, 0] = dv_acc[...].astype(dvs_ref.dtype)


def _remote_call(name, body, q, ks, ins, outs, out_shape, *, window, chunk, scale, interpret, scratch=(), aliases=None):
    """One remote kernel: ``ins`` / ``outs`` list "rows" (a ``[rows, width]`` block of the query block's own),
    "column" (its ``[rows, 1]`` float32 statistics) or "summaries" (the head's whole ``[T / chunk, d]``), with the
    dtype; BlockSpecs and the VMEM budget are both built from the one list, as ``ops/flash_attention`` builds its."""
    b, h, s, d = q.shape
    rows, n = _rows(window), ks.shape[2]
    own = lambda b_, h_, i: (b_, h_, i, 0)  # noqa: E731
    whole = lambda b_, h_, i: (b_, h_, 0, 0)  # noqa: E731
    kinds = {"rows": ((1, 1, rows, d), own), "column": ((1, 1, rows, 1), own), "summaries": ((1, 1, n, d), whole)}
    operands = [(kinds[kind][0], dtype, kinds[kind][1]) for kind, dtype in list(ins) + list(outs)]
    nw = s // window
    GRID_TILES[name, (s, window, chunk)] = ((window // rows) * nw * (nw - 1) // 2,) * 2
    specs = _block_specs(operands)
    return pl.pallas_call(
        functools.partial(body, scale=scale, per=window // chunk, blocks_a_window=window // rows),
        grid=(b, h, s // rows),
        in_specs=specs[: len(ins)],
        out_specs=tuple(specs[len(ins):]) if len(outs) > 1 else specs[-1],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(shape, t) for shape, t in scratch],
        input_output_aliases=aliases or {},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_budget(operands, d) + sum(tiled_bytes(shape, t) for shape, t in scratch),
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )


# what the forward rule names its two outputs: ``ops/flash_attention.KEPT_ACROSS_REMAT``'s names, so that one policy
# of a rematerialized block (``models/transformer._remat_policy``) keeps either operator's
KEPT_ACROSS_REMAT = ("flash_o", "flash_lse")


@functools.lru_cache(maxsize=None)
def _make_aggregate(window: int, chunk: int, scale: float, interpret: bool):
    """The aggregate over both sources behind one ``custom_vjp``, forward and backward jitted on their own as
    ``ops/flash_attention._make_flash_fn`` jits its (a model calls this once a layer)."""
    local = dict(scale=scale, block=window, groups=1, interpret=interpret)
    remote = dict(window=window, chunk=chunk, scale=scale, interpret=interpret)
    f32 = jnp.float32

    def as_windows(x):  # [b, h, T, .] -> [b, h * T / W, W, .]: each window a row of one block
        b, h, s, d = x.shape
        return x.reshape(b, h * (s // window), window, d)

    def room(q):
        # one query head a kv head lists few operands, and at a block of 2048 the body's strips against 2048 keys
        # need more than the 16 MiB the resident budget leaves them (the compiler: 28 MiB for dk/dv): three bodies' room
        need = flash._resident_need(q.dtype, window, q.shape[3], q.shape[3], window, 1)
        return dict(vmem_limit_bytes=need + 2 * flash._VMEM_BODY_BYTES)

    @jax.jit
    def forward(q, k, v, ks, vs):
        b, h, s, d = q.shape
        GRID_TILES["flash_attention_fwd", (s, window, chunk)] = (s // window,) * 2
        ones = jnp.ones((b, window), jnp.int32)
        o, lse = flash._fwd(as_windows(q), as_windows(k), as_windows(v), ones, **local, **room(q))
        o, lse = o.reshape(q.shape), lse.reshape(b, h, s, 1)
        return _remote_call(
            "eva_remote_fwd", _remote_fwd_kernel, q, ks,
            [("rows", q.dtype), ("summaries", ks.dtype), ("summaries", vs.dtype), ("rows", o.dtype), ("column", f32)],
            [("rows", o.dtype), ("column", f32)],
            (jax.ShapeDtypeStruct(o.shape, o.dtype), jax.ShapeDtypeStruct(lse.shape, f32)),
            aliases={3: 0, 4: 1}, **remote,
        )(q, ks, vs, o, lse)

    @jax.jit
    def backward(q, k, v, ks, vs, o, lse, do):
        b, h, s, d = q.shape
        for name in ("flash_attention_dq", "flash_attention_dkv"):
            GRID_TILES[name, (s, window, chunk)] = (s // window,) * 2
        ones = jnp.ones((b, window), jnp.int32)
        dq, dk, dv = flash._bwd(*(as_windows(x) for x in (q, k, v)), ones, as_windows(o), as_windows(lse), as_windows(do), **local, **room(q))
        delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)[..., None]  # (the local call's own, once in the program)
        dq = _remote_call(
            "eva_remote_dq", _remote_dq_kernel, q, ks,
            [("rows", q.dtype), ("summaries", ks.dtype), ("summaries", vs.dtype), ("rows", do.dtype), ("column", f32),
             ("column", f32), ("rows", dq.dtype)],
            [("rows", q.dtype)], jax.ShapeDtypeStruct(q.shape, q.dtype), aliases={6: 0}, **remote,
        )(q, ks, vs, do, lse, delta, dq.reshape(q.shape))
        n = ks.shape[2]
        dks, dvs = _remote_call(
            "eva_remote_dkv", _remote_dkv_kernel, q, ks,
            [("rows", q.dtype), ("rows", do.dtype), ("column", f32), ("column", f32), ("summaries", ks.dtype),
             ("summaries", vs.dtype)],
            [("summaries", ks.dtype), ("summaries", vs.dtype)],
            (jax.ShapeDtypeStruct(ks.shape, ks.dtype), jax.ShapeDtypeStruct(vs.shape, vs.dtype)),
            scratch=[((n, d), f32), ((n, d), f32)], **remote,
        )(q, do, lse, delta, ks, vs)
        return dq, dk.reshape(k.shape), dv.reshape(v.shape), dks, dvs

    @jax.custom_vjp
    def fn(q, k, v, ks, vs):
        return forward(q, k, v, ks, vs)[0]

    def fn_fwd(q, k, v, ks, vs):
        o, lse = forward(q, k, v, ks, vs)
        o, lse = checkpoint_name(o, KEPT_ACROSS_REMAT[0]), checkpoint_name(lse, KEPT_ACROSS_REMAT[1])
        return o, (q, k, v, ks, vs, o, lse)

    fn.defvjp(fn_fwd, lambda res, do: backward(*res, do))
    return fn


# ---------------------------------------------------------------------------
# the entry point
# ---------------------------------------------------------------------------


def _program(shape, dtype, *, window: int, chunk: int, mesh=None) -> str:
    """``"kernels"``, or ``"xla (<why>)"``: which form a call takes, from its shapes, the backend and the mesh."""
    b, h, s, d = shape
    if jax.default_backend() != "tpu":
        return f"xla (backend is {jax.default_backend()})"
    if mesh is not None and mesh.size > 1:
        return f"xla (a mesh of {mesh.size} devices: the kernels are one device's program over the whole row)"
    if d % 128:
        return f"xla (head of {d} is no multiple of 128 lanes)"
    if s <= window:
        return "xla (a row no longer than a window is plain causal attention)"
    if s % window:
        return f"xla (rows of {s} are no whole windows of {window})"
    if flash._pick_block(window) != window or flash._streamed(dtype, window, d, d, window, 1, None):
        return f"xla (a window of {window} is no single block of the resident flash kernels)"
    if (window // chunk) % 128:
        return f"xla (a window's {window // chunk} summaries are no whole tiles of 128)"
    return "kernels"


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int, scale: float, head_major: bool = False, mesh=None):
    """q, k, v ``[b, T, heads, d]`` (``head_major``: ``[b, heads, T, d]``), rotated; ``phi``, ``mu`` ``[heads, d]``
    -> o ``[b, T, heads, d]`` in q's dtype. ``T`` a multiple of ``chunk``. The pooling stands under the trace scope
    ``eva_pool``, everything else under ``eva_agg``."""
    from llm_fine_tune_distributed_tpu.observe.xla import scope

    if not head_major:
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    if q.shape[2] % chunk:
        raise ValueError(f"EVA attention pools chunks of {chunk} tokens: a row of {q.shape[2]} is no whole number of them")
    form = _program(q.shape, q.dtype, window=window, chunk=chunk, mesh=mesh)
    CALLS[tuple(q.shape), window, chunk, form] += 1
    with scope("eva_pool"):
        ks, vs = pool(k, v, phi, mu, chunk=chunk, scale=scale)
    with scope("eva_agg"):
        if form == "kernels":
            o = _make_aggregate(window, chunk, float(scale), False)(q, k, v, ks, vs)
        else:
            o = _aggregate_xla(q, k, v, ks, vs, window=window, chunk=chunk, scale=scale)
        return o.transpose(0, 2, 1, 3)
