"""Mixture-of-experts MLP with top-k routing and expert parallelism.

The reference trains dense models only (SURVEY.md §2.4: "EP (expert
parallel): NO — dense models only"); this module extends the framework to
the Mixtral family with a TPU-first design:

- **Einsum dispatch, not gather/scatter loops.** Routing is expressed as
  GShard/Switch-style one-hot dispatch/combine tensors contracted on the MXU:
  ``[b, s, E, C] x [b, s, h] -> [b, E, C, h]``. No dynamic shapes, no
  data-dependent control flow — one XLA program regardless of routing.
- **Capacity-bounded queues.** Each (batch row, expert) pair processes at
  most ``C = ceil(k * s / E * capacity_factor)`` tokens; overflow tokens
  fall through on the residual path (GShard drop semantics). C is static,
  so expert blocks are dense [E, C, h] tiles the MXU likes.
- **Expert parallelism over the mesh "expert" axis.** Expert weights
  [E, h, f] shard on E (parallel/sharding.py); a sharding constraint on the
  dispatched [b, E, C, h] blocks moves tokens from batch-sharded to
  expert-sharded layout — XLA inserts the all_to_all over ICI, the
  collective that defines EP. With expert=1 everything stays local.
- **Load-balancing auxiliary loss** (Switch/Mixtral):
  ``E * sum_e fraction_dispatched_e * mean_router_prob_e``, returned
  unscaled; the train step weights it by ``config.router_aux_coef``.

A second routed-experts layer lives below (``grouped_moe_mlp``): many narrow
experts behind a sigmoid router, shared experts beside them, no capacity and
no dropped token, work that grows with the (token, expert) pairs held here.
Folding the two dispatches into one is a later ``simplicity`` issue
(ROADMAP.md, Design).

Weight layout mirrors HF Mixtral names (models/hf_io.py stacks the
per-expert torch Linears): ``block_sparse_moe/gate/kernel [h, E]``,
``block_sparse_moe/experts/{w1,w3} [E, h, f]`` (gate/up), ``w2 [E, f, h]``
(down). Router softmax and the combine run in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

from llm_fine_tune_distributed_tpu.config import ModelConfig


def expert_capacity(seq_len: int, config: ModelConfig) -> int:
    """Static per-(batch-row, expert) token capacity."""
    k, e = config.num_experts_per_tok, config.num_experts
    return max(1, int(math.ceil(k * seq_len / e * config.capacity_factor)))


def moe_mlp(lp, x, config: ModelConfig, compute_dtype, mesh=None, token_mask=None,
            dropless=False):
    """Sparse MoE MLP. ``x [b, s, h] -> (y [b, s, h], aux scalar f32)``.

    ``lp`` is the ``block_sparse_moe`` params subtree. ``aux`` is the raw
    load-balancing loss (scale by ``config.router_aux_coef`` in the train
    objective); it is differentiable through the router softmax.
    ``token_mask [b, s]`` (1 = real token) excludes padding from routing:
    pad tokens get no dispatch (zero MoE output), consume no expert
    capacity, and do not pollute the load-balancing statistics.
    ``dropless=True`` sizes the capacity at the worst case (every token to
    one expert) so NO token is ever dropped — the inference semantics (HF
    Mixtral decode is dropless); capacity drops are a training-efficiency
    trade-off that would otherwise make decode output depend on how many
    tokens share the forward pass.
    """
    b, s, h = x.shape
    e, k = config.num_experts, config.num_experts_per_tok

    # Long sequences: route in independent chunks (GShard grouping) so the
    # one-hot dispatch tensors stay linear in s — [b*n, chunk, E, C_chunk]
    # instead of [b, s, E, C] whose C grows with s. The aux statistics are
    # token-means, so grouping leaves them unchanged.
    if s > config.moe_dispatch_chunk:
        # balanced grouping: n = ceil(s/budget) groups of ceil(s/n) tokens,
        # padded+masked to a chunk multiple. Handles every length (incl.
        # primes) with < n wasted positions — s=1030 @ budget 1024 becomes
        # two 515-token groups with zero padding, not two padded 1024s.
        n_groups = -(-s // config.moe_dispatch_chunk)
        chunk = -(-s // n_groups)
        pad = (-s) % chunk
        xg = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
        mg = token_mask
        if pad:
            if mg is None:
                mg = jnp.ones((b, s), jnp.int32)
            mg = jnp.pad(mg.astype(jnp.int32), ((0, 0), (0, pad)))
        n = (s + pad) // chunk
        xg = xg.reshape(b * n, chunk, h)
        mg = None if mg is None else mg.reshape(b * n, chunk)
        y, aux = moe_mlp(lp, xg, config, compute_dtype, mesh=mesh, token_mask=mg,
                         dropless=dropless)
        return y.reshape(b, s + pad, h)[:, :s], aux

    cap = s if dropless else expert_capacity(s, config)

    gate_logits = x @ lp["gate"]["kernel"].astype(compute_dtype)  # [b, s, E]
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    top_p, top_i = jax.lax.top_k(probs, k)  # [b, s, k]
    # Mixtral renormalizes the selected probabilities to sum to 1.
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    sel = jax.nn.one_hot(top_i, e, dtype=jnp.float32)          # [b, s, k, E]
    mask_se = sel.sum(2)                                       # [b, s, E] 0/1
    weight_se = (sel * top_p[..., None]).sum(2)                # [b, s, E]

    if token_mask is not None:
        real = token_mask.astype(jnp.float32)                  # [b, s]
        # masked BEFORE the capacity cumsum so pads hold no queue slots
        mask_se = mask_se * real[..., None]
        weight_se = weight_se * real[..., None]
        n_tokens = jnp.maximum(real.sum(), 1.0)
    else:
        real = None
        n_tokens = jnp.float32(b * s)

    # Queue position of each token within its (batch row, expert) capacity
    # buffer — first-come-first-served along the sequence.
    pos_se = jnp.cumsum(mask_se, axis=1).astype(jnp.int32) - 1  # [b, s, E]
    keep = mask_se * (pos_se < cap)                             # drop overflow
    dispatch = jax.nn.one_hot(
        jnp.where(keep > 0, pos_se, -1), cap, dtype=jnp.float32
    )                                                           # [b, s, E, C]
    combine = dispatch * weight_se[..., None]                  # [b, s, E, C]

    def to_experts(t):
        """Constrain dispatched blocks to the expert axis (the EP boundary)."""
        if mesh is not None and mesh.shape.get("expert", 1) > 1:
            spec = P(("data", "fsdp"), "expert", None, None)
            return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))
        return t

    xin = jnp.einsum(
        "bsec,bsh->bech", dispatch.astype(compute_dtype), x
    )                                                          # [b, E, C, h]
    xin = to_experts(xin)

    def expert_weight(name):
        """[E, in, out], dequantizing the NF4 (QLoRA) or int8 (inference,
        ops/int8.py) form when present. Under remat only one layer's
        dequantized experts are live at a time, same as the dense paths."""
        ex = lp["experts"]
        if f"{name}_int8" in ex:
            from llm_fine_tune_distributed_tpu.ops.int8 import dequantize_int8_stacked

            return dequantize_int8_stacked(
                {"int8": ex[f"{name}_int8"], "int8_scale": ex[f"{name}_int8_scale"]},
                dtype=compute_dtype,
            )
        if f"{name}_nf4" in ex:
            from llm_fine_tune_distributed_tpu.ops.nf4 import (
                QUANT_SUFFIXES,
                dequantize_nf4_stacked,
            )

            q = {
                s: ex[f"{name}_{s}"] for s in QUANT_SUFFIXES if f"{name}_{s}" in ex
            }
            return dequantize_nf4_stacked(q, dtype=compute_dtype)
        return ex[name].astype(compute_dtype)

    w1 = expert_weight("w1")                                   # [E, h, f]
    w3 = expert_weight("w3")                                   # [E, h, f]
    w2 = expert_weight("w2")                                   # [E, f, h]
    # named like the dense path's product so remat_policy="mlp"
    # (save_only_these_names("mlp_act")) works for MoE models too
    act = checkpoint_name(
        jax.nn.silu(jnp.einsum("bech,ehf->becf", xin, w1))
        * jnp.einsum("bech,ehf->becf", xin, w3),
        "mlp_act",
    )
    out = to_experts(jnp.einsum("becf,efh->bech", act, w2))    # [b, E, C, h]

    # combine in float32: the renormalized routing weights stay full
    # precision through the weighted sum (the per-token FLOPs here are tiny)
    y = jnp.einsum("bsec,bech->bsh", combine, out.astype(jnp.float32))

    # Load-balancing loss over all REAL tokens (dropped ones included):
    # uniform routing minimizes it at 1.0.
    frac = mask_se.sum(axis=(0, 1)) / (n_tokens * k)           # [E]
    if real is not None:
        mean_prob = (probs * real[..., None]).sum(axis=(0, 1)) / n_tokens
    else:
        mean_prob = probs.mean(axis=(0, 1))                    # [E]
    aux = e * jnp.sum(frac * mean_prob)

    return y.astype(x.dtype), aux


def init_moe_params(rng, config: ModelConfig, dtype):
    """Random init of one layer's ``block_sparse_moe`` subtree."""
    h, f, e = config.hidden_size, config.intermediate_size, config.num_experts
    kg, k1, k2, k3 = jax.random.split(rng, 4)

    def dense(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)

    return {
        "gate": {"kernel": dense(kg, (h, e))},
        "experts": {
            "w1": dense(k1, (e, h, f)),
            "w3": dense(k3, (e, h, f)),
            "w2": dense(k2, (e, f, h)),
        },
    }


# ---------------------------------------------------------------------------
# Routed experts with shared experts (HF DeepseekV3MoE): grouped dispatch
# ---------------------------------------------------------------------------

# What the router computes in: the scores' matrix product, the sigmoid, the
# selection and the combine weights. HF multiplies hidden.float() by
# weight.float(); near-ties among the top k flip on less. (The benchmark's
# control sets float8_e4m3fn here and has to come out not correct.)
ROUTER_DTYPE = jnp.float32

# What ``grouped_moe_mlp`` names (``checkpoint_name``) for a rematerialized
# block to keep (models/transformer._remat_policy, as the flash kernel's
# outputs): the backward pass follows from them by slices and subtractions,
# with no second router product, selection, sort or row gather. The router's
# sigmoid scores ``[T, E]`` and selection ``[T, k]``; each sorted pair's token,
# each pair's sorted row and the sorted weights, flat ``[T * k]`` (a ``[T, 6]``
# pads to 128 lanes); the held experts' counts and their running sum; the
# FIRST chunk's gathered rows ``[T, h]``, three quarters of the bytes.
KEPT_ACROSS_REMAT = (
    "moe_scores", "moe_top_i", "moe_tokens", "moe_rank", "moe_load", "moe_ends", "moe_weights", "moe_rows",
)


@jax.custom_vjp
def _sigmoid(z):
    """``jax.nn.sigmoid`` whose backward pass reads the NAMED scores: autodiff's
    own rule reads the unnamed output, and the router's product runs again for it."""
    return jax.nn.sigmoid(z)


def _sigmoid_fwd(z):
    scores = checkpoint_name(jax.nn.sigmoid(z), "moe_scores")
    return scores, scores


_sigmoid.defvjp(_sigmoid_fwd, lambda scores, d: (d * scores * (1 - scores),))


def _softmax_of(z):
    # the row maximum and sum in float32 whatever the scores are kept in
    # (float8_e4m3fn has no infinity for the reductions to start from)
    return jax.nn.softmax(z.astype(jnp.float32), axis=-1).astype(z.dtype)


@jax.custom_vjp
def _softmax(z):
    """Softmax over the experts, for ``_sigmoid``'s reason: the backward pass
    reads the NAMED probabilities and nothing upstream of them."""
    return _softmax_of(z)


def _softmax_fwd(z):
    probs = checkpoint_name(_softmax_of(z), "moe_scores")
    return probs, probs


_softmax.defvjp(_softmax_fwd, lambda probs, d: (probs * (d - (d * probs).sum(-1, keepdims=True)),))

# ModelConfig.router_scoring -> the scores of the router's product
_SCORING = {"sigmoid": _sigmoid, "softmax": _softmax}


def route(gate, x, config: ModelConfig):
    """``x [T, h]`` -> (expert ids ``[T, k]`` int32, combine weights ``[T, k]``
    float32). Scores over all ``n_routed_experts`` (``router_scoring``:
    independent sigmoids, or one softmax); the top k of the scores, plus
    ``e_score_correction_bias`` where the gate has one (DeepSeek-V3's buffer:
    it selects, it does not weigh, and no gradient reaches it); weights are
    the selected scores over their sum, times ``routed_scaling_factor``."""
    scores = _SCORING[config.router_scoring](
        jnp.dot(
            x.astype(ROUTER_DTYPE), gate["kernel"].astype(ROUTER_DTYPE),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=ROUTER_DTYPE,
        )
    )
    select_by = scores
    if "e_score_correction_bias" in gate:
        select_by = scores + jax.lax.stop_gradient(gate["e_score_correction_bias"]).astype(ROUTER_DTYPE)
    top_i = checkpoint_name(jax.lax.top_k(select_by, config.num_experts_per_tok)[1], "moe_top_i")
    # the chosen scores by a one-hot product: exact; take_along_axis's gather
    # of k of E values a token cost 1 ms a call on a v5e (PR 26)
    top_s = (jax.nn.one_hot(top_i, scores.shape[-1], dtype=scores.dtype) * scores[:, None, :]).sum(-1)
    weights = top_s / (top_s.sum(-1, keepdims=True) + 1e-20) * config.routed_scaling_factor
    return top_i.astype(jnp.int32), weights.astype(jnp.float32)


# (rows, contraction, columns) tile of the TPU's grouped product. On a v5e at
# 8192 rows against 8 experts of 2048 x 1408 (PERF.md, PR 26): the kernel's
# default of 128 each is 7 times slower, and the next larger tiles ask for
# more scoped VMEM than a kernel gets unasked (megablox sets no limit).
GMM_TILING = (512, 1024, 1024)


def grouped_matmul(lhs, rhs, group_sizes, *, impl=None):
    """``lhs [m, k]`` whose rows lie grouped by expert, ``rhs [E, k, n]``,
    ``group_sizes [E]`` (sum <= m) -> ``[m, n]``: each group's rows times its
    expert's matrix. Rows past the groups' total are undefined.

    On a TPU it is megablox ``gmm`` (a Pallas kernel that visits only the row
    tiles the groups fill, so empty rows cost nothing); elsewhere
    ``jax.lax.ragged_dot``, which XLA runs anywhere. ``impl`` overrides the
    choice (``"gmm_interpret"``: the kernel under the Pallas interpreter)."""
    impl = impl or ("gmm" if jax.default_backend() == "tpu" else "ragged_dot")
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    tiling = tuple(min(tile, size) for tile, size in zip(GMM_TILING, (m, k, rhs.shape[2])))
    return gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype, tiling, interpret=impl == "gmm_interpret")


# Moving rows between token order and sorted-pair order. A chunk holds C sorted
# pairs; ``tokens [C]`` is each pair's token, ``rank [T, k]`` each (token,
# choice)'s row in the chunk and ``ends [E]`` where each held expert's rows end
# in it (a pair is in the chunk where 0 <= rank < ends[-1]). Taking the pairs'
# rows out of the tokens and summing the pairs' rows back into their tokens are
# each other's transposes; left to autodiff the transposes are row scatters,
# which on a TPU cost several times the grouped products (PERF.md, PR 26: 4.4 ms
# a scatter of 8192 rows against 1.7 ms for an expert's SwiGLU), so each is
# written out:
# - rows OUT of tokens (``take_rows`` forward, ``sum_rows`` backward): a GATHER
#   with a mask, one row a sorted pair (``_rows_of_tokens``), everywhere.
# - rows INTO tokens (``sum_rows`` forward, ``take_rows`` backward,
#   ``_sum_into_tokens``): on a TPU ONE KERNEL that fetches only the rows of the
#   pairs held here and writes each token's float32 sum once
#   (``_sum_held_rows``); elsewhere, and for a shape the kernel refuses, k
#   masked gathers of ``[T, h]``, one a choice, held here or not (``_sum_loop``,
#   which is also what the tests hold the kernel to, to the bit).


def _rows_of_tokens(xf, tokens, n_valid):
    valid = jnp.arange(tokens.shape[0]) < n_valid
    return jnp.where(valid[:, None], xf[tokens], 0)


def _sum_loop(rows, rank, n_valid):
    total = jnp.zeros((rank.shape[0], rows.shape[1]), jnp.float32)
    for j in range(rank.shape[1]):  # k gathers of [T, h]: never [T, k, h] at once
        r = rank[:, j]
        hit = (r >= 0) & (r < n_valid)
        total = total + jnp.where(hit[:, None], rows[jnp.clip(r, 0, rows.shape[0] - 1)], 0).astype(jnp.float32)
    return total


# The kernel. Mosaic copies no single row of an array tiled (8, 128) ("slice
# shape must be aligned to tiling", in HBM and in VMEM alike), and XLA's
# relayout to rows that lie alone is a pass over the chunk of its own. But the
# sort is stable, so the rows of ONE expert lie in the order of their tokens,
# and what a tile of consecutive tokens holds of an expert is ONE RUN of
# consecutive rows: a tile's rows are E runs, fetched whole in blocks of
# ``_block_rows`` rows, each block once, 1.2 to 1.5 rows read a row used.
# ``_sum_plan`` works the runs out in XLA from ``rank`` and ``ends`` alone (a
# few passes over ``[E, T * k]`` integers); the kernel copies a tile's blocks
# HBM -> VMEM (the next tile's under this tile's adds), lays each block out
# again so that a row fills whole registers (a strided store a 128 columns),
# adds a token's rows in ascending choice as the loop does (a choice held
# elsewhere is skipped: the total is never -0.0, so the loop's + 0.0 changes
# no bit), and lays 8 tokens' sums out as a block of the output.
SUM_TILE = 128  # tokens a grid step
_LANES, _SUBLANES = 128, 8

# {(rows' shape, rows' dtype name, rank's shape, E): "kernel" or "loop: <why>"}
# of every ``_sum_into_tokens`` traced in this process: which program the
# expert layer's sum took (as ``flash_attention.GRID_TILES`` says what a grid
# was built to visit). ``sum_programs_summary()`` is the line entry points print.
SUM_PROGRAMS: dict = {}


def _block_rows(dtype) -> int:
    """Rows one copy moves: a tile of the rows' dtype (8 float32, 16 bfloat16)."""
    return _SUBLANES * 4 // jnp.dtype(dtype).itemsize


def _row_stride(h) -> int:
    """Registers' rows (of 128 lanes) a row of ``h`` takes once it lies alone: whole registers of 8."""
    return -(-(h // _LANES) // _SUBLANES) * _SUBLANES


def _sum_room(tile, k, runs, block):
    """Rows a tile's fetched blocks can fill: a run of L rows touches at most
    L / block + 2 blocks (cut at both ends), and the runs' rows are at most
    the tile's pairs."""
    return (-(-tile * k // block) + 2 * runs) * block


def _sum_vmem_bytes(h, k, runs, dtype, tile=SUM_TILE):
    """What ``_sum_held_rows`` asks for: two tiles' fetched blocks as they land, one tile's rows and
    sums lying alone, the output's two blocks, and 2 MiB of room."""
    room = _sum_room(tile, k, runs, _block_rows(dtype))
    landed = 2 * room * h * jnp.dtype(dtype).itemsize
    alone = (room + tile) * _row_stride(h) * _LANES * 4
    return landed + alone + 2 * tile * h * 4 + 2 * 2**20


def sum_kernel_refused(rows, rank, ends):
    """Why the kernel does not take these shapes (None: it does); the choice
    is made from what the call can observe, never a rescue from a kernel that
    failed to compile."""
    (c, h), (_, k) = rows.shape, rank.shape
    if jnp.dtype(rows.dtype).itemsize not in (2, 4) or not jnp.issubdtype(rows.dtype, jnp.floating):
        return f"rows of {jnp.dtype(rows.dtype).name}"
    if h % _LANES:
        return f"hidden size {h} is not a multiple of {_LANES}"
    if c % _block_rows(rows.dtype):
        return f"a chunk of {c} rows is not whole blocks of {_block_rows(rows.dtype)}"
    from llm_fine_tune_distributed_tpu.ops.tiling import VMEM_CAP_BYTES  # one cap for every kernel of the chip

    need = _sum_vmem_bytes(h, k, ends.shape[0], rows.dtype)
    if need > VMEM_CAP_BYTES:
        return f"needs {need >> 20} MiB of VMEM, a kernel may ask for {VMEM_CAP_BYTES >> 20}"
    return None


def sum_programs_summary() -> str:
    """One line for entry points to print beside ``dispatch_summary()``."""
    said = "; ".join(
        f"rows {dtype}{list(rows)} into tokens {list(rank)[:1]} of {rank[1]} choices, {runs} experts held: {program}"
        for (rows, dtype, rank, runs), program in sorted(SUM_PROGRAMS.items())
    )
    return f"expert rows summed into tokens by: {said or 'nothing traced'}"


def _sum_plan(rank, ends, tile, block):
    """Which blocks each tile of tokens fetches and where each hit's row then
    lies: ``(first [E, tiles], blocks [E, tiles], pos [tiles * tile, k])``.
    Run e of a tile is its hits in ``[ends[e - 1], ends[e])``, consecutive
    rows; it is fetched as ``blocks`` blocks from row ``first`` (a multiple of
    ``block``) into the tile's buffer behind the runs before it, and ``pos``
    is a hit's row in that buffer, -1 for a choice not held in this chunk.
    (The runs lead every array, so that a tile's pairs lie along the lanes.)"""
    t, k = rank.shape
    runs, tiles = ends.shape[0], -(-t // tile)
    flat = jnp.pad(rank, ((0, tiles * tile - t), (0, 0)), constant_values=-1).reshape(tiles, tile * k)
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    of_run = (flat >= starts[:, None, None]) & (flat < ends[:, None, None])              # [E, tiles, tile * k]; a miss is of none
    lo = jnp.where(of_run, flat, jnp.iinfo(jnp.int32).max).min(-1)                       # [E, tiles]
    hi = jnp.where(of_run, flat + 1, 0).max(-1)
    first = jnp.where(hi > lo, lo // block, 0)
    blocks = jnp.where(hi > lo, (hi - 1) // block - first + 1, 0)
    before = jnp.cumsum(blocks, axis=0) - blocks
    # (a rank that no stable sort by expert made could ask for more than the buffer holds: no copy past its end)
    blocks = jnp.minimum(blocks, jnp.maximum(_sum_room(tile, k, runs, block) // block - before, 0))
    shift = (before - first) * block                                                     # a hit's row in the buffer - its rank
    pos = jnp.where(of_run.any(0), flat + jnp.where(of_run, shift[:, :, None], 0).sum(0), -1)
    return (first * block).astype(jnp.int32), blocks.astype(jnp.int32), pos.reshape(tiles * tile, k).astype(jnp.int32)


def _sum_held_rows_body(first_ref, blocks_ref, pos_ref, rows_ref, out_ref, landed, as_rows, sums, count, sems,
                        *, tile, k, k_pad, runs, block):
    i, steps = pl.program_id(0), pl.num_programs(0)
    chunks, stride = out_ref.shape[1] // _LANES, _row_stride(out_ref.shape[1])
    whole = pl.multiple_of

    def fetch(step, slot):  # start the copies of a tile's blocks, run behind run, and count them
        def run(e, filled):
            first, n = whole(first_ref[e * steps + step], block), blocks_ref[e * steps + step]

            def copy(b, carry):
                src = rows_ref.at[pl.ds(whole(first + b * block, block), block)]
                pltpu.make_async_copy(src, landed.at[slot, pl.ds(whole((filled + b) * block, block), block)], sems.at[slot]).start()
                return carry

            jax.lax.fori_loop(0, n, copy, 0)
            return filled + n

        count[slot] = jax.lax.fori_loop(0, runs, run, jnp.int32(0))

    @pl.when(i == 0)
    def _():
        fetch(0, 0)

    @pl.when(i + 1 < steps)
    def _():
        fetch(i + 1, (i + 1) % 2)

    slot = i % 2

    def wait(b, carry):  # every copy moves one block: any block's descriptor waits for one of them
        pltpu.make_async_copy(rows_ref.at[pl.ds(0, block)], landed.at[slot, pl.ds(0, block)], sems.at[slot]).wait()
        return carry

    jax.lax.fori_loop(0, count[slot], wait, 0)

    def rows_alone(b, carry):  # a landed block holds its rows on the sublanes; row r, columns 128 ch.. -> as_rows[r * stride + ch]
        at, to = whole(b * block, block), whole(b * block * stride, _SUBLANES)
        for ch in range(chunks):
            part = landed[slot, pl.ds(at, block), ch * _LANES:(ch + 1) * _LANES].astype(jnp.float32)
            as_rows[pl.ds(to + ch, block, stride=stride), :] = part
        return carry

    jax.lax.fori_loop(0, count[slot], rows_alone, 0)

    def token(t, carry):  # a token's held rows, whole registers each, added in ascending choice
        total = jnp.zeros((stride, _LANES), jnp.float32)
        for j in range(k):
            p = pos_ref[t * k_pad + j]
            total = jax.lax.cond(
                p >= 0, lambda p=p, total=total: total + as_rows[pl.ds(whole(p * stride, _SUBLANES), stride), :],
                lambda total=total: total,
            )
        sums[pl.ds(whole(t * stride, _SUBLANES), stride), :] = total
        return carry

    jax.lax.fori_loop(0, tile, token, 0)

    def tokens_as_block(g, carry):  # 8 tokens' sums back onto the sublanes of one block of the output
        at, to = whole(g * _SUBLANES * stride, _SUBLANES), whole(g * _SUBLANES, _SUBLANES)
        for ch in range(chunks):
            out_ref[pl.ds(to, _SUBLANES), ch * _LANES:(ch + 1) * _LANES] = sums[pl.ds(at + ch, _SUBLANES, stride=stride), :]
        return carry

    jax.lax.fori_loop(0, tile // _SUBLANES, tokens_as_block, 0)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _sum_held_rows(rows, rank, ends, *, tile=SUM_TILE, interpret=False):
    h, (t, k), runs = rows.shape[1], rank.shape, ends.shape[0]
    block = _block_rows(rows.dtype)
    first, blocks, pos = _sum_plan(rank, ends, tile, block)
    k_pad = -(-k // 8) * 8  # an SMEM block of tile * k_pad integers is whole tiles of 1024
    pos = jnp.pad(pos, ((0, 0), (0, k_pad - k)), constant_values=-1).reshape(-1)
    room, stride = _sum_room(tile, k, runs, block), _row_stride(h)
    return pl.pallas_call(
        functools.partial(_sum_held_rows_body, tile=tile, k=k, k_pad=k_pad, runs=runs, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(first.shape[1],),
            in_specs=[
                pl.BlockSpec((tile * k_pad,), lambda i, *_: (i,), memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile, h), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, room, h), rows.dtype),
                pltpu.VMEM((room * stride, _LANES), jnp.float32),
                pltpu.VMEM((tile * stride, _LANES), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_sum_vmem_bytes(h, k, runs, rows.dtype, tile),
            dimension_semantics=("arbitrary",),  # a step fetches for the next one
        ),
        interpret=interpret,
        name="sum_held_rows",
    )(first.reshape(-1), blocks.reshape(-1), pos, rows)


def _sum_into_tokens(rows, rank, ends, impl=None):
    """``rows [C, h]`` summed into their tokens, ``[T, h]`` float32: token t
    gets ``sum_j rows[rank[t, j]]`` over the choices j with ``0 <= rank[t, j]
    < ends[-1]``, added in ascending j. ``impl``: ``"loop"``, ``"kernel"`` or
    ``"kernel_interpret"`` (the kernel under the Pallas interpreter); None
    chooses as ``grouped_matmul`` does, the kernel on a TPU where the shapes
    allow it, and records the choice in ``SUM_PROGRAMS``."""
    refused = sum_kernel_refused(rows, rank, ends)
    if impl is None:
        why = "backend is " + jax.default_backend() if jax.default_backend() != "tpu" else refused
        impl = "loop" if why else "kernel"
        SUM_PROGRAMS[rows.shape, jnp.dtype(rows.dtype).name, rank.shape, ends.shape[0]] = impl + (f": {why}" if why else "")
    if impl == "loop":
        return _sum_loop(rows, rank, ends[-1])
    if refused:
        raise ValueError(f"the kernel that sums rows into tokens was asked for by name and refuses: {refused}")
    return _sum_held_rows(rows, rank, ends, interpret=impl == "kernel_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def take_rows(xf, tokens, rank, ends, sum_impl=None):
    """``xf [T, h]`` -> the chunk's rows ``[C, h]`` (zeros past ``ends[-1]``)."""
    return _rows_of_tokens(xf, tokens, ends[-1])


def _take_rows_fwd(xf, tokens, rank, ends, sum_impl):
    return _rows_of_tokens(xf, tokens, ends[-1]), (rank, ends, jnp.zeros((0,), xf.dtype))


def _take_rows_bwd(sum_impl, res, d):
    rank, ends, like = res
    return _sum_into_tokens(d, rank, ends, sum_impl).astype(like.dtype), None, None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def sum_rows(rows, tokens, rank, ends, sum_impl=None):
    """The chunk's float32 rows ``[C, h]`` summed into their tokens, ``[T, h]``."""
    return _sum_into_tokens(rows, rank, ends, sum_impl)


def _sum_rows_fwd(rows, tokens, rank, ends, sum_impl):
    return _sum_into_tokens(rows, rank, ends, sum_impl), (tokens, ends)


def _sum_rows_bwd(sum_impl, res, d):
    tokens, ends = res
    return _rows_of_tokens(d, tokens, ends[-1]), None, None, None


sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


@jax.custom_vjp
def permute(x, order, rank):
    """``x[order]`` for a permutation ``order`` with inverse ``rank``; the
    transpose gathers by ``rank`` instead of scattering by ``order``."""
    return x[order]


# (the residual is ``rank`` alone: a block that keeps it need not sort for ``order`` again)
permute.defvjp(lambda x, order, rank: (x[order], rank), lambda rank, d: (d[rank], None, None))


def _expert_rows(experts, xin, weights, sizes, n_valid, compute_dtype, impl):
    """One chunk of sorted pairs through its experts: three grouped products
    over the pairs' rows ``xin [C, h]``, each row scaled by its combine
    weight. ``sizes [E]``: rows of each held expert in this chunk;
    ``n_valid``: how many of the C rows are pairs at all (a grouped product
    leaves the rows past them undefined). Returns ``[C, h]`` float32 with
    zeros past ``n_valid``."""
    w1, w3, w2 = (experts[n].astype(compute_dtype) for n in ("w1", "w3", "w2"))
    act = jax.nn.silu(grouped_matmul(xin, w1, sizes, impl=impl)) * grouped_matmul(xin, w3, sizes, impl=impl)
    out = grouped_matmul(act, w2, sizes, impl=impl).astype(jnp.float32)
    # masked BEFORE the weights meet it: what lies past the pairs is whatever
    # the kernel found there, and zero times that is not zero in a gradient
    valid = jnp.arange(xin.shape[0]) < n_valid
    return jnp.where(valid[:, None], out, 0.0) * weights[:, None]


def pairs_a_chunk(config: ModelConfig) -> int:
    """How many sorted pairs a token one chunk of ``grouped_moe_mlp`` takes:
    the load expected under even routing, ``k * held / routed`` pairs a token,
    with a quarter of room above it, in whole pairs a token. The first chunk
    then holds what the routing gives in all but a freak step, and the
    ``lax.cond`` behind it is not taken (0.75 expected where 8 of 64 experts
    are held and 6 chosen: 1; 2.0 where 16 of 64 are held and 8 chosen: 3;
    with every expert held, k)."""
    k, held = config.num_experts_per_tok, len(config.held_expert_ids)
    return min(min(k, held), max(1, math.ceil(1.25 * k * held / config.n_routed_experts)))


def grouped_moe_mlp(lp, x, config: ModelConfig, compute_dtype, *, impl=None, sum_impl=None):
    """Routed part of an expert layer without capacity (DeepSeek-V3, Mellum),
    for the experts held here. ``x [b, s, h]`` -> ``(y [b, s, h], load
    [E_held] int32)``.

    ``lp``: ``gate/{kernel [h, n_routed_experts]}`` (and DeepSeek-V3's
    ``e_score_correction_bias``) and ``experts/{w1, w3 [E_held, h, f], w2
    [E_held, f, h]}``, the rows in the order of ``config.held_expert_ids``.
    The router is as wide as the model's; a (token, expert) pair whose expert
    is held elsewhere adds nothing here (on a mesh its owner adds it), but its
    score still stands in the normaliser of the token's weights. No capacity,
    no dropped token: the pairs held here are sorted by expert and taken a
    chunk of ``pairs_a_chunk(config) * b * s`` rows at a time through grouped
    products; the first chunk covers the expected load with room above it,
    further chunks run only when the routing fills them, so the work follows
    the pairs and not tokens x experts. ``load[e]`` counts the pairs of held
    expert e (the step's counter). ``impl`` chooses the grouped product
    (``grouped_matmul``), ``sum_impl`` what sums a chunk's rows back into
    their tokens (``_sum_into_tokens``); both are for the tests."""
    from llm_fine_tune_distributed_tpu.observe.xla import scope

    b, s, h = x.shape
    t, k = b * s, config.num_experts_per_tok
    held = config.held_expert_ids
    n_held = len(held)
    rows_a_chunk = pairs_a_chunk(config) * t
    xf = x.reshape(t, h)

    with scope("router"):
        top_i, top_w = route(lp["gate"], xf, config)
        # each pair's row among the held experts, n_held where held elsewhere
        local_of = np.full((config.n_routed_experts,), n_held, np.int32)
        local_of[list(held)] = np.arange(n_held)
        local = jnp.asarray(local_of)[top_i].reshape(-1)          # [t * k]
        order = jnp.argsort(local, stable=True)                   # held pairs first, by expert
        rank = checkpoint_name(jnp.argsort(order).astype(jnp.int32), "moe_rank")  # each pair's row in that order
        # counted by compare and sum, the table above made on the host: as
        # bincount and .at[].set they are scatters of a few integers, and the
        # TPU compiler of this jaxlib aborts on them inside the step program
        # (scatter_emitter.cc "operand_indices.size() == 1", PR 26)
        load = checkpoint_name((local[:, None] == jnp.arange(n_held)[None, :]).sum(0, dtype=jnp.int32), "moe_load")
        ends = checkpoint_name(jnp.cumsum(load), "moe_ends")
        tokens = checkpoint_name((order // k).astype(jnp.int32), "moe_tokens")
        weights = checkpoint_name(permute(top_w.reshape(-1), order, rank), "moe_weights")

    def chunk(start, first=False):
        """Rows [start, start + rows_a_chunk) of the sorted pairs through the
        experts and back into their tokens: ``[t, h]`` float32. Only the
        ``first`` chunk names its rows: a block's policy reaches a name through
        the ``cond`` and the loop below, and the further chunks' rows do not fit."""
        in_chunk = lambda edge: jnp.clip(edge - start, 0, rows_a_chunk)  # noqa: E731
        chunk_ends = in_chunk(ends)  # where each held expert's rows end in this chunk
        sizes = chunk_ends - in_chunk(ends - load)
        n_valid = chunk_ends[-1]
        chunk_tokens = jax.lax.dynamic_slice(tokens, (start,), (rows_a_chunk,))
        chunk_rank = rank.reshape(t, k) - start
        xin = take_rows(xf, chunk_tokens, chunk_rank, chunk_ends, sum_impl)
        if first:
            xin = checkpoint_name(xin, "moe_rows")
        rows = _expert_rows(
            lp["experts"], xin,
            jax.lax.dynamic_slice(weights, (start,), (rows_a_chunk,)), sizes, n_valid, compute_dtype, impl,
        )
        return sum_rows(rows, chunk_tokens, chunk_rank, chunk_ends, sum_impl)

    with scope("experts"):
        # every pair held here lies in the first min(k, n_held) * t sorted rows
        overflow = -(-min(k, n_held) * t // rows_a_chunk) - 1
        past_the_tables = (overflow + 1) * rows_a_chunk - t * k
        if past_the_tables > 0:  # the last chunk's slice must not slide back
            tokens, weights = (jnp.pad(a, (0, past_the_tables)) for a in (tokens, weights))
        y = chunk(0, first=True)
        if overflow > 0:
            # Chunks past the first: a token's k choices can all be held here.
            # The whole loop sits behind ONE branch on whether the routing
            # fills more than the first chunk (inside it a chunk is skipped
            # unless reached): a loop that only skips its chunks still adds
            # k - 1 zero gradients the size of the experts in the backward
            # pass (a tenth of the step on a v5e, PR 26). A chunk's
            # activations are recomputed in the backward pass instead of
            # saved: six chunks' would not fit beside the step's state.
            def more(c, acc):
                start = c * rows_a_chunk
                return acc + jax.lax.cond(
                    ends[-1] > start, jax.checkpoint(chunk), lambda st: jnp.zeros((t, h), jnp.float32), start
                )

            y = y + jax.lax.cond(
                ends[-1] > rows_a_chunk,
                lambda: jax.lax.fori_loop(1, overflow + 1, more, jnp.zeros((t, h), jnp.float32)),
                lambda: jnp.zeros((t, h), jnp.float32),
            )
    return y.reshape(b, s, h).astype(x.dtype), load


def init_grouped_moe_params(rng, config: ModelConfig, dtype):
    """Random init of one expert layer's ``mlp`` subtree: router, the held
    experts (rows in the order of ``config.held_expert_ids``), shared experts."""
    h, f = config.hidden_size, config.moe_intermediate_size
    e, n_held = config.n_routed_experts, len(config.held_expert_ids)
    fs = f * config.n_shared_experts
    kg, k1, k2, k3, ks1, ks2, ks3 = jax.random.split(rng, 7)

    def dense(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)

    out = {
        "gate": {"kernel": dense(kg, (h, e))},
        "experts": {"w1": dense(k1, (n_held, h, f)), "w3": dense(k3, (n_held, h, f)), "w2": dense(k2, (n_held, f, h))},
    }
    if config.router_scoring == "sigmoid":  # DeepSeek-V3's selection bias, a buffer
        out["gate"]["e_score_correction_bias"] = jnp.zeros((e,), dtype)
    if fs:
        out["shared_experts"] = {
            "gate_proj": {"kernel": dense(ks1, (h, fs))},
            "up_proj": {"kernel": dense(ks2, (h, fs))},
            "down_proj": {"kernel": dense(ks3, (fs, h))},
        }
        if config.shared_expert_gate:
            out["shared_expert_gate"] = {"kernel": dense(jax.random.fold_in(kg, 1), (h, 1))}
    return out
