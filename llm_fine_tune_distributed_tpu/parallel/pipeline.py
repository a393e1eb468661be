"""Pipeline parallelism (GPipe schedule) over a ``pipe`` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.4 marks it absent;
the MPMD-pipeline paper in PAPERS.md is its design pointer). This is the
TPU-native expression: not MPMD processes with send/recv, but ONE SPMD
program over a ``pipe`` mesh axis where

- each stage device holds a contiguous slice of the transformer blocks
  (stacked layer-major, so the per-stage compute is a ``lax.scan`` over its
  own layers — one compiled block body regardless of depth);
- activations move stage-to-stage with ``jax.lax.ppermute`` (ICI
  neighbor-exchange, the cheapest collective on a TPU torus);
- the GPipe timetable is a ``lax.scan`` over ``M + S - 1`` ticks: stage ``s``
  processes microbatch ``t - s`` at tick ``t`` (bubble ticks compute on
  zeros and are masked out);
- the BACKWARD pipeline is not hand-written at all: ``jax.grad`` through the
  scan + ppermute yields the reversed schedule automatically — the
  correctness-by-construction benefit of a functional pipeline.

Embedding/unembedding and the final norm live outside the pipelined blocks:
embedding is applied to all microbatches up front (host of stage 0 data),
the last stage's outputs are collected, and the loss closes over them. The
embedding table is replicated across stages (it is ~3% of SmolLM3's params).

Wired into SFTTrainer via the ``pipe`` mesh axis (``MESH_PIPE=2 python
training.py``): ``build_pipeline_train_step`` / ``build_pipeline_eval_step``
below are the drop-in step builders, with the stacked-layer state
representation handled by ``stack_flat_layer_leaves`` and partial-layer
freezing by a per-layer gradient mask. The pipe axis composes with
data/fsdp data parallelism (the microbatch dim shards over them inside the
schedule's shard_map).

Schedule note (why GPipe, not 1F1B): differentiating the tick scan yields
the exact time-reversed pipeline, so one optimizer step costs
``2*(M + S - 1)`` stage-ticks against an ideal ``2*M`` — the same bubble
fraction ``(S-1)/(M+S-1)`` 1F1B has (1F1B reorders the SAME work; its
advantage is peak activation memory, capped at S in-flight microbatches
instead of M). Here that memory pressure is addressed where XLA can see it:
``remat_blocks`` saves only stage-boundary activations ([mb, seq, h] per
tick) and recomputes block internals, so in-flight cost is one boundary
tensor per microbatch — smaller than 1F1B's S full stage residuals whenever
h is small relative to per-block state. Cutting the bubble itself requires
interleaved virtual stages (Megatron-style), which trades v× more ppermute
volume for a v× smaller bubble — worth it only at large S; the mesh sizes
this framework targets (pipe ≤ 8) prefer raising M (grad-accum) instead.

Composes with LoRA/QLoRA (adapter leaves stack like any per-layer leaf; the
all-frozen base groups stay out of the optimizer — build_pipeline_state_leaves),
with DPO (train/dpo.build_pipeline_dpo_train_step runs both DPO forwards as
schedules), with expert parallelism (manual-subset shard_map; stacked experts
shard over pipe AND expert), and and with sequence parallelism — BOTH impls (``attention_impl="ring"`` or
``"ulysses"`` + a live seq axis: the schedule goes manual over seq and
stages call the local kernels — long-context pipe runs). Scope bounds
(raised loudly by the trainer): packing (no segment support in the
schedule) and what the layer scan asks of a model (``layer_scan_problems``).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import optax

from llm_fine_tune_distributed_tpu.config import ModelConfig, str_to_dtype
from llm_fine_tune_distributed_tpu.models.transformer import _block, report_shapes, rope_tables, unembed
from llm_fine_tune_distributed_tpu.ops.norms import rms_norm


def stack_stage_params(params: Dict, config: ModelConfig, num_stages: int) -> Dict:
    """Layer dicts -> leaves stacked [num_layers, ...] (layer-major).

    Sharding the leading dim over ``pipe`` gives each stage its contiguous
    block of layers; within a stage the compute scans over the local slice.
    """
    if config.num_layers % num_stages:
        raise ValueError(
            f"{config.num_layers} layers not divisible by {num_stages} stages"
        )
    layers = params["model"]["layers"]
    return jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[layers[str(i)] for i in range(config.num_layers)],
    )


def stage_sharding(mesh: Mesh):
    """Stacked layer leaves: leading (layer) dim sharded over ``pipe``."""
    return NamedSharding(mesh, P("pipe"))


def layer_scan_problems(config: ModelConfig, seq_parallel: bool) -> List[str]:
    """Why ``config`` cannot run under the schedule's layer scan (empty: it
    can). The scan compiles ONE block body over stacked leaves, so every
    layer's plan (``ModelConfig.layer``) must be the same but for ``rope``,
    which the scan carries as data; and the capacity experts' router must see
    whole rows, which a manual ``seq`` axis (``seq_parallel``) takes away.
    Said here once, for ``pipeline_forward`` and the trainer alike."""
    problems = []
    plans = [{**dataclasses.asdict(config.layer(i)), "rope": True} for i in range(config.num_layers)]
    odd = next((i for i, plan in enumerate(plans) if plan != plans[0]), None)
    if odd is not None:
        differ = ", ".join(f"{k} ({v!r} vs {plans[odd][k]!r})" for k, v in plans[0].items() if v != plans[odd][k])
        problems.append(
            f"layers that differ: the pipeline's layer scan runs identical layers, and layers 0 and {odd} "
            f"of {config.name!r} differ in {differ}"
        )
    if seq_parallel and plans[0]["feed_forward"] == "capacity_experts":
        problems.append(
            "a sequence-parallel attention_impl with capacity experts (MoE): inside the manual-seq "
            "schedule the router would see per-chunk token populations, changing capacity semantics"
        )
    return problems


def pipeline_forward(
    params: Dict,
    stacked_layers: Dict,
    input_ids,
    config: ModelConfig,
    mesh: Mesh,
    num_microbatches: int,
    *,
    padding_mask=None,
    compute_dtype=jnp.bfloat16,
    remat_blocks: bool = True,
    output_hidden: bool = False,
    attention_impl: str = "xla",
):
    """Pipelined forward: ``(logits for input_ids [M * mb, seq], report)``.

    ``params`` holds the non-pipelined leaves (embedding, final norm, lm_head
    if untied), replicated; ``stacked_layers`` are the transformer blocks
    stacked [L, ...] and sharded over ``pipe``. ``padding_mask [M*mb, seq]``
    (1 = real token) travels the schedule alongside each microbatch.

    MoE models work too: each stage accumulates its layers' router aux loss
    in the scan carry, bubble ticks are masked out, and the psum over the
    pipe axis yields the total. The report holds it as ``router_aux`` where
    the model's own report does (``models/transformer.report_shapes``): the
    layer-SUM averaged over microbatches — the same scale
    ``forward_with_report`` gives per microbatch. It is all the schedule
    carries of what layers count (no ``expert_load``).
    Expert parallelism composes: the schedule's shard_map is manual only
    over pipe + dp axes, so expert-sharded stacked leaves
    ([L, E, in, out] -> P("pipe", "expert", ...)) keep EP inside each stage
    (GSPMD partitions the dispatch/combine einsums over ``expert``).
    """
    S = mesh.shape["pipe"]
    M = num_microbatches
    # 3D input [M, mb, seq] keeps the microbatch dims through the whole
    # computation (loss included) — the sharded-trainer path, where flattening
    # would mix the pipe-sharded M dim into the dp-sharded row dim and force
    # GSPMD resharding of the batch. 2D input [M * mb, seq] is the
    # building-block API (parity tests vs the flat forward).
    micro_dims = input_ids.ndim == 3
    if micro_dims:
        if input_ids.shape[0] != M:
            raise ValueError(
                f"leading dim {input_ids.shape[0]} != num_microbatches {M}"
            )
        _, mb, seq = input_ids.shape
        ids = input_ids
    else:
        B, seq = input_ids.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        mb = B // M
        ids = input_ids.reshape(M, mb, seq)  # token ids, NOT embeddings: 4
        # bytes per position instead of 2*h — the schedule's input stays tiny
    L_local = config.num_layers // S

    embed = params["model"]["embed_tokens"]["weight"].astype(compute_dtype)
    if padding_mask is None:
        pm = jnp.ones((M, mb, seq), jnp.float32)
    else:
        pm = padding_mask if micro_dims else padding_mask.reshape(M, mb, seq)
    # [1, seq]: broadcasts over however many microbatch rows a device holds
    # (the mb dim shards over data/fsdp inside the shard_map)
    positions = jnp.arange(seq, dtype=jnp.int32)[None]
    cos, sin = rope_tables(config, positions)[config.layer(0).rope_kind]  # (one kind: layer_scan_problems)
    # Per-layer RoPE flags as DATA: the layer scan compiles one block body,
    # and NoPE-interleaved models (SmolLM3) select rope/no-rope per layer.
    # Uniform patterns (every preset except NoPE ones) skip the
    # rotate-then-select and keep the static branch.
    flags_list = [config.layer(i).rope for i in range(config.num_layers)]
    uniform_rope = all(flags_list) or not any(flags_list)
    rope_flags = jnp.asarray(flags_list, jnp.bool_)

    # pipe x ring composition: a live seq axis + attention_impl="ring" makes
    # the schedule manual over "seq" too; each device holds a sequence CHUNK
    # and the stage compute calls the LOCAL ring kernel ("ring_manual" in
    # ops/attention.py), rotating K/V over the seq axis per layer.
    seq_parallel = (
        attention_impl in ("ring", "ulysses") and mesh.shape.get("seq", 1) > 1
    )
    problems = layer_scan_problems(config, seq_parallel)
    if problems:
        raise ValueError("the pipeline does not compose with: " + "; ".join(problems))
    if seq_parallel and seq % mesh.shape["seq"]:
        raise ValueError(
            f"seq {seq} not divisible by the seq axis ({mesh.shape['seq']})"
        )
    if (
        attention_impl == "ulysses"
        and seq_parallel
        and config.num_kv_heads % mesh.shape["seq"]
    ):
        raise ValueError(
            f"ulysses needs kv heads ({config.num_kv_heads}) divisible by "
            f"the seq axis ({mesh.shape['seq']})"
        )
    stage_impl = f"{attention_impl}_manual" if seq_parallel else "xla"

    def run_stage(stage_layers, x, mask, stage_flags, cos_l, sin_l):
        """Scan my L_local blocks over x [mb, seq_local, h]."""

        def one_block(carry, args):
            h, aux = carry
            layer_params, flag = args
            h, _, counted = _block(
                layer_params, h, cos_l, sin_l, mask, None, None, None, 0,
                config=config, plan=config.layer(0), attention_impl=stage_impl,
                compute_dtype=compute_dtype,
                mesh=mesh if seq_parallel else None,
                rope_flag=None if uniform_rope else flag,
            )
            return (h, aux + counted.get("router_aux", 0.0)), None

        body = jax.checkpoint(one_block) if remat_blocks else one_block
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), (stage_layers, stage_flags))
        return x, aux

    def spmd(stacked_local, embed_local, ids_local, pm_local, flags_local):
        # stacked_local: this stage's layers [L_local, ...]; ids_local/
        # pm_local: this device's microbatch COLUMN of token ids + padding
        # masks ([M, mb_local, seq] — the mb dim shards over data/fsdp, so
        # the pipe axis composes with data parallelism); embed_local: the
        # embedding table (replicated, it is a param).
        s = jax.lax.axis_index("pipe")
        T = M + S - 1
        h_dim = embed_local.shape[-1]
        mb_local = ids_local.shape[1]
        seq_local = ids_local.shape[2]
        if seq_parallel:
            # my sequence chunk's RoPE tables (cos/sin enter the manual
            # context replicated at full length; positions are global)
            s_off = jax.lax.axis_index("seq") * seq_local
            cos_l = jax.lax.dynamic_slice_in_dim(cos, s_off, seq_local, axis=1)
            sin_l = jax.lax.dynamic_slice_in_dim(sin, s_off, seq_local, axis=1)
        else:
            cos_l, sin_l = cos, sin

        def tick(carry, t):
            buf, aux_sum = carry  # [mb_local, seq, h] activation at my stage
            m = t - s    # microbatch index my stage works on this tick
            m_safe = jnp.clip(m, 0, M - 1)
            # stage 0 embeds its own microbatch; others use the received
            # buffer. lax.cond (not where) so stages > 0 skip the [mb, seq, h]
            # embedding gather at runtime — legal here because neither branch
            # holds a collective.
            my_ids = jax.lax.dynamic_index_in_dim(
                ids_local, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
            )
            x_in = jax.lax.cond(
                s == 0,
                lambda: embed_local[my_ids].astype(buf.dtype),
                lambda: buf,
            )
            # my microbatch's padding mask rides the same timetable
            mask = jax.lax.dynamic_index_in_dim(pm_local, m_safe, axis=0, keepdims=False)
            y, aux_tick = run_stage(stacked_local, x_in, mask, flags_local, cos_l, sin_l)
            # mask bubble ticks so garbage never enters the ring (or the aux)
            valid = (m >= 0) & (m < M)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            aux_sum = aux_sum + jnp.where(valid, aux_tick, 0.0)
            # pass to the next stage (last stage's output falls off the end)
            y_next = jax.lax.ppermute(
                y, "pipe", [(i, (i + 1) % S) for i in range(S)]
            )
            # last stage emits microbatch m_out = t - (S - 1)
            out = jnp.where(s == S - 1, y, jnp.zeros_like(y))
            return (y_next, aux_sum), out

        (_, aux_local), outs = jax.lax.scan(
            tick,
            (jnp.zeros((mb_local, seq_local, h_dim), compute_dtype), jnp.float32(0.0)),
            jnp.arange(T),
        )
        # total router aux over every (stage, microbatch), averaged over
        # microbatches -> the per-microbatch layer-sum scale forward() uses.
        # With the mb dim sharded, each dp column saw different rows: pmean
        # over the dp axes makes the scalar truly replicated.
        aux = jax.lax.psum(aux_local, "pipe") / M
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        # outs [T, mb, seq, h]: last stage's real outputs live at ticks
        # t = m + S - 1; drop the S-1 bubble rows first so the collective
        # moves only real data. When M divides S-ways, reduce-scatter leaves
        # each stage 1/S of the output (sharded over pipe) instead of a full
        # all-reduce copy per stage.
        outs = outs[S - 1 :]
        if M % S == 0:
            return (
                jax.lax.psum_scatter(outs, "pipe", scatter_dimension=0, tiled=True),
                aux,
            )
        return jax.lax.psum(outs, "pipe"), aux

    # the microbatch dim shards over any live data-parallel axes (pipe + dp
    # composition); meshes without those axes (unit tests) stay replicated
    dp_axes = tuple(
        a for a in ("data", "fsdp") if a in mesh.shape and mesh.shape[a] > 1
    )
    mb_spec = dp_axes if dp_axes else None
    seq_spec = "seq" if seq_parallel else None
    out_spec = (
        P("pipe", mb_spec, seq_spec) if M % S == 0 else P(None, mb_spec, seq_spec)
    )
    # Manual only over the axes the schedule itself communicates on (pipe
    # ppermute/psum + the dp pmean); every other axis — EXPERT above all —
    # stays automatic, so stacked MoE leaves sharded [L->pipe, E->expert,...]
    # keep their expert-dim sharding inside the stage compute and GSPMD
    # partitions the dispatch/combine einsums over the expert axis exactly as
    # on a flat mesh (pipe x EP composition).
    manual_axes = {"pipe", *dp_axes} | ({"seq"} if seq_parallel else set())
    outs, aux = jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(
            P("pipe"), P(),
            P(None, mb_spec, seq_spec), P(None, mb_spec, seq_spec), P("pipe"),
        ),
        out_specs=(out_spec, P()),
        axis_names=manual_axes,
        check_vma=False,
    )(stacked_layers, embed, ids, pm, rope_flags)

    # [M, mb, seq, h] -> final norm (+ unembed unless the caller chunks the
    # loss; same code path as the plain forward for exact parity). With
    # micro_dims the [M, mb, ...] layout survives to the caller so the M dim
    # stays cleanly pipe-sharded all the way into the loss.
    h = outs if micro_dims else outs.reshape(M * mb, seq, -1)
    h = rms_norm(
        h,
        params["model"]["norm"]["weight"],
        config.rms_norm_eps,
        zero_centered=config.zero_centered_norm,
    )
    if output_hidden:
        out = h.astype(compute_dtype)
    else:
        out = unembed(params, h, config, compute_dtype=compute_dtype, logits_dtype=jnp.float32)
    return out, ({"router_aux": aux} if "router_aux" in report_shapes(config) else {})


def pipeline_loss_fn(
    params: Dict,
    stacked_layers: Dict,
    batch: Dict,
    config: ModelConfig,
    mesh: Mesh,
    num_microbatches: int,
    compute_dtype=jnp.bfloat16,
    loss_chunk_size=None,
    include_router_aux: bool = True,
    attention_impl: str = "xla",
):
    """Masked next-token CE through the pipeline (same objective as
    train/step.py's make_loss_fn, including the chunked large-vocab path and
    the MoE router aux term at the same layer-mean scale).
    Differentiable: jax.grad through this yields the reverse-schedule
    backward pipeline automatically.

    Batch arrays may be [B, seq] (building-block API) or [M, mb, seq]
    (trainer path — keeps the pipe-sharded M dim separate from the
    dp-sharded mb dim so no array ever needs a cross-axis reshard)."""
    ids = batch["input_ids"]
    micro_dims = ids.ndim == 3
    targets = ids[..., 1:]
    mask = batch["loss_mask"][..., 1:].astype(jnp.float32)
    tokens = jnp.maximum(mask.sum(), 1.0)
    # one schedule either way: hidden states where the loss is chunked
    # (never materialize [B, seq, vocab] logits of a 128k vocabulary: unembed
    # chunk by chunk exactly like train/step.py), logits where it is not
    out, report = pipeline_forward(
        params, stacked_layers, ids, config, mesh,
        num_microbatches, padding_mask=batch.get("attention_mask"),
        compute_dtype=compute_dtype, output_hidden=loss_chunk_size is not None,
        attention_impl=attention_impl,
    )
    if loss_chunk_size is None:
        ce = optax.softmax_cross_entropy_with_integer_labels(out[..., :-1, :], targets)
        ce_sum = (ce * mask).sum()
    else:
        from llm_fine_tune_distributed_tpu.train.step import chunked_ce_sum

        def chunked(hidden, targets, mask):
            return chunked_ce_sum(params, hidden[:, :-1], targets, mask, config, loss_chunk_size, compute_dtype)

        if micro_dims:
            # one chunked-CE pass per microbatch (lax.map keeps a single
            # compiled body and one [mb, chunk, vocab] tile live at a time)
            ce_sum = jax.lax.map(lambda args: chunked(*args), (out, targets, mask)).sum()
        else:
            ce_sum = chunked(out, targets, mask)
    loss = ce_sum / tokens
    if include_router_aux and "router_aux" in report:
        loss = loss + config.router_aux_coef * report["router_aux"] / config.num_layers
    return loss


# ---------------------------------------------------------------------------
# trainer wiring: stacked flat-state representation + step builders
# ---------------------------------------------------------------------------

# Flat state keys for the stacked transformer blocks live under this marker
# ("model/layers/@stacked/self_attn/q_proj/kernel" -> one [L, h, qd] leaf).
STACKED_PREFIX = "model/layers/@stacked/"
_LAYER_KEY = re.compile(r"^model/layers/(\d+)/(.+)$")


def stack_flat_layer_leaves(flat: Dict, num_layers: int) -> Dict:
    """Per-layer flat leaves -> one stacked [num_layers, ...] leaf each.

    The trainer's flat state dicts keep their non-layer leaves (embedding,
    final norm, lm_head) untouched; every ``model/layers/<i>/<rest>`` group
    must be present for all ``num_layers`` (uniform architectures only —
    which every preset is)."""
    groups: Dict[str, Dict[int, jnp.ndarray]] = {}
    out = {}
    for k, v in flat.items():
        m = _LAYER_KEY.match(k)
        if m is None:
            out[k] = v
        else:
            groups.setdefault(m.group(2), {})[int(m.group(1))] = v
    for rest, by_layer in groups.items():
        if len(by_layer) != num_layers:
            raise ValueError(
                f"layer leaf {rest!r} present for {sorted(by_layer)} but the "
                f"model has {num_layers} layers"
            )
        out[STACKED_PREFIX + rest] = jnp.stack(
            [by_layer[i] for i in range(num_layers)]
        )
    return out


def unstack_flat_layer_leaves(flat: Dict) -> Dict:
    """Inverse of stack_flat_layer_leaves (host-side: used for artifact
    export and checkpoint interop with non-pipelined meshes)."""
    out = {}
    for k, v in flat.items():
        if not k.startswith(STACKED_PREFIX):
            out[k] = v
            continue
        rest = k[len(STACKED_PREFIX):]
        for i in range(v.shape[0]):
            out[f"model/layers/{i}/{rest}"] = v[i]
    return out


def split_stacked_flat(flat: Dict):
    """Merged flat params -> (rest_nested, stacked_layers_nested) for
    pipeline_forward."""
    from llm_fine_tune_distributed_tpu.utils.tree import unflatten_dict

    stacked = {
        k[len(STACKED_PREFIX):]: v
        for k, v in flat.items()
        if k.startswith(STACKED_PREFIX)
    }
    rest = {k: v for k, v in flat.items() if not k.startswith(STACKED_PREFIX)}
    return unflatten_dict(rest), unflatten_dict(stacked)


def build_pipeline_state_leaves(trainable: Dict, frozen: Dict, flat_mask: Dict, num_layers: int):
    """Stack the per-layer block leaves of a flat (trainable, frozen) state
    split and re-partition for pipe mode.

    A stacked leaf may span frozen AND trainable layers (last-N freezing), so
    any stacked group with at least one trainable layer lives in
    ``trainable`` and the per-layer freeze mask becomes the gradient/update
    mask the pipeline train step applies. Groups trainable in NO layer (LoRA
    base kernels, ``lora_scale``) stay ``frozen`` — which is what keeps the
    optimizer state at adapter size under pipe x LoRA/QLoRA, exactly like
    the flat path. Returns ``(trainable, frozen, layer_vec)``. Single source
    for the trainer and the dryrun harness."""
    merged = stack_flat_layer_leaves({**trainable, **frozen}, num_layers)

    def group_trains(stacked_key: str) -> bool:
        rest = stacked_key[len(STACKED_PREFIX):]
        return any(
            flat_mask.get(f"model/layers/{i}/{rest}", False)
            for i in range(num_layers)
        )

    new_trainable = {
        k: v
        for k, v in merged.items()
        if (group_trains(k) if k.startswith(STACKED_PREFIX) else flat_mask.get(k, False))
    }
    new_frozen = {k: v for k, v in merged.items() if k not in new_trainable}
    return new_trainable, new_frozen, layer_trainable_vector(flat_mask, num_layers)


# NF4-quantized expert leaves ([L, E, in/8, out] packed + [L, E, in/b, out]
# absmax) keep the base orientation; _validate_spec (the trainer applies it
# to every pipeline spec) drops any dim the packed shapes no longer divide.
# absmax_scale [L, G] / absmax_offset [L] fall through to plain P("pipe").
_STACKED_EXPERT = re.compile(
    r"block_sparse_moe/experts/(w1|w3|w2)(_nf4|_absmax_q|_absmax)?$"
)


def pipeline_param_spec(path: str, leaf, mesh: Mesh) -> P:
    """Sharding for the pipe-mode state: stacked block leaves shard their
    leading (layer) dim over ``pipe``; stacked MoE expert weights
    ([L, E, in, out]) additionally shard the expert dim over ``expert`` and
    their in/out dims like the flat rules (pipe x EP — the memory win both
    axes exist for on mixtral-class models). Everything else (embedding,
    norms, lm_head) is replicated — those leaves enter the schedule's
    shard_map with replicated in_specs. (FSDP-within-stage is a possible
    refinement; the at-rest cost of replicating non-block leaves is the
    embedding only.)"""
    if path.startswith(STACKED_PREFIX):
        m = _STACKED_EXPERT.search(path)
        if m is not None and "expert" in mesh.shape:
            # same orientation as parallel/sharding._MATRIX_RULES, shifted
            # one dim right for the leading layer axis — but only AUTO axes
            # (expert, tensor) may shard here: fsdp/data are MANUAL inside
            # the schedule's shard_map, and a manual-axis sharding not
            # described by the P("pipe") in_spec would just be gathered away
            # at shard_map entry
            if m.group(1) == "w2":
                return P("pipe", "expert", "tensor", None)
            return P("pipe", "expert", None, "tensor")
        return P("pipe")
    return P()


def layer_trainable_vector(flat_mask: Dict, num_layers: int):
    """[num_layers] 0/1 vector: layer i is trainable iff any of its leaves
    is trainable under the freezing policy (parallel/freeze.py). Applied as
    a gradient/update mask on the stacked leaves, which keeps optax's
    whole-leaf masking semantics while freezing layer slices."""
    import numpy as np

    vec = np.zeros((num_layers,), np.float32)
    for k, v in flat_mask.items():
        m = _LAYER_KEY.match(k)
        if m is not None and v:
            vec[int(m.group(1))] = 1.0
    return jnp.asarray(vec)


def _mask_stacked(tree: Dict, layer_vec):
    """Multiply stacked-leaf entries by the per-layer mask (broadcast over
    the trailing dims); non-stacked leaves pass through."""
    out = {}
    for k, g in tree.items():
        if k.startswith(STACKED_PREFIX):
            vec = layer_vec.reshape((-1,) + (1,) * (g.ndim - 1))
            g = g * vec.astype(g.dtype)
        out[k] = g
    return out


def build_pipeline_train_step(model_config, train_config, optimizer, mesh, layer_vec):
    """train_step(state, batch) -> (state, metrics) over the pipe mesh axis.

    ``batch`` arrays are [grad_accum, global_batch, seq] (the standard loader
    layout); the accumulation dim becomes the pipeline's microbatch stream
    (M = grad_accum), so one optimizer step is ONE schedule of
    M + S - 1 ticks — accumulation and pipelining are the same loop.

    Loss semantics: global token-mean over the whole per-step batch (the flat
    path computes the mean of per-microbatch means; the two agree exactly
    when microbatches carry equal token counts, and to < 1e-3 relative on
    this dataset's padding distribution).

    Freezing: grads AND updates on stacked leaves are masked by
    ``layer_vec`` — masking updates too keeps AdamW's decoupled weight decay
    off frozen layers."""
    compute_dtype = str_to_dtype(train_config.compute_dtype)
    M = train_config.gradient_accumulation_steps
    chunk = train_config.loss_chunk_size

    def loss_fn(trainable, frozen, flat_batch):
        params, stacked_layers = split_stacked_flat({**trainable, **frozen})
        return pipeline_loss_fn(
            params, stacked_layers, flat_batch, model_config, mesh, M,
            compute_dtype=compute_dtype, loss_chunk_size=chunk,
            attention_impl=train_config.attention_impl,
        )

    def train_step(state, batch):
        # batch arrays stay [accum, B, seq]: microbatch m of the schedule is
        # exactly accumulation slice m, and the (pipe-sharded) accum dim is
        # never reshaped into the (dp-sharded) batch dim
        loss, grads = jax.value_and_grad(loss_fn)(
            state.trainable, state.frozen, batch
        )
        grads = _mask_stacked(grads, layer_vec)
        grad_norm = optax.global_norm(grads)
        updates, new_opt_state = optimizer.update(
            grads, state.opt_state, state.trainable
        )
        updates = _mask_stacked(updates, layer_vec)
        new_trainable = optax.apply_updates(state.trainable, updates)
        new_state = state.replace(
            step=state.step + 1,
            trainable=new_trainable,
            opt_state=new_opt_state,
        )
        return new_state, {"loss": loss, "grad_norm": grad_norm}

    return train_step


def eval_microbatches(mesh: Mesh, batch_rows: int) -> int:
    """Microbatch count for an eval schedule over ``batch_rows`` rows.

    M=S fills the schedule when legal; the schedule's shard_map shards the
    microbatch dim over live dp axes, so rows/M must stay divisible by them.
    Degenerate M=1 keeps any batch size valid (full bubble, correct result).
    Shared by the SFT and DPO pipe eval builders so the rule cannot drift."""
    S = mesh.shape["pipe"]
    dp = 1
    for ax in ("data", "fsdp"):
        if ax in mesh.shape:
            dp *= mesh.shape[ax]
    return S if batch_rows % S == 0 and (batch_rows // S) % dp == 0 else 1


def build_pipeline_eval_step(model_config, train_config, mesh):
    """eval_step(state, batch[b, s]) -> (ce_sum, token_count), matching
    train/step.build_eval_step's contract (pure CE, no router aux)."""
    compute_dtype = str_to_dtype(train_config.compute_dtype)
    chunk = train_config.loss_chunk_size

    def eval_step(state, batch):
        params, stacked_layers = split_stacked_flat(
            {**state.trainable, **state.frozen}
        )
        b = batch["input_ids"].shape[0]
        m = eval_microbatches(mesh, b)
        micro_batch = {
            k: v.reshape((m, b // m) + v.shape[1:]) for k, v in batch.items()
        }
        loss = pipeline_loss_fn(
            params, stacked_layers, micro_batch, model_config, mesh, m,
            compute_dtype=compute_dtype, loss_chunk_size=chunk,
            include_router_aux=False,
            attention_impl=train_config.attention_impl,
        )
        tokens = jnp.maximum(batch["loss_mask"][:, 1:].astype(jnp.float32).sum(), 1.0)
        return loss * tokens, tokens

    return eval_step


def bubble_fraction(num_microbatches: int, num_stages: int) -> float:
    """Idle fraction of the GPipe timetable: (S-1)/(M+S-1) per pass (the
    backward pass, being the scan's exact transpose, has the same fraction).
    The trainer warns when grad_accum makes this large."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
