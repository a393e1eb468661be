"""The jit-compiled train/eval steps — the hot loop the reference delegates to
TRL/HF (``trainer.train()``, reference ``training.py:300``; loop anatomy in
SURVEY.md §3.1). One XLA program per optimizer step:

  scan over grad-accum microbatches (fwd+bwd, remat'd blocks)
  -> mean grads -> clip(1.0) -> AdamW on trainable subset -> new state

Gradient synchronization across data-parallel devices is NOT explicit: the
loss averages over the (sharded) global microbatch, so jax.grad's psum is
emitted by XLA from the sharding annotations — the compiler-native equivalent
of DDP's bucketed NCCL all-reduce (reference ``docs/architecture-diagram.md:119-135``).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax

from llm_fine_tune_distributed_tpu.config import ModelConfig, TrainConfig, str_to_dtype
from llm_fine_tune_distributed_tpu.models.transformer import forward_with_report, report_shapes, unembed
from llm_fine_tune_distributed_tpu.observe.xla import scope
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.utils.tree import merge_flat


def chunked_ce_sum(params, hidden, targets, mask, model_config: ModelConfig, chunk_size: int, compute_dtype, mesh=None, extra_mask=None):
    """Masked cross-entropy SUM computed in sequence chunks.

    Unembeds ``chunk_size`` positions at a time (each chunk rematerialized on
    backward) so peak HBM holds one [batch, chunk, vocab] f32 tile instead of
    the full [batch, seq, vocab] logits — what makes 128k-vocab models
    trainable on a 16GB chip at seq 1024.

    ``extra_mask``: optional second mask — returns (sum, extra_sum) from ONE
    streamed unembed (the answer-only eval metric must not double the eval
    pause it exists to diagnose).

    A model of several next-token heads (``num_pred_heads`` P, ``heads_ahead``)
    hands ``targets`` and the masks as ``[batch, seq, P]``: one ``[chunk, P x
    vocab]`` product a chunk, read as P blocks of columns, each against its
    own targets under its own mask, in the same pass over the hidden rows.
    """
    b, s, h = hidden.shape
    heads = targets.shape[2:]  # () for the one head, (P,) for several
    pad = (-s) % chunk_size
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        by_position = ((0, 0), (0, pad)) + ((0, 0),) * len(heads)
        targets = jnp.pad(targets, by_position)
        mask = jnp.pad(mask, by_position)
        if extra_mask is not None:
            extra_mask = jnp.pad(extra_mask, by_position)
    n = (s + pad) // chunk_size
    # [n_chunks, batch, chunk, ...] so lax.map scans over chunks
    hc = hidden.reshape(b, n, chunk_size, h).transpose(1, 0, 2, 3)
    tc = jnp.moveaxis(targets.reshape(b, n, chunk_size, *heads), 1, 0)
    masks = (mask,) if extra_mask is None else (mask, extra_mask)
    mcs = tuple(jnp.moveaxis(m.reshape(b, n, chunk_size, *heads), 1, 0) for m in masks)

    @jax.checkpoint
    def one_chunk(args):
        h_c, t_c, m_cs = args
        logits = unembed(params, h_c, model_config, compute_dtype=compute_dtype, mesh=mesh)
        if heads:
            logits = logits.reshape(*logits.shape[:-1], *heads, model_config.vocab_size)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, t_c)
        return jnp.stack([(ce * m).sum() for m in m_cs])

    sums = jax.lax.map(one_chunk, (hc, tc, mcs)).sum(axis=0)
    if extra_mask is None:
        return sums[0]
    return sums[0], sums[1]


def heads_ahead(x, heads: int):
    """What the next-token heads of a row ``x [batch, seq]`` (ids, or a mask) are held to, for the positions 0 ..
    seq - 2 that predict: ``x[:, 1:]`` for the usual one head; for P heads ``[batch, seq - 1, P]`` with head i's
    entry at position t the row's ``t + 1 + i``, and 0 (no target, masked out) where that lies past the row's end."""
    if heads == 1:
        return x[:, 1:]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (0, heads - 1)))
    return jnp.stack([padded[:, 1 + i: s + i] for i in range(heads)], axis=-1)


def vocab_chunked_ce_sum(params, hidden, targets, mask, model_config: ModelConfig,
                         vocab_chunk: int, compute_dtype, mesh=None, extra_mask=None):
    """Masked cross-entropy SUM streamed over VOCAB chunks (online logsumexp).

    The full-logits path materializes a [batch, seq, vocab] float32 tensor
    (~1 GB at the flagship's microbatch) and re-reads it through logsumexp,
    gather, and the softmax backward. Here the unembed runs one
    [hidden, vocab_chunk] slice at a time carrying (running max, running
    exp-sum, gold logit) — the logits tensor never exists in fwd OR bwd
    (the chunk body is rematerialized on backward: one extra matmul per
    chunk instead of the f32 logits residual). Measured 2.5-3x faster than
    the full path at flagship shapes in isolation (an earlier round's
    reading; not re-measured on the attached v5e).
    """
    V = model_config.vocab_size
    if V % vocab_chunk:
        raise ValueError(
            f"vocab_size {V} not divisible by loss_vocab_chunk {vocab_chunk}"
        )
    n = V // vocab_chunk
    b, s, h = hidden.shape
    x = hidden.astype(compute_dtype).reshape(b * s, h)
    flat_targets = targets.reshape(-1)

    tied = model_config.tie_word_embeddings
    table = (
        params["model"]["embed_tokens"]["weight"]
        if tied
        else params["lm_head"]["kernel"]
    )
    if mesh is not None:
        # same layout treatment unembed() applies: vocab over tensor, hidden
        # gathered — without it GSPMD reshards the activations (and their
        # cotangents) through a replicate-then-repartition fallback on every
        # scan iteration's slice
        from llm_fine_tune_distributed_tpu.models.transformer import (
            _lookup_table_constraint,
        )

        table = _lookup_table_constraint(table, mesh, vocab_dim=0 if tied else 1)

    @jax.checkpoint
    def body(carry, i):
        m, acc, gold = carry
        if tied:  # [V, H] slice -> logits via x @ Wc^T
            wc = jax.lax.dynamic_slice(
                table, (i * vocab_chunk, 0), (vocab_chunk, h)
            ).astype(compute_dtype)
            lg = (x @ wc.T).astype(jnp.float32)
        else:  # [H, V] slice
            wc = jax.lax.dynamic_slice(
                table, (0, i * vocab_chunk), (h, vocab_chunk)
            ).astype(compute_dtype)
            lg = (x @ wc).astype(jnp.float32)
        if model_config.logits_scaling != 1.0:  # as unembed(): Granite divides its logits by a constant
            lg = lg / jnp.float32(model_config.logits_scaling)
        if model_config.final_logit_softcap is not None:
            from llm_fine_tune_distributed_tpu.ops.attention import softcap

            # Gemma2 softcap is elementwise per logit, so it streams
            lg = softcap(lg, model_config.final_logit_softcap)
        m_new = jnp.maximum(m, lg.max(axis=-1))
        acc = acc * jnp.exp(m - m_new) + jnp.exp(lg - m_new[:, None]).sum(-1)
        loc = flat_targets - i * vocab_chunk
        hit = (loc >= 0) & (loc < vocab_chunk)
        g = jnp.take_along_axis(
            lg, jnp.clip(loc, 0, vocab_chunk - 1)[:, None], axis=-1
        )[:, 0]
        return (m_new, acc, jnp.where(hit, g, gold)), None

    init = (
        jnp.full((b * s,), -1e30, jnp.float32),
        jnp.zeros((b * s,), jnp.float32),
        jnp.zeros((b * s,), jnp.float32),
    )
    (m, acc, gold), _ = jax.lax.scan(body, init, jnp.arange(n))
    ce = m + jnp.log(acc) - gold  # == logsumexp(logits) - logits[target]
    ce = ce.reshape(b, s)
    if extra_mask is not None:
        # per-token ce already materialized — the second metric is one more
        # masked reduction, no extra unembed streaming
        return (ce * mask).sum(), (ce * extra_mask).sum()
    return (ce * mask).sum()


def static_seq_parallel_size(
    model_config: ModelConfig, train_config: TrainConfig, mesh
) -> int:
    """The seq-axis sharding factor that will ACTUALLY apply at runtime, as
    far as it is statically decidable — the auto remat policy keys on
    per-chip sequence length, and a provisioned-but-unused (or fallen-back)
    seq axis must count as full per-chip seq or auto under-remats and OOMs
    at long context (ADVICE r4). Mirrors the trainer's seq_sharded predicate
    plus the static half of seq_parallel_preconditions
    (parallel/ring_attention.py); the batch-divisibility precondition is
    satisfied by construction for trainer-built batches
    (global batch = per_device_batch_size * data * fsdp)."""
    from llm_fine_tune_distributed_tpu.parallel.ring_attention import (
        seq_parallel_static_preconditions,
    )
    from llm_fine_tune_distributed_tpu.parallel.ulysses import (
        ulysses_static_preconditions,
    )

    if mesh is None or train_config.attention_impl not in ("ring", "ulysses"):
        return 1
    n = mesh.shape.get("seq", 1)
    if n <= 1:
        return 1
    if not seq_parallel_static_preconditions(
        train_config.max_seq_length, model_config.num_heads,
        model_config.num_kv_heads, mesh,
        sliding_window=model_config.sliding_window,
    ):
        return 1  # runtime fallback -> full per-chip sequence
    if train_config.attention_impl == "ulysses" and not ulysses_static_preconditions(
        model_config.num_heads, model_config.num_kv_heads, mesh
    ):
        return 1
    return n


def make_loss_fn(model_config: ModelConfig, train_config: TrainConfig, activation_sharding=None,
                 quant_impl: Optional[str] = None, include_router_aux: bool = True,
                 frozen_layers: int = 0):
    """``loss_fn(trainable, frozen, batch) -> (loss, stats)``. ``stats`` is a
    dict: ``tokens`` always; ``answer_ce_sum`` and ``answer_tokens`` when the
    batch carries a ``completion_mask``; ``expert_load`` where the model's
    report has one (``models/transformer.forward_with_report``).
    ``include_router_aux``: add the report's ``router_aux``, where it has
    one, to the loss (eval leaves the balancing loss out)."""
    compute_dtype = str_to_dtype(train_config.compute_dtype)
    _mesh = getattr(activation_sharding, "mesh", None)
    seq_parallel = static_seq_parallel_size(model_config, train_config, _mesh)
    remat_policy = train_config.resolved_remat_policy(model_config, seq_parallel)
    chunk = train_config.loss_chunk_size
    vocab_chunk = getattr(train_config, "loss_vocab_chunk", None)
    if chunk is not None and vocab_chunk is not None:
        raise ValueError(
            "loss_chunk_size (sequence chunking) and loss_vocab_chunk "
            "(vocab streaming) are mutually exclusive"
        )
    quant_impl = quant_impl or train_config.quant_matmul_impl
    # Frozen-trunk fast path (ISSUE 20): frozen_layers is the trainable
    # boundary (parallel/freeze.frozen_trunk_boundary) the trainer computed
    # from the freeze mask; forward() runs those leading layers w8a8 with a
    # boundary stop_gradient when frozen_compute="int8". The default "bf16"
    # (or boundary 0 — lora/qlora/full fine-tune) leaves forward untouched.
    # A model of P next-token heads: head i at position t answers token t + 1 + i, and the loss is the mean of the
    # heads' cross-entropies, each the mean over the positions whose target lies inside the row and under the loss
    # mask. ``weigh`` makes a head's masked SUM that mean over P; one head keeps the sum and the division below.
    heads = model_config.num_pred_heads
    if heads > 1 and vocab_chunk is not None:
        raise ValueError(f"model {model_config.name!r} has {heads} next-token heads: loss_vocab_chunk streams one "
                         "head's vocabulary; use loss_chunk_size (or neither)")
    weigh = (lambda m: m) if heads == 1 else (lambda m: m / (heads * jnp.maximum(m.sum(axis=(0, 1)), 1.0)))
    frozen_compute = getattr(train_config, "frozen_compute", "bf16")
    if frozen_compute not in ("bf16", "int8"):
        raise ValueError(
            f"unknown frozen_compute {frozen_compute!r} (expected 'bf16' or 'int8')"
        )

    def loss_fn(trainable, frozen, batch):
        """Masked next-token cross-entropy (token-mean within the batch) —
        the SFT objective TRL computes for packing=False full-sequence LM
        loss (reference ``training.py:282-283``).

        When the batch additionally carries a ``completion_mask`` (eval
        batches only — trainer._prepare_data), ``stats`` holds the
        completion-span CE, computed from the SAME forward pass, so the
        answer-only eval metric (the full-sequence eval_loss is dominated by
        the constant system prompt) costs one extra masked reduction on the
        full-logits path (and one extra streamed unembed on the chunked
        paths, which rematerialize per-mask)."""
        params = merge_flat(trainable, frozen)
        packed_kw = {}
        if "segment_ids" in batch:  # packing=True path (data/packing.py)
            packed_kw = {
                "segment_ids": batch["segment_ids"],
                "positions": batch["positions"],
            }
        out, _, report = forward_with_report(
            params,
            batch["input_ids"],
            model_config,
            padding_mask=batch["attention_mask"],
            **packed_kw,
            attention_impl=train_config.attention_impl,
            compute_dtype=compute_dtype,
            remat=train_config.gradient_checkpointing,
            remat_policy=remat_policy,
            activation_sharding=activation_sharding,
            logits_dtype=jnp.float32,
            output_hidden=chunk is not None or vocab_chunk is not None,
            quant_impl=quant_impl,
            frozen_layers=frozen_layers,
            frozen_compute=frozen_compute,
        )
        targets = heads_ahead(batch["input_ids"], heads)
        mask = heads_ahead(batch["loss_mask"], heads).astype(jnp.float32)
        tokens = jnp.maximum((mask if heads == 1 else mask[..., 0]).sum(), 1.0)
        amask = None
        if "completion_mask" in batch:
            amask = heads_ahead(batch["completion_mask"], heads).astype(jnp.float32)
        # ce_fn(mask) -> sum; ce_fn(mask, extra) -> (sum, extra_sum) from a
        # SINGLE unembed on every path. One scope for the three of them (on
        # the full-logits path the unembed itself ran inside forward, under
        # the same name).
        with scope("loss_head"):
            if vocab_chunk is not None:
                ce_fn = lambda m, e=None: vocab_chunked_ce_sum(
                    params, out[:, :-1], targets, m, model_config, vocab_chunk,
                    compute_dtype, mesh=_mesh, extra_mask=e,
                )
            elif chunk is not None:
                ce_fn = lambda m, e=None: chunked_ce_sum(
                    params, out[:, :-1], targets, m, model_config, chunk,
                    compute_dtype, mesh=_mesh, extra_mask=e,
                )
            else:
                logits = out[:, :-1]
                if heads > 1:
                    logits = logits.reshape(*logits.shape[:-1], heads, model_config.vocab_size)
                ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
                ce_fn = lambda m, e=None: (
                    (ce * m).sum() if e is None else ((ce * m).sum(), (ce * e).sum())
                )
            if amask is not None:
                ce_sum, ans_sum = ce_fn(weigh(mask), weigh(amask))
            else:
                ce_sum = ce_fn(weigh(mask))
        # several heads: the weights have made each head's sum its mean over its own targets, an eighth of it
        loss = ce_sum / tokens if heads == 1 else ce_sum
        if include_router_aux and "router_aux" in report:
            # The load-balancing loss joins the TRAIN objective only (eval
            # loss stays pure CE so perplexity/best-model tracking is
            # comparable with dense runs). Layer-MEAN of the per-layer aux
            # (the report has the sum), so router_aux_coef is
            # depth-independent — matching the effective scale of HF
            # Mixtral's router_aux_loss_coef rather than growing the
            # balancing pressure 32x on a 32-layer model
            aux = report["router_aux"] / model_config.num_layers
            loss = loss + model_config.router_aux_coef * aux
        stats = {"tokens": tokens}
        if amask is not None:
            answer_tokens = (amask if heads == 1 else amask[..., 0]).sum()
            stats.update(answer_ce_sum=ans_sum if heads == 1 else ans_sum * answer_tokens, answer_tokens=answer_tokens)
        if "expert_load" in report:
            stats["expert_load"] = report["expert_load"]
        return loss, stats

    return loss_fn


# What the train step keeps of the loss function's ``stats``, by the
# statistic's key: what one microbatch adds to the accumulation scan's carry,
# how the carry combines it, and the metrics made of the step's totals.
def _kept_of_a_microbatch(stats):
    kept = {}
    if "expert_load" in stats:
        load = stats["expert_load"].astype(jnp.float32)  # [expert layers, held experts]
        worst = (load.max(-1) / jnp.maximum(load.mean(-1), 1.0)).max()
        kept.update(expert_load=load.sum(0), expert_load_max_over_mean=worst)
    return kept


_COMBINE_KEPT = {"expert_load": jnp.add, "expert_load_max_over_mean": jnp.maximum}


def _metrics_of_kept(kept, report, batch_positions: int):
    if "expert_load" not in kept:
        return {}
    positions = batch_positions * report["expert_load"].shape[0]  # tokens x expert layers
    return dict(
        # pairs held here a token and expert layer (the expectation under
        # even routing is k * held / routed experts); each held expert's
        # pairs by that same measure; and the fullest held expert over the
        # mean, in the step's worst layer and microbatch
        expert_pairs_per_token=kept["expert_load"].sum() / positions,
        expert_load=kept["expert_load"] / positions,
        expert_load_max_over_mean=kept["expert_load_max_over_mean"],
    )


def build_train_step(
    model_config: ModelConfig,
    train_config: TrainConfig,
    optimizer: optax.GradientTransformation,
    activation_sharding=None,
    quant_impl: Optional[str] = None,
    frozen_layers: int = 0,
) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    ``batch`` arrays are [grad_accum, per_device_or_host_batch, seq]; the
    accumulation loop is a lax.scan so XLA compiles ONE program regardless of
    the accumulation factor (reference ``gradient_accumulation_steps=4``,
    ``training.py:262``).
    """
    loss_fn = make_loss_fn(
        model_config, train_config, activation_sharding, quant_impl, frozen_layers=frozen_layers,
    )
    accum = train_config.gradient_accumulation_steps
    report = report_shapes(model_config)
    kept_shapes = jax.eval_shape(_kept_of_a_microbatch, report)

    def train_step(state: TrainState, batch):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def micro_step(carry, micro):
            g_acc, loss_acc, kept = carry
            (loss, stats), grads = grad_fn(state.trainable, state.frozen, micro)
            with scope("grad_accum"):
                g_acc = jax.tree.map(jnp.add, g_acc, grads)
            kept = {k: _COMBINE_KEPT[k](kept[k], v) for k, v in _kept_of_a_microbatch(stats).items()}
            return (g_acc, loss_acc + loss, kept), None

        with scope("grad_accum"):
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.trainable)
        kept = jax.tree.map(lambda k: jnp.zeros(k.shape, k.dtype), kept_shapes)
        (g_sum, loss_sum, kept), _ = jax.lax.scan(
            micro_step, (zeros, jnp.float32(0.0), kept), batch
        )

        # Mean over accumulation steps (HF semantics: mean of microbatch means).
        with scope("grad_accum"):
            grads = jax.tree.map(lambda g: g / accum, g_sum)
        loss = loss_sum / accum

        with scope("optimizer"):
            grad_norm = optax.global_norm(grads)  # pre-clip, matches HF's logged grad_norm
            updates, new_opt_state = optimizer.update(grads, state.opt_state, state.trainable)
            new_trainable = optax.apply_updates(state.trainable, updates)

        new_state = state.replace(
            step=state.step + 1,
            trainable=new_trainable,
            opt_state=new_opt_state,
        )
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            **_metrics_of_kept(kept, report, batch["input_ids"].size),
        }
        return new_state, metrics

    return train_step


def build_eval_step(
    model_config: ModelConfig,
    train_config: TrainConfig,
    activation_sharding=None,
    quant_impl: Optional[str] = None,
    frozen_layers: int = 0,
) -> Callable:
    """eval_step(state, batch[b, s]) -> (sum_ce, token_count), or
    (sum_ce, tokens, answer_sum_ce, answer_tokens) when the batch carries a
    ``completion_mask`` (the answer-only eval metric).

    Returns sums (not means) so the caller aggregates a token-weighted eval
    loss over the whole validation set — the quantity behind
    ``eval_loss``/best-model tracking (reference ``training.py:273-275``)."""
    loss_fn = make_loss_fn(
        model_config, train_config, activation_sharding, quant_impl,
        include_router_aux=False, frozen_layers=frozen_layers,
    )

    def eval_step(state: TrainState, batch):
        loss, stats = loss_fn(state.trainable, state.frozen, batch)
        tokens = stats["tokens"]
        if "answer_ce_sum" in stats:
            return loss * tokens, tokens, stats["answer_ce_sum"], stats["answer_tokens"]
        return loss * tokens, tokens

    return eval_step


# XLA:TPU options for a step program partitioned over more than one device.
# On an fsdp axis laid out as a ring of neighbours (what jax.make_mesh builds
# on a 2x2 v5e host: devices 0, 1, 3, 2) the SPMD partitioner rewrites every
# weight all-gather + matmul as a windowed einsum: the shards travel the ring
# by collective-permute while partial matmuls run. In this step that keeps
# about 700 weight-shard buffers live at once, 0.31 GiB a layer and 11 GiB a
# device at SmolLM3-3B depth, and the chip's compiler refuses the flagship
# recipe on the default four-chip mesh (18.69 GB of a device's 15.75; PR 21,
# the chip and a deviceless compile in that device order agree). With the
# rewrite off the partitioner emits plain all-gathers and the same step asks
# for 5.8 GiB of temporaries. What the rewrite would buy in overlap where it
# fits is not measured (PERF.md section 7).
SPMD_STEP_COMPILER_OPTIONS = {
    "xla_tpu_enable_windowed_einsum_for_all_gather": False,
    "xla_tpu_enable_windowed_einsum_for_reduce_scatter": False,
}


def jit_train_step(train_step, donate_state: bool = True, mesh=None):
    """Jit with state donation — the step's output state reuses the input
    buffers (param + opt-state memory is not duplicated during the update).
    ``mesh``: the mesh the step is partitioned over; on more than one TPU
    device the program is compiled with ``SPMD_STEP_COMPILER_OPTIONS``."""
    options = None
    if mesh is not None and mesh.size > 1 and mesh.devices.flat[0].platform == "tpu":
        options = SPMD_STEP_COMPILER_OPTIONS
    return jax.jit(
        train_step,
        donate_argnums=(0,) if donate_state else (),
        compiler_options=options,
    )
