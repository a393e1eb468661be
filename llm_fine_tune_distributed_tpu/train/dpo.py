"""Direct Preference Optimization — the TPU-native replacement for TRL's
``DPOTrainer`` (BASELINE.json config #4: "Mistral-7B-Instruct DPO via TRL
DPOTrainer -> JAX (preference-pair path)"). The reference repo contains no DPO
code of its own; the capability arrives wholesale from TRL, so everything here
is first-party.

TPU-first design decisions:
- **One forward for both completions.** Chosen and rejected sequences are
  concatenated along the batch axis and run through the policy in a single
  call — a [2B, S] matmul keeps the MXU at full occupancy instead of two
  half-sized launches (TRL does the same concat on GPU).
- **Reference model = frozen copy of the trainable subset.** The policy and
  the DPO reference share every frozen parameter (freezing policy / LoRA base),
  so only the trainable leaves are duplicated — in bf16, with no optimizer
  state. With LoRA (B=0 at init) the reference is exactly the base model.
- **Chunked logprobs.** Per-token target logprobs are computed by unembedding
  ``loss_chunk_size`` positions at a time under ``jax.checkpoint`` so the
  [2B, S, vocab] float32 logits never materialize — same HBM strategy as the
  SFT chunked cross-entropy (train/step.py).
- Accumulation is a ``lax.scan``; gradient psum across data-parallel devices
  is emitted by XLA from the shardings, exactly as in the SFT step.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax

from llm_fine_tune_distributed_tpu.config import ModelConfig, TrainConfig, str_to_dtype
from llm_fine_tune_distributed_tpu.models.transformer import forward_with_report, unembed
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.train.step import jit_train_step
from llm_fine_tune_distributed_tpu.utils.tree import merge_flat


def masked_sequence_logprob(per_token_logprob, loss_mask):
    """Sum of target-token logprobs over masked (completion) positions.

    ``per_token_logprob`` is [b, s-1] for targets 1..s-1; ``loss_mask`` is the
    [b, s] label mask from the tokenizer (mask[t] gates predicting token t).
    Returns [b] float32.
    """
    return (per_token_logprob * loss_mask[:, 1:]).sum(axis=-1)


def _target_logprobs(params, hidden, targets, model_config, chunk, compute_dtype, mesh=None):
    """Per-token logprob of ``targets`` given final hidden states.

    hidden: [b, s-1, h] (positions 0..s-2 predicting 1..s-1); returns [b, s-1]
    float32. Chunked along the sequence so only one [b, chunk, vocab] tile of
    logits is live at a time.
    """
    if chunk is None:
        logits = unembed(params, hidden, model_config, compute_dtype=compute_dtype, mesh=mesh)
        return -optax.softmax_cross_entropy_with_integer_labels(logits, targets)

    b, s, h = hidden.shape
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    n = (s + pad) // chunk
    hc = hidden.reshape(b, n, chunk, h).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def one_chunk(args):
        h_c, t_c = args
        logits = unembed(params, h_c, model_config, compute_dtype=compute_dtype, mesh=mesh)
        return -optax.softmax_cross_entropy_with_integer_labels(logits, t_c)

    lp = jax.lax.map(one_chunk, (hc, tc))  # [n, b, chunk]
    return lp.transpose(1, 0, 2).reshape(b, s + pad)[:, :s]


def _dpo_pair_loss(pi_c, pi_r, ref_c, ref_r, beta: float, eps: float):
    """Sigmoid DPO loss + reward aux from chosen/rejected logprobs (any
    shape — flat [B] or pipe-mode [M, B]). Single source for the flat and
    pipeline loss builders so the objective cannot drift between them.

      margin = (pi_c - pi_r) - (ref_c - ref_r)
      loss   = -(1-eps) log sigma(beta*margin) - eps log sigma(-beta*margin)
    """
    margin = (pi_c - pi_r) - (ref_c - ref_r)
    rewards_chosen = beta * (pi_c - ref_c)
    rewards_rejected = beta * (pi_r - ref_r)
    per_pair_loss = (
        -(1.0 - eps) * jax.nn.log_sigmoid(beta * margin)
        - eps * jax.nn.log_sigmoid(-beta * margin)
    )
    aux = {
        "rewards_chosen": rewards_chosen.mean(),
        "rewards_rejected": rewards_rejected.mean(),
        "rewards_margin": (rewards_chosen - rewards_rejected).mean(),
        "rewards_accuracy": (rewards_chosen > rewards_rejected).mean(),
        # per-pair vectors for exact (pad-aware) eval aggregation
        # (pure DPO loss — the router aux joins only the train scalar)
        "per_pair_loss": per_pair_loss,
        "per_pair_correct": (rewards_chosen > rewards_rejected).astype(jnp.float32),
    }
    return per_pair_loss.mean(), aux


def _with_router_aux(loss, report, model_config: ModelConfig):
    """Where the POLICY forward's report has a router load-balancing loss, it
    joins the train objective (layer-mean scale, same as SFT); the reference
    model is stop-gradient so its routers need no balancing pressure."""
    if "router_aux" not in report:
        return loss
    return loss + model_config.router_aux_coef * report["router_aux"] / model_config.num_layers


def make_dpo_loss_fn(
    model_config: ModelConfig,
    train_config: TrainConfig,
    activation_sharding=None,
    quant_impl=None,
) -> Callable:
    """Returns loss_fn(trainable, ref_trainable, frozen, batch) -> (loss, aux).

    Sigmoid DPO loss (Rafailov et al. 2023; TRL ``loss_type="sigmoid"``) with
    optional label smoothing (conservative DPO):
      margin = (pi_c - pi_r) - (ref_c - ref_r)
      loss   = -(1-eps) log sigma(beta * margin) - eps log sigma(-beta * margin)
    """
    compute_dtype = str_to_dtype(train_config.compute_dtype)
    _mesh = getattr(activation_sharding, "mesh", None)
    _seq_parallel = _mesh.shape.get("seq", 1) if _mesh is not None else 1
    remat_policy = train_config.resolved_remat_policy(model_config, _seq_parallel)
    chunk = train_config.loss_chunk_size
    if getattr(train_config, "loss_vocab_chunk", None) is not None:
        # DPO's per-token logprobs stream by SEQUENCE (loss_chunk_size);
        # reject rather than silently materialize the f32 logits the vocab
        # flag promises to avoid
        raise ValueError(
            "loss_vocab_chunk is not supported for objective='dpo'; use "
            "loss_chunk_size"
        )
    quant_impl = quant_impl or train_config.quant_matmul_impl
    beta = train_config.dpo_beta
    eps = train_config.dpo_label_smoothing

    def batch_logprobs(params, input_ids, attention_mask, loss_mask):
        hidden, _, report = forward_with_report(
            params,
            input_ids,
            model_config,
            padding_mask=attention_mask,
            attention_impl=train_config.attention_impl,
            compute_dtype=compute_dtype,
            remat=train_config.gradient_checkpointing,
            remat_policy=remat_policy,
            activation_sharding=activation_sharding,
            output_hidden=True,
            quant_impl=quant_impl,
        )
        per_token = _target_logprobs(
            params, hidden[:, :-1], input_ids[:, 1:], model_config, chunk, compute_dtype,
            mesh=getattr(activation_sharding, "mesh", None),
        )
        return masked_sequence_logprob(per_token, loss_mask), report

    def loss_fn(trainable, ref_trainable, frozen, batch):
        # one [2B, S] forward per model: rows 0..B-1 chosen, B..2B-1 rejected
        ids = jnp.concatenate([batch["chosen_input_ids"], batch["rejected_input_ids"]])
        attn = jnp.concatenate(
            [batch["chosen_attention_mask"], batch["rejected_attention_mask"]]
        )
        mask = jnp.concatenate([batch["chosen_loss_mask"], batch["rejected_loss_mask"]])
        b = batch["chosen_input_ids"].shape[0]

        policy_lp, report = batch_logprobs(merge_flat(trainable, frozen), ids, attn, mask)
        ref_params = merge_flat(
            {k: jax.lax.stop_gradient(v) for k, v in ref_trainable.items()}, frozen
        )
        ref_lp = jax.lax.stop_gradient(batch_logprobs(ref_params, ids, attn, mask)[0])

        pi_c, pi_r = policy_lp[:b], policy_lp[b:]
        ref_c, ref_r = ref_lp[:b], ref_lp[b:]
        loss, aux = _dpo_pair_loss(pi_c, pi_r, ref_c, ref_r, beta, eps)
        return _with_router_aux(loss, report, model_config), aux

    return loss_fn


def build_dpo_train_step(
    model_config: ModelConfig,
    train_config: TrainConfig,
    optimizer: optax.GradientTransformation,
    activation_sharding=None,
    quant_impl=None,
) -> Callable:
    """train_step(state, ref_trainable, batch) -> (state, metrics).

    Batch arrays are [grad_accum, per_host_batch, seq] per key; the
    accumulation loop is a lax.scan compiled into one XLA program (same shape
    as the SFT step, train/step.py:96).
    """
    loss_fn = make_dpo_loss_fn(model_config, train_config, activation_sharding, quant_impl)
    accum = train_config.gradient_accumulation_steps
    aux_keys = ("rewards_chosen", "rewards_rejected", "rewards_margin", "rewards_accuracy")

    def train_step(state: TrainState, ref_trainable, batch):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def micro_step(carry, micro):
            g_acc, loss_acc, aux_acc = carry
            (loss, aux), grads = grad_fn(state.trainable, ref_trainable, state.frozen, micro)
            g_acc = jax.tree.map(jnp.add, g_acc, grads)
            aux_acc = {k: aux_acc[k] + aux[k] for k in aux_keys}
            return (g_acc, loss_acc + loss, aux_acc), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.trainable)
        aux0 = {k: jnp.float32(0.0) for k in aux_keys}
        (g_sum, loss_sum, aux_sum), _ = jax.lax.scan(
            micro_step, (zeros, jnp.float32(0.0), aux0), batch
        )

        grads = jax.tree.map(lambda g: g / accum, g_sum)
        grad_norm = optax.global_norm(grads)
        updates, new_opt_state = optimizer.update(grads, state.opt_state, state.trainable)
        new_trainable = optax.apply_updates(state.trainable, updates)

        new_state = state.replace(
            step=state.step + 1, trainable=new_trainable, opt_state=new_opt_state
        )
        metrics = {
            "loss": loss_sum / accum,
            "grad_norm": grad_norm,
            **{k: v / accum for k, v in aux_sum.items()},
        }
        return new_state, metrics

    return train_step


def make_pipeline_dpo_loss_fn(model_config: ModelConfig, train_config: TrainConfig, mesh):
    """DPO loss through the GPipe schedule (pipe mesh axis): both the policy
    and the stop-gradient reference forward run as pipelined schedules over
    the stacked-layer state; per-token logprobs are chunk-unembedded per
    microbatch exactly like the flat path.

    loss_fn(trainable, ref_trainable, frozen, batch) -> (loss, aux) where
    ``batch`` arrays are [M, B, seq] (microbatch dims kept separate, the
    pipe-mode trainer layout) and chosen/rejected concatenate along the ROW
    dim so each microbatch stays one [2B, seq] schedule entry.
    """
    from llm_fine_tune_distributed_tpu.parallel.pipeline import (
        pipeline_forward,
        split_stacked_flat,
    )

    compute_dtype = str_to_dtype(train_config.compute_dtype)
    chunk = train_config.loss_chunk_size
    beta = train_config.dpo_beta
    eps = train_config.dpo_label_smoothing

    def batch_logprobs(flat_params, ids, attn, mask, M):
        params, stacked = split_stacked_flat(flat_params)
        hidden, report = pipeline_forward(
            params, stacked, ids, model_config, mesh, M,
            padding_mask=attn, compute_dtype=compute_dtype,
            output_hidden=True,
        )

        def lp_one(args):
            h, t = args
            return _target_logprobs(
                params, h[:, :-1], t, model_config, chunk, compute_dtype
            )

        per_token = jax.lax.map(lp_one, (hidden, ids[..., 1:]))  # [M, 2B, S-1]
        lp = (per_token * mask[..., 1:]).sum(axis=-1)  # [M, 2B]
        return lp, report

    def loss_fn(trainable, ref_trainable, frozen, batch):
        ids = jnp.concatenate(
            [batch["chosen_input_ids"], batch["rejected_input_ids"]], axis=1
        )  # [M, 2B, S]
        attn = jnp.concatenate(
            [batch["chosen_attention_mask"], batch["rejected_attention_mask"]], axis=1
        )
        mask = jnp.concatenate(
            [batch["chosen_loss_mask"], batch["rejected_loss_mask"]], axis=1
        ).astype(jnp.float32)
        M, b = batch["chosen_input_ids"].shape[:2]

        policy_lp, report = batch_logprobs({**trainable, **frozen}, ids, attn, mask, M)
        ref_flat = {
            **{k: jax.lax.stop_gradient(v) for k, v in ref_trainable.items()},
            **frozen,
        }
        ref_lp, _ = batch_logprobs(ref_flat, ids, attn, mask, M)
        ref_lp = jax.lax.stop_gradient(ref_lp)

        pi_c, pi_r = policy_lp[:, :b], policy_lp[:, b:]
        ref_c, ref_r = ref_lp[:, :b], ref_lp[:, b:]
        loss, aux = _dpo_pair_loss(pi_c, pi_r, ref_c, ref_r, beta, eps)
        return _with_router_aux(loss, report, model_config), aux

    return loss_fn


def build_pipeline_dpo_train_step(
    model_config: ModelConfig,
    train_config: TrainConfig,
    optimizer: optax.GradientTransformation,
    mesh,
    layer_vec,
) -> Callable:
    """Pipe-mode DPO train_step(state, ref_trainable, batch): one schedule of
    M = grad_accum microbatches per optimizer step (accumulation IS the
    pipeline stream, as in parallel/pipeline.build_pipeline_train_step), with
    the per-layer freeze mask applied to grads and updates."""
    from llm_fine_tune_distributed_tpu.parallel.pipeline import _mask_stacked

    loss_fn = make_pipeline_dpo_loss_fn(model_config, train_config, mesh)
    aux_keys = ("rewards_chosen", "rewards_rejected", "rewards_margin", "rewards_accuracy")

    def train_step(state: TrainState, ref_trainable, batch):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.trainable, ref_trainable, state.frozen, batch
        )
        grads = _mask_stacked(grads, layer_vec)
        grad_norm = optax.global_norm(grads)
        updates, new_opt_state = optimizer.update(grads, state.opt_state, state.trainable)
        updates = _mask_stacked(updates, layer_vec)
        new_trainable = optax.apply_updates(state.trainable, updates)
        new_state = state.replace(
            step=state.step + 1, trainable=new_trainable, opt_state=new_opt_state
        )
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            **{k: aux[k] for k in aux_keys},
        }
        return new_state, metrics

    return train_step


def build_pipeline_dpo_eval_step(
    model_config: ModelConfig, train_config: TrainConfig, mesh
) -> Callable:
    """Pipe-mode eval_step(state, ref_trainable, batch) -> (loss_sum,
    acc_sum, n_real), matching build_dpo_eval_step's contract."""
    from llm_fine_tune_distributed_tpu.parallel.pipeline import eval_microbatches

    loss_fn = make_pipeline_dpo_loss_fn(model_config, train_config, mesh)

    def eval_step(state: TrainState, ref_trainable, batch):
        batch = dict(batch)
        pair_mask = batch.pop("pair_mask")
        b = batch["chosen_input_ids"].shape[0]
        m = eval_microbatches(mesh, b)
        micro = {k: v.reshape((m, b // m) + v.shape[1:]) for k, v in batch.items()}
        _, aux = loss_fn(state.trainable, ref_trainable, state.frozen, micro)
        loss_sum = (aux["per_pair_loss"].reshape(-1) * pair_mask).sum()
        acc_sum = (aux["per_pair_correct"].reshape(-1) * pair_mask).sum()
        return loss_sum, acc_sum, pair_mask.sum()

    return eval_step


def build_dpo_eval_step(
    model_config: ModelConfig,
    train_config: TrainConfig,
    activation_sharding=None,
    quant_impl=None,
) -> Callable:
    """eval_step(state, ref_trainable, batch) -> (loss_sum, acc_sum, n_real).

    ``batch["pair_mask"]`` is 1 for real rows, 0 for tail padding; sums are
    taken over real rows only so the caller aggregates exact means.
    """
    loss_fn = make_dpo_loss_fn(model_config, train_config, activation_sharding, quant_impl)

    def eval_step(state: TrainState, ref_trainable, batch):
        batch = dict(batch)
        pair_mask = batch.pop("pair_mask")
        _, aux = loss_fn(state.trainable, ref_trainable, state.frozen, batch)
        loss_sum = (aux["per_pair_loss"] * pair_mask).sum()
        acc_sum = (aux["per_pair_correct"] * pair_mask).sum()
        return loss_sum, acc_sum, pair_mask.sum()

    return eval_step


from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer


class DPOTrainer(SFTTrainer):
    """Preference-pair trainer sharing the SFT trainer's full infrastructure
    (mesh, sharding, freezing/LoRA, Orbax checkpoints, Aim metrics, artifact
    contract) with the DPO objective swapped in.

    The DPO reference model is NOT checkpointed: it is a deterministic bf16
    copy of the initial trainable leaves, so a resume rebuilds it bit-identically
    from the same base weights.
    """


    # ------------------------------------------------------------------ data

    def _prepare_data(self) -> None:
        import os

        from llm_fine_tune_distributed_tpu.data.dataset import train_validation_split
        from llm_fine_tune_distributed_tpu.data.loader import SFTBatchLoader
        from llm_fine_tune_distributed_tpu.data.preference import (
            build_dpo_arrays,
            load_rows,
            preference_schema,
            synthesize_preference_rows,
        )
        from llm_fine_tune_distributed_tpu.runtime.distributed import is_primary_host

        cfg = self.config
        path = os.path.join(cfg.data_dir, cfg.dataset_file)
        rows = load_rows(path)
        schema = preference_schema(rows)
        if is_primary_host():
            print(f"Total preference dataset size: {len(rows):,} pairs ({schema})")
        train_rows, val_rows = train_validation_split(
            rows, test_size=cfg.validation_fraction, seed=cfg.split_seed
        )
        if schema == "qa":
            # Synthesize WITHIN each split: rotating answers across the whole
            # file first would make validation rejected-texts verbatim copies
            # of train chosen-texts (held-out metric contamination).
            train_rows = synthesize_preference_rows(train_rows, seed=cfg.seed)
            val_rows = synthesize_preference_rows(val_rows, seed=cfg.seed)
        self.n_train, self.n_val = len(train_rows), len(val_rows)
        prompt_kw = self._prompt_kwargs()
        self.train_arrays = build_dpo_arrays(
            train_rows, self.tokenizer, cfg.max_seq_length, **prompt_kw
        )
        self.val_arrays = build_dpo_arrays(
            val_rows, self.tokenizer, cfg.max_seq_length, **prompt_kw
        )
        # the native C++ loader assembles the SFT key triplet only; DPO's
        # six-key pair layout uses the generic Python loader
        self.loader = SFTBatchLoader(self.train_arrays, **self._loader_kwargs())
        self.steps_per_epoch = self.loader.steps_per_epoch
        self.total_steps = self.steps_per_epoch * cfg.epochs

    # ----------------------------------------------------------------- state

    def _prepare_state(self) -> None:
        import jax as _jax
        import jax.numpy as _jnp

        super()._prepare_state()
        # frozen bf16 snapshot of the policy's trainable leaves at init =
        # the DPO reference model (shares every frozen leaf with the policy)
        compute_dtype = str_to_dtype(self.config.compute_dtype)
        self.ref_trainable = {
            k: _jax.device_put(_jnp.asarray(v, compute_dtype), v.sharding)
            for k, v in self.state.trainable.items()
        }

    # ----------------------------------------------------------------- steps

    def _tokens_per_sample(self) -> int:
        # a preference pair = chosen + rejected, each a full sequence
        return 2 * self.config.max_seq_length

    def _prepare_steps(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from llm_fine_tune_distributed_tpu.observe.xla import (
            CompileLedger,
            instrument,
        )

        act = self._make_shardings()
        self._pair_mask_sharding = NamedSharding(self.mesh, P(("data", "fsdp")))
        # train() reads this ledger for compile_total/recompiles_after_warmup
        # step logs; aot=False throughout — the train step donates its state.
        self.compile_ledger = CompileLedger()

        if getattr(self, "_pipe_size", 1) > 1:
            # pipe mesh axis: both DPO forwards run as GPipe schedules over
            # the stacked-layer state (DPO x pipe)
            step = build_pipeline_dpo_train_step(
                self.model_config, self.config, self.optimizer, self.mesh,
                self._layer_vec,
            )
            jitted = instrument(
                "dpo_train_step", jit_train_step(step, mesh=self.mesh),
                self.compile_ledger, aot=False,
            )
            self.train_step = lambda state, batch: jitted(state, self.ref_trainable, batch)
            self._dpo_eval = instrument(
                "dpo_eval_step",
                jax.jit(
                    build_pipeline_dpo_eval_step(
                        self.model_config, self.config, self.mesh
                    )
                ),
                self.compile_ledger, aot=False,
            )
            return

        quant_impl = self._resolved_quant_impl()
        step = build_dpo_train_step(
            self.model_config, self.config, self.optimizer, activation_sharding=act,
            quant_impl=quant_impl,
        )
        jitted = instrument(
            "dpo_train_step", jit_train_step(step, mesh=self.mesh),
            self.compile_ledger, aot=False,
        )
        self.train_step = lambda state, batch: jitted(state, self.ref_trainable, batch)
        self._dpo_eval = instrument(
            "dpo_eval_step",
            jax.jit(
                build_dpo_eval_step(self.model_config, self.config,
                                    activation_sharding=act,
                                    quant_impl=quant_impl)
            ),
            self.compile_ledger, aot=False,
        )

    # ------------------------------------------------------------------ eval

    def evaluate(self) -> float:
        import numpy as np

        bs = self._eval_global_batch_size()
        n = self.val_arrays["chosen_input_ids"].shape[0]
        if n == 0:
            return float("nan")
        loss_sum = acc_sum = count = 0.0
        for lo in range(0, n, bs):
            batch = {k: v[lo : lo + bs] for k, v in self.val_arrays.items()}
            real = batch["chosen_input_ids"].shape[0]
            pair_mask = np.ones((real,), np.float32)
            if real < bs:  # wrap-pad the tail; padded rows masked out
                pad = bs - real
                batch = {
                    k: np.concatenate([v, v[:pad] if pad <= real else
                                       np.repeat(v, -(-pad // real), 0)[:pad]])
                    for k, v in batch.items()
                }
                pair_mask = np.concatenate([pair_mask, np.zeros((pad,), np.float32)])
            dev = {
                k: jax.device_put(v, self._eval_sharding) for k, v in batch.items()
            }
            dev["pair_mask"] = jax.device_put(pair_mask, self._pair_mask_sharding)
            l, a, c = self._dpo_eval(self.state, self.ref_trainable, dev)
            loss_sum += float(l)
            acc_sum += float(a)
            count += float(c)
        count = max(count, 1.0)
        self.extra_eval_logs = {"eval_rewards_accuracy": acc_sum / count}
        return loss_sum / count

