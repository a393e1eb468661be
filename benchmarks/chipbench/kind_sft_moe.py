"""Cells of kind ``sft_moe``: ``kind_sft.py``'s cell for a configuration with
latent attention and routed experts (DeepSeek-V3 layout), every parameter
trainable. The program's jitted train step is driven directly, by the same
loop, readings and comparison; what differs is where the model's shape comes
from (``model_config`` below), who makes the weights (``weights_mla_moe.py``),
who follows the steps (``reference_mla_moe.py``), how the required operations
are counted (``flops_mla_moe.py``, from the pairs the step reports), and that
the step's expert counters are kept for the readers in ``readers/moe.py``.

Copied from ``kind_sft.py`` because they name its own ``model_config`` and
``weights`` inside: ``Program.__init__``, ``Program.make_state`` and ``run``
(PERF.md lists them for a ``benchmark`` issue to fold). Reused by import:
``Program.release``/``put_batch``, ``program_readings``, ``compare``.

The mix's ``control`` sets ``router_dtype``: the router's scores, selection
and combine weights in ``float8_e4m3fn`` (``ops/moe.ROUTER_DTYPE``), the
program's own path at the nearest precision below the bfloat16 the
configuration states (the router in bfloat16 itself hardly moves a number:
PERF.md section 2). It is a key of the benchmark's mix, not an option of the
program.
"""

from __future__ import annotations

import math
import time

from benchmarks.chipbench import flops_mla_moe, kind_sft, reference_mla_moe, traffic, weights, weights_mla_moe


def model_config(cfg: dict):
    import dataclasses

    from llm_fine_tune_distributed_tpu.config import ModelConfig

    if "kv_lora_rank" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise SystemExit("chipbench: this checkout's program has no latent attention or routed experts with shared "
                         "experts (ModelConfig.kv_lora_rank): it cannot run a cell of kind sft_moe")
    return ModelConfig(
        name=cfg.get("model_type", "chipbench"),
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        rope_theta=float(cfg["rope_theta"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_routed_experts=cfg["router_experts"],
        held_experts=tuple(cfg["held_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
    )


class Program(kind_sft.Program):
    def __init__(self, cfg: dict, mix: dict):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
        from llm_fine_tune_distributed_tpu.observe.xla import CompileLedger, instrument
        from llm_fine_tune_distributed_tpu.ops import moe
        from llm_fine_tune_distributed_tpu.parallel.optimizer import build_optimizer
        from llm_fine_tune_distributed_tpu.runtime.mesh import data_parallel_size, make_mesh
        from llm_fine_tune_distributed_tpu.train import step as step_mod

        recipe = mix["recipe"]
        if recipe["freeze_strategy"] != "none":
            raise SystemExit("chipbench sft_moe cells train every parameter")
        moe.ROUTER_DTYPE = jnp.dtype(mix.get("router_dtype", "float32"))  # the control's one change
        self.cfg, self.mix = cfg, mix
        self.mc = model_config(cfg)
        self.tc = TrainConfig(
            model_preset=None,
            per_device_batch_size=int(mix["microbatch"]),
            gradient_accumulation_steps=int(mix["accum"]),
            max_seq_length=int(mix["seq_len"]),
            **{k: recipe[k] for k in kind_sft.RECIPE_KEYS if k in recipe},
        )
        self.mesh = make_mesh(MeshConfig(data=1, fsdp=-1, tensor=1, seq=1))
        if data_parallel_size(self.mesh) != 1:
            raise SystemExit("chipbench sft cells are written for one chip")
        self.optimizer = build_optimizer(
            self.tc, None, total_steps=int(recipe["total_steps"]), data_parallel_size=1
        )
        self.frozen_layers = 0
        act = NamedSharding(self.mesh, P(("data", "fsdp"), None, None))
        self.ledger = CompileLedger()
        self.step_fn = instrument(
            "train_step",
            step_mod.jit_train_step(
                step_mod.build_train_step(self.mc, self.tc, self.optimizer, activation_sharding=act),
                mesh=self.mesh,
            ),
            self.ledger,
        )
        self._batch_sharding = NamedSharding(self.mesh, P(None, ("data", "fsdp")))
        self._jax = jax

    def make_state(self, seed: int):
        """As ``kind_sft.Program.make_state``: the freeze split, master dtype
        and float32 Adam zeros of the trainer's state, over weights that
        ``weights_mla_moe.py`` makes from the seed."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from llm_fine_tune_distributed_tpu.config import str_to_dtype
        from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
        from llm_fine_tune_distributed_tpu.parallel.optimizer import init_opt_state
        from llm_fine_tune_distributed_tpu.parallel.sharding import _validate_spec, param_spec
        from llm_fine_tune_distributed_tpu.train.state import TrainState
        from llm_fine_tune_distributed_tpu.utils.tree import split_by_mask

        mesh = self.mesh
        t0 = time.perf_counter()
        shardings = {
            k: NamedSharding(mesh, _validate_spec(param_spec(k, len(shape)), shape, mesh))
            for k, shape in weights_mla_moe.leaf_shapes(self.cfg).items()
        }
        flat = weights_mla_moe.make_flat(seed, self.cfg, shardings=shardings)
        jax.block_until_ready(flat)
        print(f"set-up: weights from the seed {time.perf_counter() - t0:.1f} s", flush=True)
        params = weights.nest(flat)
        del flat
        trainable, frozen = split_by_mask(params, trainable_mask(params, self.mc, self.tc))
        del params
        p_dtype = str_to_dtype(self.tc.param_dtype)
        trainable = {k: v.astype(p_dtype) for k, v in trainable.items()}
        # float32 zeros from the start, as kind_sft.py makes them and for its reason
        opt_state = jax.jit(lambda tree: jax.tree.map(
            lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x, tree
        ))(init_opt_state(self.optimizer, trainable, mesh))
        weights.drop_programs()
        return TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P())),
            trainable=trainable,
            frozen=frozen,
            opt_state=opt_state,
        )


def reference_readings(cfg, mix, seed, steps, keep_first_grad=False):
    flat = weights_mla_moe.make_flat(seed, cfg)
    batches = [traffic.sft_batch(mix, cfg["vocab_size"], seed, i)["input_ids"] for i in range(steps)]
    return reference_mla_moe.sft_reference(
        flat, cfg, mix["recipe"], batches, lambda names: weights_mla_moe.make_flat(seed, cfg, only=names),
        keep_first_grad=keep_first_grad,
    )


def run(cell, args, harness):
    import jax

    cfg, mix, limits = cell["config"], cell["traffic"], cell["limits"]
    steps = int(limits["steps"])
    t_a = time.perf_counter()
    program = Program(cfg, mix)
    step_fn, put_batch, ledger = program.step_fn, program.put_batch, program.ledger
    state = program.make_state(args.seed)
    jax.block_until_ready(state)
    t_b = time.perf_counter()
    keep = "first_grad_worst_leaf_rel_err" in limits
    state, read = kind_sft.program_readings(program, state, args.seed, steps, keep_first_grad=keep)
    ledger.mark_warm()
    print(f"set-up: state from the seed {t_b - t_a:.1f} s, first {steps} steps with their "
          f"readings (compile or cache load included) {time.perf_counter() - t_b:.1f} s", flush=True)

    tokens_per_step = int(mix["accum"]) * int(mix["microbatch"]) * int(mix["seq_len"])
    vocab = cfg["vocab_size"]
    losses, ends, pairs, skews = [], [], [], []
    harness.start_window()
    t0 = time.perf_counter()
    i = steps
    while time.perf_counter() - t0 < args.seconds:
        with harness.span("feed"):
            batch = put_batch(traffic.sft_batch(mix, vocab, args.seed, i))
        with harness.span("train_step"):
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))  # ends the step: the device is done
        ends.append(time.perf_counter() - t0)
        pairs.append(float(metrics["expert_pairs_per_token"]))
        skews.append(float(metrics["expert_load_max_over_mean"]))
        harness.trace_tick(ends[-1])
        i += 1
    harness.stop_window()
    wall = ends[-1]
    chips = program.mesh.size
    rate = len(ends) * tokens_per_step / wall / chips
    failed = sum(1 for x in losses if not math.isfinite(x))

    from llm_fine_tune_distributed_tpu.ops.attention import dispatch_summary

    print(dispatch_summary(), flush=True)
    memory, held = harness.memory_peak(), harness.memory_held()
    del state, metrics, batch, step_fn
    program.release()
    t_ref = time.perf_counter()
    ref = reference_readings(cfg, mix, args.seed, steps, keep_first_grad=keep)
    print(f"reference: {steps} steps in {time.perf_counter() - t_ref:.1f} s (outside set-up and window)", flush=True)
    checks = kind_sft.compare(read, ref, limits)

    pairs_per_token = sum(pairs) / len(pairs)
    need = flops_mla_moe.train_flops_per_token(cfg, int(mix["seq_len"]), pairs_per_token)
    return {
        "end_to_end": {"train_tokens_per_s": rate},
        "attempted": len(ends),
        "failed": failed,
        "checks": checks,
        "memory_peak_bytes": memory,
        "sources": {
            "kind": "sft",
            "tokens_per_s_per_chip": rate,
            "chips": chips,
            "steps": len(ends),
            "step_ends_s": ends,
            "window_losses": losses,
            "flops_per_token": need,
            "compile_ledger": ledger.snapshot(),
            "memory_peak_bytes": memory,
            "memory_held_bytes": held,
            "seq_len": int(mix["seq_len"]),
            "microbatch": int(mix["microbatch"]),
            "accum": int(mix["accum"]),
            "expert_pairs_per_token": pairs_per_token,
            "expert_load_max_over_mean": max(skews),
        },
    }
