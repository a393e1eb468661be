"""Cells of kind ``sft``: the program's jitted train step, driven directly.

The state is built as ``bench.py:build`` builds it (same freeze split,
shardings, optimizer and ``jit_train_step``), except that the weights come
from ``weights.py``. Set-up builds ONE compiled step with its state, drives it
through the first ``steps`` optimizer steps of the seed's feed (these are also
the warm-up: optax's moments turn float32 after the first update, so the step
has two signatures and both compile here), keeps what the check compares, and
hands the same object to the window.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.chipbench import check, flops, reference, traffic, weights

RECIPE_KEYS = (
    "learning_rate", "param_dtype", "compute_dtype", "remat_policy", "attention_impl",
    "gradient_checkpointing", "freeze_strategy", "unfreeze_last_n_layers", "optimizer",
    "weight_decay", "adam_b1", "adam_b2", "adam_eps", "max_grad_norm", "lr_schedule",
    "warmup_ratio", "frozen_compute", "loss_chunk_size", "loss_vocab_chunk",
)


def model_config(cfg: dict):
    from llm_fine_tune_distributed_tpu.config import ModelConfig

    n = cfg["num_hidden_layers"]
    interval = cfg.get("no_rope_layer_interval") or 0
    return ModelConfig(
        name=cfg.get("model_type", "chipbench"),
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=n,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        no_rope_layers=tuple(0 if interval and (i + 1) % interval == 0 else 1 for i in range(n)),
        sliding_window=cfg.get("sliding_window"),
    )


class Program:
    """The program's pieces for one cell: the mesh, the jitted step behind
    the program's own compile ledger, and the state for a seed."""

    def __init__(self, cfg: dict, mix: dict):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
        from llm_fine_tune_distributed_tpu.observe.xla import CompileLedger, instrument
        from llm_fine_tune_distributed_tpu.parallel.optimizer import build_optimizer
        from llm_fine_tune_distributed_tpu.runtime.mesh import data_parallel_size, make_mesh
        from llm_fine_tune_distributed_tpu.train import step as step_mod

        recipe = mix["recipe"]
        self.cfg, self.mix = cfg, mix
        self.mc = model_config(cfg)
        self.tc = TrainConfig(
            model_preset=None,
            per_device_batch_size=int(mix["microbatch"]),
            gradient_accumulation_steps=int(mix["accum"]),
            max_seq_length=int(mix["seq_len"]),
            **{k: recipe[k] for k in RECIPE_KEYS if k in recipe},
        )
        self.mesh = make_mesh(MeshConfig(data=1, fsdp=-1, tensor=1, seq=1))
        if data_parallel_size(self.mesh) != 1:
            raise SystemExit("chipbench sft cells are written for one chip")
        self.optimizer = build_optimizer(
            self.tc, None, total_steps=int(recipe["total_steps"]), data_parallel_size=1
        )
        self.frozen_layers = 0
        if self.tc.frozen_compute == "int8":
            self.frozen_layers = cfg["num_hidden_layers"] - int(recipe["unfreeze_last_n_layers"])
        act = NamedSharding(self.mesh, P(("data", "fsdp"), None, None))
        self.ledger = CompileLedger()
        self.step_fn = instrument(
            "train_step",
            step_mod.jit_train_step(
                step_mod.build_train_step(
                    self.mc, self.tc, self.optimizer, activation_sharding=act,
                    frozen_layers=self.frozen_layers,
                ),
                mesh=self.mesh,
            ),
            self.ledger,
        )
        self._batch_sharding = NamedSharding(self.mesh, P(None, ("data", "fsdp")))
        self._jax = jax

    def release(self) -> None:
        """Unload the step's program: the chip keeps its scratch reserved for
        as long as it is loaded, and the reference needs the room."""
        self.step_fn = None
        self._jax.clear_caches()

    def put_batch(self, batch):
        return {k: self._jax.device_put(v, self._batch_sharding) for k, v in batch.items()}

    def make_state(self, seed: int):
        """The train state as ``bench.py:build`` lays it out, over weights
        that ``weights.py`` makes from the seed."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from llm_fine_tune_distributed_tpu.config import str_to_dtype
        from llm_fine_tune_distributed_tpu.parallel.freeze import quantize_trunk_int8, trainable_mask
        from llm_fine_tune_distributed_tpu.parallel.optimizer import init_opt_state
        from llm_fine_tune_distributed_tpu.parallel.sharding import _validate_spec, param_spec
        from llm_fine_tune_distributed_tpu.train.state import TrainState
        from llm_fine_tune_distributed_tpu.utils.tree import split_by_mask

        mesh = self.mesh
        t0 = time.perf_counter()
        shardings = {
            k: NamedSharding(mesh, _validate_spec(param_spec(k, len(shape)), shape, mesh))
            for k, shape in weights.leaf_shapes(self.cfg).items()
        }
        flat = weights.make_flat(seed, self.cfg, shardings=shardings)
        jax.block_until_ready(flat)
        print(f"set-up: weights from the seed {time.perf_counter() - t0:.1f} s", flush=True)
        params = weights.nest(flat)
        del flat
        trainable, frozen = split_by_mask(params, trainable_mask(params, self.mc, self.tc))
        del params
        if self.frozen_layers > 0:
            frozen, _ = quantize_trunk_int8(frozen, self.frozen_layers)
        p_dtype = str_to_dtype(self.tc.param_dtype)
        trainable = {k: v.astype(p_dtype) for k, v in trainable.items()}
        # optax keeps Adam's moments in the gradients' float32 from the first
        # update on, whatever type init gave them: a state built as the trainer
        # builds it (bfloat16 zeros) runs step 1 through a program of its own
        # whose float32 moments cannot reuse the donated buffers, and the chip
        # cannot hold that program beside the steady one (PERF.md section 6).
        # The zeros are therefore float32 from the start: the state every step
        # after the first sees, and one program.
        opt_state = jax.jit(lambda tree: jax.tree.map(
            lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x, tree
        ))(init_opt_state(self.optimizer, trainable, mesh))
        weights.drop_programs()  # the chip keeps room for every loaded program
        return TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P())),
            trainable=trainable,
            frozen=frozen,
            opt_state=opt_state,
        )


def _adam_mu(opt_state):
    """The first-moment tree of optax's Adam inside the optimizer's state."""
    found = []

    def walk(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(opt_state)
    if len(found) != 1:
        raise RuntimeError("expected one Adam state in the optimizer's state")
    return found[0]


def _norms(tree):
    import jax
    import jax.numpy as jnp

    out = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in t.items()})(tree)
    return {k: float(v) for k, v in out.items()}


def _host_f32(tree):
    return {k: np.asarray(v).astype(np.float32) for k, v in tree.items()}


def program_readings(program, state, seed, steps, keep_first_grad=False):
    """Drive the compiled step through the seed's first ``steps`` batches by
    the window's own call and feed; returns (state, readings). The trainable
    leaves are copied to the host before and after (0.8 to 1.1 GB each way):
    beside the step's program the chip has no room for a second copy."""
    cfg, mix, step_fn, put_batch = program.cfg, program.mix, program.step_fn, program.put_batch
    recipe = mix["recipe"]
    vocab = cfg["vocab_size"]
    before = _host_f32(state.trainable)
    read = {"losses": []}
    for i in range(steps):
        state, metrics = step_fn(state, put_batch(traffic.sft_batch(mix, vocab, seed, i)))
        read["losses"].append(float(metrics["loss"]))
        if i == 0:
            read["grad_norm"] = float(metrics["grad_norm"])
            scale = 1.0 / (1.0 - float(recipe["adam_b1"]))
            mu = _adam_mu(state.opt_state)
            read["first_grad_norms"] = {k: v * scale for k, v in _norms(mu).items()}
            if keep_first_grad:  # to the host: the device has no room for a copy
                read["first_grad"] = {k: np.asarray(v, np.float32) * scale for k, v in mu.items()}
    after = _host_f32(state.trainable)
    read["delta_norms"] = {k: float(np.linalg.norm((after[k] - before[k]).ravel())) for k in after}
    return state, read


def reference_readings(cfg, mix, seed, steps, keep_first_grad=False):
    flat = weights.make_flat(seed, cfg)
    batches = [traffic.sft_batch(mix, cfg["vocab_size"], seed, i)["input_ids"] for i in range(steps)]
    return reference.sft_reference(
        flat, cfg, mix["recipe"], batches, lambda names: weights.make_flat(seed, cfg, only=names),
        keep_first_grad=keep_first_grad,
    )


def compare(read: dict, ref: dict, limits: dict) -> check.Checks:
    checks = check.Checks()
    for i, (got, want) in enumerate(zip(read["losses"], ref["losses"])):
        checks.add(f"loss_step{i + 1}_abs_gap", abs(got - want), limits["loss_abs_gap"],
                   f"program {got!r} reference {want!r}")
    checks.add("grad_norm_rel_gap", abs(read["grad_norm"] - ref["grad_norm"]) / ref["grad_norm"],
               limits["grad_norm_rel_gap"], f"program {read['grad_norm']!r} reference {ref['grad_norm']!r}")
    gap, where = check.worst_leaf_gap(read["first_grad_norms"], ref["first_grad_norms"])
    checks.add("first_grad_worst_leaf_gap", gap, limits["first_grad_worst_leaf_gap"], f"at {where}")
    if "first_grad_worst_leaf_rel_err" in limits:
        err, where = check.worst_leaf_rel_err(read["first_grad"], ref["first_grad"], ref["first_grad_norms"])
        checks.add("first_grad_worst_leaf_rel_err", err, limits["first_grad_worst_leaf_rel_err"], f"at {where}")
    gap, where = check.worst_leaf_gap(read["delta_norms"], ref["delta_norms"])
    checks.add("param_change_worst_leaf_gap", gap, limits["param_change_worst_leaf_gap"], f"at {where}")
    return checks


def run(cell, args, harness):

    cfg, mix, limits = cell["config"], cell["traffic"], cell["limits"]
    steps = int(limits["steps"])
    t_a = time.perf_counter()
    program = Program(cfg, mix)
    step_fn, put_batch, ledger = program.step_fn, program.put_batch, program.ledger
    state = program.make_state(args.seed)
    import jax
    jax.block_until_ready(state)
    t_b = time.perf_counter()
    keep = "first_grad_worst_leaf_rel_err" in limits
    state, read = program_readings(program, state, args.seed, steps, keep_first_grad=keep)
    ledger.mark_warm()
    print(f"set-up: state from the seed {t_b - t_a:.1f} s, first {steps} steps with their "
          f"readings (compile or cache load included) {time.perf_counter() - t_b:.1f} s", flush=True)

    tokens_per_step = int(mix["accum"]) * int(mix["microbatch"]) * int(mix["seq_len"])
    vocab = cfg["vocab_size"]
    losses, ends = [], []
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
        harness.trace_tick(ends[-1])
        i += 1
    harness.stop_window()
    wall = ends[-1]
    chips = program.mesh.size
    rate = len(ends) * tokens_per_step / wall / chips
    failed = sum(1 for x in losses if not math.isfinite(x))

    memory, held = harness.memory_peak(), harness.memory_held()
    del state, metrics, batch, step_fn
    program.release()
    t_ref = time.perf_counter()
    ref = reference_readings(cfg, mix, args.seed, steps, keep_first_grad=keep)
    print(f"reference: {steps} steps in {time.perf_counter() - t_ref:.1f} s (outside set-up and window)", flush=True)
    checks = compare(read, ref, limits)

    need = flops.recipe_train_flops_per_token(cfg, mix["recipe"], int(mix["seq_len"]))
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
        },
    }
