"""Cells of kind ``sft_ssd``: ``kind_sft.py``'s cell (the last layers and the
head trained over a frozen trunk, the program's jitted train step driven
directly, the same readings and comparison) for a ``granitemoehybrid``
configuration (IBM Granite 4.0-H): Mamba-2 state-space layers beside a few GQA
layers without rope, the family's four multipliers, a TIED table, so that the
backward pass crosses every frozen layer on its way to the lookup. What differs
is where the model's shape comes from (``model_config`` below: the program's own
``from_hf_config`` over the configuration file's published keys), who makes the
weights (``weights_ssd.py``), who follows the steps (``reference_ssd.py``), how
the required operations are counted (``flops_ssd.py``), and the window's loop,
which is ``kind_sft_swa_moe.run``'s (one step queued behind the one that runs).

Copied from ``kind_sft_eva.py`` (which copied ``kind_sft.py``) because they name
their own ``model_config``, weights, reference and FLOP count inside:
``Program.__init__``, ``Program.make_state``, ``reference_readings`` and ``run``
(PERF.md lists the copies for a ``benchmark`` issue to fold). Reused by import:
``kind_sft.Program.release`` / ``put_batch``, ``kind_sft.program_readings``,
``kind_sft.compare``, ``kind_sft.RECIPE_KEYS``.

The mix's ``control`` is the dense cells': ``frozen_compute: int8``, the frozen
trunk's projections as int8 x int8 products. A Mamba-2 layer's convolution, its
bias, ``A_log``, ``D``, ``dt_bias`` and the gated norm are no ``kernel`` and stay
out of the int8 trunk.
"""

from __future__ import annotations

import math
import time
from types import SimpleNamespace

from benchmarks.chipbench import flops_ssd, kind_sft, reference_ssd, traffic, weights_ssd

def model_config(cfg: dict):
    from llm_fine_tune_distributed_tpu.models import configs

    if "granite_4_0_h_micro" not in configs.PRESETS:
        raise SystemExit("chipbench: this checkout's program has no state-space layer (models/configs.PRESETS has no "
                         "granite_4_0_h_micro: Mamba-2 mixers beside GQA layers without rope, the embedding, the "
                         "residual adds, the scores and the logits under constant multipliers): it cannot run a cell "
                         "of kind sft_ssd")
    return configs.from_hf_config(SimpleNamespace(**cfg))


def ssd_counters():
    """What ``ops/ssd.py`` counted while this process traced: the scan's calls by form."""
    from llm_fine_tune_distributed_tpu.ops import ssd

    return {str(list(shape)): (n, form) for shape, (n, form) in ssd.CALLS.items()}

class Program(kind_sft.Program):
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
        self.mc = model_config(cfg)  # (refuses a program without the mixer, at once)
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
        if self.tc.frozen_compute == "int8":
            self.frozen_layers = cfg["num_hidden_layers"] - int(recipe["unfreeze_last_n_layers"])
        act = NamedSharding(self.mesh, P(("data", "fsdp"), None, None))
        self.ledger = CompileLedger()
        self.step_fn = instrument(
            "train_step",
            step_mod.jit_train_step(
                step_mod.build_train_step(
                    self.mc, self.tc, self.optimizer, activation_sharding=act, frozen_layers=self.frozen_layers,
                ),
                mesh=self.mesh,
            ),
            self.ledger,
        )
        self._batch_sharding = NamedSharding(self.mesh, P(None, ("data", "fsdp")))
        self._jax = jax

    def make_state(self, seed: int):
        """``kind_sft.Program.make_state`` over weights that ``weights_ssd.py`` makes from the seed."""
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
            for k, shape in weights_ssd.leaf_shapes(self.cfg).items()
        }
        flat = weights_ssd.make_flat(seed, self.cfg, shardings=shardings)
        jax.block_until_ready(flat)
        print(f"set-up: weights from the seed {time.perf_counter() - t0:.1f} s", flush=True)
        params = weights_ssd.nest(flat)
        del flat
        trainable, frozen = split_by_mask(params, trainable_mask(params, self.mc, self.tc))
        del params
        if self.frozen_layers > 0:
            frozen, _ = quantize_trunk_int8(frozen, self.frozen_layers)
        p_dtype = str_to_dtype(self.tc.param_dtype)
        trainable = {k: v.astype(p_dtype) for k, v in trainable.items()}
        # float32 zeros from the start, as kind_sft.py makes them and for its reason
        opt_state = jax.jit(lambda tree: jax.tree.map(
            lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x, tree
        ))(init_opt_state(self.optimizer, trainable, mesh))
        weights_ssd.drop_programs()
        return TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P())),
            trainable=trainable,
            frozen=frozen,
            opt_state=opt_state,
        )


def reference_readings(cfg, mix, seed, steps, keep_first_grad=False):
    flat = weights_ssd.make_flat(seed, cfg)
    batches = [traffic.sft_batch(mix, cfg["vocab_size"], seed, i)["input_ids"] for i in range(steps)]
    return reference_ssd.sft_reference(
        flat, cfg, mix["recipe"], batches, lambda names: weights_ssd.make_flat(seed, cfg, only=names),
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
    losses, ends = [], []
    stage = lambda step: put_batch(traffic.sft_batch(mix, vocab, args.seed, step))  # noqa: E731
    batch, i = stage(steps), steps
    harness.start_window()
    t0 = time.perf_counter()
    # kind_sft_swa_moe.run's loop: one step is always queued behind the one that runs, each step still ends by
    # fetching its loss; the step that ends past --seconds is the last one counted, the one queued behind it is
    # drained outside the measured time.
    in_flight = step_fn(state, batch)
    while True:
        with harness.span("train_step"):
            state, metrics = in_flight
            with harness.span("feed"):
                i += 1
                in_flight = step_fn(state, stage(i))
            losses.append(float(metrics["loss"]))  # ends the step: the device is done with it
        ends.append(time.perf_counter() - t0)
        harness.trace_tick(ends[-1])
        if ends[-1] >= args.seconds:
            break
    harness.stop_window()
    state, metrics = in_flight
    float(metrics["loss"])  # the queued step, uncounted
    wall = ends[-1]
    chips = program.mesh.size
    rate = len(ends) * tokens_per_step / wall / chips
    failed = sum(1 for x in losses if not math.isfinite(x))

    from llm_fine_tune_distributed_tpu.models.transformer import remat_summary
    from llm_fine_tune_distributed_tpu.ops.attention import dispatch_summary
    from llm_fine_tune_distributed_tpu.ops.ssd import calls_summary
    from llm_fine_tune_distributed_tpu.ops.rope import calls_summary as in_pass_summary

    for said in (dispatch_summary(), calls_summary(), in_pass_summary(), remat_summary()):
        print(said, flush=True)
    took = sorted(b - a for a, b in zip([0.0] + ends, ends))
    print(f"window: {len(ends)} steps, median {1e3 * took[len(took) // 2]:.1f} ms, longest {1e3 * took[-1]:.1f} ms "
          "(a stalled run shows here)", flush=True)
    calls = ssd_counters()
    memory, held = harness.memory_peak(), harness.memory_held()
    del state, metrics, in_flight, batch, step_fn
    program.release()
    t_ref = time.perf_counter()
    ref = reference_readings(cfg, mix, args.seed, steps, keep_first_grad=keep)
    print(f"reference: {steps} steps in {time.perf_counter() - t_ref:.1f} s (outside set-up and window)", flush=True)
    checks = kind_sft.compare(read, ref, limits)

    need = flops_ssd.recipe_train_flops_per_token(cfg, mix["recipe"], int(mix["seq_len"]))
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
            "ssd_calls": calls,
        },
    }
