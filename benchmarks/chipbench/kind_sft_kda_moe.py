"""Cells of kind ``sft_kda_moe``: ``kind_sft_gdn_moe.py``'s cell for a
``kimi_linear`` configuration (moonshotai Kimi-Linear-48B-A3B): Kimi Delta
Attention layers (a delta rule with a decay a channel behind low-rank gates)
beside latent-attention layers without rope, a leading dense layer, then
routed experts behind a sigmoid router with a selection bias beside one shared
expert, every parameter trainable but that bias. The program's jitted train
step is driven directly, by the same readings and comparison and by
``kind_sft_swa_moe.run``'s loop (one step queued behind the one that runs).
What differs is where the model's shape comes from (``model_config`` below:
the program's own ``from_hf_config`` over the configuration file's published
keys, then the chip's share), who makes the weights (``weights_kda_moe.py``),
who follows the steps (``reference_kda_moe.py``) and how the required
operations are counted (``flops_kda_moe.py``).

Copied from ``kind_sft_gdn_moe.py`` because they name its own
``model_config``, weights, reference and FLOP count inside:
``Program.__init__``, ``Program.make_state``, ``reference_readings`` and
``run`` (PERF.md lists the now six copies for a ``benchmark`` issue to fold:
those four as parameters of ``kind_sft.Program`` and ``kind_sft.run``). Reused
by import: ``kind_sft.Program.release``/``put_batch``,
``kind_sft.program_readings``, ``kind_sft.compare``, ``kind_sft.RECIPE_KEYS``,
``kind_sft_gdn_moe.gdn_calls`` (the forms the rule was traced in, for
``readers/gdn.py``).

The mix's ``control`` sets ``router_dtype`` (``ops/moe.ROUTER_DTYPE``: the
sigmoid router's product, scores, selection and combine weights in
``float8_e4m3fn``, the Moonlight cell's control) and ``state_dtype``
(``ops/gated_delta.STATE_DTYPE``: the state the rule carries from chunk to
chunk in bfloat16, the Qwen3-Next cell's): the program's own paths at a
precision below the one the configuration states. They are keys of the
benchmark's mix, not options of the program.
"""

from __future__ import annotations

import math
import time

from types import SimpleNamespace

from benchmarks.chipbench import flops_kda_moe, kind_sft, reference_kda_moe, traffic, weights, weights_kda_moe
from benchmarks.chipbench.kind_sft_gdn_moe import gdn_calls

# keys of the configuration file that are the cut's and the benchmark's, not the source's
NOT_THE_SOURCES = ("router_experts", "held_experts", "n_routed_experts")


def model_config(cfg: dict):
    from llm_fine_tune_distributed_tpu.models import configs

    if "kimi_linear_48b_a3b" not in configs.PRESETS:
        raise SystemExit("chipbench: this checkout's program has no Kimi Delta Attention mixer (models/configs.PRESETS "
                         "has no kimi_linear_48b_a3b: a delta rule with a decay a channel beside latent attention "
                         "without rope): it cannot run a cell of kind sft_kda_moe")
    source = {k: v for k, v in cfg.items() if k not in NOT_THE_SOURCES}
    source["num_experts"] = cfg["router_experts"]  # (in the file it counts the experts held here)
    published = configs.from_hf_config(SimpleNamespace(**source))
    # the chip's share: the router keeps the published width, the stacked leaves hold the experts held here
    return published.replace(held_experts=tuple(cfg["held_experts"]))


class Program(kind_sft.Program):
    def __init__(self, cfg: dict, mix: dict):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
        from llm_fine_tune_distributed_tpu.observe.xla import CompileLedger, instrument
        from llm_fine_tune_distributed_tpu.parallel.optimizer import build_optimizer
        from llm_fine_tune_distributed_tpu.runtime.mesh import data_parallel_size, make_mesh
        from llm_fine_tune_distributed_tpu.train import step as step_mod

        recipe = mix["recipe"]
        if recipe["freeze_strategy"] != "none":
            raise SystemExit("chipbench sft_kda_moe cells train every parameter")
        self.cfg, self.mix = cfg, mix
        self.mc = model_config(cfg)  # (refuses a program without the mixer, at once)
        from llm_fine_tune_distributed_tpu.ops import gated_delta, moe

        moe.ROUTER_DTYPE = jnp.dtype(mix.get("router_dtype", "float32"))          # the control's two changes
        gated_delta.STATE_DTYPE = jnp.dtype(mix.get("state_dtype", "float32"))
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
        """As ``kind_sft_swa_moe.Program.make_state``: the freeze split (the
        selection bias is a buffer), master dtype and float32 Adam zeros of the
        trainer's state, over weights that ``weights_kda_moe.py`` makes from the seed."""
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
            for k, shape in weights_kda_moe.leaf_shapes(self.cfg).items()
        }
        flat = weights_kda_moe.make_flat(seed, self.cfg, shardings=shardings)
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
    flat = weights_kda_moe.make_flat(seed, cfg)
    batches = [traffic.sft_batch(mix, cfg["vocab_size"], seed, i)["input_ids"] for i in range(steps)]
    return reference_kda_moe.sft_reference(
        flat, cfg, mix["recipe"], batches, lambda names: weights_kda_moe.make_flat(seed, cfg, only=names),
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
    losses, ends, counters = [], [], []
    stage = lambda step: put_batch(traffic.sft_batch(mix, vocab, args.seed, step))  # noqa: E731
    batch, i = stage(steps), steps
    harness.start_window()
    t0 = time.perf_counter()
    # kind_sft_swa_moe.run's loop: one step is always queued behind the one that runs, each step still ends by
    # fetching its loss, the two counters stay on the device until the window is over; the step that ends past
    # --seconds is the last one counted, the one queued behind it is drained outside the measured time.
    in_flight = step_fn(state, batch)
    while True:
        with harness.span("train_step"):
            state, metrics = in_flight
            with harness.span("feed"):
                i += 1
                in_flight = step_fn(state, stage(i))
            losses.append(float(metrics["loss"]))  # ends the step: the device is done with it
        ends.append(time.perf_counter() - t0)
        counters.append((metrics["expert_pairs_per_token"], metrics["expert_load_max_over_mean"]))
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

    from llm_fine_tune_distributed_tpu.ops.attention import dispatch_summary
    from llm_fine_tune_distributed_tpu.ops.gated_delta import calls_summary

    print(dispatch_summary(), flush=True)
    print(calls_summary(), flush=True)
    took = sorted(b - a for a, b in zip([0.0] + ends, ends))
    print(f"window: {len(ends)} steps, median {1e3 * took[len(took) // 2]:.1f} ms, longest {1e3 * took[-1]:.1f} ms "
          "(a stalled run shows here)", flush=True)
    traced = gdn_calls()
    pairs, skews = ([float(c[k]) for c in counters] for k in (0, 1))
    memory, held = harness.memory_peak(), harness.memory_held()
    del state, metrics, in_flight, batch, step_fn, counters
    program.release()
    t_ref = time.perf_counter()
    ref = reference_readings(cfg, mix, args.seed, steps, keep_first_grad=keep)
    # in tens: the reference walks every row token by token, it is in every run's wall time and in neither metric
    print(f"reference: {steps} steps in {time.perf_counter() - t_ref:.1f} s (outside set-up and window)", flush=True)
    checks = kind_sft.compare(read, ref, limits)

    pairs_per_token = sum(pairs) / len(pairs)
    need = flops_kda_moe.train_flops_per_token(cfg, int(mix["seq_len"]), pairs_per_token)
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
            "gdn_calls": traced,
        },
    }
