"""Pipeline parallelism wired into SFTTrainer: a `pipe` mesh
axis trains end-to-end with loss parity against the flat mesh, composes with
data parallelism, honors the freezing policy via the per-layer gradient
mask, and exports the identical per-layer artifact contract."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
from llm_fine_tune_distributed_tpu.parallel.pipeline import (
    STACKED_PREFIX,
    bubble_fraction,
    layer_trainable_vector,
    stack_flat_layer_leaves,
    unstack_flat_layer_leaves,
)

from tests.test_train_e2e import make_config, qa_parquet  # noqa: F401 (fixture)


def test_stack_unstack_roundtrip():
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

    mc = get_preset("tiny")
    flat = flatten_dict(init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32))
    stacked = stack_flat_layer_leaves(flat, mc.num_layers)
    stacked_keys = [k for k in stacked if k.startswith(STACKED_PREFIX)]
    assert stacked_keys, "no stacked leaves produced"
    for k in stacked_keys:
        assert stacked[k].shape[0] == mc.num_layers
    back = unstack_flat_layer_leaves(stacked)
    assert set(back) == set(flat)
    for k in flat:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(flat[k]))


def test_layer_trainable_vector_last_two():
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

    mc = get_preset("tiny")  # 4 layers
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    cfg = TrainConfig(model_preset="tiny")  # default last-2+head freezing
    vec = layer_trainable_vector(flatten_dict(trainable_mask(params, mc, cfg)), mc.num_layers)
    np.testing.assert_array_equal(np.asarray(vec), [0.0, 0.0, 1.0, 1.0])


def test_bubble_fraction():
    assert bubble_fraction(1, 4) == pytest.approx(3 / 4)
    assert bubble_fraction(8, 2) == pytest.approx(1 / 9)
    assert bubble_fraction(16, 1) == 0.0


def test_schedule_tick_count():
    """The compiled schedule is a scan of exactly M + S - 1 ticks (the GPipe
    timetable) — pinned so a schedule regression is loud."""
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.parallel.pipeline import (
        pipeline_forward,
        stack_stage_params,
        stage_sharding,
    )
    from jax.sharding import Mesh

    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("pipe",))
    stacked = jax.device_put(stack_stage_params(params, mc, 2), stage_sharding(mesh))
    ids = jnp.zeros((4, 16), jnp.int32)  # M=4 microbatches of 1
    jaxpr = str(
        jax.make_jaxpr(
            lambda p, st, i: pipeline_forward(
                p, st, i, mc, mesh, 4, compute_dtype=jnp.float32
            )
        )(params, stacked, ids)
    )
    M, S = 4, 2
    assert f"length={M + S - 1}" in jaxpr, "GPipe timetable length changed"


@pytest.mark.slow
def test_pipe_trainer_e2e_loss_parity(qa_parquet, tmp_path):  # noqa: F811
    """MESH_PIPE-style run: same tiny recipe on (a) a flat 1-device mesh and
    (b) a pipe=4 mesh; first-step loss agrees (same init, same data), both
    decrease, and the pipeline's exported best_model/ has the same per-layer
    safetensors contract."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet

    flat_cfg = make_config(
        tmp_path / "flat", data_dir, dataset_file,
        epochs=1,
        mesh=MeshConfig(data=1, fsdp=1, tensor=1, seq=1),
    )
    pipe_cfg = make_config(
        tmp_path / "pipe", data_dir, dataset_file,
        epochs=1,
        mesh=MeshConfig(data=1, fsdp=1, tensor=1, seq=1, pipe=4),
    )

    flat = SFTTrainer(flat_cfg)
    flat_summary = flat.train()
    pipe = SFTTrainer(pipe_cfg)
    pipe_summary = pipe.train()

    flat_losses = [h["loss"] for h in flat.metrics.history if "loss" in h]
    pipe_losses = [h["loss"] for h in pipe.metrics.history if "loss" in h]
    assert len(flat_losses) >= 3 and len(pipe_losses) >= 3
    # same initial params + same first batch: the first logged loss must
    # agree up to the mean-of-means vs global-token-mean difference
    assert pipe_losses[0] == pytest.approx(flat_losses[0], rel=2e-2)
    assert pipe_losses[-1] < pipe_losses[0], "pipeline run did not learn"
    # end-of-training losses in the same neighborhood
    assert pipe_losses[-1] == pytest.approx(flat_losses[-1], rel=0.15)
    assert np.isfinite(pipe_summary["final_train_loss"])

    # artifact contract identical to the flat run (per-layer keys, no
    # @stacked leak)
    from safetensors import safe_open

    def keys(out_dir):
        with safe_open(
            os.path.join(out_dir, "best_model", "model.safetensors"), "np"
        ) as f:
            return set(f.keys())

    k_flat, k_pipe = keys(str(tmp_path / "flat")), keys(str(tmp_path / "pipe"))
    assert k_flat == k_pipe
    assert not any("@stacked" in k for k in k_pipe)

    # freezing parity: frozen layers (0, 1) bit-identical to init in the
    # exported pipeline model is covered by test_train_e2e for the flat
    # path; here assert the summary reports the same trainable fraction
    assert pipe_summary["trainable_params"] == flat_summary["trainable_params"]


@pytest.mark.slow
def test_pipe_composes_with_dp(qa_parquet, tmp_path):  # noqa: F811
    """pipe=2 x fsdp=2 mesh: microbatch columns shard over fsdp inside the
    schedule; training runs and learns."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    cfg = make_config(
        tmp_path / "pipedp", data_dir, dataset_file,
        epochs=1,
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1, pipe=2),
    )
    trainer = SFTTrainer(cfg)
    summary = trainer.train()
    losses = [h["loss"] for h in trainer.metrics.history if "loss" in h]
    assert losses[-1] < losses[0]
    assert np.isfinite(summary["final_train_loss"])


def test_pipe_rejects_unsupported_combos(qa_parquet, tmp_path):  # noqa: F811
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    for bad in (
        {"packing": True},
        # ring/ulysses compose with pipe — but not on MoE presets
        {"attention_impl": "ring", "model_preset": "tiny_moe",
         "freeze_strategy": "none"},
        {"attention_impl": "ulysses", "model_preset": "tiny_moe",
         "freeze_strategy": "none"},
        # Gemma2's local/global window alternation needs per-layer masks the
        # pipeline layer-scan cannot express
        {"model_preset": "tiny_gemma2", "freeze_strategy": "none"},
    ):
        cfg = make_config(
            tmp_path / "bad", data_dir, dataset_file,
            mesh=MeshConfig(data=1, fsdp=1, tensor=1, seq=1, pipe=2),
            **bad,
        )
        with pytest.raises(ValueError, match="pipe mesh axis"):
            SFTTrainer(cfg)


@pytest.mark.parametrize("preset, impl, differ", [
    ("tiny_mla_moe", "xla", "feed_forward ('dense' vs 'grouped_experts')"),  # a leading dense layer
    ("tiny_gemma2", "xla", "window (8 vs None)"),                            # alternating windows
    ("tiny_moe", "ring", "capacity experts"),                               # MoE under a sequence axis
])
def test_layer_scan_rule_is_one_and_raised_alike(preset, impl, differ, qa_parquet, tmp_path):  # noqa: F811
    """What the schedule's layer scan asks of a model is said once
    (``layer_scan_problems``, from ``ModelConfig.layer``) and raised by
    ``pipeline_forward`` and by the trainer in the same words."""
    from jax.sharding import Mesh

    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.parallel.pipeline import layer_scan_problems, pipeline_forward
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    mc = get_preset(preset)
    seq_parallel = impl != "xla"
    (problem,) = layer_scan_problems(mc, seq_parallel)
    assert differ in problem
    assert layer_scan_problems(get_preset("tiny"), True) == []  # NoPE layers differ in rope alone: data

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pipe", "seq"))
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    with pytest.raises(ValueError) as from_schedule:
        pipeline_forward(params, None, jnp.zeros((2, 16), jnp.int32), mc, mesh, 2, attention_impl=impl)
    assert problem in str(from_schedule.value)

    data_dir, dataset_file = qa_parquet
    cfg = make_config(
        tmp_path / "bad", data_dir, dataset_file, model_preset=preset, freeze_strategy="none",
        attention_impl=impl, mesh=MeshConfig(data=1, fsdp=1, tensor=1, seq=1, pipe=2),
    )
    with pytest.raises(ValueError, match="pipe mesh axis") as from_trainer:
        SFTTrainer(cfg)
    assert problem in str(from_trainer.value)


def test_pipeline_state_split_lora():
    """Under LoRA, only adapters are trainable in pipe mode: stacked base
    kernels land in `frozen` (no optimizer state, like the flat path) and the
    per-layer mask is all-ones (every layer has trainable adapters)."""
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
    from llm_fine_tune_distributed_tpu.parallel.lora import add_lora_params
    from llm_fine_tune_distributed_tpu.parallel.pipeline import (
        build_pipeline_state_leaves,
    )
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict, split_by_mask

    mc = get_preset("tiny")
    cfg = TrainConfig(model_preset="tiny", freeze_strategy="lora")
    params = add_lora_params(params=init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32), rng=jax.random.PRNGKey(1))
    mask = trainable_mask(params, mc, cfg)
    trainable, frozen = split_by_mask(params, mask)
    t, f, vec = build_pipeline_state_leaves(
        trainable, frozen, flatten_dict(mask), mc.num_layers
    )
    stacked_t = [k for k in t if k.startswith(STACKED_PREFIX)]
    assert stacked_t and all(k.endswith(("lora_a", "lora_b")) for k in stacked_t)
    assert any(k.endswith("/kernel") for k in f if k.startswith(STACKED_PREFIX))
    assert any(k.endswith("lora_scale") for k in f if k.startswith(STACKED_PREFIX))
    np.testing.assert_array_equal(np.asarray(vec), np.ones(mc.num_layers))


@pytest.mark.slow
def test_pipe_lora_loss_parity(qa_parquet, tmp_path):  # noqa: F811
    """pipe=2 x LoRA trains with loss parity vs the flat LoRA run, keeps the
    optimizer state at adapter size, and exports the PEFT adapter +
    merged model exactly like the flat path."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    flat_cfg = make_config(
        tmp_path / "flat", data_dir, dataset_file,
        epochs=1, freeze_strategy="lora",
        mesh=MeshConfig(data=1, fsdp=1, tensor=1, seq=1),
    )
    pipe_cfg = make_config(
        tmp_path / "pipe", data_dir, dataset_file,
        epochs=1, freeze_strategy="lora",
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1, pipe=2),
    )
    flat = SFTTrainer(flat_cfg)
    flat_summary = flat.train()
    pipe = SFTTrainer(pipe_cfg)
    pipe_summary = pipe.train()

    flat_losses = [h["loss"] for h in flat.metrics.history if "loss" in h]
    pipe_losses = [h["loss"] for h in pipe.metrics.history if "loss" in h]
    assert pipe_losses[0] == pytest.approx(flat_losses[0], rel=2e-2)
    assert pipe_losses[-1] < pipe_losses[0], "pipe x lora did not learn"
    assert pipe_summary["trainable_params"] == flat_summary["trainable_params"]

    # optimizer state covers ONLY adapter leaves (the LoRA memory win)
    assert all(
        k.endswith(("lora_a", "lora_b")) for k in pipe.state.trainable
    ), sorted(pipe.state.trainable)[:5]
    # adapter + merged exports both present, no stacked leak
    assert (tmp_path / "pipe" / "adapter" / "adapter_model.safetensors").exists()
    from safetensors import safe_open

    with safe_open(
        os.path.join(tmp_path / "pipe", "best_model", "model.safetensors"), "np"
    ) as f:
        keys = set(f.keys())
    assert not any("@stacked" in k or "lora" in k for k in keys)


@pytest.mark.slow
def test_pipe_qlora_trains(qa_parquet, tmp_path):  # noqa: F811
    """pipe=2 x QLoRA: stacked [L, in, out] base kernels quantize to NF4
    (packed along the per-layer in dim), training learns, and the export
    decodes back to plain per-layer bf16 safetensors."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    cfg = make_config(
        tmp_path / "qlora_pipe", data_dir, dataset_file,
        epochs=1, freeze_strategy="qlora",
        mesh=MeshConfig(data=1, fsdp=1, tensor=1, seq=1, pipe=2),
    )
    trainer = SFTTrainer(cfg)
    summary = trainer.train()
    # the stacked frozen base really is NF4 at rest
    assert any(k.endswith("kernel_nf4") for k in trainer.state.frozen)
    losses = [h["loss"] for h in trainer.metrics.history if "loss" in h]
    assert losses[-1] < losses[0], f"no learning: {losses[0]} -> {losses[-1]}"
    assert np.isfinite(summary["final_train_loss"])
    from safetensors import safe_open

    with safe_open(
        os.path.join(tmp_path / "qlora_pipe", "best_model", "model.safetensors"),
        "np",
    ) as f:
        keys = set(f.keys())
    assert not any("@stacked" in k or "nf4" in k or "lora" in k for k in keys)


@pytest.mark.slow
def test_pipe_qlora_moe_quantizes_experts(qa_parquet, tmp_path):  # noqa: F811
    """qlora x pipe x MoE: the pipe-stacked 4-D expert
    weights — the dominant bytes of an MoE model — are NF4 at rest, training
    learns through the dequantizing stage scan, and the export decodes back
    to plain safetensors."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    cfg = make_config(
        tmp_path / "qlora_moe_pipe", data_dir, dataset_file,
        epochs=1,
        model_preset="tiny_moe",
        freeze_strategy="qlora",
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1, expert=2, pipe=2),
    )
    trainer = SFTTrainer(cfg)
    # expert leaves are NF4 at rest, with the [L, E, ...] layout the
    # schedule's per-layer scan slices, sharded over pipe AND expert
    expert_nf4 = [
        k for k in trainer.state.frozen
        if "/experts/" in k and k.endswith("_nf4")
    ]
    assert expert_nf4, "pipe-stacked experts were not quantized"
    for k in expert_nf4:
        leaf = trainer.state.frozen[k]
        assert leaf.ndim == 4, (k, leaf.shape)
        spec = leaf.sharding.spec
        assert spec[0] == "pipe" and spec[1] == "expert", (k, spec)
    # no bf16 expert weight remains
    assert not any(
        k.endswith(("w1", "w2", "w3")) for k in trainer.state.frozen
        if "/experts/" in k
    )
    summary = trainer.train()
    losses = [h["loss"] for h in trainer.metrics.history if "loss" in h]
    assert losses[-1] < losses[0], f"no learning: {losses[0]} -> {losses[-1]}"
    assert np.isfinite(summary["final_train_loss"])
    from safetensors import safe_open

    with safe_open(
        os.path.join(tmp_path / "qlora_moe_pipe", "best_model", "model.safetensors"),
        "np",
    ) as f:
        keys = set(f.keys())
    assert not any("@stacked" in k or "nf4" in k or "lora" in k for k in keys)
    assert any("experts" in k for k in keys)


@pytest.mark.slow
def test_pipe_trainer_moe(qa_parquet, tmp_path):  # noqa: F811
    """MoE + pipeline at the TRAINER level: stacked expert leaves shard over
    pipe, router aux rides the schedule, training learns."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    cfg = make_config(
        tmp_path / "moe_pipe", data_dir, dataset_file,
        epochs=1,
        model_preset="tiny_moe",
        freeze_strategy="none",
        mesh=MeshConfig(data=1, fsdp=1, tensor=1, seq=1, pipe=2),
    )
    trainer = SFTTrainer(cfg)
    summary = trainer.train()
    losses = [h["loss"] for h in trainer.metrics.history if "loss" in h]
    assert losses[-1] < losses[0], f"no learning: {losses[0]} -> {losses[-1]}"
    assert np.isfinite(summary["final_train_loss"])


@pytest.mark.slow
def test_pipe_trainer_moe_expert_parallel(qa_parquet, tmp_path):  # noqa: F811
    """pipe x EP: on a pipe=2 x expert=2 x fsdp=2 mesh the
    stacked expert weights shard over pipe AND expert (the memory win both
    axes exist for), the schedule keeps EP inside each stage, and training
    learns."""
    from jax.sharding import PartitionSpec as P

    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    cfg = make_config(
        tmp_path / "moe_ep_pipe", data_dir, dataset_file,
        epochs=1,
        model_preset="tiny_moe",
        freeze_strategy="none",
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1, expert=2, pipe=2),
    )
    trainer = SFTTrainer(cfg)

    # the stacked expert leaves really are expert-sharded at rest
    expert_keys = [
        k for k in trainer.state.trainable
        if STACKED_PREFIX in k and "/experts/" in k and k.endswith(("w1", "w2", "w3"))
    ]
    assert expert_keys, "no stacked expert leaves in pipe-mode state"
    for k in expert_keys:
        spec = trainer.state.trainable[k].sharding.spec
        assert len(spec) >= 2 and spec[0] == "pipe" and spec[1] == "expert", (
            f"{k} not pipe+expert sharded: {spec}"
        )

    summary = trainer.train()
    losses = [h["loss"] for h in trainer.metrics.history if "loss" in h]
    assert losses[-1] < losses[0], f"no learning: {losses[0]} -> {losses[-1]}"
    assert np.isfinite(summary["final_train_loss"])


@pytest.mark.slow
@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_pipe_seq_parallel_attention_trains(qa_parquet, tmp_path, impl):  # noqa: F811
    """pipe x sequence parallelism inside the schedule (both impls): a
    pipe=2 x seq=2 x fsdp=2 mesh trains — stages go manual over seq and
    call the local ring/ulysses kernel — with first-step loss parity
    against the flat seq-parallel mesh."""
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    flat_cfg = make_config(
        tmp_path / f"flat_{impl}", data_dir, dataset_file,
        epochs=1, attention_impl=impl,
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=2),
    )
    pipe_cfg = make_config(
        tmp_path / f"pipe_{impl}", data_dir, dataset_file,
        epochs=1, attention_impl=impl,
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=2, pipe=2),
    )
    from llm_fine_tune_distributed_tpu.parallel.diagnostics import assert_seq_parallel

    with assert_seq_parallel(impl):
        flat = SFTTrainer(flat_cfg)
        flat.train()
    with assert_seq_parallel(f"{impl}_manual"):
        pipe = SFTTrainer(pipe_cfg)
        pipe.train()

    flat_losses = [h["loss"] for h in flat.metrics.history if "loss" in h]
    pipe_losses = [h["loss"] for h in pipe.metrics.history if "loss" in h]
    assert pipe_losses[0] == pytest.approx(flat_losses[0], rel=2e-2)
    assert pipe_losses[-1] < pipe_losses[0], f"pipe x {impl} did not learn"
    assert pipe_losses[-1] == pytest.approx(flat_losses[-1], rel=0.15)
