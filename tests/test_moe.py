"""Mixture-of-experts: routing math against a per-token reference loop,
expert-parallel sharding parity, aux-loss behavior, end-to-end train-step
convergence, and the Mixtral-8x7B abstract trace.

The reference is dense-only (SURVEY.md §2.4: EP absent); ops/moe.py extends
the framework to the Mixtral family with GShard-style einsum dispatch."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llm_fine_tune_distributed_tpu.config import MeshConfig, ModelConfig, TrainConfig
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.ops.moe import expert_capacity, init_moe_params, moe_mlp
from llm_fine_tune_distributed_tpu.parallel.diagnostics import assert_seq_parallel


def _cfg(**kw):
    base = dict(
        name="t",
        vocab_size=128,
        hidden_size=16,
        intermediate_size=32,
        num_layers=1,
        num_heads=2,
        num_kv_heads=2,
        num_experts=4,
        num_experts_per_tok=2,
        capacity_factor=8.0,  # big: no drops unless a test wants them
    )
    base.update(kw)
    return ModelConfig(**base)


def _reference_moe(lp, x, config):
    """Per-token numpy loop: top-k renormalized routing, no capacity."""
    b, s, h = x.shape
    gate = np.asarray(lp["gate"]["kernel"], np.float32)
    w1 = np.asarray(lp["experts"]["w1"], np.float32)
    w2 = np.asarray(lp["experts"]["w2"], np.float32)
    w3 = np.asarray(lp["experts"]["w3"], np.float32)
    y = np.zeros_like(np.asarray(x, np.float32))
    for bi in range(b):
        for si in range(s):
            t = np.asarray(x[bi, si], np.float32)
            logits = t @ gate
            p = np.exp(logits - logits.max())
            p /= p.sum()
            top = np.argsort(-p)[: config.num_experts_per_tok]
            w = p[top] / p[top].sum()
            for e, we in zip(top, w):
                hidden = (t @ w1[e]) * (1 / (1 + np.exp(-(t @ w1[e])))) * (t @ w3[e])
                y[bi, si] += we * (hidden @ w2[e])
    return y


def test_moe_matches_reference_loop():
    config = _cfg()
    lp = init_moe_params(jax.random.PRNGKey(0), config, jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16), jnp.float32)
    y, aux = jax.jit(lambda lp, x: moe_mlp(lp, x, config, jnp.float32))(lp, x)
    ref = _reference_moe(lp, x, config)
    np.testing.assert_allclose(np.asarray(y), ref, atol=1e-5)
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_capacity_drops_overflow_tokens():
    """With capacity 1 per (row, expert), later tokens routed to a full
    expert are dropped — output attenuates but stays finite."""
    config = _cfg(capacity_factor=1e-6)  # floor -> cap = 1
    assert expert_capacity(16, config) == 1
    lp = init_moe_params(jax.random.PRNGKey(0), config, jnp.float32)
    x = jnp.asarray(np.random.RandomState(1).randn(1, 16, 16), jnp.float32)
    y, aux = jax.jit(lambda lp, x: moe_mlp(lp, x, config, jnp.float32))(lp, x)
    full = _reference_moe(lp, x, config)
    y = np.asarray(y)
    assert np.all(np.isfinite(y))
    # 16 tokens x k=2 = 32 assignments compete for 4 expert slots: most
    # tokens are FULLY dropped (exact-zero output rows)
    zero_rows = np.abs(y[0]).sum(-1) == 0
    assert zero_rows.sum() >= 8
    # the first token wins position 0 in both of its experts' queues, so it
    # is never dropped and matches the capacity-free reference exactly
    np.testing.assert_allclose(y[0, 0], full[0, 0], atol=1e-5)


def test_uniform_router_aux_is_one():
    """A perfectly uniform router gives aux = 1.0 (the minimum)."""
    config = _cfg()
    lp = init_moe_params(jax.random.PRNGKey(0), config, jnp.float32)
    lp["gate"]["kernel"] = jnp.zeros_like(lp["gate"]["kernel"])  # uniform probs
    x = jnp.asarray(np.random.RandomState(2).randn(2, 32, 16), jnp.float32)
    _, aux = moe_mlp(lp, x, config, jnp.float32)
    # top-k tie-breaking still dispatches k of E experts; probs are uniform
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)


def test_expert_parallel_matches_unsharded(eight_devices):
    """moe_mlp under an expert=4 mesh == the single-device result."""
    config = _cfg()
    lp = init_moe_params(jax.random.PRNGKey(0), config, jnp.float32)
    x = jnp.asarray(np.random.RandomState(3).randn(4, 8, 16), jnp.float32)
    ref, aux_ref = moe_mlp(lp, x, config, jnp.float32)

    mesh = Mesh(
        np.array(eight_devices).reshape(2, 1, 1, 1, 4),
        ("data", "fsdp", "tensor", "seq", "expert"),
    )
    from llm_fine_tune_distributed_tpu.parallel.sharding import shard_params

    # rules match on the full path, so shard under the real subtree name
    lp_sharded = shard_params({"block_sparse_moe": lp}, mesh)["block_sparse_moe"]
    x_sharded = jax.device_put(x, NamedSharding(mesh, P(("data", "fsdp"))))
    y, aux = jax.jit(
        lambda lp, x: moe_mlp(lp, x, config, jnp.float32, mesh=mesh)
    )(lp_sharded, x_sharded)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


def test_forward_tiny_moe_and_aux():
    from llm_fine_tune_distributed_tpu.models.transformer import forward_with_report, init_params

    config = get_preset("tiny_moe")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 16)), jnp.int32)
    logits, _, report = forward_with_report(params, ids, config, compute_dtype=jnp.float32)
    assert logits.shape == (2, 16, 512)
    assert np.isfinite(np.asarray(logits)).all()
    assert set(report) == {"router_aux"}
    assert float(report["router_aux"]) > 0  # 2 MoE layers contribute


@pytest.mark.slow
def test_moe_train_step_converges():
    """Loss (CE + aux) decreases over a few steps on tiny_moe."""
    from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
    from llm_fine_tune_distributed_tpu.parallel.optimizer import build_optimizer
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.train.state import TrainState
    from llm_fine_tune_distributed_tpu.train.step import build_train_step
    from llm_fine_tune_distributed_tpu.utils.tree import split_by_mask

    config = get_preset("tiny_moe")
    tc = TrainConfig(
        model_preset="tiny_moe",
        per_device_batch_size=4,
        gradient_accumulation_steps=1,
        max_seq_length=32,
        learning_rate=5e-3,
        freeze_strategy="none",
        gradient_checkpointing=False,
        attention_impl="xla",
    )
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    mask = trainable_mask(params, config, tc)
    trainable, frozen = split_by_mask(params, mask)
    optimizer = build_optimizer(tc, None, total_steps=20, data_parallel_size=1)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        trainable=trainable,
        frozen=frozen,
        opt_state=optimizer.init(trainable),
    )
    step = jax.jit(build_train_step(config, tc, optimizer))
    rng = np.random.RandomState(0)
    batch = {
        "input_ids": jnp.asarray(rng.randint(0, 512, (1, 4, 32)), jnp.int32),
        "loss_mask": jnp.ones((1, 4, 32), jnp.float32),
        "attention_mask": jnp.ones((1, 4, 32), jnp.int32),
    }
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], f"MoE loss did not decrease: {losses}"


def test_hf_io_roundtrip_moe():
    """Stacked expert leaves <-> HF Mixtral per-expert names, bit-exact."""
    from llm_fine_tune_distributed_tpu.models.hf_io import (
        hf_state_dict_to_pytree,
        pytree_to_hf_state_dict,
    )
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

    config = get_preset("tiny_moe")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    state = pytree_to_hf_state_dict(params)
    # per-expert names exist with torch [out, in] layout
    assert "model.layers.0.block_sparse_moe.experts.0.w1.weight" in state
    assert state["model.layers.0.block_sparse_moe.experts.0.w1.weight"].shape == (128, 64)
    assert "model.layers.0.block_sparse_moe.gate.weight" in state
    back = hf_state_dict_to_pytree(state, config)
    a, b = flatten_dict(params), flatten_dict(back)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]), err_msg=k)


def test_mixtral_8x7b_traces():
    """Config #6-style scale check: param count and a full abstract train
    step on an fsdp x expert mesh (cf. tests/test_big_configs.py)."""
    from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
    from llm_fine_tune_distributed_tpu.parallel.optimizer import build_optimizer
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.train.state import TrainState
    from llm_fine_tune_distributed_tpu.train.step import build_train_step
    from llm_fine_tune_distributed_tpu.utils.tree import split_by_mask

    mc = get_preset("mixtral_8x7b")
    assert mc.num_params == pytest.approx(46.7e9, rel=0.01)
    tc = TrainConfig(
        model_preset="mixtral_8x7b",
        remat_policy="full",  # memory-limited recipe: minimum-HBM remat
        max_seq_length=1024,
        gradient_accumulation_steps=2,
        loss_chunk_size=512,
        attention_impl="xla",
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1, expert=4),
    )
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    )
    mask = trainable_mask(params, mc, tc)
    trainable, frozen = split_by_mask(params, mask)
    optimizer = build_optimizer(tc, None, total_steps=10, data_parallel_size=1)
    opt_state = jax.eval_shape(optimizer.init, trainable)
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        trainable=trainable,
        frozen=frozen,
        opt_state=opt_state,
    )
    batch = {
        "input_ids": jax.ShapeDtypeStruct((2, 2, 1024), jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct((2, 2, 1024), jnp.float32),
        "attention_mask": jax.ShapeDtypeStruct((2, 2, 1024), jnp.int32),
    }
    step = build_train_step(mc, tc, optimizer)
    new_state, metrics = jax.eval_shape(step, state, batch)
    assert metrics["loss"].shape == ()


def test_expert_weights_get_expert_axis_spec():
    """Sharding rules give stacked expert leaves a leading expert axis."""
    from llm_fine_tune_distributed_tpu.parallel.sharding import param_spec

    spec = param_spec("model/layers/0/block_sparse_moe/experts/w1", 3)
    assert spec[0] == "expert"
    spec2 = param_spec("model/layers/0/block_sparse_moe/experts/w2", 3)
    assert spec2[0] == "expert"


@pytest.mark.slow
def test_pipeline_moe_matches_plain(eight_devices):
    """GPipe schedule on tiny_moe == plain forward (logits AND router aux):
    capacity queues are per batch row, so microbatching changes nothing."""
    from llm_fine_tune_distributed_tpu.parallel.pipeline import (
        pipeline_forward,
        stack_stage_params,
        stage_sharding,
    )

    config = get_preset("tiny_moe")
    from llm_fine_tune_distributed_tpu.models.transformer import forward, forward_with_report, init_params

    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.RandomState(8).randint(0, config.vocab_size, (4, 32)), jnp.int32
    )
    mesh = Mesh(np.array(eight_devices[:2]), ("pipe",))
    stacked = jax.device_put(
        stack_stage_params(params, config, 2), stage_sharding(mesh)
    )
    logits_pipe, report_pipe = pipeline_forward(
        params, stacked, ids, config, mesh, 2,
        compute_dtype=jnp.float32, remat_blocks=False,
    )
    assert set(report_pipe) == {"router_aux"}
    logits_plain, _ = forward(
        params, ids, config, compute_dtype=jnp.float32, logits_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(logits_pipe), np.asarray(logits_plain), atol=2e-4, rtol=2e-4
    )
    # aux statistics are nonlinear in the token distribution, so the pipeline
    # (mean of per-microbatch auxes — the same semantics the grad-accum scan
    # gives the plain path) must equal forward() run per microbatch
    per_mb = []
    for m in range(2):
        _, _, report = forward_with_report(
            params, ids[m * 2 : (m + 1) * 2], config, compute_dtype=jnp.float32,
        )
        per_mb.append(float(report["router_aux"]))
    np.testing.assert_allclose(float(report_pipe["router_aux"]), np.mean(per_mb), rtol=1e-5)


@pytest.mark.slow
def test_dpo_moe_train_step_converges():
    """DPO on tiny_moe: the policy's router aux joins the train objective
    (layer-mean scale) and rewards_accuracy climbs over a few steps."""
    from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
    from llm_fine_tune_distributed_tpu.parallel.optimizer import build_optimizer
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.train.dpo import build_dpo_train_step
    from llm_fine_tune_distributed_tpu.train.state import TrainState
    from llm_fine_tune_distributed_tpu.utils.tree import split_by_mask

    config = get_preset("tiny_moe")
    tc = TrainConfig(
        model_preset="tiny_moe",
        objective="dpo",
        per_device_batch_size=2,
        gradient_accumulation_steps=1,
        max_seq_length=32,
        learning_rate=5e-3,
        freeze_strategy="none",
        gradient_checkpointing=False,
        attention_impl="xla",
    )
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    mask = trainable_mask(params, config, tc)
    trainable, frozen = split_by_mask(params, mask)
    ref = {k: jnp.asarray(v, jnp.bfloat16) for k, v in trainable.items()}
    optimizer = build_optimizer(tc, None, total_steps=10, data_parallel_size=1)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        trainable=trainable,
        frozen=frozen,
        opt_state=optimizer.init(trainable),
    )
    step = jax.jit(build_dpo_train_step(config, tc, optimizer))
    rng = np.random.RandomState(0)
    batch = {}
    for side in ("chosen", "rejected"):
        batch[f"{side}_input_ids"] = jnp.asarray(
            rng.randint(0, 512, (1, 2, 32)), jnp.int32
        )
        batch[f"{side}_loss_mask"] = jnp.ones((1, 2, 32), jnp.float32)
        batch[f"{side}_attention_mask"] = jnp.ones((1, 2, 32), jnp.float32)
    losses = []
    for _ in range(6):
        state, metrics = step(state, ref, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"DPO-MoE loss did not decrease: {losses}"
    assert float(metrics["rewards_accuracy"]) >= 0.5


def test_padding_excluded_from_routing():
    """Pad tokens get zero MoE output, hold no capacity, and the aux loss
    equals the trimmed batch's aux exactly."""
    config = _cfg()
    lp = init_moe_params(jax.random.PRNGKey(0), config, jnp.float32)
    real_len = 6
    x_real = jnp.asarray(np.random.RandomState(4).randn(2, real_len, 16), jnp.float32)
    x_pad = jnp.concatenate(
        [x_real, jnp.asarray(np.random.RandomState(5).randn(2, 10, 16), jnp.float32)],
        axis=1,
    )
    mask = jnp.concatenate(
        [jnp.ones((2, real_len), jnp.int32), jnp.zeros((2, 10), jnp.int32)], axis=1
    )
    y_pad, aux_pad = moe_mlp(lp, x_pad, config, jnp.float32, token_mask=mask)
    y_ref, aux_ref = moe_mlp(lp, x_real, config, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(y_pad)[:, :real_len], np.asarray(y_ref), atol=1e-5
    )
    assert np.abs(np.asarray(y_pad)[:, real_len:]).max() == 0.0  # pads untouched
    np.testing.assert_allclose(float(aux_pad), float(aux_ref), rtol=1e-5)


def test_chunked_dispatch_matches_unchunked():
    """Grouped (chunked-sequence) routing == single-group routing when
    capacity is ample — the long-context memory path changes nothing
    numerically."""
    import dataclasses

    config = _cfg()  # moe_dispatch_chunk default 1024 >> s: single group
    chunked = dataclasses.replace(config, moe_dispatch_chunk=16)
    lp = init_moe_params(jax.random.PRNGKey(0), config, jnp.float32)
    x = jnp.asarray(np.random.RandomState(6).randn(2, 64, 16), jnp.float32)
    y_ref, aux_ref = moe_mlp(lp, x, config, jnp.float32)
    y_chk, aux_chk = jax.jit(lambda lp, x: moe_mlp(lp, x, chunked, jnp.float32))(lp, x)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(float(aux_chk), float(aux_ref), rtol=1e-5)

    # non-divisible length: 60 pads to 64 (4 chunks of 16), tail masked out
    x60 = x[:, :60]
    y_ref60, aux_ref60 = moe_mlp(lp, x60, config, jnp.float32)
    y60, aux60 = jax.jit(lambda lp, x: moe_mlp(lp, x, chunked, jnp.float32))(lp, x60)
    assert y60.shape == x60.shape
    np.testing.assert_allclose(np.asarray(y60), np.asarray(y_ref60), atol=1e-5)
    np.testing.assert_allclose(float(aux60), float(aux_ref60), rtol=1e-5)


def test_moe_dropless_matches_reference():
    """dropless=True must ignore capacity entirely: even with a degenerate
    capacity_factor the output equals the per-token reference loop."""
    config = _cfg(capacity_factor=1e-6)
    lp = init_moe_params(jax.random.PRNGKey(0), config, jnp.float32)
    x = jnp.asarray(np.random.RandomState(7).randn(2, 12, 16), jnp.float32)
    y, _ = jax.jit(
        lambda lp, x: moe_mlp(lp, x, config, jnp.float32, dropless=True)
    )(lp, x)
    np.testing.assert_allclose(
        np.asarray(y), _reference_moe(lp, x, config), atol=1e-5
    )


@pytest.mark.slow
def test_moe_kv_cache_decode_matches_full_forward():
    """Greedy KV-cache decode on tiny_moe == re-running the growing prefix
    through the cache-free forward. The decode path is dropless (HF Mixtral
    semantics), so the reference forward runs with ample capacity to be
    dropless too — then the cache must be numerically transparent."""
    import dataclasses

    from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
    from llm_fine_tune_distributed_tpu.infer import GenerationConfig, Generator
    from llm_fine_tune_distributed_tpu.models.transformer import forward, init_params

    mc = get_preset("tiny_moe")
    mc_ample = dataclasses.replace(mc, capacity_factor=4.0)  # cap >= s: dropless
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    tok = ByteChatMLTokenizer()
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    prompt = tok.encode("water purification")
    cfg = GenerationConfig(max_new_tokens=6, do_sample=False, repetition_penalty=1.0)
    out = gen.generate_ids(prompt, cfg)
    assert len(out) == 6

    seq = list(prompt)
    for tok_id in out:
        logits, _ = forward(
            params, jnp.asarray([seq], jnp.int32), mc_ample, compute_dtype=jnp.float32
        )
        assert int(jnp.argmax(logits[0, -1])) == tok_id
        seq.append(tok_id)


def test_stacked_nf4_roundtrip_matches_per_expert():
    """quantize_nf4_stacked on [E, in, out] must equal quantizing each
    expert standalone — block grids never cross expert boundaries."""
    from llm_fine_tune_distributed_tpu.ops.nf4 import (
        dequantize_nf4,
        dequantize_nf4_stacked,
        quantize_nf4,
        quantize_nf4_stacked,
    )

    rng = np.random.RandomState(9)
    w = rng.randn(3, 128, 32).astype(np.float32)
    for dq in (False, True):
        qs = quantize_nf4_stacked(jnp.asarray(w), 64, dq)
        back = np.asarray(dequantize_nf4_stacked(qs, dtype=jnp.float32))
        assert back.shape == w.shape
        for e in range(3):
            ref = np.asarray(
                dequantize_nf4(quantize_nf4(w[e], 64, dq), dtype=jnp.float32)
            )
            if dq:
                # double-quant groups span experts, so scales differ slightly
                np.testing.assert_allclose(back[e], ref, atol=0.05)
            else:
                np.testing.assert_array_equal(back[e], ref)
        # reconstruction error bounded (NF4 at block 64 on N(0,1) data)
        assert np.abs(back - w).max() < 0.6


def test_qlora_moe_quantizes_experts():
    """quantize_frozen NF4-packs stacked expert weights and the dequant
    inverse restores them for export."""
    from llm_fine_tune_distributed_tpu.parallel.qlora import (
        dequantize_frozen,
        quantize_frozen,
        quantized_fraction,
    )
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

    config = get_preset("tiny_moe")
    params = flatten_dict(init_params(jax.random.PRNGKey(0), config, jnp.float32))
    frozen = {k: v for k, v in params.items() if "/layers/" in k}
    q = quantize_frozen(frozen)
    assert "model/layers/0/block_sparse_moe/experts/w1_nf4" in q
    assert "model/layers/0/block_sparse_moe/experts/w1" not in q
    assert q["model/layers/0/block_sparse_moe/experts/w1_nf4"].shape == (4, 8, 128)
    assert quantized_fraction(q) > 0.5
    back = dequantize_frozen(q, dtype=jnp.float32)
    assert set(back) == set(frozen)
    w1 = np.asarray(frozen["model/layers/0/block_sparse_moe/experts/w1"])
    w1_back = np.asarray(back["model/layers/0/block_sparse_moe/experts/w1"])
    assert w1_back.shape == w1.shape
    assert np.abs(w1 - w1_back).max() < 0.1  # NF4 reconstruction error


@pytest.mark.slow
def test_qlora_moe_trainer_e2e(tmp_path):
    """Full QLoRA training on tiny_moe: adapters train against an
    NF4-quantized base (experts included), artifacts export."""
    import json

    from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data = tmp_path / "data"
    data.mkdir()
    jsonl = data / "qa.jsonl"
    with open(jsonl, "w") as f:
        for i in range(32):
            f.write(
                json.dumps({"topic": "Fire", "question": f"q {i}?", "answer": f"a {i}"})
                + "\n"
            )
    convert_jsonl_to_parquet(str(jsonl), str(data / "qa_dataset.parquet"), verbose=False)

    tc = TrainConfig(
        model_preset="tiny_moe",
        model_name="tiny-random",
        tokenizer_path="byte-chatml",
        data_dir=str(data),
        output_dir=str(tmp_path / "out"),
        epochs=1,
        per_device_batch_size=2,
        gradient_accumulation_steps=2,
        max_seq_length=64,
        eval_steps=100,
        save_steps=100,
        freeze_strategy="qlora",
        attention_impl="xla",
        mesh=MeshConfig(data=1, fsdp=1, tensor=1, seq=1, expert=1),
    )
    trainer = SFTTrainer(tc)
    assert any(k.endswith("experts/w1_nf4") for k in trainer.state.frozen)
    assert all(k.endswith(("lora_a", "lora_b")) for k in trainer.state.trainable)
    trainer.train()
    losses = [h["loss"] for h in trainer.metrics.history if "loss" in h]
    assert losses and np.isfinite(losses).all()
    assert (tmp_path / "out" / "best_model" / "model.safetensors").exists()


@pytest.mark.slow
def test_trainer_e2e_with_expert_axis(tmp_path):
    """SFTTrainer glue with a live expert axis: 8-device mesh
    (data=2, fsdp=2, expert=2), tiny_moe, full training loop + artifacts."""
    import json

    from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data = tmp_path / "data"
    data.mkdir()
    jsonl = data / "qa.jsonl"
    with open(jsonl, "w") as f:
        for i in range(48):
            f.write(
                json.dumps(
                    {"topic": "Knots", "question": f"q {i}?", "answer": f"a {i} " + "w " * 5}
                )
                + "\n"
            )
    convert_jsonl_to_parquet(str(jsonl), str(data / "qa_dataset.parquet"), verbose=False)

    tc = TrainConfig(
        model_preset="tiny_moe",
        model_name="tiny-random",
        tokenizer_path="byte-chatml",
        data_dir=str(data),
        output_dir=str(tmp_path / "out"),
        epochs=1,
        per_device_batch_size=1,
        gradient_accumulation_steps=2,
        max_seq_length=64,
        eval_steps=100,
        save_steps=100,
        freeze_strategy="none",
        attention_impl="xla",
        mesh=MeshConfig(data=2, fsdp=2, tensor=1, seq=1, expert=2),
    )
    trainer = SFTTrainer(tc)
    assert trainer.mesh.shape["expert"] == 2
    trainer.train()
    losses = [h["loss"] for h in trainer.metrics.history if "loss" in h]
    assert losses and np.isfinite(losses).all()
    assert (tmp_path / "out" / "best_model" / "model.safetensors").exists()


@pytest.mark.slow
def test_mixtral_8x7b_qlora_traces():
    """QLoRA at 8x7B scale, abstractly: experts quantize to the NF4 layout
    (only adapters trainable), and the full train step traces."""
    from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
    from llm_fine_tune_distributed_tpu.parallel.lora import add_lora_from_config
    from llm_fine_tune_distributed_tpu.parallel.optimizer import build_optimizer
    from llm_fine_tune_distributed_tpu.parallel.qlora import quantize_frozen_abstract
    from llm_fine_tune_distributed_tpu.models.transformer import init_params
    from llm_fine_tune_distributed_tpu.train.state import TrainState
    from llm_fine_tune_distributed_tpu.train.step import build_train_step
    from llm_fine_tune_distributed_tpu.utils.tree import split_by_mask

    mc = get_preset("mixtral_8x7b")
    tc = TrainConfig(
        model_preset="mixtral_8x7b",
        remat_policy="full",
        max_seq_length=1024,
        gradient_accumulation_steps=2,
        loss_chunk_size=512,
        attention_impl="xla",
        freeze_strategy="qlora",
        quant_matmul_impl="xla",
        mesh=MeshConfig(data=1, fsdp=2, tensor=1, seq=1, expert=4),
    )
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mc, jnp.float32))
    params = jax.eval_shape(
        lambda: add_lora_from_config(
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params),
            jax.random.PRNGKey(0),
            tc,
        )
    )
    mask = trainable_mask(params, mc, tc)
    trainable, frozen = split_by_mask(params, mask)
    frozen = quantize_frozen_abstract(
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in frozen.items()},
        tc.quant_block_size,
        tc.quant_double_quant,
    )
    # experts packed: [E, in/8, out] int32; router gate NOT quantized
    k1 = "model/layers/0/block_sparse_moe/experts/w1_nf4"
    assert frozen[k1].shape == (8, 4096 // 8, 14336)
    assert frozen[k1].dtype == jnp.int32
    assert "model/layers/0/block_sparse_moe/gate/kernel" in frozen
    # memory at rest: quantized frozen bytes ~4.5 bits/param of 46.7B
    frozen_bytes = sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in frozen.values()
    )
    assert frozen_bytes < 30e9, f"{frozen_bytes / 1e9:.1f} GB frozen (want < 30 GB)"

    optimizer = build_optimizer(tc, None, total_steps=10, data_parallel_size=2)
    opt_state = jax.eval_shape(optimizer.init, trainable)
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        trainable=trainable,
        frozen=frozen,
        opt_state=opt_state,
    )
    batch = {
        "input_ids": jax.ShapeDtypeStruct((2, 2, 1024), jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct((2, 2, 1024), jnp.float32),
        "attention_mask": jax.ShapeDtypeStruct((2, 2, 1024), jnp.int32),
    }
    step = build_train_step(mc, tc, optimizer)
    new_state, metrics = jax.eval_shape(step, state, batch)
    assert metrics["loss"].shape == ()
    assert all(k.endswith(("lora_a", "lora_b")) for k in state.trainable)


def test_moe_with_ring_attention_matches_unsharded(eight_devices):
    """MoE x sequence parallelism on a FLAT mesh:
    a live seq axis with ring attention must not change MoE semantics —
    logits AND router aux (capacity/dispatch identical: the MoE runs in
    global view under GSPMD, only attention shard_maps over seq)."""
    from llm_fine_tune_distributed_tpu.models.transformer import forward_with_report, init_params

    config = get_preset("tiny_moe")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 64)), jnp.int32)
    ref, _, report_ref = forward_with_report(
        params, ids, config, attention_impl="xla", compute_dtype=jnp.float32,
    )
    aux_ref = report_ref["router_aux"]

    mesh = Mesh(
        np.array(eight_devices).reshape(2, 1, 1, 4, 1),
        ("data", "fsdp", "tensor", "seq", "expert"),
    )
    act = NamedSharding(mesh, P(("data", "fsdp"), "seq", None))
    with assert_seq_parallel("ring"):
        out, _, report = jax.jit(
            lambda p, i: forward_with_report(
                p, i, config, attention_impl="ring", compute_dtype=jnp.float32,
                activation_sharding=act,
            )
        )(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-4)
    np.testing.assert_allclose(float(report["router_aux"]), float(aux_ref), rtol=1e-5)


def test_moe_with_ulysses_attention_matches_unsharded(eight_devices):
    """Companion to the ring case: Ulysses all-to-all over seq with MoE."""
    from llm_fine_tune_distributed_tpu.models.transformer import forward_with_report, init_params

    config = get_preset("tiny_moe")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 512, (2, 64)), jnp.int32)
    ref, _, report_ref = forward_with_report(
        params, ids, config, attention_impl="xla", compute_dtype=jnp.float32,
    )
    aux_ref = report_ref["router_aux"]

    # mesh must SATISFY seq_parallel_preconditions (batch 2 % (data*fsdp) == 0,
    # kv heads 2 % seq 2 == 0) — the r4 version used data=2 x fsdp=2 with
    # batch 2, which silently tested the fallback; the
    # guard makes any such regression fail loudly instead of passing.
    mesh = Mesh(
        np.array(eight_devices).reshape(2, 1, 1, 2, 2),
        ("data", "fsdp", "tensor", "seq", "expert"),
    )
    act = NamedSharding(mesh, P(("data", "fsdp"), "seq", None))
    with assert_seq_parallel("ulysses"):
        out, _, report = jax.jit(
            lambda p, i: forward_with_report(
                p, i, config, attention_impl="ulysses", compute_dtype=jnp.float32,
                activation_sharding=act,
            )
        )(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-4)
    np.testing.assert_allclose(float(report["router_aux"]), float(aux_ref), rtol=1e-5)


def test_moe_seq_axis_with_expert_axis_matches_unsharded(eight_devices):
    """seq x expert together: ring attention over seq while expert weights
    shard over the expert axis — the full long-context MoE mesh family."""
    from llm_fine_tune_distributed_tpu.models.transformer import forward_with_report, init_params

    config = get_preset("tiny_moe")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 512, (2, 64)), jnp.int32)
    ref, _, report_ref = forward_with_report(
        params, ids, config, attention_impl="xla", compute_dtype=jnp.float32,
    )
    aux_ref = report_ref["router_aux"]

    mesh = Mesh(
        np.array(eight_devices).reshape(2, 1, 1, 2, 2),
        ("data", "fsdp", "tensor", "seq", "expert"),
    )
    from llm_fine_tune_distributed_tpu.parallel.sharding import shard_params

    params_sharded = shard_params(params, mesh)
    act = NamedSharding(mesh, P(("data", "fsdp"), "seq", None))
    with assert_seq_parallel("ring"):
        out, _, report = jax.jit(
            lambda p, i: forward_with_report(
                p, i, config, attention_impl="ring", compute_dtype=jnp.float32,
                activation_sharding=act,
            )
        )(params_sharded, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-4)
    np.testing.assert_allclose(float(report["router_aux"]), float(aux_ref), rtol=1e-5)


# ---------------------------------------------------------------------------
# The routed experts' sum of rows back into tokens (ops/moe._sum_into_tokens):
# the kernel, under the Pallas interpreter, against the masked-gather loop
# ---------------------------------------------------------------------------


def _sorted_routing(rng, tokens, k, routed, held, rows_a_chunk, start):
    """``rank [T, k]`` and ``ends [held]`` of one chunk as ``grouped_moe_mlp``
    makes them (a stable sort of the pairs by held expert, pairs held elsewhere
    last; the chunk is sorted rows [start, start + rows_a_chunk)). A third of the
    tokens choose held experts only (all k where ``held >= k``), a third none,
    the rest as drawn."""
    top_i = np.stack([rng.permutation(routed)[:k] for _ in range(tokens)])
    third = tokens // 3
    top_i[:third] = np.stack([rng.permutation(held)[:k] if held >= k else top_i[i] for i in range(third)])
    if routed - held >= k:
        top_i[third:2 * third] = held + np.stack([rng.permutation(routed - held)[:k] for _ in range(third)])
    local = np.where(top_i < held, top_i, held).reshape(-1)
    rank = np.argsort(np.argsort(local, kind="stable")).astype(np.int32).reshape(tokens, k)
    ends = np.cumsum((local[:, None] == np.arange(held)).sum(0))
    return jnp.asarray(rank - start), jnp.asarray(np.clip(ends - start, 0, rows_a_chunk).astype(np.int32))


@pytest.mark.parametrize(
    "dtype, tokens, k, routed, held, rows_a_chunk, start",
    [
        (jnp.float32, 48, 8, 16, 8, 192, 0),     # some tokens hold all 8 of their choices, some none
        (jnp.bfloat16, 48, 8, 16, 8, 192, 0),    # the backward's rows: blocks of 16, cast in the kernel
        (jnp.float32, 48, 6, 16, 4, 96, 0),      # k 6; no token can hold more than 4
        (jnp.bfloat16, 48, 6, 16, 8, 96, 0),     # k 6, the chunk cuts the held pairs (n_valid = its 96 rows)
        (jnp.float32, 48, 8, 16, 8, 64, 64),     # an overflow chunk: ranks before it are negative, ranks past it miss
        (jnp.bfloat16, 48, 8, 16, 8, 64, 128),   # a later one, cut at both ends
        (jnp.float32, 37, 8, 16, 8, 296, 0),     # T no multiple of the tile, every held pair in one chunk
        (jnp.float32, 48, 8, 16, 16, 384, 0),    # every expert held: k rows a token
        (jnp.float32, 48, 4, 16, 4, 48, 10_000), # a chunk no pair reaches: zeros
    ],
    ids=["f32-k8", "bf16-k8", "f32-k6", "bf16-k6-cut", "f32-overflow", "bf16-overflow-cut", "f32-ragged-T", "f32-all-held", "f32-empty"],
)
def test_sum_kernel_equals_the_masked_gather_loop_to_the_bit(dtype, tokens, k, routed, held, rows_a_chunk, start):
    """Same pairs, same float32 adds in the same order: the same bits (a
    choice the kernel skips adds + 0.0 in the loop, and the total is never
    -0.0, so not even a zero's sign differs)."""
    from llm_fine_tune_distributed_tpu.ops import moe

    rng = np.random.default_rng(tokens * k + held + start)
    rank, ends = _sorted_routing(rng, tokens, k, routed, held, rows_a_chunk, start)
    rows = jnp.asarray(rng.normal(size=(rows_a_chunk, 256)), dtype)
    want = moe._sum_into_tokens(rows, rank, ends, impl="loop")
    hits = np.asarray((rank >= 0) & (rank < ends[-1])).sum(1)
    if start == 0 and held >= k and int(ends[-1]) < rows_a_chunk:  # (a chunk filled to its end cuts the pairs)
        assert hits.min() == 0 and hits.max() == k  # tokens with none and with all k of their choices held
    for tile in (16, moe.SUM_TILE):  # three tiles (the last ragged), and the tile the step runs (one, ragged)
        got = moe._sum_held_rows(rows, rank, ends, tile=tile, interpret=True)
        assert got.dtype == jnp.float32 and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got).view(np.int32), np.asarray(want).view(np.int32))
    assert float(jnp.abs(want).max()) > 0 or start == 10_000


def test_sum_into_tokens_records_which_program_it_took(monkeypatch):
    """One entry a traced shape (as ``flash_attention.GRID_TILES``): the loop
    off a TPU and why, the kernel on one where the shapes allow, the loop and
    the kernel's refusal where they do not; a kernel asked for by name on a
    shape it refuses raises. ``sum_programs_summary()`` is the line the
    trainer prints beside ``dispatch_summary()``."""
    from llm_fine_tune_distributed_tpu.ops import moe

    monkeypatch.setattr(moe, "SUM_PROGRAMS", {})
    shapes = lambda c, h, dtype=jnp.float32: (  # noqa: E731
        jax.ShapeDtypeStruct((c, h), dtype), jax.ShapeDtypeStruct((64, 8), jnp.int32), jax.ShapeDtypeStruct((16,), jnp.int32))
    jax.eval_shape(moe._sum_into_tokens, *shapes(192, 256))
    assert moe.SUM_PROGRAMS == {((192, 256), "float32", (64, 8), 16): "loop: backend is cpu"}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.sum_kernel_refused(*shapes(98304, 2304)) is None and moe.sum_kernel_refused(*shapes(16384, 2048, jnp.bfloat16)) is None
    jax.eval_shape(moe._sum_into_tokens, *shapes(192, 192))
    jax.eval_shape(moe._sum_into_tokens, *shapes(200, 256, jnp.bfloat16))
    jax.eval_shape(moe._sum_into_tokens, *shapes(192, 256, jnp.int32))
    jax.eval_shape(moe._sum_into_tokens, *shapes(192, 65536))
    took = {key[:2]: program for key, program in moe.SUM_PROGRAMS.items()}
    assert took[(192, 192), "float32"] == "loop: hidden size 192 is not a multiple of 128"
    assert took[(200, 256), "bfloat16"] == "loop: a chunk of 200 rows is not whole blocks of 16"
    assert took[(192, 256), "int32"] == "loop: rows of int32"
    assert took[(192, 65536), "float32"].startswith("loop: needs ") and "MiB of VMEM" in took[(192, 65536), "float32"]
    with pytest.raises(ValueError, match="refuses: hidden size 192"):
        jax.eval_shape(lambda *a: moe._sum_into_tokens(*a, impl="kernel"), *shapes(192, 192))
    said = moe.sum_programs_summary()
    assert said.startswith("expert rows summed into tokens by: ") and "loop: backend is cpu" in said and said.count(";") == 4
    monkeypatch.setattr(moe, "SUM_PROGRAMS", {})
    assert moe.sum_programs_summary().endswith("nothing traced")


@pytest.mark.parametrize("preset, pulled", [("tiny_mla_moe", ()), ("tiny_mla_moe", (0, 1, 2)), ("tiny_mellum", ()), ("tiny_mellum", (0, 1, 2, 3))],
                         ids=["moonlight-as-drawn", "moonlight-all-k-held", "mellum-as-drawn", "mellum-all-k-held"])
def test_grouped_experts_with_the_sum_kernel_equal_the_loop(preset, pulled):
    """The routed experts' output and gradients (input, router, experts) with
    both sums by the kernel (interpreter: ``sum_rows`` forward on float32
    rows, ``take_rows`` backward) against the ``ragged_dot`` and loop path,
    at the tiny Moonlight and Mellum presets the suite uses, the hidden size
    one register's 128 lanes (the kernel refuses their 64); as drawn, and
    with a router that sends every token to k held experts, so that the
    overflow chunks behind the ``lax.cond`` run the kernel too (``rank -
    start`` negative there). Same bits: run operation by operation, as one
    program XLA may fuse the two differently."""
    from llm_fine_tune_distributed_tpu.ops import moe

    config = get_preset(preset).replace(hidden_size=128)
    lp = moe.init_grouped_moe_params(jax.random.PRNGKey(0), config, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, config.hidden_size), jnp.float32)
    if pulled:
        push = np.zeros((config.hidden_size, config.n_routed_experts), np.float32)
        push[:, list(pulled)] = 1.0
        lp["gate"]["kernel"] = lp["gate"]["kernel"] + jnp.asarray(push)
        x = jnp.abs(x)

    def run(sum_impl):
        loss = lambda lp, x: (moe.grouped_moe_mlp(lp, x, config, jnp.float32, sum_impl=sum_impl)[0] ** 2).sum()  # noqa: E731
        (y, load), grads = moe.grouped_moe_mlp(lp, x, config, jnp.float32, sum_impl=sum_impl), jax.grad(loss, argnums=(0, 1))(lp, x)
        return y, load, grads

    y, load, grads = run("kernel_interpret")
    want_y, want_load, want_grads = run("loop")
    if pulled:  # every pair of every token is held here: more chunks than the first
        assert int(load.sum()) == x.shape[0] * x.shape[1] * config.num_experts_per_tok > moe.pairs_a_chunk(config) * 64
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want_y))
    assert float(jnp.abs(want_y).max()) > 0
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
