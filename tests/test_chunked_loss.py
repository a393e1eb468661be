"""Chunked cross-entropy (loss_chunk_size) must match the full-logits loss
bit-for-bit in value and gradients — it is a pure memory-layout optimization
(train/step.py:chunked_ce_sum)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_fine_tune_distributed_tpu.config import TrainConfig
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import init_params
from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
from llm_fine_tune_distributed_tpu.train.step import make_loss_fn
from llm_fine_tune_distributed_tpu.utils.tree import split_by_mask


@pytest.mark.parametrize("chunk", [40, 96, 128])  # non-divisor, divisor, > seq
@pytest.mark.slow
def test_chunked_ce_matches_full(chunk):
    mc = get_preset("tiny")
    common = dict(model_preset="tiny", max_seq_length=96, compute_dtype="float32")
    tc_full = TrainConfig(loss_chunk_size=None, **common)
    tc_chunk = TrainConfig(loss_chunk_size=chunk, **common)

    params = init_params(jax.random.PRNGKey(0), mc)
    trainable, frozen = split_by_mask(params, trainable_mask(params, mc, tc_full))
    rng = np.random.RandomState(0)
    batch = {
        "input_ids": rng.randint(0, mc.vocab_size, (2, 96)).astype(np.int32),
        "loss_mask": (rng.rand(2, 96) > 0.3).astype(np.float32),
        "attention_mask": np.ones((2, 96), np.int32),
    }

    loss_full, stats_full = make_loss_fn(mc, tc_full)(trainable, frozen, batch)
    loss_chunk, stats_chunk = make_loss_fn(mc, tc_chunk)(trainable, frozen, batch)
    assert set(stats_full) == set(stats_chunk) == {"tokens"}  # a dense model, no completion_mask
    assert float(stats_full["tokens"]) == float(stats_chunk["tokens"])
    assert abs(float(loss_full) - float(loss_chunk)) < 1e-5

    g_full = jax.grad(lambda t: make_loss_fn(mc, tc_full)(t, frozen, batch)[0])(trainable)
    g_chunk = jax.grad(lambda t: make_loss_fn(mc, tc_chunk)(t, frozen, batch)[0])(trainable)
    diff = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_chunk))
    )
    assert diff < 1e-5


@pytest.mark.parametrize("vchunk", [128, 256])  # tiny vocab_size=512
@pytest.mark.slow
def test_vocab_streamed_ce_matches_full(vchunk):
    """Vocab-streamed CE (loss_vocab_chunk, online logsumexp) must match the
    full-logits loss in value AND gradients — a traffic optimization, not a
    semantic change (train/step.vocab_chunked_ce_sum)."""
    mc = get_preset("tiny")
    common = dict(model_preset="tiny", max_seq_length=96, compute_dtype="float32")
    tc_full = TrainConfig(**common)
    tc_v = TrainConfig(loss_vocab_chunk=vchunk, **common)

    params = init_params(jax.random.PRNGKey(0), mc)
    trainable, frozen = split_by_mask(params, trainable_mask(params, mc, tc_full))
    rng = np.random.RandomState(0)
    batch = {
        "input_ids": rng.randint(0, mc.vocab_size, (2, 96)).astype(np.int32),
        "loss_mask": (rng.rand(2, 96) > 0.3).astype(np.float32),
        "attention_mask": np.ones((2, 96), np.int32),
    }

    loss_full, stats_full = make_loss_fn(mc, tc_full)(trainable, frozen, batch)
    loss_v, stats_v = make_loss_fn(mc, tc_v)(trainable, frozen, batch)
    assert float(stats_full["tokens"]) == float(stats_v["tokens"])
    assert abs(float(loss_full) - float(loss_v)) < 1e-5

    g_full = jax.grad(lambda t: make_loss_fn(mc, tc_full)(t, frozen, batch)[0])(trainable)
    g_v = jax.grad(lambda t: make_loss_fn(mc, tc_v)(t, frozen, batch)[0])(trainable)
    for k in g_full:
        np.testing.assert_allclose(
            np.asarray(g_v[k]), np.asarray(g_full[k]), atol=2e-5, err_msg=k
        )


def test_vocab_chunk_validations():
    mc = get_preset("tiny")
    with pytest.raises(ValueError, match="mutually exclusive"):
        make_loss_fn(mc, TrainConfig(model_preset="tiny", loss_chunk_size=64,
                                     loss_vocab_chunk=128))
    tc_bad = TrainConfig(model_preset="tiny", loss_vocab_chunk=100)  # 512 % 100
    params = init_params(jax.random.PRNGKey(0), mc)
    trainable, frozen = split_by_mask(params, trainable_mask(params, mc, tc_bad))
    batch = {
        "input_ids": np.zeros((1, 16), np.int32),
        "loss_mask": np.ones((1, 16), np.float32),
        "attention_mask": np.ones((1, 16), np.int32),
    }
    with pytest.raises(ValueError, match="not divisible"):
        make_loss_fn(mc, tc_bad)(trainable, frozen, batch)


def test_softcap_streams_through_both_chunking_schemes():
    """Gemma2 final_logit_softcap must produce the SAME loss from the full
    path, seq-chunked CE, and vocab-streamed CE (elementwise cap streams)."""
    mc = get_preset("tiny_gemma2")
    common = dict(
        model_preset="tiny_gemma2", max_seq_length=64, compute_dtype="float32"
    )
    params = init_params(jax.random.PRNGKey(0), mc)
    tc_full = TrainConfig(loss_chunk_size=None, **common)
    trainable, frozen = split_by_mask(params, trainable_mask(params, mc, tc_full))
    rng = np.random.RandomState(0)
    batch = {
        "input_ids": rng.randint(0, mc.vocab_size, (2, 64)).astype(np.int32),
        "loss_mask": np.ones((2, 64), np.float32),
        "attention_mask": np.ones((2, 64), np.int32),
    }
    loss_full, _ = make_loss_fn(mc, tc_full)(trainable, frozen, batch)
    loss_seq, _ = make_loss_fn(mc, TrainConfig(loss_chunk_size=32, **common))(
        trainable, frozen, batch
    )
    loss_voc, _ = make_loss_fn(mc, TrainConfig(loss_vocab_chunk=128, **common))(
        trainable, frozen, batch
    )
    assert abs(float(loss_full) - float(loss_seq)) < 1e-5
    assert abs(float(loss_full) - float(loss_voc)) < 1e-5


@pytest.mark.parametrize("kind", ["full", "seq_chunk", "vocab_chunk"])
def test_dual_mask_eval_metrics_agree_across_ce_paths(kind):
    """The answer-only eval metric (completion_mask in the batch) must come
    out identical from every CE implementation, computed from ONE unembed
    per path (no doubled eval pause — r5 review finding)."""
    mc = get_preset("tiny")
    kw = {"seq_chunk": dict(loss_chunk_size=40),
          "vocab_chunk": dict(loss_vocab_chunk=128)}.get(kind, {})
    tc = TrainConfig(model_preset="tiny", max_seq_length=96,
                     compute_dtype="float32", **kw)
    params = init_params(jax.random.PRNGKey(0), mc)
    trainable, frozen = split_by_mask(params, trainable_mask(params, mc, tc))
    rng = np.random.RandomState(1)
    lm = (rng.rand(2, 96) > 0.2).astype(np.float32)
    cm = lm * (rng.rand(2, 96) > 0.5).astype(np.float32)  # strict subset
    batch = {
        "input_ids": rng.randint(0, mc.vocab_size, (2, 96)).astype(np.int32),
        "loss_mask": lm,
        "attention_mask": np.ones((2, 96), np.int32),
        "completion_mask": cm,
    }
    loss, stats = make_loss_fn(mc, tc)(trainable, frozen, batch)
    assert set(stats) == {"tokens", "answer_ce_sum", "answer_tokens"}
    ans_ce, ans_tok = stats["answer_ce_sum"], stats["answer_tokens"]
    # reference: full-logits path with the completion mask AS the loss mask
    ref_batch = dict(batch, loss_mask=cm)
    ref_batch.pop("completion_mask")
    ref_loss, ref_stats = make_loss_fn(mc, TrainConfig(
        model_preset="tiny", max_seq_length=96, compute_dtype="float32"
    ))(trainable, frozen, ref_batch)
    assert float(ans_tok) == float(ref_stats["tokens"])
    np.testing.assert_allclose(
        float(ans_ce) / float(ans_tok), float(ref_loss), rtol=2e-5
    )
    # and the primary loss is unaffected by the extra mask
    plain = dict(batch)
    plain.pop("completion_mask")
    loss_plain, _ = make_loss_fn(mc, tc)(trainable, frozen, plain)
    np.testing.assert_allclose(float(loss), float(loss_plain), rtol=1e-6)
