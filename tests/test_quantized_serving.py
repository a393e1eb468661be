"""Quantized serving (ISSUE 12): int8 paged-KV pool with per-block absmax
scales, weight-only int8/NF4 serving params, and the fused Pallas paged
decode-attention kernel.

Pinned contracts:

- per-block KV quantization round-trips within tolerance under the
  engine's copy-on-write discipline (a shared block is written by exactly
  one prefill; sharers write only their divergent suffix), the null block
  0 stays all-zero no matter what is scattered at it, and a prefix block
  shared by many tables dequantizes bit-identically for every sharer;
- the paged engine over an int8 pool emits exactly solo generate_ids'
  greedy tokens — the same bit-parity headline the bf16 pool pins — and
  keeps doing so with speculation (K>0) and across preempt/resume;
- the fused kernel (pl.pallas_call(interpret=True) in tier-1) matches the
  XLA gather+dequant reference to f32 resolution; the compiled TPU path
  rides the slow marker; off-TPU the engine defaults to the XLA fallback;
- memory accounting: the int8 pool halves KV bytes/token, the breakdown
  (weight_bytes / kv_pool_bytes / kv_scale_bytes / bytes_saved_vs_bf16)
  adds up, and the serving gauges expose weight/KV residency.
"""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
from llm_fine_tune_distributed_tpu.infer import GenerationConfig, Generator
from llm_fine_tune_distributed_tpu.infer.engine import (
    ContinuousBatchingEngine,
    PagedContinuousBatchingEngine,
)
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import (
    init_paged_cache,
    init_params,
)
from llm_fine_tune_distributed_tpu.ops.flash_attention import (
    paged_decode_attention,
    paged_decode_mode,
)
from llm_fine_tune_distributed_tpu.ops.int8 import (
    dequantize_kv_gather,
    maybe_quantize,
    quantize_kv_write,
)

GREEDY = GenerationConfig(max_new_tokens=8, do_sample=False)
SAMPLED = GenerationConfig(max_new_tokens=6, do_sample=True, temperature=1.0)


@pytest.fixture(scope="module")
def generator():
    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    return Generator(
        params, mc, ByteChatMLTokenizer(), compute_dtype=jnp.float32,
        eos_token_ids=[],
    )


@pytest.fixture(scope="module")
def int8_generator():
    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    return Generator(
        maybe_quantize(params, "int8"), mc, ByteChatMLTokenizer(),
        compute_dtype=jnp.float32, eos_token_ids=[],
    )


def _paged(generator, **kw):
    return PagedContinuousBatchingEngine(
        generator, slots=4, buf_len=96, prompt_bucket=16,
        block_len=16, prefill_chunk=32, **kw,
    )


def _enc(text):
    return ByteChatMLTokenizer().encode(text)


def _prompts():
    return [_enc(t) for t in ("alpha", "beta bravo", "the quick brown fox")]


# --------------------------------------------------- per-block KV quant unit


def _empty_pool(num_blocks=8, block_len=8, heads=2, head_dim=16):
    codes = jnp.zeros((num_blocks, block_len, heads, head_dim), jnp.int8)
    scales = jnp.zeros((num_blocks, heads), jnp.float32)
    return codes, scales


def test_kv_write_roundtrip_and_scale_placement():
    """One prefill writes two blocks of one row; a sharer then writes only
    its divergent suffix into a third block (the COW discipline). Content
    round-trips within int8 tolerance and scales land per (block, head)."""
    rng = np.random.default_rng(0)
    codes, scales = _empty_pool()
    x0 = jnp.asarray(rng.normal(size=(1, 16, 2, 16)), jnp.float32)
    blk0 = jnp.asarray([[1] * 8 + [2] * 8], jnp.int32)
    off0 = jnp.asarray([list(range(8)) * 2], jnp.int32)
    codes, scales = quantize_kv_write(codes, scales, blk0, off0, x0)
    assert scales.shape == (8, 2)  # one absmax per (block, kv head)
    # blocks 1 and 2 carry exactly the per-block absmax of what was written
    w = np.asarray(x0[0]).reshape(2, 8, 2, 16)
    expect = np.abs(w).max(axis=(1, 3))
    np.testing.assert_allclose(np.asarray(scales)[1:3], expect, rtol=1e-6)
    assert float(jnp.abs(scales[3:]).max()) == 0.0

    # the sharer appends its suffix (2 tokens) into its own block 4
    x1 = jnp.asarray(rng.normal(size=(1, 2, 2, 16)), jnp.float32)
    codes, scales = quantize_kv_write(
        codes, scales, jnp.asarray([[4, 4]], jnp.int32),
        jnp.asarray([[0, 1]], jnp.int32), x1,
    )

    tables = jnp.asarray([[1, 2, 0], [1, 4, 0]], jnp.int32)
    got = np.asarray(dequantize_kv_gather(codes, scales, tables, jnp.float32))
    ref0 = np.asarray(x0[0])
    err = np.abs(got[0, :16] - ref0).max() / np.abs(ref0).max()
    assert err < 0.01  # int8 per-block absmax resolution
    err1 = np.abs(got[1, 8:10] - np.asarray(x1[0])).max()
    assert err1 < 0.01 * float(jnp.abs(x1).max())
    # the shared prefix block dequantizes IDENTICALLY for both sharers
    np.testing.assert_array_equal(got[0, :8], got[1, :8])
    # table positions past the allocation (null block) gather exact zeros
    assert np.abs(got[:, 16:]).max() == 0.0


def test_kv_null_block_zero_stays_zero():
    """Scatters redirected at block 0 (the engine's clip-redirect target
    for out-of-range writes) must not leave residue: codes and scales of
    the null block stay zero, so every table's padding reads as zeros."""
    codes, scales = _empty_pool()
    x = jnp.full((1, 4, 2, 16), 7.5, jnp.float32)
    codes, scales = quantize_kv_write(
        codes, scales, jnp.asarray([[0, 0, 1, 1]], jnp.int32),
        jnp.asarray([[0, 1, 0, 1]], jnp.int32), x,
    )
    assert int(jnp.abs(codes[0]).max()) == 0
    assert float(jnp.abs(scales[0]).max()) == 0.0
    # the legitimate block-1 write landed normally
    assert float(scales[1].min()) > 0.0


def test_kv_scale_growth_rescales_resident_codes():
    """A later, larger-magnitude write into a half-full block grows the
    block scale; the already-resident codes are re-quantized under the new
    scale so earlier content still dequantizes correctly."""
    codes, scales = _empty_pool()
    small = jnp.full((1, 4, 2, 16), 0.1, jnp.float32)
    codes, scales = quantize_kv_write(
        codes, scales, jnp.full((1, 4), 3, jnp.int32),
        jnp.arange(4, dtype=jnp.int32)[None], small,
    )
    big = jnp.full((1, 4, 2, 16), 10.0, jnp.float32)
    codes, scales = quantize_kv_write(
        codes, scales, jnp.full((1, 4), 3, jnp.int32),
        (4 + jnp.arange(4, dtype=jnp.int32))[None], big,
    )
    assert float(scales[3].min()) == 10.0
    got = np.asarray(
        dequantize_kv_gather(codes, scales, jnp.asarray([[3]], jnp.int32),
                             jnp.float32)
    )[0]
    # the early tokens survived the rescale (1 int8 step of 10/127 ~ 0.079)
    np.testing.assert_allclose(got[:4], 0.1, atol=10.0 / 127 + 1e-6)
    np.testing.assert_allclose(got[4:], 10.0, atol=10.0 / 127 + 1e-6)


def test_init_paged_cache_int8_layout_and_validation():
    mc = get_preset("tiny")
    cache = init_paged_cache(mc, num_blocks=6, block_len=8, kv_quant="int8")
    entry = cache["layers"]["0"]
    assert entry["k"].dtype == jnp.int8 and entry["v"].dtype == jnp.int8
    assert entry["k"].shape[:3] == (6, 8, mc.num_kv_heads)
    # one scale per (block, kv head), riding the same block ids as the pool
    assert entry["k_scale"].shape == (6, mc.num_kv_heads)
    assert entry["k_scale"].dtype == jnp.float32
    with pytest.raises(ValueError, match="kv_quant"):
        init_paged_cache(mc, num_blocks=6, block_len=8, kv_quant="int4")


# ------------------------------------------------------------ engine parity


def test_paged_int8_kv_greedy_parity_with_live_neighbors(generator):
    """Greedy over the int8 pool, with sampled neighbors mutating the same
    pool, emits exactly solo generate_ids' tokens (the bf16 pool's
    headline guarantee carried over to the quantized layout)."""
    eng = _paged(generator, kv_quant="int8")
    prompts = _prompts()
    solo = [generator.generate_ids(p, GREEDY) for p in prompts]
    done = []
    sampled = [
        threading.Thread(
            target=lambda s=s: eng.submit(_enc("noise maker"), SAMPLED, seed=s)
        )
        for s in range(2)
    ]
    for t in sampled:
        t.start()
    outs = [eng.submit(p, GREEDY) for p in prompts]
    for t in sampled:
        t.join()
    assert outs == solo


def test_paged_int8_kv_speculative_parity(generator):
    """Speculative verify (K>0) writes K+1 positions per tick through the
    quantized scatter and rolls back rejected tokens by pointer math only —
    greedy output stays bit-identical to solo."""
    eng = _paged(generator, kv_quant="int8", speculative_k=3)
    prompts = _prompts()
    solo = [generator.generate_ids(p, GREEDY) for p in prompts]
    outs = [eng.submit(p, GREEDY) for p in prompts]
    assert outs == solo


def test_dense_int8_weights_greedy_parity(int8_generator):
    """Weight-only int8 serving on the DENSE engine: the slot batch emits
    exactly what solo generate_ids produces over the same quantized
    params, and the breakdown reports the weight savings."""
    eng = ContinuousBatchingEngine(
        int8_generator, slots=2, buf_len=96, prompt_bucket=16,
    )
    prompts = _prompts()
    solo = [int8_generator.generate_ids(p, GREEDY) for p in prompts]
    outs = [eng.submit(p, GREEDY) for p in prompts]
    assert outs == solo
    mem = eng.memory_breakdown()
    assert mem["bytes_saved_vs_bf16"] > 0


def test_paged_int8_weights_and_kv_parity(int8_generator):
    """The full quantized stack — int8 weights AND int8 KV pool — on the
    paged engine keeps the engine-vs-solo bit-parity."""
    eng = _paged(int8_generator, kv_quant="int8")
    prompts = _prompts()
    solo = [int8_generator.generate_ids(p, GREEDY) for p in prompts]
    outs = [eng.submit(p, GREEDY) for p in prompts]
    assert outs == solo


def test_preempt_resume_over_quantized_pool(generator):
    """A best_effort greedy victim preempted by an interactive arrival and
    resumed from banked blocks emits the uninterrupted run's tokens — the
    banked blocks live in the int8 pool and re-dequantize on resume."""
    eng = PagedContinuousBatchingEngine(
        generator, slots=2, buf_len=256, prompt_bucket=64,
        block_len=16, prefill_chunk=256, kv_quant="int8",
    )
    victim_cfg = GenerationConfig(max_new_tokens=48, do_sample=False)
    prompt = _enc("a forty-ish token victim prompt for block banking")
    solo = generator.generate_ids(prompt, victim_cfg)
    # warm the programs/buckets this dance touches
    eng.submit(prompt, victim_cfg, priority="best_effort", timeout=240)
    eng.submit(_enc("interactive warm"), SAMPLED, seed=3, timeout=240)
    eng.submit(_enc("x" * 70), GREEDY, timeout=240)
    eng.mark_compile_warm()

    occupier = threading.Thread(
        target=lambda: eng.submit(
            _enc("long sampled occupier"),
            GenerationConfig(max_new_tokens=64, do_sample=True,
                             temperature=1.0),
            seed=9, timeout=240,
        )
    )
    occupier.start()
    deadline = time.monotonic() + 120
    while eng.live_slots < 1:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    stream = eng.stream(prompt, victim_cfg, priority="best_effort",
                        timeout=240)
    tokens = [next(stream), next(stream)]  # victim is decoding now
    trigger_result = []
    trigger = threading.Thread(
        target=lambda: trigger_result.append(
            eng.submit(
                _enc("interactive arrival"),
                GenerationConfig(max_new_tokens=8, do_sample=True,
                                 temperature=1.0),
                seed=4, timeout=240,
            )
        )
    )
    trigger.start()
    tokens.extend(stream)
    trigger.join()
    occupier.join()
    snap = eng.stats_snapshot()
    assert snap["preemptions"] >= 1
    assert tokens == solo


# ------------------------------------------------------------- fused kernel


def _kernel_case(seed=0, b=2, hkv=2, groups=2, d=16, block_len=16,
                 num_blocks=8, nb=3):
    rng = np.random.default_rng(seed)
    codes, scales = _empty_pool(num_blocks, block_len, hkv, d)
    vcodes, vscales = _empty_pool(num_blocks, block_len, hkv, d)
    lengths = np.asarray([block_len * 2 + 5, block_len + 3], np.int32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    for row in range(b):
        n = int(lengths[row])
        x = jnp.asarray(rng.normal(size=(1, n, hkv, d)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(1, n, hkv, d)), jnp.float32)
        blk = tables[row][jnp.arange(n) // block_len][None]
        off = (jnp.arange(n) % block_len)[None]
        codes, scales = quantize_kv_write(codes, scales, blk, off, x)
        vcodes, vscales = quantize_kv_write(vcodes, vscales, blk, off, y)
    q = jnp.asarray(rng.normal(size=(b, 1, hkv * groups, d)), jnp.float32)
    return q, codes, vcodes, scales, vscales, tables, jnp.asarray(lengths)


def _xla_reference(q, ck, cv, ks, vs, tables, lengths):
    """The default fallback path: gather+dequant then masked attention."""
    b, _, hq, d = q.shape
    k = dequantize_kv_gather(ck, ks, tables, jnp.float32)
    v = dequantize_kv_gather(cv, vs, tables, jnp.float32)
    groups = hq // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k)
    logits = logits * (float(d) ** -0.5)
    mask = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def test_fused_kernel_interpret_matches_xla_reference():
    """pl.pallas_call(interpret=True): the fused gather+dequant+online-
    softmax kernel reproduces the XLA reference to f32 resolution,
    including rows whose tables end in null-block padding."""
    q, ck, cv, ks, vs, tables, lengths = _kernel_case()
    got = paged_decode_attention(
        q, ck, cv, ks, vs, tables, lengths=lengths, interpret=True,
    )
    ref = _xla_reference(q, ck, cv, ks, vs, tables, lengths)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=1e-5, atol=1e-5,
    )


def test_fused_kernel_single_head_no_groups():
    """Degenerate GQA (hq == hkv) exercises the groups=1 reshape path."""
    q, ck, cv, ks, vs, tables, lengths = _kernel_case(seed=1, groups=1)
    got = paged_decode_attention(
        q, ck, cv, ks, vs, tables, lengths=lengths, interpret=True,
    )
    ref = _xla_reference(q, ck, cv, ks, vs, tables, lengths)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=1e-5, atol=1e-5,
    )


def test_paged_decode_mode_defaults_and_env(monkeypatch):
    """Off-TPU the engine must default to the XLA fallback (zero Pallas in
    CPU tier-1 decode); PAGED_DECODE overrides for the gated head-to-head."""
    monkeypatch.delenv("PAGED_DECODE", raising=False)
    if jax.default_backend() != "tpu":
        assert paged_decode_mode() == "xla"
    monkeypatch.setenv("PAGED_DECODE", "fused")
    assert paged_decode_mode() == "fused"
    monkeypatch.setenv("PAGED_DECODE", "interpret")
    assert paged_decode_mode() == "interpret"
    monkeypatch.setenv("PAGED_DECODE", "xla")
    assert paged_decode_mode() == "xla"
    # a typo does not silently become the default
    monkeypatch.setenv("PAGED_DECODE", "fuzed")
    with pytest.raises(ValueError, match="fuzed"):
        paged_decode_mode()


def test_engine_parity_through_interpreted_fused_kernel(generator,
                                                        monkeypatch):
    """End-to-end: the paged engine decoding THROUGH the fused kernel
    (interpret mode) emits exactly solo generate_ids' greedy tokens."""
    monkeypatch.setenv("PAGED_DECODE", "interpret")
    eng = _paged(generator, kv_quant="int8")
    prompts = _prompts()
    solo = [generator.generate_ids(p, GREEDY) for p in prompts]
    outs = [eng.submit(p, GREEDY) for p in prompts]
    assert outs == solo


# The COMPILED kernel: Mosaic lowering for v5e at SmolLM3 head shapes is
# pinned without a chip by tests/test_tpu_compile.py, and its agreement with
# the XLA gather (rtol = atol = 2e-2) is checked on the chip by chip_smoke.py.


# --------------------------------------------------------- memory accounting


def test_memory_breakdown_halves_kv_and_exposes_gauges(generator):
    bf16 = _paged(generator)
    q = _paged(generator, kv_quant="int8")
    # one request through each so the worker thread has built its pool
    bf16.submit(_enc("warm"), GREEDY)
    q.submit(_enc("warm"), GREEDY)
    mb, mq = bf16.memory_breakdown(), q.memory_breakdown()
    # same pool geometry: the f32 test pool stores 4 bytes/elem, int8 one
    assert mq["kv_pool_bytes"] * 4 == mb["kv_pool_bytes"]
    assert mq["kv_scale_bytes"] > 0 and mb["kv_scale_bytes"] == 0
    # unquantized residency saves nothing; int8 KV saves pool-minus-scales
    # against the bf16 logical layout
    assert mb["bytes_saved_vs_bf16"] == 0
    assert mq["bytes_saved_vs_bf16"] == (
        mq["kv_pool_bytes"] - mq["kv_scale_bytes"]
    )
    snap = q.stats_snapshot()
    assert snap["weight_bytes"] == mq["weight_bytes"] > 0
    assert snap["kv_pool_bytes"] == mq["kv_pool_bytes"] > 0
