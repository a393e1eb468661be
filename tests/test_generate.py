"""Generation engine tests: KV-cache decode vs full-sequence forward parity,
greedy determinism, EOS stopping, repetition penalty, sampling shape, and the
model-dir round trip that backs ask_tuned_model.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
from llm_fine_tune_distributed_tpu.infer import GenerationConfig, Generator
from llm_fine_tune_distributed_tpu.infer.sampling import apply_repetition_penalty, sample_token
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import forward, init_params


@pytest.fixture(scope="module")
def tiny_setup():
    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    tok = ByteChatMLTokenizer()
    return mc, params, tok


@pytest.mark.slow
def test_greedy_decode_matches_full_forward(tiny_setup):
    """Token t from the KV-cache loop == token t from re-running the whole
    prefix through the cache-free forward (numerical parity of the cache)."""
    mc, params, tok = tiny_setup
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    prompt = tok.encode("the quick brown fox")
    cfg = GenerationConfig(max_new_tokens=8, do_sample=False, repetition_penalty=1.0)
    out = gen.generate_ids(prompt, cfg)
    assert len(out) == 8

    seq = list(prompt)
    for tok_id in out:
        logits, _ = forward(
            params, jnp.asarray([seq], jnp.int32), mc, compute_dtype=jnp.float32
        )
        expect = int(jnp.argmax(logits[0, -1]))
        assert expect == tok_id
        seq.append(tok_id)


def test_greedy_is_deterministic(tiny_setup):
    mc, params, tok = tiny_setup
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    cfg = GenerationConfig(max_new_tokens=6, do_sample=False)
    a = gen.generate_ids(tok.encode("hello"), cfg, seed=0)
    b = gen.generate_ids(tok.encode("hello"), cfg, seed=7)  # seed irrelevant for greedy
    assert a == b


def test_eos_stops_generation(tiny_setup):
    """Force the first sampled token to be EOS by making eos the argmax."""
    mc, params, tok = tiny_setup
    cfg = GenerationConfig(max_new_tokens=16, do_sample=False, repetition_penalty=1.0)
    prompt = tok.encode("x")
    logits, _ = forward(params, jnp.asarray([prompt], jnp.int32), mc, compute_dtype=jnp.float32)
    forced_eos = int(jnp.argmax(logits[0, -1]))
    gen_forced = Generator(
        params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[forced_eos]
    )
    out = gen_forced.generate_ids(prompt, cfg)
    assert out == []  # first token was the stop token -> empty continuation


def test_sampled_generation_reproducible_and_in_vocab(tiny_setup):
    mc, params, tok = tiny_setup
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    cfg = GenerationConfig(max_new_tokens=10, do_sample=True, temperature=0.8, top_k=20)
    a = gen.generate_ids(tok.encode("abc"), cfg, seed=3)
    b = gen.generate_ids(tok.encode("abc"), cfg, seed=3)
    c = gen.generate_ids(tok.encode("abc"), cfg, seed=4)
    assert a == b
    assert all(0 <= t < mc.vocab_size for t in a)
    assert len(c) == 10


def test_repetition_penalty_semantics():
    logits = jnp.asarray([[2.0, -2.0, 1.0]])
    seen = jnp.asarray([[True, True, False]])
    out = apply_repetition_penalty(logits, seen, 2.0)
    np.testing.assert_allclose(np.asarray(out), [[1.0, -4.0, 1.0]])


def test_top_p_keeps_first_token():
    """Even with a tiny top_p, the most probable token must stay samplable."""
    rng = jax.random.PRNGKey(0)
    logits = jnp.asarray([[10.0, 0.0, -1.0, -2.0]])
    seen = jnp.zeros((1, 4), bool)
    cfg = GenerationConfig(do_sample=True, temperature=1.0, top_p=0.01, top_k=4,
                           repetition_penalty=1.0)
    t = sample_token(rng, logits, seen, cfg)
    assert int(t[0]) == 0


def test_chat_roundtrip_and_model_dir(tiny_setup, tmp_path):
    """save_hf_checkpoint -> load_model_dir -> chat() returns text (the
    artifact contract ask_tuned_model.py consumes)."""
    import json

    from llm_fine_tune_distributed_tpu.infer import load_model_dir, load_tokenizer_dir
    from llm_fine_tune_distributed_tpu.models.hf_io import save_hf_checkpoint

    mc, params, tok = tiny_setup
    d = tmp_path / "best_model"
    save_hf_checkpoint(params, str(d))
    tok.save_pretrained(str(d))
    with open(d / "config.json", "w") as f:
        json.dump(
            {
                "model_type": mc.name,
                "vocab_size": mc.vocab_size,
                "hidden_size": mc.hidden_size,
                "intermediate_size": mc.intermediate_size,
                "num_hidden_layers": mc.num_layers,
                "num_attention_heads": mc.num_heads,
                "num_key_value_heads": mc.num_kv_heads,
                "rope_theta": mc.rope_theta,
                "max_position_embeddings": mc.max_position_embeddings,
                "rms_norm_eps": mc.rms_norm_eps,
                "tie_word_embeddings": mc.tie_word_embeddings,
                "no_rope_layers": list(mc.no_rope_layers),
            },
            f,
        )
    params2, mc2 = load_model_dir(str(d))
    assert mc2.num_layers == mc.num_layers
    tok2 = load_tokenizer_dir(str(d))
    gen = Generator(params2, mc2, tok2, compute_dtype=jnp.float32)
    text = gen.chat(
        [{"role": "user", "content": "hi"}],
        GenerationConfig(max_new_tokens=5, do_sample=False),
    )
    assert isinstance(text, str)


def test_batched_ragged_matches_single(tiny_setup):
    """generate_batch on ragged prompts == generate_ids per prompt, token
    for token: per-row cache slots keep the slot == position invariant, so
    batching is numerically transparent (greedy)."""
    mc, params, tok = tiny_setup
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    cfg = GenerationConfig(max_new_tokens=6, do_sample=False, repetition_penalty=1.0)
    prompts = [
        tok.encode("the quick brown fox"),
        tok.encode("hi"),
        tok.encode("water purification methods in the wild"),
    ]
    batched = gen.generate_batch(prompts, cfg)
    for p, got in zip(prompts, batched):
        assert got == gen.generate_ids(p, cfg), f"prompt {p} diverged"


def test_batched_eos_stops_rows_independently(tiny_setup):
    """A row hitting EOS stops early (output trimmed) without truncating
    the other rows."""
    mc, params, tok = tiny_setup
    # find what greedy emits first for a prompt, then declare THAT token eos
    probe = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    cfg = GenerationConfig(max_new_tokens=5, do_sample=False, repetition_penalty=1.0)
    p1, p2 = tok.encode("abc"), tok.encode("the quick brown fox")
    first_tok = probe.generate_ids(p1, cfg)[0]
    other = probe.generate_ids(p2, cfg)
    if first_tok in other:
        other = other[: other.index(first_tok)]

    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[first_tok])
    out = gen.generate_batch([p1, p2], cfg)
    assert out[0] == []  # first emission was eos -> trimmed to empty
    assert out[1] == other


@pytest.mark.slow
def test_speculative_greedy_exact_equivalence(tiny_setup):
    """Prompt-lookup speculative decode must emit EXACTLY the plain greedy
    sequence — incl. evolving repetition penalty — on normal and highly
    repetitive prompts (where drafting actually engages)."""
    mc, params, tok = tiny_setup
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    for text in (
        "the quick brown fox",
        "water water water water water water",
        "abc abc abc abc abc abc abc abc",
    ):
        prompt = tok.encode(text)
        for rp in (1.0, 1.1):
            plain = gen.generate_ids(
                prompt,
                GenerationConfig(
                    max_new_tokens=12, do_sample=False, repetition_penalty=rp
                ),
            )
            spec = gen.generate_ids(
                prompt,
                GenerationConfig(
                    max_new_tokens=12, do_sample=False, repetition_penalty=rp,
                    speculative_lookup=4,
                ),
            )
            assert spec == plain, f"{text!r} rp={rp}: {spec} != {plain}"


def test_speculative_eos_stops(tiny_setup):
    mc, params, tok = tiny_setup
    probe = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    cfg = GenerationConfig(max_new_tokens=8, do_sample=False, repetition_penalty=1.0)
    prompt = tok.encode("the quick brown fox")
    plain = probe.generate_ids(prompt, cfg)
    eos_tok = plain[3]  # declare the 4th emission to be eos
    expect = plain[: plain.index(eos_tok)]

    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[eos_tok])
    spec_cfg = GenerationConfig(
        max_new_tokens=8, do_sample=False, repetition_penalty=1.0, speculative_lookup=4
    )
    assert gen.generate_ids(prompt, spec_cfg) == expect


@pytest.mark.slow
def test_speculative_batched_per_row_equivalence(tiny_setup):
    """Batched speculation: every row of a speculative batch
    emits exactly the plain greedy sequence for ITS prompt — rows draft from
    their own contexts and desynchronize as acceptance diverges."""
    mc, params, tok = tiny_setup
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    prompts = [
        tok.encode("the quick brown fox"),
        tok.encode("water water water water water water"),
        tok.encode("abc abc abc abc abc abc abc abc"),
    ]
    plain_cfg = GenerationConfig(
        max_new_tokens=10, do_sample=False, repetition_penalty=1.0
    )
    spec_cfg = GenerationConfig(
        max_new_tokens=10, do_sample=False, repetition_penalty=1.0,
        speculative_lookup=4,
    )
    plain = [gen.generate_ids(p, plain_cfg) for p in prompts]
    batched = gen.generate_batch(prompts, spec_cfg)
    assert batched == plain
    assert gen.last_spec_steps is not None  # the batch really speculated
    assert gen.last_acceptance_rate is not None
    # the repetitive rows accept drafts, so the batch finishes in fewer
    # sequential forwards than tokens generated
    assert gen.last_acceptance_rate > 0

    # sampled batched speculation: seeded-deterministic, valid tokens
    sampled = GenerationConfig(max_new_tokens=4, do_sample=True, speculative_lookup=4)
    out = gen.generate_batch(prompts[:2], sampled, seed=1)
    assert all(0 <= t < mc.vocab_size for row in out for t in row)
    assert out == gen.generate_batch(prompts[:2], sampled, seed=1)
    assert gen.last_acceptance_rate is not None


@pytest.mark.slow
def test_speculative_accepts_on_repetitive_output(tiny_setup):
    """When greedy output repeats a bigram, drafting must accept multiple
    tokens per forward: sequential steps < generated tokens."""
    mc, params, tok = tiny_setup
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    spec_cfg = GenerationConfig(
        max_new_tokens=16, do_sample=False, repetition_penalty=1.0,
        speculative_lookup=4,
    )
    # find a prompt whose greedy continuation contains a repeated bigram
    plain_cfg = GenerationConfig(
        max_new_tokens=16, do_sample=False, repetition_penalty=1.0
    )
    for text in ("a", "the", "x y z", "hello world"):
        prompt = tok.encode(text)
        out = gen.generate_ids(prompt, plain_cfg)
        bigrams = list(zip(out, out[1:]))
        if len(set(bigrams)) < len(bigrams):  # some bigram repeats
            spec = gen.generate_ids(prompt, spec_cfg)
            assert spec == out
            assert gen.last_spec_steps is not None
            assert gen.last_spec_steps < len(spec), (
                f"no multi-accepts: {gen.last_spec_steps} steps for "
                f"{len(spec)} tokens"
            )
            return
    raise AssertionError("no repetitive greedy continuation found to test with")



@pytest.mark.slow
def test_sampled_speculative_near_greedy_temperature_matches(tiny_setup):
    """At a temperature low enough that the warped distribution is a point
    mass, rejection-sampling speculation must reproduce the deterministic
    plain-sampling output exactly (accept probability q(argmax) == 1)."""
    mc, params, tok = tiny_setup
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    prompt = tok.encode("hello world")
    base = dict(max_new_tokens=12, do_sample=True, temperature=1e-4,
                top_k=40, top_p=0.95, repetition_penalty=1.1)
    plain = GenerationConfig(**base)
    spec = GenerationConfig(**base, speculative_lookup=3)
    for seed in range(3):
        assert gen.generate_ids(prompt, spec, seed=seed) == gen.generate_ids(
            prompt, plain, seed=seed
        )


@pytest.mark.slow
def test_sampled_speculative_matches_plain_distribution(tiny_setup):
    """Rejection-sampling verification preserves the sampling distribution:
    over many seeds, the marginal token distribution at each position matches
    plain sampling's within the null noise level (calibrated by comparing
    two disjoint plain-sampling seed ranges against each other)."""
    mc, params, tok = tiny_setup
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    prompt = tok.encode("ab ab ab ab")  # repeated bigrams -> drafts fire
    n_pos = 3
    base = dict(max_new_tokens=n_pos, do_sample=True, temperature=1.0,
                top_k=20, top_p=0.95, repetition_penalty=1.1)
    plain = GenerationConfig(**base)
    spec = GenerationConfig(**base, speculative_lookup=3)

    n = 400
    from collections import Counter

    plain_a = [Counter() for _ in range(n_pos)]
    plain_b = [Counter() for _ in range(n_pos)]
    spec_c = [Counter() for _ in range(n_pos)]
    accepted_any = False
    for seed in range(n):
        a = gen.generate_ids(prompt, plain, seed=seed)
        b = gen.generate_ids(prompt, plain, seed=n + seed)
        c = gen.generate_ids(prompt, spec, seed=seed)
        accepted_any = accepted_any or (gen.last_acceptance_rate or 0) > 0
        for j in range(n_pos):
            plain_a[j][a[j]] += 1
            plain_b[j][b[j]] += 1
            spec_c[j][c[j]] += 1
    assert accepted_any, "no draft was ever accepted - the test has no power"

    def tv(x, y):
        support = set(x) | set(y)
        return 0.5 * sum(abs(x[t] / n - y[t] / n) for t in support)

    # position 0 precedes any speculation and shares the rng split layout:
    # bit-identical draws
    assert tv(plain_a[0], spec_c[0]) == 0.0
    for j in range(1, n_pos):
        null = tv(plain_a[j], plain_b[j])  # pure sampling noise at this n
        got = tv(plain_a[j], spec_c[j])
        assert got < 2.0 * null + 0.05, (
            f"position {j}: TV(plain, spec) = {got:.3f} vs plain-vs-plain "
            f"null {null:.3f} - speculative sampling skews the distribution"
        )


def test_generate_stream_matches_plain_decode(tiny_setup):
    """Streaming decode yields EXACTLY the plain decode's tokens, greedy and
    sampled (same sampler, same rng split sequence, chunked host readout)."""
    mc, params, tok = tiny_setup
    gen = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    prompt = tok.encode("the quick brown fox")
    for cfg in (
        GenerationConfig(max_new_tokens=11, do_sample=False, repetition_penalty=1.1),
        GenerationConfig(max_new_tokens=11, do_sample=True, temperature=0.8),
    ):
        plain = gen.generate_ids(prompt, cfg, seed=3)
        streamed = []
        for piece in gen.generate_stream(prompt, cfg, seed=3, chunk=4):
            streamed.extend(piece)
        assert streamed == plain, (cfg.do_sample, streamed, plain)


def test_generate_stream_stops_at_eos(tiny_setup):
    mc, params, tok = tiny_setup
    probe = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    cfg = GenerationConfig(max_new_tokens=10, do_sample=False, repetition_penalty=1.0)
    plain = probe.generate_ids(tok.encode("the quick brown fox"), cfg)
    eos_tok = plain[4]
    gen = Generator(
        params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[eos_tok]
    )
    streamed = []
    for piece in gen.generate_stream(tok.encode("the quick brown fox"), cfg, chunk=3):
        streamed.extend(piece)
    # the stream stops at the FIRST occurrence of the eos token (which may
    # be earlier than index 4 if the greedy sequence repeats tokens)
    assert streamed == plain[: plain.index(eos_tok)]
    assert eos_tok not in streamed


@pytest.mark.slow
def test_draft_model_speculation_exact_and_accepting(tiny_setup):
    """Draft-MODEL speculation: greedy output is exactly the plain greedy
    sequence regardless of the draft's quality; a perfect draft (the target
    itself) accepts every proposal, finishing in far fewer sequential
    forwards than tokens."""
    mc, params, tok = tiny_setup
    prompt = tok.encode("the quick brown fox")
    plain_cfg = GenerationConfig(max_new_tokens=12, do_sample=False, repetition_penalty=1.1)
    spec_cfg = GenerationConfig(
        max_new_tokens=12, do_sample=False, repetition_penalty=1.1,
        speculative_lookup=4,
    )
    base = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    plain = base.generate_ids(prompt, plain_cfg)

    # an unrelated (differently-initialized) draft: exactness must survive
    bad_draft = init_params(jax.random.PRNGKey(9), mc, dtype=jnp.float32)
    g_bad = Generator(
        params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[],
        draft_params=bad_draft, draft_config=mc,
    )
    assert g_bad.generate_ids(prompt, spec_cfg) == plain
    assert g_bad.last_acceptance_rate is not None

    # the target as its own draft: greedy proposals == greedy choices, so
    # every draft is accepted and steps collapse. max_new=11 = 1 (prefill)
    # + 2 steps x (1 + 4 drafts), so no draft is wasted on the max_new cap
    # and the acceptance rate is exactly 1.
    g_self = Generator(
        params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[],
        draft_params=params, draft_config=mc,
    )
    exact_cfg = GenerationConfig(
        max_new_tokens=11, do_sample=False, repetition_penalty=1.1,
        speculative_lookup=4,
    )
    plain11 = base.generate_ids(prompt, GenerationConfig(
        max_new_tokens=11, do_sample=False, repetition_penalty=1.1,
    ))
    assert g_self.generate_ids(prompt, exact_cfg) == plain11
    assert g_self.last_acceptance_rate == pytest.approx(1.0)
    assert g_self.last_spec_steps == 1 + 2  # prefill + 2 fully-accepted steps

    # sampled verify stays seeded-deterministic with a draft model
    sampled = GenerationConfig(max_new_tokens=6, do_sample=True, speculative_lookup=3)
    a = g_bad.generate_ids(prompt, sampled, seed=5)
    assert a == g_bad.generate_ids(prompt, sampled, seed=5)
    assert all(0 <= t < mc.vocab_size for t in a)


def test_draft_model_validation():
    from llm_fine_tune_distributed_tpu.models.configs import get_preset

    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    with pytest.raises(ValueError, match="come together"):
        Generator(params, mc, ByteChatMLTokenizer(), draft_params=params)
