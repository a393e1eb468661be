"""Tensor-parallel inference: a Generator given a tp mesh
shards weights (and the KV cache by propagation) and produces the same
greedy tokens as single-device decode; sampled decode stays seeded-
deterministic; the weights are actually distributed (per-device shards)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
from llm_fine_tune_distributed_tpu.infer import GenerationConfig, Generator
from llm_fine_tune_distributed_tpu.infer.generate import make_tp_mesh
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import init_params


@pytest.fixture(scope="module")
def setup():
    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    return mc, params, ByteChatMLTokenizer()


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_greedy_matches_single_device(setup, tp):
    mc, params, tok = setup
    solo = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    sharded = Generator(
        params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[],
        mesh=make_tp_mesh(tp),
    )
    cfg = GenerationConfig(max_new_tokens=12, do_sample=False, repetition_penalty=1.1)
    for text in ("hello world", "ab ab ab"):
        prompt = tok.encode(text)
        assert sharded.generate_ids(prompt, cfg) == solo.generate_ids(prompt, cfg)


def test_tp_weights_are_sharded(setup):
    mc, params, tok = setup
    g = Generator(
        params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[],
        mesh=make_tp_mesh(4),
    )
    # a column-parallel kernel: out dim sharded 4 ways
    k = g.params["model"]["layers"]["0"]["self_attn"]["q_proj"]["kernel"]
    shard = k.addressable_shards[0].data
    assert shard.shape[1] * 4 == k.shape[1], (
        f"q_proj not tensor-sharded: shard {shard.shape} of {k.shape}"
    )


def test_tp_sampled_deterministic_and_valid(setup):
    mc, params, tok = setup
    g = Generator(
        params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[],
        mesh=make_tp_mesh(2),
    )
    cfg = GenerationConfig(max_new_tokens=8, do_sample=True)
    prompt = tok.encode("hello")
    a = g.generate_ids(prompt, cfg, seed=3)
    assert a == g.generate_ids(prompt, cfg, seed=3)
    assert all(0 <= t < mc.vocab_size for t in a)


def test_tp_speculative_greedy_matches(setup):
    """The speculative decoder also runs sharded (its gather/scatter fori
    loop partitions; drafts verify identically)."""
    mc, params, tok = setup
    solo = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    sharded = Generator(
        params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[],
        mesh=make_tp_mesh(2),
    )
    cfg = GenerationConfig(
        max_new_tokens=10, do_sample=False, repetition_penalty=1.0,
        speculative_lookup=3,
    )
    prompt = tok.encode("ab ab ab ab")
    assert sharded.generate_ids(prompt, cfg) == solo.generate_ids(prompt, cfg)


def test_tp_batched_ragged(setup):
    mc, params, tok = setup
    sharded = Generator(
        params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[],
        mesh=make_tp_mesh(2),
    )
    solo = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    cfg = GenerationConfig(max_new_tokens=6, do_sample=False, repetition_penalty=1.0)
    prompts = [tok.encode(t) for t in ("one", "two tokens here")]
    assert sharded.generate_batch(prompts, cfg) == solo.generate_batch(prompts, cfg)


@pytest.mark.slow
def test_moe_tp_ep_decode_matches_single_device():
    """Mixtral-style serving: a tensor x expert inference mesh decodes
    identically to single-device (expert weights shard over `expert`,
    dropless dispatch under the KV cache)."""
    from llm_fine_tune_distributed_tpu.config import MeshConfig
    from llm_fine_tune_distributed_tpu.runtime.mesh import make_mesh

    mc = get_preset("tiny_moe")
    params = init_params(jax.random.PRNGKey(1), mc, dtype=jnp.float32)
    tok = ByteChatMLTokenizer()
    solo = Generator(params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[])
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, tensor=2, seq=1, expert=4, pipe=1))
    ep = Generator(
        params, mc, tok, compute_dtype=jnp.float32, eos_token_ids=[], mesh=mesh
    )
    cfg = GenerationConfig(max_new_tokens=8, do_sample=False, repetition_penalty=1.0)
    prompt = tok.encode("hello world")
    assert ep.generate_ids(prompt, cfg) == solo.generate_ids(prompt, cfg)


@pytest.mark.slow
def test_llama3_70b_tp_decode_program_lowers():
    """The 70B preset's TP decode program compiles (abstractly) over a
    tensor=8 mesh: every weight in the decode path is partitionable, which
    is the property that makes the preset servable on a real slice. Uses
    jax.eval_shape-style lowering — no 70B params are materialized."""
    from llm_fine_tune_distributed_tpu.config import MeshConfig
    from llm_fine_tune_distributed_tpu.models.transformer import (
        forward,
        init_cache,
        unembed,
    )
    from llm_fine_tune_distributed_tpu.parallel.sharding import (
        param_sharding_rules,
    )
    from llm_fine_tune_distributed_tpu.runtime.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    mc = get_preset("llama3_70b")
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, tensor=8, seq=1, expert=1, pipe=1))
    act = NamedSharding(mesh, P())

    def shapes_fn(rng):
        return init_params(rng, mc, dtype=jnp.bfloat16)

    params_shapes = jax.eval_shape(shapes_fn, jax.random.PRNGKey(0))
    shardings = param_sharding_rules(params_shapes, mesh)

    def step(params, tok, cache):
        hidden, cache = forward(
            params, tok, mc, cache=cache, cache_pos=8,
            compute_dtype=jnp.bfloat16, output_hidden=True,
            activation_sharding=act,
        )
        return unembed(params, hidden[:, -1], mc, compute_dtype=jnp.bfloat16, mesh=mesh), cache

    cache_shapes = jax.eval_shape(lambda: init_cache(mc, 1, 64, dtype=jnp.bfloat16))
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    lowered = (
        jax.jit(step)
        .lower(
            jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                params_shapes, shardings,
            ),
            tok,
            cache_shapes,
        )
    )
    hlo = lowered.as_text()
    assert "sharding" in hlo  # the program is genuinely partitioned
