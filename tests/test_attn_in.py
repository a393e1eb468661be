"""The fused IN pass of a softmax layer (``ops/rope.heads_in``: q/k norms, rope
and the head-major layout as one Pallas kernel forward and one back), on a CPU
under the Pallas interpreter, held to the XLA form it replaces (``rms_norm`` +
``apply_rope`` over ``[b, s, h, d]``, then the transposes of
``pallas_flash_attention``), forward and gradient:

- at the head shapes of the five cells that run ``_heads_qkv`` (rows cut to
  256: the kernels' blocks follow the row, the arithmetic does not), with and
  without norm and gate, with rope off, with a traced bool, in float32 (the
  two forms differ by summation order: 5e-6) and bfloat16 (the pass rounds
  once where the XLA form rounds after the norm too: a few units of the last
  bit, 2e-2 of the largest entry);
- through a whole model: a gated block with q/k norms whose layers take the
  pass and the head-major flash entry (interpreted) against the same model on
  XLA attention;
- which calls do NOT take it, and that ``CALLS`` says so with the reason.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from llm_fine_tune_distributed_tpu.config import MeshConfig
from llm_fine_tune_distributed_tpu.models import transformer
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import forward, init_cache, init_params
from llm_fine_tune_distributed_tpu.ops import flash_attention as fa
from llm_fine_tune_distributed_tpu.ops import rope
from llm_fine_tune_distributed_tpu.ops.norms import rms_norm
from llm_fine_tune_distributed_tpu.runtime.mesh import make_mesh

SEQ = 256
# cell: q heads, kv heads, head, table width, gate, norm, zero-centred
SHAPES = {
    "smollm3": (16, 4, 128, 128, False, False, False),
    "mistral": (32, 8, 128, 128, False, False, False),
    "mellum": (32, 4, 128, 128, False, False, False),
    "trinity": (32, 4, 128, 128, True, True, False),
    "qwen3-next": (16, 2, 256, 64, True, True, True),
}
TOLERANCE = {"float32": 5e-6, "bfloat16": 2e-2}


def xla_form(xq, xk, xv, cos, sin, wq, wk, *, heads, kv, gated, zc, do_rope):
    """``models/transformer._heads_qkv``'s XLA form, then the layout ``pallas_flash_attention`` made."""
    b, s, _ = xk.shape
    d = xk.shape[2] // kv
    gate = None
    if gated:
        q = xq.reshape(b, s, heads, 2 * d)
        q, gate = q[..., :d], q[..., d:].reshape(b, s, heads * d)
    else:
        q = xq.reshape(b, s, heads, d)
    k, v = xk.reshape(b, s, kv, d), xv.reshape(b, s, kv, d)
    if wq is not None:
        q, k = rms_norm(q, wq, 1e-6, zero_centered=zc), rms_norm(k, wk, 1e-6, zero_centered=zc)
    if not isinstance(do_rope, bool):
        qr, kr = rope.apply_rope(q, k, cos, sin)
        q, k = jnp.where(do_rope, qr, q), jnp.where(do_rope, kr, k)
    elif do_rope:
        q, k = rope.apply_rope(q, k, cos, sin)
    return q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), gate


def fused_form(xq, xk, xv, cos, sin, wq, wk, *, heads, kv, gated, zc, do_rope):
    """The pass over flat q, k, v; a gated layer's ``[q | gate]`` by head cut as ``_heads_qkv_head_major`` cuts the leaf
    (here the activation: the products are not this test's)."""
    mult = lambda w: None if w is None else (1.0 + w if zc else w)  # noqa: E731
    gate = None
    if gated:
        b, s, _ = xq.shape
        xq, gate = (xq.reshape(b, s, heads, 2, -1)[:, :, :, i].reshape(b, s, -1) for i in (0, 1))
    return (*rope.heads_in(xq, xk, xv, cos, sin, heads=heads, kv_heads=kv, q_weight=mult(wq), k_weight=mult(wk),
                           rope=do_rope, interpret=True), gate)


def _both_ways(cell, dtype, do_rope, rows, seq):
    heads, kv, d, width, gated, norm, zc = SHAPES[cell]
    ks = jax.random.split(jax.random.PRNGKey(41), 9)
    xq = jax.random.normal(ks[0], (rows, seq, heads * d * (2 if gated else 1)), jnp.float32).astype(dtype)
    xk, xv = (jax.random.normal(k, (rows, seq, kv * d), jnp.float32).astype(dtype) for k in ks[1:3])
    positions = jnp.broadcast_to(jnp.arange(seq)[None], (rows, seq)) + jnp.arange(rows)[:, None] * 7  # a table a row
    cos, sin = rope.rope_cos_sin(positions, width, 10000.0)
    wq = wk = None
    if norm:
        wq, wk = (0.2 * jax.random.normal(k, (d,), jnp.float32) + (0.0 if zc else 1.0) for k in ks[3:5])
    static = dict(heads=heads, kv=kv, gated=gated, zc=zc, do_rope=do_rope)
    out = {}
    for name, form in (("xla", xla_form), ("fused", fused_form)):
        y, vjp = jax.vjp(lambda xq, xk, xv, wq, wk: form(xq, xk, xv, cos, sin, wq, wk, **static), xq, xk, xv, wq, wk)
        cts = tuple(None if o is None else jax.random.normal(k, o.shape, jnp.float32).astype(o.dtype) for k, o in zip(ks[5:], y))
        out[name] = (y, vjp(cts))
    return out


@functools.cache
def _compiled(cell, dtype, do_rope, rows, seq):
    return jax.jit(lambda: _both_ways(cell, dtype, do_rope, rows, seq))


def both_ways(cell, dtype, do_rope, rows=2, seq=SEQ):
    """Both forms' outputs and cotangents as ONE compiled program a case (eagerly every primitive of both compiled by itself)."""
    return _compiled(cell, jnp.dtype(dtype), do_rope, rows, seq)()


def worst_distance(a, b):
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.shape == y.shape and x.dtype == y.dtype, (x.shape, y.shape, x.dtype, y.dtype)
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        worst = max(worst, float(np.abs(x - y).max() / np.abs(y).max()))
    return worst


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cell", list(SHAPES))
def test_the_pass_against_the_xla_form_at_a_cells_heads(cell, dtype):
    out = both_ways(cell, jnp.dtype(dtype), True)
    (y, grads), (y_ref, grads_ref) = out["fused"], out["xla"]
    heads, kv, d, _, gated, norm, _ = SHAPES[cell]
    assert y[0].shape == (2, heads, SEQ, d) and y[1].shape == y[2].shape == (2, kv, SEQ, d)  # head-major
    assert (y[3] is not None) == gated and (grads[3] is not None) == norm
    assert worst_distance(y, y_ref) <= TOLERANCE[dtype]
    assert worst_distance(grads, grads_ref) <= TOLERANCE[dtype]
    np.testing.assert_array_equal(np.asarray(y[2], np.float32), np.asarray(y_ref[2], np.float32))          # v: a relayout
    np.testing.assert_array_equal(np.asarray(grads[2], np.float32), np.asarray(grads_ref[2], np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cell", ["smollm3", "trinity"])  # every fourth layer of the one, the global layer of the other
def test_a_layer_without_rope_takes_the_same_kernel(cell, dtype):
    out = both_ways(cell, jnp.dtype(dtype), False)
    assert worst_distance(out["fused"], out["xla"]) <= TOLERANCE[dtype]
    if SHAPES[cell][5] is False:  # no norm either: q and k pass as they are, to the bit
        for i in (0, 1):
            np.testing.assert_array_equal(np.asarray(out["fused"][0][i], np.float32), np.asarray(out["xla"][0][i], np.float32))


@pytest.mark.parametrize("on", [True, False])
@pytest.mark.parametrize("cell", ["smollm3", "qwen3-next"])
def test_a_traced_bool_selects_between_the_tables(cell, on):
    pick = jax.jit(lambda flag: _both_ways(cell, jnp.float32, flag, rows=1, seq=128))(jnp.asarray(on))
    fixed = both_ways(cell, jnp.float32, on, rows=1, seq=128)
    assert worst_distance(pick["fused"], pick["xla"]) <= TOLERANCE["float32"]
    assert worst_distance(pick["fused"], fixed["fused"]) <= TOLERANCE["float32"]


def test_the_blocks_follow_the_shape():
    """A grid step holds a kv head's whole query group up to eight heads, and as many tokens as keep the group's
    block under 2 MiB: rows of 1024 are one block (SmolLM3), a short row is its own."""
    assert rope._plan(2, 8192, 128, 32, 4, 2) == (8, 8, 1024, 256)      # Trinity, Mellum
    assert rope._plan(2, 1024, 128, 16, 4, 2) == (4, 4, 1024, 256)      # SmolLM3
    assert rope._plan(1, 2048, 128, 32, 8, 2) == (4, 4, 2048, 256)      # Mistral
    assert rope._plan(2, 8192, 256, 16, 2, 2) == (8, 8, 512, 256)       # Qwen3-Next
    assert rope._plan(1, 128, 128, 32, 2, 4) == (16, 8, 128, 128)       # 16 queries a kv head: two steps of eight
    assert rope._plan(1, 384, 128, 4, 4, 2) == (1, 1, 128, 128)


@pytest.mark.parametrize("leaf", ["kernel", "lora and bias", "a pool of adapters"])
def test_a_gated_layers_q_and_gate_are_two_products_of_the_cut_leaf(leaf):
    """``q_proj``'s ``[q | gate]`` by head stays ONE leaf; ``_heads_qkv_head_major`` cuts it by head and column
    (``_by_columns(..., heads=)``: ``lora_b`` and the bias are cut with the kernel, ``lora_a`` is not) and makes two
    products, so that the gate and its cotangent never pass through the kernel. A leaf that cannot be cut makes one
    product whose output is cut. Both equal ``lin(hid, p)`` cut, and the leaf's cotangent comes back whole."""
    heads, d = 4, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    hid = jax.random.normal(ks[0], (2, 5, 16))
    p = {"kernel": jax.random.normal(ks[1], (16, heads * 2 * d))}
    if leaf == "lora and bias":
        p |= {"lora_a": jax.random.normal(ks[2], (16, 4)), "lora_b": jax.random.normal(ks[3], (4, heads * 2 * d)),
              "lora_scale": jnp.asarray(0.5), "bias": jax.random.normal(ks[4], (heads * 2 * d,))}
    elif leaf == "a pool of adapters":
        p |= {"lora_a_pool": jnp.zeros((2, 16, 4)), "lora_b_pool": jnp.zeros((2, 4, heads * 2 * d)), "lora_scale_pool": jnp.ones((2,))}
    products = []
    lin = lambda x, q: products.append(q) or transformer._linear(x, q, jnp.float32)  # noqa: E731
    whole = transformer._linear(hid, p, jnp.float32).reshape(2, 5, heads, 2 * d)
    q, gate = transformer._by_columns(hid, p, (0, d, 2 * d), lin, heads=heads)
    assert [x["kernel"].shape[1] for x in products] == ([heads * 2 * d] if leaf == "a pool of adapters" else [heads * d] * 2)
    np.testing.assert_allclose(q, whole[..., :d].reshape(2, 5, heads * d), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gate, whole[..., d:].reshape(2, 5, heads * d), rtol=1e-5, atol=1e-5)
    if leaf != "a pool of adapters":
        cut = lambda p: sum(jnp.sum(jnp.sin(y)) for y in transformer._by_columns(hid, p, (0, d, 2 * d), lin, heads=heads))  # noqa: E731
        for got, want in zip(jax.tree.leaves(jax.grad(cut)(p)), jax.tree.leaves(jax.grad(lambda p: jnp.sum(jnp.sin(lin(hid, p))))(p)), strict=True):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -- through a model -----------------------------------------------------------------------------------------------

GATED = dataclasses.replace(
    get_preset("tiny"), name="tiny_gated_128", head_dim=128, qk_norm=True, attention_output_gate=True, num_layers=2,
    no_rope_layers=(1, 0))


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The dispatch as a TPU makes it, the kernels under the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(rope, "heads_in", functools.partial(rope.heads_in, interpret=True))
    monkeypatch.setattr(fa, "pallas_flash_attention", functools.partial(fa.pallas_flash_attention, interpret=True))
    monkeypatch.setattr(rope, "CALLS", {})


def _loss(params, ids, impl):
    logits = forward(params, ids, GATED, compute_dtype=jnp.float32, attention_impl=impl)[0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - logits[..., 0])


def test_a_gated_model_through_the_pass_and_the_head_major_flash_entry(as_on_a_tpu):
    params = init_params(jax.random.PRNGKey(1), GATED, dtype=jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 128), 0, GATED.vocab_size)
    loss_and_grads = jax.jit(jax.value_and_grad(_loss), static_argnums=2)
    loss, grads = loss_and_grads(params, ids, "flash")
    shape = (2, 128, 4, 2, 128, 128, "norm", "gate")
    assert set(rope.CALLS) == {(shape, "fused")}
    loss_ref, grads_ref = loss_and_grads(params, ids, "xla")
    assert set(rope.CALLS) == {(shape, "fused"), (shape, "xla (attention_impl is 'xla')")}
    np.testing.assert_allclose(loss, loss_ref, rtol=1e-5)
    flat, flat_ref = jax.tree.leaves(grads), jax.tree.leaves(grads_ref)
    for g, g_ref in zip(flat, flat_ref, strict=True):
        np.testing.assert_allclose(g, g_ref, rtol=2e-3, atol=1e-6 + 1e-4 * float(jnp.abs(g_ref).max()))
    q_norm = grads["model"]["layers"]["0"]["self_attn"]["q_norm"]["weight"]
    assert float(jnp.abs(q_norm).max()) > 0  # the norm's multiplier got its cotangent through the kernel's partial sums


# -- which calls take the XLA form ---------------------------------------------------------------------------------


def _traced_forms(config, *, impl="flash", seq=128, cache=False, segment_ids=None, mesh=None):
    rope.CALLS.clear()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32))
    ids = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    kwargs = dict(attention_impl=impl, compute_dtype=jnp.float32)
    if mesh is not None:  # (forward reads the mesh from its activations' sharding)
        kwargs["activation_sharding"] = NamedSharding(mesh, P(("data", "fsdp"), "seq", None))
    if cache:
        kwargs["cache"] = jax.eval_shape(lambda: init_cache(config, 2, 2 * seq, dtype=jnp.float32))
    if segment_ids is not None:
        kwargs["segment_ids"] = segment_ids
    jax.eval_shape(lambda p, x: forward(p, x, config, **kwargs)[0], params, ids)
    return sorted({form for _, form in rope.CALLS})


def test_a_cpu_takes_the_xla_form_and_calls_says_so():
    assert _traced_forms(GATED) == ["xla (backend is cpu, the kernel is compiled for TPU only)"]
    assert "xla (backend is cpu" in rope.calls_summary()


@pytest.mark.parametrize("case, why", [
    ("cache entry", "xla (a cache entry)"),
    # (the flash kernels take a head of half a lane register as it lies since PR 49; the pass's flat blocks do not)
    ("head of 64", "xla (head dim 64 is not whole 128-lane registers: the pass reads a head's lanes of the flat projections)"),
    ("head of 96", "xla (head dim 96 is not a multiple of the 128 lanes)"),
    ("explicit mask", "xla (an explicit mask)"),
    ("xla attention", "xla (attention_impl is 'xla')"),
    ("ring attention over a live seq axis", "xla (attention_impl is 'ring')"),
    ("ring attention without a mesh", "fused"),  # attention() falls back to the flash kernels on the whole row: so does this
    ("flash attention on a mesh of two", "xla (a mesh of 2 devices: the kernel runs per shard, inside a shard_map over [b, s, h, d])"),
    ("row of 96", "xla (seq 96 is not a multiple of 128)"),
    ("softcap", "xla (a custom scale or logit softcap takes XLA attention)"),
])
def test_every_call_the_flash_kernels_do_not_take_whole_keeps_the_xla_form(case, why, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config, kwargs = GATED, {}
    if case == "cache entry":
        kwargs = dict(cache=True)
    elif case in ("head of 64", "head of 96"):
        config = dataclasses.replace(GATED, head_dim=int(case.split()[-1]))
    elif case == "explicit mask":  # a packed batch under a window: the window's distance is per segment
        config = dataclasses.replace(GATED, sliding_window=32)
        kwargs = dict(segment_ids=jnp.ones((2, 128), jnp.int32))
    elif case in ("xla attention", "ring attention without a mesh"):
        kwargs = dict(impl=case.split()[0])
    elif case in ("ring attention over a live seq axis", "flash attention on a mesh of two"):
        axis = "seq" if "ring" in case else "fsdp"
        kwargs = dict(impl=case.split()[0], mesh=make_mesh(MeshConfig(**{"data": 1, "fsdp": 1, "tensor": 1, "seq": 1, axis: 2})))
    elif case == "row of 96":
        kwargs = dict(seq=96)
    elif case == "softcap":
        config = dataclasses.replace(GATED, attn_logit_softcap=30.0)
    assert _traced_forms(config, **kwargs) == [why]


def test_head_major_operands_are_refused_where_the_kernels_do_not_take_the_call(monkeypatch):
    """``attention(..., head_major=True)`` runs what ``_route`` answers, as every call does: where that is not the
    flash kernels in one device's program (here: a CPU) it raises, it does not guess a transpose."""
    from llm_fine_tune_distributed_tpu.ops.attention import attention, head_major_reason

    q = jnp.zeros((1, 2, 128, 128), jnp.float32)
    rows_first = jax.ShapeDtypeStruct((1, 128, 2, 128), jnp.float32)
    assert head_major_reason(rows_first, rows_first, rows_first, impl="flash").startswith("backend is cpu")
    with pytest.raises(ValueError, match="head-major operands are for the flash kernels"):
        attention(q, q, q, impl="flash", head_major=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert head_major_reason(rows_first, rows_first, rows_first, impl="flash") is None
    assert head_major_reason(rows_first, rows_first, rows_first, impl="ring") is None  # no seq axis: the flash kernels' call
    monkeypatch.setattr(fa, "pallas_flash_attention", functools.partial(fa.pallas_flash_attention, interpret=True))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 128, 128), jnp.float32)
    np.testing.assert_array_equal(attention(x, x, x, impl="flash", head_major=True),
                                  attention(*(x.transpose(0, 2, 1, 3),) * 3, impl="flash"))


def test_on_a_tpu_a_whole_row_takes_the_pass_and_latent_attention_never_counts(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []
    monkeypatch.setattr(rope, "heads_in", lambda xq, xk, xv, *a, heads, kv_heads, **k: seen.append((xq.shape, k)) or (
        jnp.zeros((xq.shape[0], heads, xq.shape[1], 128), xq.dtype), *(jnp.zeros((x.shape[0], kv_heads, x.shape[1], 128), x.dtype) for x in (xk, xv))))
    whole = transformer.attention
    monkeypatch.setattr(transformer, "attention", lambda q, k, v, *, head_major=False, **asked: jnp.zeros(
        (q.shape[0], q.shape[2], q.shape[1], q.shape[3]), q.dtype) if head_major else whole(q, k, v, **asked))
    assert _traced_forms(GATED) == ["fused"]
    assert [k["rope"] for _, k in seen] == [True, False]         # the plan's bool, as it stands
    assert all(shape == (2, 128, 4 * 128) for shape, _ in seen)  # q alone: the gate is a product of its own
    latent = get_preset("tiny_mla_moe")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert _traced_forms(latent, impl="xla", seq=64) == []  # _latent_qkv: another computation, not this pass's
