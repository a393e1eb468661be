"""The train step names its device time from inside the program: every scope
of ``observe/xla.STEP_SCOPES`` reaches the compiled step's ``op_name``s (what a
device trace carries as ``tf_op``), JAX writes forward, backward and recompute
into the same path, and ``benchmarks/chipbench/readers/scopes.py`` classifies
what comes out. One tiny step is lowered and compiled once for the module; the
three loss paths are compiled as the gradient of ``make_loss_fn`` alone.

Scopes are debug information only: the lowering without it does not hold them,
which is why a persistent compile cache keyed without debug information (JAX's
default) can hand a scoped program the executable of an unscoped one (PERF.md
section 6, PR 24).
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from llm_fine_tune_distributed_tpu.config import TrainConfig
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import init_params
from llm_fine_tune_distributed_tpu.observe.xla import STEP_SCOPES, scope
from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.train.step import build_train_step, make_loss_fn
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench.readers import scopes as reader  # noqa: E402

MC = get_preset("tiny")  # 4 layers; last_n_and_head unfreezes 2: layers 0, 1 frozen, tied table trainable
FIRST_TRAINABLE = MC.num_layers - 2
ACCUM, BATCH, SEQ = 2, 2, 32


def _config(**loss_path):
    return TrainConfig(
        model_preset="tiny", compute_dtype="float32", gradient_checkpointing=True,
        remat_policy="dots_no_batch", per_device_batch_size=BATCH,
        gradient_accumulation_steps=ACCUM, max_seq_length=SEQ, **loss_path,
    )


def _split(tc):
    params = init_params(jax.random.PRNGKey(0), MC, dtype=jnp.float32)
    mask = flatten_dict(trainable_mask(params, MC, tc))
    flat = flatten_dict(params)
    return ({k: v for k, v in flat.items() if mask[k]}, {k: v for k, v in flat.items() if not mask[k]})


def _batch(lead=()):
    shape = (*lead, BATCH, SEQ)
    ids = np.random.RandomState(3).randint(0, MC.vocab_size, shape).astype(np.int32)
    return {"input_ids": jnp.asarray(ids), "loss_mask": jnp.ones(shape, jnp.float32),
            "attention_mask": jnp.ones(shape, jnp.int32)}


def _op_names(hlo_text):
    return re.findall(r'op_name="([^"]+)"', hlo_text)


@pytest.fixture(scope="module")
def lowered():
    tc = _config(loss_vocab_chunk=64)  # the flagship recipe's loss path
    trainable, frozen = _split(tc)
    optimizer = optax.adamw(1e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), trainable=trainable, frozen=frozen,
                       opt_state=optimizer.init(trainable))
    return jax.jit(build_train_step(MC, tc, optimizer)).lower(state, _batch((ACCUM,)))


@pytest.fixture(scope="module")
def paths(lowered):
    """The ``op_name`` of every instruction of the compiled step: the whole
    path, as the chip's trace carries it."""
    return _op_names(lowered.compile().as_text())


def _components(path):
    return [reader.bare(c) for c in path.split("/")]


# the scopes of an expert layer are checked where a model has one: tests/test_mla_moe.py
EXPERT_LAYER_SCOPES = ("router", "experts", "shared_expert")
# those of a linear-attention layer and of a gated softmax-attention layer likewise: tests/test_gdn_moe.py
LINEAR_LAYER_SCOPES = ("linear_attn", "gdn_conv", "gdn_scan", "gdn_gate_norm", "attn_gate")


# the q/k norms and the output norms of a block of four norms: ``test_the_gated_four_norm_blocks_scopes`` below
GATED_BLOCK_SCOPES = ("qk_norm", "out_norm")
# what a Kimi Delta Attention mixer has and the other linear mixer has not: ``test_the_kda_mixers_scopes`` below
KDA_SCOPES = ("kda_gates",)
# the two halves of EVA attention between the IN pass and ``o_proj``: ``test_the_eva_mixers_scopes`` below
EVA_SCOPES = ("eva_pool", "eva_agg")
# the three parts of a Mamba-2 mixer between ``in_proj`` and ``out_proj``: ``test_the_ssd_mixers_scopes`` below
SSD_SCOPES = ("ssd_in", "ssd_scan", "ssd_gate_norm")


@pytest.mark.parametrize(
    "name", [s for s in STEP_SCOPES if s not in EXPERT_LAYER_SCOPES + LINEAR_LAYER_SCOPES + GATED_BLOCK_SCOPES + KDA_SCOPES + EVA_SCOPES + SSD_SCOPES])
def test_every_scope_of_the_vocabulary_is_named(paths, name):
    want = re.compile(r"^layer\d+$") if name == "layer" else re.compile(f"^{name}$")
    assert any(want.match(c) for p in paths for c in _components(p)), name


def test_every_layer_has_its_own_index_with_attn_and_mlp_inside(paths):
    for i in range(MC.num_layers):
        inside = set()
        for components in map(_components, paths):
            if f"layer{i}" in components:
                inside.update(components[components.index(f"layer{i}") + 1:])
        assert {"attn", "mlp"} <= inside, (i, inside)


def test_scopes_are_debug_information_only(lowered):
    """Without debug information the lowering names no scope: the scopes
    change no operation (and a cache key that strips it cannot see them)."""
    bare = lowered.as_text()
    for name in ("loss_head", "final_norm", "grad_accum", "optimizer", "layer0"):
        assert name not in bare
    assert "loss_head" in lowered.as_text(debug_info=True)


def test_scope_helper_takes_only_the_vocabulary():
    with pytest.raises(AssertionError):
        scope("trunk")


def test_a_trainable_layer_occurs_under_transpose(paths):
    last = f"layer{MC.num_layers - 1}"
    assert any(p for p in paths if reader.BACKWARD in p and f"jvp({last})" in p)
    # ...and forward: the same scope on a path with no transpose
    assert any(p for p in paths if reader.BACKWARD not in p and f"jvp({last})" in p)


def test_a_recomputed_operation_carries_the_readers_marker(paths):
    # (a reducer's sub-computation keeps a relative name: whole paths only)
    remat = [p for p in paths if reader.RECOMPUTED in p and p.startswith("jit(train_step)")]
    assert remat, "jax.checkpoint no longer writes rematted_computation into the path"
    assert all(reader.BACKWARD in p for p in remat)  # recompute runs in the backward pass
    assert any("layer" in p for p in remat) and any("loss_head" in p for p in remat)


@pytest.mark.parametrize("loss_path", [{"loss_vocab_chunk": 64}, {"loss_chunk_size": 16}, {}],
                         ids=["vocab_chunked", "seq_chunked", "full_logits"])
def test_loss_head_is_scoped_on_every_loss_path(loss_path):
    tc = _config(**loss_path)
    trainable, frozen = _split(tc)
    grad = jax.jit(jax.grad(lambda t, f, b: make_loss_fn(MC, tc)(t, f, b)[0]))
    names = _op_names(grad.lower(trainable, frozen, _batch()).compile().as_text())
    head = [n for n in names if "loss_head" in n]
    assert any(n.endswith("dot_general") for n in head), "the unembed matmul is outside loss_head"
    assert any(reader.BACKWARD in n for n in head)
    assert any("final_norm" in n for n in names)


@pytest.mark.parametrize("cls,backward,recomputed", [
    ("frozen", False, False), ("frozen", True, False), ("frozen", True, True),
    ("tail", False, False), ("tail", True, False), ("tail", True, True),
    ("loss_head", False, False), ("loss_head", True, False), ("loss_head", True, True),
    ("optimizer", False, False), ("embed", False, False), ("embed", True, False),
])
def test_the_reader_finds_each_class_in_the_compiled_step(paths, cls, backward, recomputed):
    """Tied table, two frozen layers: every class the SmolLM3 cell reads
    exists here, the frozen layers' activation gradients included."""
    found = {reader.classify(p, FIRST_TRAINABLE) for p in paths}
    assert (cls, backward, recomputed) in found


def test_most_of_the_compiled_step_is_scoped(paths):
    """What stays unscoped is bookkeeping (loop counters, the batch's
    slices, rope's tables, the causal mask); a scope that falls off the
    step shows here before it shows on the chip."""
    classes = [reader.classify(p, FIRST_TRAINABLE)[0] for p in paths if p.startswith("jit(train_step)")]
    assert sum(c is not None for c in classes) / len(classes) > 0.75


def _scoped_forward(preset):
    """The scope paths of a preset's forward pass, from its lowering with debug information."""
    from llm_fine_tune_distributed_tpu.models.transformer import forward

    mc = get_preset(preset)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32))
    ids = jax.ShapeDtypeStruct((BATCH, 64), jnp.int32)
    text = jax.jit(lambda p, x: forward(p, x, mc, compute_dtype=jnp.float32)[0]).lower(params, ids).as_text(debug_info=True)
    return set(re.findall(r'"jit\(<lambda>\)/([^"]*?)/[^/"]*"', text))


def test_the_gated_four_norm_blocks_scopes():
    """A block with q/k norms, an output gate and four norms (``tiny_trinity``)
    names all three inside ``attn`` and the feed-forward's output norm inside
    ``mlp``, on the dense layer and on the expert layers; and every other model
    that has one of the parts carries its scope too: Gemma2's output norms,
    the q/k norms and the gate of Qwen3-Next's full layer (its linear layers
    have neither)."""
    paths = _scoped_forward("tiny_trinity")
    for layer in (0, 3):  # the dense layer behind a window, an expert layer behind the global mixer
        # (the q/k norms inside attn_in: everything between the projections and the attention call, PR 41)
        for inside in ("attn/attn_in/qk_norm", "attn/attn_gate", "attn/out_norm", "mlp/out_norm"):
            assert any(p.startswith(f"layer{layer}/{inside}") for p in paths), (layer, inside)
    gemma = _scoped_forward("tiny_gemma2")
    assert any(p.startswith("layer0/attn/out_norm") for p in gemma) and any(p.startswith("layer1/mlp/out_norm") for p in gemma)
    assert not any("qk_norm" in p or "attn_gate" in p for p in gemma)
    qwen = _scoped_forward("tiny_qwen3_next")
    assert any(p.startswith("layer3/attn/attn_in/qk_norm") for p in qwen) and any(p.startswith("layer3/attn/attn_gate") for p in qwen)
    assert not any("out_norm" in p for p in qwen) and not any(p.startswith("layer0/") and "qk_norm" in p for p in qwen)
    tiny = _scoped_forward("tiny")
    assert not any("qk_norm" in p or "out_norm" in p for p in tiny) and any(p.startswith("layer0/attn/attn_in") for p in tiny)


def test_the_kda_mixers_scopes():
    """A Kimi Delta Attention mixer (``tiny_kimi_linear``) stands under ``linear_attn`` with the same names for the
    same parts as the other linear mixer (``gdn_conv``, ``gdn_scan``, ``gdn_gate_norm``) and ONE more, ``kda_gates``,
    around what that mixer does not have (the low-rank products, the softplus); its latent layer keeps ``attn``, and
    its expert layers' scopes are the expert layers'. Qwen3-Next's linear layers have no ``kda_gates``."""
    paths = _scoped_forward("tiny_kimi_linear")
    for layer in (0, 1, 2, 4):
        for inside in ("kda_gates", "gdn_conv", "gdn_scan", "gdn_gate_norm"):
            assert any(p.startswith(f"layer{layer}/linear_attn/{inside}") for p in paths), (layer, inside)
        assert not any(p.startswith(f"layer{layer}/attn") for p in paths)
    assert any(p.startswith("layer3/attn") for p in paths) and not any(p.startswith("layer3/linear_attn") for p in paths)
    assert any(p.startswith("layer1/mlp/router") for p in paths) and any(p.startswith("layer1/mlp/shared_expert") for p in paths)
    assert not any(p.startswith("layer0/mlp/router") for p in paths)  # the leading dense layer
    assert not any("kda_gates" in p for p in _scoped_forward("tiny_qwen3_next"))


def test_the_eva_mixers_scopes():
    """An EVA layer (``tiny_evabyte``) keeps ``attn`` and ``attn_in`` as any layer of heads and names, between the IN
    pass and ``o_proj``, the pooling (``eva_pool``) and everything else (``eva_agg``); the heads' product stays under
    ``loss_head``; no other model carries the two names."""
    paths = _scoped_forward("tiny_evabyte")
    for layer in range(4):
        for inside in ("attn/attn_in", "attn/eva_pool", "attn/eva_agg", "mlp"):
            assert any(p.startswith(f"layer{layer}/{inside}") for p in paths), (layer, inside)
    assert any(p.startswith("loss_head") for p in paths)
    assert not any("eva_" in p for p in _scoped_forward("tiny"))


def test_the_ssd_mixers_scopes():
    """A Mamba-2 layer (``tiny_granite_h``) stands under ``linear_attn`` (so ``linear_attn_time_pct.train`` reads it) with
    the convolution and dt's softplus (``ssd_in``), the scan (``ssd_scan``) and the gate and norm (``ssd_gate_norm``)
    inside; its one attention layer keeps ``attn`` and ``attn_in``; no other model carries the three names."""
    paths = _scoped_forward("tiny_granite_h")
    for layer in (0, 4, 9):
        for inside in ("linear_attn/ssd_in", "linear_attn/ssd_scan", "linear_attn/ssd_gate_norm", "mlp"):
            assert any(p.startswith(f"layer{layer}/{inside}") for p in paths), (layer, inside)
    assert any(p.startswith("layer5/attn/attn_in") for p in paths) and not any(p.startswith("layer5/linear_attn") for p in paths)
    assert not any("ssd_" in p for p in _scoped_forward("tiny"))
