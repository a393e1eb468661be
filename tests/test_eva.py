"""EvaByte (``evabyte``: EVA attention in every layer, a float32 residual stream, eight next-byte heads) against its
plain reference (``benchmarks/chipbench/reference_eva.py``, which imports nothing of the program), and the operator
(``ops/eva_attention.py``) against the equations written out here.

The shared tests are ``family_suite.ModelSuite``'s at ``tiny_evabyte`` (rows of 160 over windows of 32 and chunks of 4:
five windows): logits, loss, every gradient leaf (``adaptive_phi`` and ``adaptive_mu_k`` among them) in float32, two
steps' change at bfloat16 masters, the published configuration, the refusals, the checkpoint's names.

**Tolerances.** ``RTOL`` 2e-5: program and reference are float32 under ``highest`` and compute the same sums in another
order (the program a window at a time in two masked blocks, the reference one masked softmax a window and block of
heads; the heads' loss stacked against head by head); the worst leaf observed is 2e-6. The operator against the dense
form: 5e-6 absolute on outputs of order 1. A row no longer than a window against ``ops.attention.attention``: 1e-6 (the
sums run in another order). The kernels under the Pallas interpreter against the XLA form: 5e-6. bfloat16 against the
float32 reference: the logits within 2e-2 of their norm, the loss within 2e-3 (the other dense models' gaps)."""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from family_suite import (
    CATALOG, Model, ModelSuite, Published, Refusals, _batch, _bfloat16_gaps, _params, _rel,
)
from llm_fine_tune_distributed_tpu.config import TrainConfig
from llm_fine_tune_distributed_tpu.models import transformer
from llm_fine_tune_distributed_tpu.models.configs import from_hf_config, get_preset
from llm_fine_tune_distributed_tpu.ops import eva_attention as eva
from llm_fine_tune_distributed_tpu.ops.attention import attention
from llm_fine_tune_distributed_tpu.parallel.freeze import quantize_trunk_int8, trainable_mask
from llm_fine_tune_distributed_tpu.train import step as step_mod
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import reference_eva as ref, weights_eva  # noqa: E402

MC = get_preset("tiny_evabyte")
RTOL = 2e-5
SEQ = 160


def bench_cfg(mc=MC) -> dict:
    """The benchmark's configuration dict (the published names) of a ModelConfig."""
    return dict(
        model_type="evabyte", attention_class="eva", vocab_size=mc.vocab_size, hidden_size=mc.hidden_size,
        intermediate_size=mc.intermediate_size, num_hidden_layers=mc.num_layers, num_attention_heads=mc.num_heads,
        num_key_value_heads=mc.num_kv_heads, head_dim=mc.resolved_head_dim, rope_theta=mc.rope_theta,
        max_position_embeddings=mc.max_position_embeddings, rms_norm_eps=mc.rms_norm_eps, tie_word_embeddings=False,
        window_size=mc.eva_window, chunk_size=mc.eva_chunk, num_pred_heads=mc.num_pred_heads, fp32_skip_add=True,
        norm_add_unit_offset=True, num_chunks=None, rope_scaling=None, init_std=0.05,
    )


def _redraw(flat):
    """Norms off 0 (the benchmark draws the unit multiplier), so that the unit offset is not a no-op here."""
    return {k: (0.1 * jnp.cos(jnp.arange(v.shape[0], dtype=jnp.float32)).astype(v.dtype) if v.ndim == 1 else v)
            for k, v in flat.items()}


FAMILY = Model(
    mc=MC, bench_cfg=bench_cfg, weights=weights_eva, ref=ref, redraw=_redraw, rows=2, seq=SEQ, accum=1,
    rtol=RTOL, delta_tol=3e-3,  # as in the other models' tests
    buffers=(),
    # HF evabyte's names (as remembered): a Llama block's, EVA's two leaves a head stored [1, heads, 1, 1, d], the
    # heads side by side in one lm_head
    checkpoint_names=("model.layers.0.self_attn.q_proj.weight", "model.layers.0.self_attn.adaptive_phi",
                      "model.layers.3.self_attn.adaptive_mu_k", "model.layers.1.mlp.gate_proj.weight",
                      "model.layers.2.post_attention_layernorm.weight", "model.norm.weight", "lm_head.weight"),
    refusals=Refusals(
        base={k: v for k, v in bench_cfg().items() if k != "init_std"},
        cases=(("attention_class", "softmax"), ("num_chunks", 8), ("chunk_size", 5), ("window_size", None),
               ("rope_scaling", {"rope_type": "linear", "factor": 2.0}), ("tie_word_embeddings", True),
               ("num_key_value_heads", 2)),
        match=lambda key: "evabyte config has .*" + {"window_size": "window_size None"}.get(key, key)),
    published=Published(catalog_name="EvaByte", preset="evabyte_6_5b", tiny="tiny_evabyte",
                        params=(6.4e9, 6.5e9),  # "6.5B": 6,488,330,240
                        cut=dict(num_layers=10), cut_params=2_035_716_096),
)


class TestEvaByte(ModelSuite):
    family = FAMILY

    def check_leaves(self, own):
        d = MC.resolved_head_dim
        for leaf in ("adaptive_phi", "adaptive_mu_k"):  # drawn clip(normal, -1, 1) * d ** -0.5
            x = np.asarray(own[f"model/layers/0/self_attn/{leaf}"])
            assert x.shape == (MC.num_heads, d) and 0 < np.abs(x).max() <= d ** -0.5
        assert own["lm_head/kernel"].shape == (MC.hidden_size, 8 * MC.vocab_size)
        assert get_preset("evabyte_6_5b").num_params == 32 * 202_391_552 + 1_310_720 + 10_485_760 + 4_096 == 6_488_330_240

    def check_gradients(self, got):
        """The pooling's parameters take a gradient in every layer, through the attention's dK, dV of the summaries."""
        for i in range(MC.num_layers):
            for leaf in ("adaptive_phi", "adaptive_mu_k"):
                assert np.linalg.norm(got[f"model/layers/{i}/self_attn/{leaf}"]) > 1e-6, (i, leaf)

    def check_published(self, mc, config):
        assert (mc.eva_window, mc.eva_chunk, mc.num_pred_heads) == (2048, 16, 8)
        assert mc.fp32_residual and mc.zero_centered_norm and not mc.tie_word_embeddings
        assert all(mc.layer(i).attention == "eva" and mc.layer(i).rope for i in range(mc.num_layers))

    def check_checkpoint(self, state, params, flat):
        assert state["model.layers.0.self_attn.adaptive_phi"].shape == (1, MC.num_heads, 1, 1, MC.resolved_head_dim)
        assert state["lm_head.weight"].shape == (8 * MC.vocab_size, MC.hidden_size)

    def test_bfloat16_stands_as_far_from_the_reference_as_the_other_dense_models(self, flat, ids):
        logits_gap, loss_gap = _bfloat16_gaps(FAMILY, flat, ids)
        assert logits_gap < 2e-2 and loss_gap < 2e-3, (logits_gap, loss_gap)

    def test_the_loss_of_the_heads_agrees_with_the_reference_on_padded_rows_and_one_head_is_the_usual_loss(self, flat, ids):
        """Head i at position t answers byte t + 1 + i; each head's mean runs over its own targets under the loss
        mask. Against the reference head by head on full rows (the shared tests) and here on rows padded on the right,
        by the reference's loss over the real part alone; and with one head the targets are the plain shift."""
        real = 96  # (whole windows for the reference; what the pads compute reaches no real token)
        tc = TrainConfig(model_preset=None, compute_dtype="float32", param_dtype="float32", freeze_strategy="none",
                         loss_chunk_size=64, gradient_checkpointing=False, max_seq_length=SEQ)
        loss_fn = jax.jit(step_mod.make_loss_fn(MC, tc))
        got, stats = loss_fn(flatten_dict(_params(flat)), {}, _batch(ids[0, 0], real=real))
        hidden = ref.forward_hidden(flat, bench_cfg(), ids[0, 0][:, :real])[0]
        with jax.default_matmul_precision("highest"):
            want = ref.heads_loss(hidden, flat["model/norm/weight"].astype(jnp.float32), flat["lm_head/kernel"].astype(jnp.float32),
                                  jnp.asarray(ids[0, 0][:, :real]), bench_cfg())
        assert abs(float(got) - float(want)) < RTOL * float(want)
        assert float(stats["tokens"]) == 2 * (real - 1)
        x = jnp.arange(12).reshape(2, 6)
        np.testing.assert_array_equal(step_mod.heads_ahead(x, 1), x[:, 1:])
        np.testing.assert_array_equal(step_mod.heads_ahead(x, 3)[0], [[1, 2, 3], [2, 3, 4], [3, 4, 5], [4, 5, 0], [5, 0, 0]])

    def test_under_last_n_and_head_the_tails_phi_and_mu_train_and_the_trunks_stay_out_of_int8(self, flat):
        tc = TrainConfig(model_preset=None, freeze_strategy="last_n_and_head", unfreeze_last_n_layers=2)
        params = _params(flat)
        mask = flatten_dict(trainable_mask(params, MC, tc))
        for leaf in ("adaptive_phi", "adaptive_mu_k"):
            assert [mask[f"model/layers/{i}/self_attn/{leaf}"] for i in range(4)] == [False, False, True, True]
        assert mask["lm_head/kernel"] and not mask["model/embed_tokens/weight"] and not mask["model/norm/weight"]
        frozen, n = quantize_trunk_int8({k: v for k, v in flatten_dict(params).items() if not mask[k]}, 2)
        assert n == 2 * 7  # q, k, v, o, gate, up, down of the two trunk layers, and nothing else
        assert "model/layers/0/self_attn/adaptive_phi" in frozen and not [k for k in frozen if "adaptive" in k and "int8" in k]


# -- the operator against the equations ---------------------------------------------------------


def dense_eva(q, k, v, phi, mu, *, window, chunk, scale):
    """The equations as one ``[T, T + T / C]`` masked softmax; q, k, v ``[b, T, h, d]``."""
    b, t, h, d = q.shape
    kc, vc = k.reshape(b, t // chunk, chunk, h, d), v.reshape(b, t // chunk, chunk, h, d)
    a = jax.nn.softmax(scale * jnp.einsum("bcjhd,hd->bcjh", kc, phi), axis=2)
    ks, vs = jnp.einsum("bcjh,bcjhd->bchd", a, kc) + mu, jnp.einsum("bcjh,bcjhd->bchd", a, vc)
    n, m, c = jnp.arange(t)[:, None], jnp.arange(t)[None, :], jnp.arange(t // chunk)[None, :]
    mask = jnp.concatenate([(m // window == n // window) & (m <= n), c < (window // chunk) * (n // window)], axis=1)
    scores = scale * jnp.einsum("bqhd,bkhd->bhqk", q, jnp.concatenate([k, ks], axis=1))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.concatenate([v, vs], axis=1))


def operands(b, t, h, d, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, w = (jax.random.normal(keys[i], (b, t, h, d), jnp.float32) for i in (0, 1, 2, 5))
    phi, mu = (jax.random.normal(keys[i], (h, d), jnp.float32) for i in (3, 4))
    return (q, k, v, phi, mu), w


SMALL = dict(window=32, chunk=4, scale=16 ** -0.5)


def test_the_operator_is_the_equations_forward_and_in_every_cotangent():
    args, w = operands(2, 160, 3, 16)
    got = eva.eva_attention(*args, **SMALL)
    np.testing.assert_allclose(got, dense_eva(*args, **SMALL), atol=5e-6)
    grads = jax.grad(lambda *a: jnp.sum(eva.eva_attention(*a, **SMALL) * w), argnums=range(5))(*args)
    wants = jax.grad(lambda *a: jnp.sum(dense_eva(*a, **SMALL) * w), argnums=range(5))(*args)
    for name, g, want in zip(("q", "k", "v", "phi", "mu"), grads, wants):
        assert _rel(g, want) < RTOL, name
    assert min(float(jnp.linalg.norm(g)) for g in grads[3:]) > 0.1  # phi and mu take a gradient
    assert eva.CALLS[(2, 3, 160, 16), 32, 4, "xla (backend is cpu)"] >= 1


@pytest.mark.parametrize("rows", [32, 20])
def test_a_row_no_longer_than_a_window_is_plain_causal_attention(rows):
    (q, k, v, phi, mu), _ = operands(2, rows, 3, 16, seed=1)
    got = eva.eva_attention(q, k, v, phi, mu, **SMALL)
    np.testing.assert_allclose(got, attention(q, k, v, impl="xla", causal=True), atol=1e-6)


def test_a_token_reaches_later_windows_through_the_summaries_and_nothing_before_it():
    (q, k, v, phi, mu), _ = operands(1, 160, 2, 16, seed=2)
    at = 70  # in window 2 (tokens 64..95), chunk 17
    base = eva.eva_attention(q, k, v, phi, mu, **SMALL)
    moved = eva.eva_attention(q, k.at[:, at].add(1.0), v.at[:, at].add(1.0), phi, mu, **SMALL)
    change = np.abs(np.asarray(moved - base)).max(axis=(0, 2, 3))
    assert change[:at].max() == 0.0  # no output before it, in its own window or earlier
    assert change[at:96].min() > 0  # its own window sees it exactly
    assert change[96:128].min() > 0 and change[128:].min() > 0  # every later window, through chunk 17's summary


@pytest.mark.parametrize("leaf", ["phi", "mu"])
def test_the_pooling_parameters_move_nothing_in_the_first_window(leaf):
    (q, k, v, phi, mu), _ = operands(1, 96, 2, 16, seed=3)
    base = eva.eva_attention(q, k, v, phi, mu, **SMALL)
    moved = eva.eva_attention(q, k, v, phi + (leaf == "phi"), mu + (leaf == "mu"), **SMALL)
    change = np.abs(np.asarray(moved - base)).max(axis=(0, 2, 3))
    assert change[:32].max() == 0.0 and change[32:].min() > 0


KERNELS = dict(window=256, chunk=2, scale=128 ** -0.5)  # a window's 128 summaries are one tile; heads of whole lanes


def _kernels_and_xla(q, k, v, phi, mu, w):
    """Head-major operands through both forms of the aggregate, the kernels under the Pallas interpreter: ``(o, the
    five cotangents)`` of each."""
    def run(aggregate):
        def loss(q, k, v, phi, mu):
            ks, vs = eva.pool(k, v, phi, mu, chunk=KERNELS["chunk"], scale=KERNELS["scale"])
            o = aggregate(q, k, v, ks, vs)
            return jnp.sum(o * w), o
        (_, o), grads = jax.value_and_grad(loss, argnums=range(5), has_aux=True)(q, k, v, phi, mu)
        return o, grads

    return run(eva._make_aggregate(KERNELS["window"], KERNELS["chunk"], KERNELS["scale"], True)), run(
        lambda *a: eva._aggregate_xla(*a, **KERNELS))


def _head_major(args, w):
    q, k, v, phi, mu = args
    return (*(x.transpose(0, 2, 1, 3) for x in (q, k, v)), phi, mu, w.transpose(0, 2, 1, 3))


@pytest.mark.parametrize("windows", [4, 5, 7])
def test_the_kernels_under_the_interpreter_are_the_xla_form(windows):
    """The resident flash kernels on the row cut into windows, joined by the remote kernels over the summaries, and
    the backward of both sources under the merged lse: against the XLA form, output and every cotangent. Windows of
    256 in chunks of 2, heads of whole lanes; four, five and seven windows, so the remote forward's blocks see 0 to 6
    windows: no trip, single trips alone, a wide trip of four alone, and a wide trip with one and two single ones."""
    rows = windows * KERNELS["window"]
    (o, grads), (want_o, wants) = _kernels_and_xla(*_head_major(*operands(1, rows, 2, 128, seed=4)))
    np.testing.assert_allclose(o, want_o, atol=5e-6)
    for name, g, want in zip(("q", "k", "v", "phi", "mu"), grads, wants):
        assert _rel(g, want) < RTOL, name
    # the grids visit a window's own diagonal block, and a query block against each EARLIER window's summaries: no more
    tiles = {name: v for (name, shape), v in eva.GRID_TILES.items() if shape == (rows, 256, 2)}
    seen = windows * (windows - 1) // 2
    assert tiles == {"flash_attention_fwd": (windows,) * 2, "flash_attention_dq": (windows,) * 2, "flash_attention_dkv": (windows,) * 2,
                     "eva_remote_fwd": (seen,) * 2, "eva_remote_dq": (seen,) * 2, "eva_remote_dkv": (seen,) * 2}


@pytest.mark.parametrize("lead", [90.0, -90.0, 40.0, -40.0])
def test_one_source_far_above_the_other_neither_overflows_nor_loses_the_smaller(lead):
    """The remote forward starts from the local source's state and its first trip's ``alpha`` takes the whole gap
    between the sources at once (six windows: blocks that make wide trips, single ones and both). Every query
    gets the same component along ``u`` and ``mu`` moves every summary's key along ``u``, so the summaries' scores
    stand ``lead`` above the own window's lse, give or take 10 (below it where ``lead`` is negative). At 90 the gap
    passes 80 in every row and 88.7 in some, where ``exp`` without the joint maximum overflows; at 40 the SMALLER
    source, 1e-14 to 1e-21 of the sum and far under the output's tolerance, is still told apart in the leaves that
    only it reaches (the own window where the summaries lead: the last window's v, which no later window pools; the
    summaries where the own window leads: phi and mu. At 90 float32 flushes those in either form)."""
    window, scale = KERNELS["window"], KERNELS["scale"]
    q, k, v, phi, mu, w = _head_major(*operands(1, 6 * window, 2, 128, seed=5))
    u, along = jnp.ones((128,)) / 128 ** 0.5, 8.0
    q = q - (q @ u)[..., None] * u + along * u
    mu = mu + lead / (scale * along) * u
    ks, _ = eva.pool(k, v, phi, mu, chunk=KERNELS["chunk"], scale=scale)
    by_window = lambda x: x.reshape(1, 2, 6, window, 128)  # noqa: E731
    own = jnp.where(jnp.tril(jnp.ones((window, window), bool)), jnp.einsum("bhwqd,bhwkd->bhwqk", by_window(q), by_window(k)) * scale, -jnp.inf)
    gap = (jnp.einsum("bhwqd,bhcd->bhwqc", by_window(q), ks[:, :, :128]) * scale).max(-1) - jax.nn.logsumexp(own, axis=-1)
    gap = np.sign(lead) * np.asarray(gap[:, :, 1:])
    assert abs(lead) - 10 < gap.min() and gap.max() < abs(lead) + 10

    (o, grads), (want_o, wants) = _kernels_and_xla(q, k, v, phi, mu, w)
    assert all(bool(jnp.isfinite(x).all()) for x in (o, *grads))
    np.testing.assert_allclose(o, want_o, atol=5e-6)
    for name, g, want in zip(("q", "k", "v", "phi", "mu"), grads, wants):
        if name == "mu" and lead > 0:
            # (every summary moves alike under mu and the summaries hold all the weight: its cotangent cancels to rounding,
            # 4e-6 beside k's norm of 70)
            np.testing.assert_allclose(g, want, atol=2e-5)
        else:
            assert _rel(g, want) < RTOL, name
    if abs(lead) < 80:
        smaller = {"v of the last window": (grads[2][:, :, 5 * window:], wants[2][:, :, 5 * window:])} if lead > 0 else {
            "phi": (grads[3], wants[3]), "mu": (grads[4], wants[4])}
        for name, (g, want) in smaller.items():
            assert 0 < float(jnp.linalg.norm(want)) < 1e-10 and _rel(g, want) < 1e-4, name


def test_the_first_windows_rows_leave_the_remote_forward_as_the_local_kernels_wrote_them():
    """A block of the first window sees no summaries (``w = 0``): no trip runs, ``m = lse``, ``l = exp(0)``, and ``o``
    and ``lse`` pass through bit for bit. Every later window's rows change."""
    from llm_fine_tune_distributed_tpu.ops import flash_attention as flash

    window, chunk, scale = KERNELS["window"], KERNELS["chunk"], KERNELS["scale"]
    q, k, v, phi, mu, _ = _head_major(*operands(1, 3 * window, 2, 128, seed=6))
    ks, vs = eva.pool(k, v, phi, mu, chunk=chunk, scale=scale)
    o, residuals = eva._make_aggregate(window, chunk, scale, True).fwd(q, k, v, ks, vs)
    lse = residuals[-1]
    as_windows = lambda x: x.reshape(1, 2 * 3, window, 128)  # noqa: E731
    local_o, local_lse = flash._fwd(as_windows(q), as_windows(k), as_windows(v), jnp.ones((1, window), jnp.int32),
                                    scale=scale, block=window, groups=1, interpret=True)
    local_o, local_lse = local_o.reshape(o.shape), local_lse.reshape(lse.shape)
    np.testing.assert_array_equal(o[:, :, :window], local_o[:, :, :window])
    np.testing.assert_array_equal(lse[:, :, :window], local_lse[:, :, :window])
    assert float(jnp.abs(o[:, :, window:] - local_o[:, :, window:]).max()) > 1e-2
    assert float((lse[:, :, window:] - local_lse[:, :, window:]).min()) > 0  # a softmax over more keys


@pytest.mark.parametrize("shape, window, chunk, backend, mesh, said", [
    ((1, 32, 32768, 128), 2048, 16, "tpu", None, "kernels"),
    ((2, 32, 16384, 128), 2048, 16, "tpu", None, "kernels"),
    ((1, 32, 32768, 128), 2048, 16, "cpu", None, "xla (backend is cpu)"),
    ((1, 32, 32768, 128), 2048, 16, "tpu", 4, "xla (a mesh of 4 devices"),
    ((2, 4, 160, 16), 32, 4, "tpu", None, "xla (head of 16 is no multiple of 128 lanes)"),
    ((1, 32, 2048, 128), 2048, 16, "tpu", None, "xla (a row no longer than a window"),
    ((1, 32, 5120, 128), 2048, 16, "tpu", None, "xla (rows of 5120 are no whole windows of 2048)"),
    ((1, 32, 32768, 128), 4096, 16, "tpu", None, "xla (a window of 4096 is no single block"),
    ((1, 32, 32768, 128), 2048, 64, "tpu", None, "xla (a window's 32 summaries are no whole tiles of 128)"),
])
def test_which_form_a_call_takes_is_read_from_its_shapes_the_backend_and_the_mesh(monkeypatch, shape, window, chunk, backend, mesh, said):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    got = eva._program(shape, jnp.bfloat16, window=window, chunk=chunk, mesh=mesh and SimpleNamespace(size=mesh))
    assert got.startswith(said), got


def test_a_rematerialized_block_keeps_o_and_lse_by_the_keys_a_query_reads():
    big = get_preset("evabyte_6_5b")
    # 32,768: 3,969 keys a query (tokens of the window and summaries) against a hidden size of 4096: recompute
    assert not transformer.keeps_flash_outputs(big, 32768) and not transformer.keeps_flash_outputs(big, 2048)
    assert transformer.keeps_flash_outputs(big, 65536)
    transformer._remat_policy("full", big, 32768, None, "eva")
    assert transformer.REMAT_KEEPS["eva"] == ("full", ())


# -- what the mixer and the loss refuse, and what one head lowers to ------------------------------


def test_packing_a_cache_and_serving_are_refused_with_their_sentences():
    from llm_fine_tune_distributed_tpu.infer.generate import Generator, LatentAttentionNotServed

    params = transformer.init_params(jax.random.PRNGKey(0), MC)
    ids = jnp.zeros((1, 32), jnp.int32)
    with pytest.raises(NotImplementedError, match="packed.*windows and chunks would have to restart"):
        transformer.forward(params, ids, MC, segment_ids=jnp.ones((1, 32), jnp.int32), positions=jnp.arange(32)[None])
    cache = transformer.init_cache(MC, 1, 64)
    with pytest.raises(NotImplementedError, match="training form only; its cache is a window of keys and values beside"):
        transformer.forward(params, ids, MC, cache=cache)
    with pytest.raises(LatentAttentionNotServed, match="'tiny_evabyte' has EVA attention.*training path only"):
        Generator(params, MC, None)
    with pytest.raises(ValueError, match="8 next-token heads: loss_vocab_chunk"):
        step_mod.make_loss_fn(MC, TrainConfig(model_preset=None, loss_vocab_chunk=64, loss_chunk_size=None))
    with pytest.raises(ValueError, match="a row of 30 is no whole number"):
        transformer.forward(params, jnp.zeros((1, 30), jnp.int32), MC)


def _chunked_ce_sum_at_the_parent(params, hidden, targets, mask, model_config, chunk_size, compute_dtype):
    """``train/step.chunked_ce_sum`` as it stood before the heads (PR 45's tree), one mask: the yardstick of the test below."""
    b, s, h = hidden.shape
    pad = (-s) % chunk_size
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (s + pad) // chunk_size
    hc = hidden.reshape(b, n, chunk_size, h).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n, chunk_size).transpose(1, 0, 2)
    mcs = (mask.reshape(b, n, chunk_size).transpose(1, 0, 2),)

    @jax.checkpoint
    def one_chunk(args):
        h_c, t_c, m_cs = args
        logits = transformer.unembed(params, h_c, model_config, compute_dtype=compute_dtype, mesh=None)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, t_c)
        return jnp.stack([(ce * m).sum() for m in m_cs])

    return jax.lax.map(one_chunk, (hc, tc, mcs)).sum(axis=0)[0]


def test_a_model_of_one_head_lowers_to_the_text_it_lowered_to_before_the_heads():
    mc = get_preset("tiny")
    params = transformer.init_params(jax.random.PRNGKey(0), mc)
    hidden = jnp.ones((2, 100, mc.hidden_size), jnp.bfloat16)
    targets, mask = jnp.ones((2, 100), jnp.int32), jnp.ones((2, 100), jnp.float32)

    def text(fn):
        return jax.jit(lambda p, h, t, m: jax.grad(lambda p: fn(p, h, t, m, mc, 64, jnp.bfloat16))(p)).lower(params, hidden, targets, mask).as_text()

    assert text(step_mod.chunked_ce_sum) == text(_chunked_ce_sum_at_the_parent)


def test_the_published_row_is_read_verbatim():
    if not os.path.exists(CATALOG):
        pytest.skip("the driver's catalog is not installed here")
    import json

    with open(CATALOG) as f:
        row = [json.loads(line) for line in f if '"EvaByte"' in line][0]["config"]
    with open(os.path.join(REPO, "benchmarks/chipbench/configs/evabyte-6.5b-d10.json")) as f:
        cell = json.load(f)
    differs = {k for k, v in row.items() if cell.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(cell["reduced"]) and cell["published"] == {"num_hidden_layers": 32}
    assert from_hf_config(SimpleNamespace(**cell)) == from_hf_config(SimpleNamespace(**row)).replace(num_layers=10, head_dim=128)
