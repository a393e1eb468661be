"""Model core tests: shapes, param counts, KV-cache consistency, presets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_fine_tune_distributed_tpu.config import LayerPlan
from llm_fine_tune_distributed_tpu.models import PRESETS, get_preset, init_params
from llm_fine_tune_distributed_tpu.models.transformer import forward, init_cache
from llm_fine_tune_distributed_tpu.utils.tree import count_params


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_param_count_matches_formula(tiny):
    cfg, params = tiny
    assert count_params(params) == cfg.num_params


def test_smollm3_param_count_is_3b():
    # claude.md:243 reports 3.075B total params for SmolLM3-3B.
    cfg = get_preset("smollm3_3b")
    assert abs(cfg.num_params - 3.075e9) / 3.075e9 < 0.01


def test_forward_shapes_and_dtype(tiny):
    cfg, params = tiny
    ids = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], dtype=jnp.int32)
    logits, cache = forward(params, ids, cfg, compute_dtype=jnp.float32)
    assert logits.shape == (1, 8, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache is None
    assert np.isfinite(np.asarray(logits)).all()


def test_padding_mask_changes_nothing_for_valid_tokens(tiny):
    """Causal attention: masking out future padding must not change logits of
    real positions."""
    cfg, params = tiny
    ids_full = jnp.array([[5, 6, 7, 8, 1, 1, 1, 1]], dtype=jnp.int32)
    mask = jnp.array([[1, 1, 1, 1, 0, 0, 0, 0]], dtype=jnp.int32)
    lg_masked, _ = forward(params, ids_full, cfg, padding_mask=mask, compute_dtype=jnp.float32)
    lg_plain, _ = forward(params, ids_full[:, :4], cfg, compute_dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(lg_masked[:, :4]), np.asarray(lg_plain), rtol=1e-5, atol=1e-5
    )


@pytest.mark.slow
def test_kv_cache_decode_matches_full_forward(tiny):
    """Prefill + one-token-at-a-time decode must reproduce the full forward
    pass logits (the correctness gate for infer/generate.py)."""
    cfg, params = tiny
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, cfg.vocab_size)
    full_logits, _ = forward(params, ids, cfg, compute_dtype=jnp.float32)

    cache = init_cache(cfg, batch_size=2, max_len=16, dtype=jnp.float32)
    prefill_len = 6
    lg, cache = forward(
        params, ids[:, :prefill_len], cfg, cache=cache, cache_pos=0, compute_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(full_logits[:, :prefill_len]), rtol=2e-4, atol=2e-4
    )
    for t in range(prefill_len, 10):
        lg, cache = forward(
            params, ids[:, t : t + 1], cfg, cache=cache, cache_pos=t, compute_dtype=jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(full_logits[:, t]), rtol=2e-4, atol=2e-4
        )


@pytest.mark.slow
def test_remat_matches_no_remat(tiny):
    cfg, params = tiny
    ids = jnp.array([[1, 2, 3, 4]], dtype=jnp.int32)

    def loss(p, remat):
        lg, _ = forward(p, ids, cfg, compute_dtype=jnp.float32, remat=remat)
        return jnp.mean(lg**2)

    g1 = jax.grad(lambda p: loss(p, False))(params)
    g2 = jax.grad(lambda p: loss(p, True))(params)
    flat1 = jax.tree_util.tree_leaves(g1)
    flat2 = jax.tree_util.tree_leaves(g2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_untied_and_sliding_window_preset():
    cfg = get_preset("tiny_mistral")
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert "lm_head" in params
    ids = jnp.ones((1, 8), dtype=jnp.int32)
    logits, _ = forward(params, ids, cfg, compute_dtype=jnp.float32)
    assert logits.shape == (1, 8, cfg.vocab_size)


# What ModelConfig.layer says of every preset's layers, where it is not the
# Llama default (q/k/v heads, rope, no window, dense MLP on every layer).
_EVERY_4TH = lambda n: tuple(range(3, n, 4))  # noqa: E731
_EVEN_ONLY = lambda w: (lambda i: w if i % 2 == 0 else None)  # noqa: E731
_LEADING_DENSE = lambda i: "dense" if i == 0 else "grouped_experts"  # noqa: E731
_SCALED = dict(rope_kind=lambda i: "scaled")  # Llama-3.1's context extension: every layer's rope
# Mellum: three window layers with plain rope to one global layer with YaRN's; experts on every layer
_MELLUM = lambda w: dict(  # noqa: E731
    window=lambda i: None if i % 4 == 3 else w, rope_kind=lambda i: "scaled" if i % 4 == 3 else "plain",
    feed_forward=lambda i: "grouped_experts",
)
# Qwen3-Next: three linear-recurrence layers (no rope, no window) to one softmax layer; experts on every layer
_QWEN3_NEXT = dict(
    attention=lambda i: "heads" if i % 4 == 3 else "linear", nope=lambda i: i % 4 != 3,
    feed_forward=lambda i: "grouped_experts",
)
# afmoe (Trinity): three window layers with rope to one global layer WITHOUT; leading dense layers, then experts
_TRINITY = lambda w, dense: dict(  # noqa: E731
    window=lambda i: None if i % 4 == 3 else w, nope=lambda i: i % 4 == 3,
    feed_forward=lambda i: "dense" if i < dense else "grouped_experts",
)
# Kimi Linear: three Kimi Delta Attention layers to one latent layer WITHOUT rope (the 27th latent too: ``latent``
# the 0-based latent layers); one leading dense layer, then experts
_KIMI = lambda latent: dict(  # noqa: E731
    attention=lambda i: "latent" if i in latent else "kda", nope=lambda i: True, feed_forward=_LEADING_DENSE,
)
PLAN_OF_PRESET = {
    "llama3_1_8b": _SCALED, "llama3_2_1b": _SCALED, "llama3_2_3b": _SCALED,
    "mellum2_12b_a2_5b": _MELLUM(1024), "tiny_mellum": _MELLUM(32),
    "tiny": dict(nope=_EVERY_4TH(4)),
    "smollm3_3b": dict(nope=_EVERY_4TH(36)),  # 3, 7, ..., 35
    "tiny_mistral": dict(window=lambda i: 64),  # Mistral: on all layers
    "tiny_gemma2": dict(window=_EVEN_ONLY(8)),  # Gemma2: local on even layers, global on odd
    "gemma2_9b": dict(window=_EVEN_ONLY(4096)),
    "tiny_moe": dict(feed_forward=lambda i: "capacity_experts"),
    "mixtral_8x7b": dict(feed_forward=lambda i: "capacity_experts"),
    "moonlight_16b_a3b": dict(attention="latent", feed_forward=_LEADING_DENSE),
    "tiny_mla_moe": dict(attention="latent", feed_forward=_LEADING_DENSE),
    "qwen3_next_80b_a3b": _QWEN3_NEXT, "tiny_qwen3_next": _QWEN3_NEXT,
    "trinity_mini": _TRINITY(2048, 2), "tiny_trinity": _TRINITY(32, 1),
    "kimi_linear_48b_a3b": _KIMI((3, 7, 11, 15, 19, 23, 26)), "tiny_kimi_linear": _KIMI((3,)),
    "evabyte_6_5b": dict(attention="eva"), "tiny_evabyte": dict(attention="eva"),  # every layer, with rope
    # Granite 4.0-H: nine Mamba-2 layers to one softmax layer (at 5, 15, ...), no rope anywhere
    "granite_4_0_h_micro": dict(attention=lambda i: "heads" if i % 10 == 5 else "ssd", nope=lambda i: True),
    "tiny_granite_h": dict(attention=lambda i: "heads" if i % 10 == 5 else "ssd", nope=lambda i: True),
}


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_layer_plan_of_every_preset(name):
    cfg = get_preset(name)
    want = {"attention": "heads", "nope": (), "rope_kind": lambda i: "plain", "window": lambda i: None,
            "feed_forward": lambda i: "dense", **PLAN_OF_PRESET.get(name, {})}
    # (a preset whose layers differ in kind gives a function of the layer; the others a name, a tuple)
    attention = want["attention"] if callable(want["attention"]) else (lambda i: want["attention"])
    nope = want["nope"] if callable(want["nope"]) else (lambda i: i in want["nope"])
    for i in range(cfg.num_layers):
        assert cfg.layer(i) == LayerPlan(
            attention=attention(i), rope=not nope(i), rope_kind=want["rope_kind"](i),
            window=want["window"](i), feed_forward=want["feed_forward"](i),
        ), (name, i)
    # hashable; no preset has more than two kinds but afmoe's three (dense + window, experts + window, experts + global)
    # and Kimi Linear's (two mixers and two feed-forwards in one model: dense + KDA, experts + KDA, experts + latent)
    assert len({cfg.layer(i) for i in range(cfg.num_layers)}) <= (3 if "trinity" in name or "kimi" in name else 2)


@pytest.mark.parametrize("name, counted, step_counters", [
    ("tiny", set(), set()),
    ("tiny_moe", {"router_aux"}, set()),
    ("tiny_mla_moe", {"expert_load"}, {"expert_pairs_per_token", "expert_load", "expert_load_max_over_mean"}),
])
def test_report_of_what_the_layers_counted(name, counted, step_counters):
    """One pytree, its structure fixed by the plan (``report_shapes``);
    ``forward`` is the first two results; the loss function and the step
    read it by key."""
    import optax

    from llm_fine_tune_distributed_tpu.config import TrainConfig
    from llm_fine_tune_distributed_tpu.models.transformer import forward_with_report, report_shapes
    from llm_fine_tune_distributed_tpu.train.state import TrainState
    from llm_fine_tune_distributed_tpu.train.step import build_train_step, make_loss_fn
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

    cfg = get_preset(name)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 2, 16)), jnp.int32)
    tc = TrainConfig(model_preset=name, compute_dtype="float32", freeze_strategy="none",
                     per_device_batch_size=2, gradient_accumulation_steps=2, max_seq_length=16)
    batch = {"input_ids": ids, "loss_mask": jnp.ones(ids.shape, jnp.float32),
             "attention_mask": jnp.ones(ids.shape, jnp.int32)}
    micro = jax.tree.map(lambda a: a[0], batch)
    flat = flatten_dict(params)
    run = lambda p, i: forward_with_report(p, i, cfg, compute_dtype=jnp.float32)  # noqa: E731
    shape_of = lambda tree: jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), tree)  # noqa: E731

    # structure, by shapes alone (no compile): the report, the loss function's stats, the step's metrics
    _, _, report = jax.eval_shape(run, params, ids[0])
    assert set(report) == counted
    assert shape_of(report) == shape_of(report_shapes(cfg))
    _, stats = jax.eval_shape(make_loss_fn(cfg, tc), flat, {}, micro)
    assert set(stats) == {"tokens"} | (counted & {"expert_load"})
    optimizer = optax.sgd(1e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), trainable=flat, frozen={}, opt_state=optimizer.init(flat))
    _, metrics = jax.eval_shape(build_train_step(cfg, tc, optimizer), state, batch)
    assert set(metrics) == {"loss", "grad_norm"} | step_counters
    if "expert_load" in report:
        held = len(cfg.held_expert_ids)
        assert report["expert_load"].shape == (cfg.num_layers - 1, held)  # one leading dense layer
        assert metrics["expert_load"].shape == (held,)
        return  # its numbers against the reference: tests/test_mla_moe.py

    # numbers: forward is the first two results, and the balancing loss joins the train objective only
    both = jax.jit(lambda p, i: (run(p, i), forward(p, i, cfg, compute_dtype=jnp.float32)))
    (out, cache, report), (plain, plain_cache) = both(params, ids[0])
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(out))
    assert plain_cache is None and cache is None
    if "router_aux" in report:
        loss, _ = make_loss_fn(cfg, tc)(flat, {}, micro)
        ce, _ = make_loss_fn(cfg, tc, include_router_aux=False)(flat, {}, micro)
        want = cfg.router_aux_coef * float(report["router_aux"]) / cfg.num_layers  # the layer-mean
        np.testing.assert_allclose(float(loss) - float(ce), want, rtol=1e-4)


def test_smollm3_nope_pattern():
    cfg = get_preset("smollm3_3b")
    # every 4th layer (1-indexed) has NO rope — HF SmolLM3Config convention.
    assert not cfg.layer(3).rope and not cfg.layer(7).rope and not cfg.layer(35).rope
    assert cfg.layer(0).rope and cfg.layer(34).rope
    assert sum(cfg.no_rope_layers) == 27


def test_qk_norm_cache_decode_and_grad():
    """Qwen3-style qk_norm: cached decode matches the full forward, and the
    norm weights receive gradient (they sit inside the attention block)."""
    cfg = get_preset("tiny").replace(qk_norm=True, name="tiny_qwen3")
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    full_logits, _ = forward(params, ids, cfg, compute_dtype=jnp.float32)

    cache = init_cache(cfg, batch_size=2, max_len=8, dtype=jnp.float32)
    lg, cache = forward(params, ids[:, :5], cfg, cache=cache, cache_pos=0,
                        compute_dtype=jnp.float32)
    for t in range(5, 8):
        lg, cache = forward(params, ids[:, t:t + 1], cfg, cache=cache,
                            cache_pos=t, compute_dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(full_logits[:, t]),
            rtol=2e-4, atol=2e-4,
        )

    def loss(p):
        out, _ = forward(p, ids, cfg, compute_dtype=jnp.float32)
        return jnp.mean(out**2)

    g = jax.jit(jax.grad(loss))(params)
    gq = g["model"]["layers"]["0"]["self_attn"]["q_norm"]["weight"]
    assert float(jnp.abs(gq).sum()) > 0.0


def test_auto_remat_policy_by_size_and_seq():
    """Auto remat resolution per (model size, seq) cell."""
    from llm_fine_tune_distributed_tpu.config import TrainConfig

    small, big = get_preset("smollm3_3b"), get_preset("llama3_8b")
    assert TrainConfig(max_seq_length=1024).resolved_remat_policy(small) == "dots_no_batch"
    assert TrainConfig(max_seq_length=4096).resolved_remat_policy(small) == "mlp"
    assert TrainConfig(max_seq_length=8192).resolved_remat_policy(small) == "full"
    # seq-parallel: the ledger keys on PER-CHIP seq — global 8k over a
    # 4-chip seq axis is 2k/chip, back to the fastest policy
    assert (
        TrainConfig(max_seq_length=8192).resolved_remat_policy(small, seq_parallel_size=4)
        == "dots_no_batch"
    )
    assert (
        TrainConfig(max_seq_length=8192).resolved_remat_policy(small, seq_parallel_size=2)
        == "mlp"
    )
    assert TrainConfig(max_seq_length=1024).resolved_remat_policy(big) == "full"
    assert (
        TrainConfig(max_seq_length=4096, remat_policy="dots").resolved_remat_policy(small)
        == "dots"
    )


def test_static_seq_parallel_size_gates_on_live_seq_path(eight_devices):
    """The auto remat policy must key on the seq sharding that ACTUALLY
    applies (ADVICE r4): a provisioned seq axis counts only when the
    attention impl is ring/ulysses AND the static preconditions hold —
    otherwise runtime falls back to full per-chip sequences and a divided
    policy would under-remat and OOM."""
    from jax.sharding import Mesh

    from llm_fine_tune_distributed_tpu.config import MeshConfig, TrainConfig
    from llm_fine_tune_distributed_tpu.runtime.mesh import make_mesh
    from llm_fine_tune_distributed_tpu.train.step import static_seq_parallel_size

    small = get_preset("smollm3_3b")
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, tensor=1, seq=4), eight_devices)

    # live seq axis + ring + divisible -> the axis counts
    tc = TrainConfig(max_seq_length=8192, attention_impl="ring")
    assert static_seq_parallel_size(small, tc, mesh) == 4
    # seq axis provisioned but attention_impl is not sequence-parallel:
    # runtime never shards the sequence -> full per-chip seq
    tc = TrainConfig(max_seq_length=8192, attention_impl="flash")
    assert static_seq_parallel_size(small, tc, mesh) == 1
    # indivisible seq length -> runtime fallback -> full per-chip seq
    tc = TrainConfig(max_seq_length=8190, attention_impl="ring")
    assert static_seq_parallel_size(small, tc, mesh) == 1
    # ulysses capped by kv heads: smollm3 has 4 kv heads, seq=4 divides ->
    # live; a model with 2 kv heads on seq=4 falls back
    tc = TrainConfig(max_seq_length=8192, attention_impl="ulysses")
    assert static_seq_parallel_size(small, tc, mesh) == 4
    assert static_seq_parallel_size(get_preset("tiny"), tc, mesh) == 1
    # sliding-window models: seq-parallel impls reject windows
    tc = TrainConfig(max_seq_length=8192, attention_impl="ring")
    assert static_seq_parallel_size(get_preset("mistral_7b").replace(
        sliding_window=4096), tc, mesh) == 1
    # no mesh -> 1
    assert static_seq_parallel_size(small, tc, None) == 1


def test_gemma2_preset_param_count_and_decode():
    """gemma2_9b preset arithmetic (9.24B, HF google/gemma-2-9b) and
    KV-cache decode self-consistency for the full Gemma2 feature set
    (sandwich norms, softcaps, alternating local/global window)."""
    cfg9 = get_preset("gemma2_9b")
    assert 9.0e9 < cfg9.num_params < 9.5e9
    # local/global alternation
    assert cfg9.layer(0).window == 4096
    assert cfg9.layer(1).window is None

    tiny = cfg9.replace(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, sliding_window=6,
        query_pre_attn_scalar=16.0, max_position_embeddings=64,
    )
    params = init_params(jax.random.PRNGKey(0), tiny, dtype=jnp.float32)
    assert count_params(params) == tiny.num_params
    l0 = params["model"]["layers"]["0"]
    assert "pre_feedforward_layernorm" in l0
    assert float(l0["input_layernorm"]["weight"].sum()) == 0.0  # zero-centered

    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, tiny.vocab_size)
    full_logits, _ = forward(params, ids, tiny, compute_dtype=jnp.float32)

    cache = init_cache(tiny, batch_size=2, max_len=12, dtype=jnp.float32)
    lg, cache = forward(params, ids[:, :7], tiny, cache=cache, cache_pos=0,
                        compute_dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(full_logits[:, :7]), rtol=2e-4, atol=2e-4
    )
    for t in range(7, 12):
        lg, cache = forward(params, ids[:, t:t + 1], tiny, cache=cache,
                            cache_pos=t, compute_dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(full_logits[:, t]),
            rtol=2e-4, atol=2e-4,
        )


def test_new_families_shard_on_mesh():
    """Qwen3 (qk_norm) and Gemma2 (sandwich norms etc.) param trees shard
    and train-step on the 8-device mesh: the new 1-D leaves replicate, the
    jitted fwd+grad matches the unsharded forward."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llm_fine_tune_distributed_tpu.config import MeshConfig
    from llm_fine_tune_distributed_tpu.parallel.sharding import param_sharding_rules
    from llm_fine_tune_distributed_tpu.runtime.mesh import make_mesh

    mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2, seq=1))
    for preset, tweak in (
        ("tiny", dict(qk_norm=True, name="tiny_qwen3")),
        ("tiny_gemma2", {}),
    ):
        cfg = get_preset(preset).replace(**tweak)
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
        ids = jnp.ones((4, 16), jnp.int32)

        def loss(p, sharding=None):
            lg, _ = forward(p, ids, cfg, compute_dtype=jnp.float32,
                            activation_sharding=sharding)
            return lg.mean(), lg

        (_, ref_logits), ref_grads = jax.value_and_grad(loss, has_aux=True)(params)
        sharded = jax.tree_util.tree_map(
            jax.device_put, params, param_sharding_rules(params, mesh)
        )
        act = NamedSharding(mesh, P(("data", "fsdp"), None, None))
        (_, lg), g = jax.jit(
            jax.value_and_grad(lambda p: loss(p, act), has_aux=True)
        )(sharded)
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(ref_grads), jax.tree_util.tree_leaves(g)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
            )


# -- the families' suite keeps its promise (tests/family_suite.py) ----------------------------


def test_every_family_states_the_whole_description_and_keeps_no_helper_of_its_own():
    """One suite, one file a family: every file that subclasses ``FamilySuite`` states a whole ``Family`` (a field has
    no default, so a family that lacks one fails at import and no shared test is silently skipped for it), inherits
    every shared test as the suite wrote it, says what its cell's compiled step must hold, and defines none of the
    suite's helpers or fixtures under its own roof (other files' helpers of those names are theirs)."""
    import ast
    import dataclasses
    import glob
    import importlib
    import os

    import family_suite
    from family_suite import Family, FamilySuite, ModelSuite

    described = [c for c in vars(family_suite).values() if dataclasses.is_dataclass(c) and c.__module__ == "family_suite"]
    assert Family in described and len(described) == 7  # (Model: what a family without experts states, tests/test_eva.py)
    assert all(f.default is dataclasses.MISSING and f.default_factory is dataclasses.MISSING
               for c in described for f in dataclasses.fields(c))
    may_be_none = {"redraw", "shared_once", "mc"}  # each read by the suite where it is None: "as drawn", "nothing", "its own"
    shared = {name for suite in (ModelSuite, FamilySuite) for name in vars(suite) if name.startswith("test_")}
    assert len(shared) == 13
    theirs = {"_params", "_rel", "_state", "_train_config", "_batch", "_logits", "_logit_gap", "_bfloat16_gaps",
              "flat", "ids", "two_steps", "one_step"}
    families = {}
    for path in sorted(glob.glob(os.path.join(os.path.dirname(__file__), "test_*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        suites = [n.name for n in tree.body if isinstance(n, ast.ClassDef) and any(getattr(b, "id", "") == "FamilySuite" for b in n.bases)]
        if not suites:
            continue
        defined = {n.name for n in ast.walk(tree) if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
        defined |= {t.id for n in ast.walk(tree) if isinstance(n, ast.Assign) for t in n.targets if isinstance(t, ast.Name)}
        assert not defined & theirs, (path, defined & theirs)
        module = importlib.import_module(os.path.splitext(os.path.basename(path))[0])
        for name in suites:
            suite = getattr(module, name)
            families[os.path.basename(path)] = suite.family.mc.name
            for part in [suite.family] + [v for v in vars(suite.family).values() if type(v) in described]:
                assert type(part) in described
                assert not [k for k, v in vars(part).items() if v is None and k not in may_be_none], (path, part)
            assert all(getattr(suite, test) is getattr(FamilySuite, test) for test in shared), path
            assert suite.check_the_cells_step is not FamilySuite.check_the_cells_step, path
            assert not hasattr(suite, "pytestmark") and not hasattr(module, "pytestmark"), path  # nothing skipped wholesale
    assert set(families) == {"test_mla_moe.py", "test_swa_moe.py", "test_gdn_moe.py", "test_afmoe.py", "test_kda_moe.py"}
    assert len(set(families.values())) == 5  # five models, not one description five times
