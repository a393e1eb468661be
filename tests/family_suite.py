"""What a model family has to hold against its plain reference, written once.

A family (``tests/test_mla_moe.py``, ``test_swa_moe.py``, ``test_gdn_moe.py``,
``test_afmoe.py``, ``test_kda_moe.py``: one file each, because under ``--dist
loadfile`` a file is what one worker carries) states a ``Family`` and
subclasses ``FamilySuite``; the subclass inherits the shared tests below, fills
the hooks (``check_*``) with what only it asserts, and adds the tests no other
family has as methods of its own. Nothing here is collected by itself (no
``test_`` prefix, no ``Test`` class).

The helpers (``_params``, ``_rel``, ``_state``, ``_train_config``, ``_batch``,
``_logits``, ``_logit_gap``, ``_bfloat16_gaps``) and the ``two_steps`` fixture live here and nowhere
else (``tests/test_model.py`` holds the families' files to that). The fixture
builds ONE float32 step a family, compiled once, and hands out the executable,
its text and its results: a test at the same shapes calls it and compiles
nothing. Forward passes run under ``jax.jit`` (eagerly a pass compiles every
primitive by itself, several times the cost).
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
from types import ModuleType, SimpleNamespace
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from llm_fine_tune_distributed_tpu.config import ModelConfig, TrainConfig
from llm_fine_tune_distributed_tpu.models import hf_io, transformer
from llm_fine_tune_distributed_tpu.models.configs import from_hf_config, get_preset, to_hf_dict
from llm_fine_tune_distributed_tpu.models.transformer import forward_with_report, init_params
from llm_fine_tune_distributed_tpu.ops import moe
from llm_fine_tune_distributed_tpu.parallel.freeze import trainable_mask
from llm_fine_tune_distributed_tpu.parallel.pipeline import layer_scan_problems
from llm_fine_tune_distributed_tpu.parallel.sharding import param_spec
from llm_fine_tune_distributed_tpu.train.state import TrainState
from llm_fine_tune_distributed_tpu.train.step import build_train_step
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import check, weights  # noqa: E402

RECIPE = {"learning_rate": 1e-3, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8, "max_grad_norm": 1.0,
          "lr_schedule": "constant", "optimizer": "adamw", "weight_decay": 0.0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BIAS = "e_score_correction_bias"
# The softmax mixer's IN pass (PR 41): one forward and one backward Mosaic program a MODEL
IN_PASS_PROGRAMS = {"attn_in_fwd": 1, "attn_in_bwd": 1}


# -- the description ----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Shares:
    """How the held experts are cut into shares (the share test)."""

    count: int                       # programs, each holding ``experts / count`` experts in a row
    layer: int                       # the layer of the uncut weights whose experts are cut
    tokens: int                      # tokens a row of the two rows of activations
    experts_key: str                 # the benchmark configuration's name for how many experts a program holds
    shared_once: Optional[Callable]  # (lp, h, items) -> what every share computes alike, counted once; None: nothing
    bias: bool                       # the selection bias set to one that moves choices (a zero buffer otherwise)
    mc: Optional[ModelConfig]        # another model than the family's (the deployment's count of experts); None: its own


@dataclasses.dataclass(frozen=True)
class Refusals:
    """The published keys the family's loader refuses by name."""

    base: dict       # a small published configuration the loader takes
    cases: tuple     # (key, value): ``base`` with each is refused
    match: Callable  # key -> what the ValueError says


@dataclasses.dataclass(frozen=True)
class Published:
    """The published configuration in the driver's catalog, and the cell cut from it."""

    catalog_name: str
    preset: str
    tiny: str
    params: tuple    # (above, below): the published model's parameter count
    cut: dict        # the cell's overrides of the published configuration...
    cut_params: int  # ...and what it then counts


@dataclasses.dataclass(frozen=True)
class Rules:
    """Sharding, freeze and pipeline rules over the family's leaves."""

    specs: dict           # leaf path -> (ndim, the axes of its PartitionSpec)
    mc: ModelConfig       # the model the freeze and the pipeline are asked about
    unfreeze_last_n: int  # ``last_n_and_head`` with this many layers...
    trained: tuple        # ...trains these leaves
    held: tuple           # ...and not these
    scan_problems: tuple  # (overrides of ``mc``, what the layer scan's one refusal names)


@dataclasses.dataclass(frozen=True)
class CellStep:
    """The cell's step as its traffic file states it, cut in depth: the deviceless compile for v5e."""

    preset: str
    seq: int
    rows: int              # a microbatch (of two)
    overrides: dict        # of the preset: depth, the chip's share of experts and vocabulary
    float32_moments: bool  # Adam's moments float32 beside bfloat16 masters, where the memory line is held


@dataclasses.dataclass(frozen=True)
class Model:
    """What any model family states, with routed experts or without (``ModelSuite``)."""

    mc: ModelConfig             # the configuration under test
    bench_cfg: Callable         # ModelConfig -> the benchmark's configuration dict (the published names)
    weights: ModuleType         # benchmarks/chipbench/weights_*.py
    ref: ModuleType             # benchmarks/chipbench/reference_*.py (imports nothing of the program)
    redraw: Optional[Callable]  # flat -> flat: leaves drawn otherwise than the benchmark draws them; None: as drawn
    rows: int
    seq: int
    accum: int
    rtol: float                 # logits, loss, every gradient (the family's docstring says why)
    delta_tol: float            # the worst leaf's change over two steps at bfloat16 masters
    buffers: tuple              # the leaves ``trainable_mask`` holds back though nothing is frozen
    checkpoint_names: tuple     # HF names the stored state has to hold
    refusals: Refusals
    published: Published


@dataclasses.dataclass(frozen=True)
class Family(Model):
    """A family of routed experts: what ``FamilySuite`` asks besides."""

    pairs_per_token: tuple      # (above, below): the step's ``expert_pairs_per_token`` at this seed
    shares: Shares
    rules: Rules
    cell: CellStep


# -- the helpers --------------------------------------------------------------


def _params(flat, dtype=jnp.float32):
    return weights.nest({k: v.astype(dtype) for k, v in flat.items()})


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def _state(family, flat, tc, dtype):
    params = _params(flat, dtype)
    mask = flatten_dict(trainable_mask(params, family.mc, tc))
    assert tuple(k for k, on in mask.items() if not on) == family.buffers  # nothing else is frozen
    optimizer = optax.chain(optax.clip_by_global_norm(RECIPE["max_grad_norm"]),
                            optax.adamw(RECIPE["learning_rate"], weight_decay=0.0))
    every = flatten_dict(params)
    trainable = {k: v for k, v in every.items() if mask[k]}
    return optimizer, TrainState(step=jnp.zeros((), jnp.int32), trainable=trainable,
                                 frozen={k: v for k, v in every.items() if not mask[k]},
                                 opt_state=optimizer.init(trainable))


def _train_config(family, param_dtype):
    return TrainConfig(model_preset=None, compute_dtype="float32", param_dtype=param_dtype,
                       gradient_checkpointing=True, remat_policy="full", freeze_strategy="none",
                       per_device_batch_size=family.rows, gradient_accumulation_steps=family.accum,
                       max_seq_length=family.seq)


def _batch(ids, real=None):
    """A step's batch; ``real``: rows of that many tokens padded on the right to the row length."""
    mask = np.ones(ids.shape, np.float32) * (1.0 if real is None else np.arange(ids.shape[-1]) < real)
    return {"input_ids": jnp.asarray(ids), "loss_mask": jnp.asarray(mask, jnp.float32),
            "attention_mask": jnp.asarray(mask, jnp.int32)}


def _logits(params, ids, mc, dtype=jnp.float32, **kwargs):
    """``forward_with_report`` as ONE compiled program: (logits, report)."""
    out = jax.jit(lambda p, x: forward_with_report(p, x, mc, compute_dtype=dtype, **kwargs))(params, jnp.asarray(ids))
    return out[0], out[2]


def _logit_gap(family, flat, ids, mc=None, params=None):
    """How far the program's logits stand from the reference's with a part of ``mc`` or ``params`` another's."""
    got = _logits(params or _params(flat), ids[0, 0], mc or family.mc)[0]
    return _rel(got, family.ref.logits(flat, family.bench_cfg(family.mc), ids[0, 0]))


def _bfloat16_gaps(family, flat, ids):
    """The cell's compute dtype against the float32 reference: how far the logits stand, and the loss."""
    want = family.ref.logits(flat, family.bench_cfg(family.mc), ids[0, 0])
    got = _logits(_params(flat, jnp.bfloat16), ids[0, 0], family.mc, jnp.bfloat16)[0]

    def loss(logits):
        logp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)[:, :-1], axis=-1)
        return -float(jnp.take_along_axis(logp, jnp.asarray(ids[0, 0])[:, 1:, None], axis=-1).mean())

    return _rel(got, want), abs(loss(got) - loss(want))


def mosaic_calls(text, kernel):
    """How many Mosaic calls of a compiled step's text stand under ``/kernel/``."""
    return sum("tpu_custom_call" in line and f"/{kernel}/" in line for line in text.splitlines())


def sum_kernel_calls(text):
    """The paths (``op_name``) of the Mosaic calls that sum rows into tokens."""
    found = (re.search(r'op_name="([^"]*/sum_held_rows/pallas_call)"', ln) for ln in text.splitlines() if "tpu_custom_call" in ln)
    return [m.group(1) for m in found if m]


def assert_two_sums_an_expert_layer(text, expert_layers):
    """One call forward (``sum_rows``) and one backward (``take_rows``'
    transpose) an expert layer for the first chunk, the same two again inside
    the overflow chunks' ``cond``, and none recomputed: the block's last
    operation is dead in the recompute."""
    calls = sum_kernel_calls(text)
    assert len(calls) == 4 * expert_layers, calls
    first_chunk = [c for c in calls if "/cond/" not in c]
    assert len(first_chunk) == 2 * expert_layers and sum("transpose(" in c for c in first_chunk) == expert_layers
    assert not [c for c in calls if "rematted_computation" in c]
    assert all("/mlp/experts/" in c and "gmm" not in c and "flash_attention" not in c for c in calls)


def xla_remats(text):
    """The instructions XLA's own rematerialization added to an optimized program (it names them ``<name>.remat``)."""
    return re.findall(r"%[\w.\-]*\.remat[\w.\-]* = ", text)


def kernel_passes(text, scope, kernels):
    """(``jvp(layerN)`` or its transpose, recomputed or not, [scope,] kernel) of every Mosaic call of ``kernels`` (a
    pattern with ONE group, the kernel's name) under ``scope`` (a pattern; with a group of its own it is reported)."""
    n = 3 + re.compile(scope).groups
    return sorted(re.findall(
        r'op_name="[^"]*?/(transpose\(jvp\(layer\d\)\)|jvp\(layer\d\))/(?:[^"]*?/)?(rematted_computation/)?' + scope
        + rf'/jit\(({kernels})\)/\{n}/pallas_call"', "\n".join(ln for ln in text.splitlines() if "tpu_custom_call" in ln)))


def mixer_passes(layers):
    """What ``kernel_passes`` finds of a linear mixer's two fused passes (PR 39) in ``layers``: the forward kernels in
    the forward and the recomputed pass, the backward kernels once."""
    return sorted(
        found for i in layers for scope, way in (("gdn_conv", "in"), ("gdn_gate_norm", "out")) for found in (
            (f"jvp(layer{i})", "", scope, f"gdn_{way}_fwd"), (f"transpose(jvp(layer{i}))", "rematted_computation/", scope, f"gdn_{way}_fwd"),
            (f"transpose(jvp(layer{i}))", "", scope, f"gdn_{way}_bwd")))


# -- the suite ----------------------------------------------------------------


class ModelSuite:
    """The shared tests of any model against its plain reference, with routed experts or without; a subclass
    (``Test...``) sets ``family`` (a ``Model``) and overrides the hooks it has something for. ``FamilySuite`` adds
    what only a family of routed experts is asked."""

    family: Model

    def pytest_generate_tests(self, metafunc):
        if "refused" in metafunc.fixturenames:  # one case a refused key, each counted
            cases = metafunc.cls.family.refusals.cases
            metafunc.parametrize("refused", cases, ids=[f"{key}-{i}" for i, (key, _) in enumerate(cases)])

    # hooks: what only one family asserts, beside the shared assertions of the test that calls each
    def check_leaves(self, own): pass
    def check_gradients(self, got): pass
    def check_report(self, report, flat, ids): assert report == {}
    def check_published(self, mc, config): pass
    def check_refusal_base(self, mc): pass
    def check_checkpoint(self, state, params, flat): pass

    @pytest.fixture(scope="class")
    def flat(self):
        f = self.family
        flat = f.weights.make_flat(11, f.bench_cfg(f.mc))
        return f.redraw(flat) if f.redraw else flat

    @pytest.fixture(scope="class")
    def ids(self):
        f = self.family
        return np.random.RandomState(5).randint(0, f.mc.vocab_size, (2, f.accum, f.rows, f.seq)).astype(np.int32)  # two steps

    @pytest.fixture(scope="class")
    def two_steps(self, flat, ids):
        """Two optimizer steps through ``build_train_step`` (the normal path:
        freeze split, optimizer, scopes), at float32 masters for the gradients
        (ONE compiled step, handed out as ``step`` with its ``text`` and the
        ``state`` it started from) and at the cell's bfloat16 masters for the
        parameters' change, and the reference's two steps."""
        f = self.family
        tc = _train_config(f, "float32")
        optimizer, state = _state(f, flat, tc, jnp.float32)
        step = jax.jit(build_train_step(f.mc, tc, optimizer)).lower(state, _batch(ids[0])).compile()
        new_state, metrics = step(state, _batch(ids[0]))
        mu = new_state.opt_state[1][0].mu
        tc16 = _train_config(f, "bfloat16")
        optimizer16, state16 = _state(f, flat, tc16, jnp.bfloat16)
        step16 = jax.jit(build_train_step(f.mc, tc16, optimizer16))
        before = {k: np.asarray(v, np.float32) for k, v in state16.trainable.items()}
        for batch in ids:
            state16, _ = step16(state16, _batch(batch))
        delta = {k: float(np.linalg.norm(np.asarray(v, np.float32) - before[k])) for k, v in state16.trainable.items()}
        # (copies: the reference's optimizer donates the leaves it replaces, and asks for them afresh)
        want = f.ref.sft_reference({k: jnp.array(v) for k, v in flat.items()}, f.bench_cfg(f.mc), RECIPE, list(ids),
                                   lambda names: {k: flat[k] for k in names}, keep_first_grad=True)
        return {"step": step, "text": step.as_text(), "state": state, "new": new_state, "metrics": metrics,
                "delta": delta, "want": want, "first_grad": {k: np.asarray(v) / (1 - RECIPE["adam_b1"]) for k, v in mu.items()}}

    def test_leaves_and_parameter_count_agree_with_the_benchmarks_weights(self):
        f = self.family
        own = flatten_dict(init_params(jax.random.PRNGKey(0), f.mc))
        shapes = f.weights.leaf_shapes(f.bench_cfg(f.mc))
        assert {k: v.shape for k, v in own.items()} == shapes
        assert f.mc.num_params == sum(int(np.prod(s)) for s in shapes.values())
        assert all(k in own for k in f.buffers)
        self.check_leaves(own)

    def test_forward_logits_agree_with_the_reference(self, flat, ids):
        f = self.family
        got, report = _logits(_params(flat), ids[0, 0], f.mc)
        assert _rel(got, f.ref.logits(flat, f.bench_cfg(f.mc), ids[0, 0])) < f.rtol
        self.check_report(report, flat, ids)
    def test_loss_and_gradient_norm_agree_with_the_reference(self, two_steps):
        assert abs(float(two_steps["metrics"]["loss"]) - two_steps["want"]["losses"][0]) < self.family.rtol
        assert abs(float(two_steps["metrics"]["grad_norm"]) / two_steps["want"]["grad_norm"] - 1) < self.family.rtol

    def test_every_leafs_gradient_agrees_with_the_reference(self, two_steps):
        got, want = two_steps["first_grad"], two_steps["want"]["first_grad"]
        assert sorted(got) == sorted(want) and not set(want) & set(self.family.buffers)
        worst = max((_rel(got[k], want[k]), k) for k in want)
        assert worst[0] < self.family.rtol, worst
        self.check_gradients(got)

    def test_two_steps_parameter_change_agrees_with_the_reference(self, two_steps):
        """The norm by leaf of what two AdamW steps changed, bfloat16 masters on
        both sides (the update computed in float32, the sum rounded once a step):
        the benchmark's own comparison. Where the two float32 sums differ in their
        last bits a rounding to bfloat16 falls the other way, an element here and
        there by 2^-8 of its value: ``delta_tol`` (3e-3 where the worst observed
        is 7e-4, 5e-3 where it is 2e-3); a step left out, or a second step from
        the wrong moments, is 0.3 and more."""
        gap, where = check.worst_leaf_gap(two_steps["delta"], two_steps["want"]["delta_norms"])
        assert gap < self.family.delta_tol, (gap, where)
        # a buffer is no parameter: the step hands it back as it came
        frozen, came = two_steps["new"].frozen, two_steps["state"].frozen
        assert tuple(frozen) == self.family.buffers
        assert all(np.array_equal(np.asarray(frozen[k]), np.asarray(came[k])) for k in came)

    def test_published_config_builds_and_round_trips(self):
        p = self.family.published
        if not os.path.exists(CATALOG):
            pytest.skip("the driver's catalog is not installed here")
        with open(CATALOG) as f:
            row = [json.loads(line) for line in f if f'"{p.catalog_name}"' in line][0]
        mc = from_hf_config(SimpleNamespace(**row["config"]))  # verbatim
        assert dataclasses.replace(mc, name=p.preset) == get_preset(p.preset)
        assert p.params[0] < mc.num_params < p.params[1]
        assert mc.replace(**p.cut).num_params == p.cut_params  # the cell's count
        for preset in (p.preset, p.tiny):
            assert from_hf_config(SimpleNamespace(**to_hf_dict(get_preset(preset)))) == get_preset(preset)
        self.check_published(mc, row["config"])

    def test_what_is_not_implemented_is_refused_by_name(self, refused):
        r, (key, value) = self.family.refusals, refused
        self.check_refusal_base(from_hf_config(SimpleNamespace(**r.base)))
        with pytest.raises(ValueError, match=r.match(key)):
            from_hf_config(SimpleNamespace(**dict(r.base, **{key: value})))

    def test_checkpoint_names_round_trip(self, flat):
        f = self.family
        params = _params(flat)
        state = hf_io.pytree_to_hf_state_dict(params, f.mc)
        for name in f.checkpoint_names:
            assert name in state, name
        back = flatten_dict(hf_io.hf_state_dict_to_pytree(state, f.mc))
        for k, v in flatten_dict(params).items():
            np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(v), err_msg=k)
        self.check_checkpoint(state, params, flat)


class FamilySuite(ModelSuite):
    """``ModelSuite`` for a family of routed experts (``family`` a ``Family``): the step's expert counters, the shares
    that add up, the sharding, freeze and pipeline rules, the router's precision, the cell's compiled step."""

    family: Family

    def check_counters(self, two_steps, ids): pass
    def check_shares(self, parts, lp, h, items, shared_once): pass
    def check_rules(self, monkeypatch): pass
    def before_the_cells_step(self, monkeypatch): pass

    def check_the_cells_step(self, step):
        """What the compiled text of the family's cell must hold: every family has something."""
        raise NotImplementedError(f"{type(self).__name__} does not say what its cell's compiled step holds")

    def check_report(self, report, flat, ids):
        """The program's counter against the reference's selection, expert layer by expert layer."""
        f = self.family
        assert set(report) == {"expert_load"}
        chosen = f.ref.selections(flat, f.bench_cfg(f.mc), ids[0, 0])
        assert sorted(chosen) == [i for i in range(f.mc.num_layers) if f.mc.layer(i).feed_forward == "grouped_experts"]
        held = list(f.mc.held_expert_ids)
        want_load = np.stack([np.asarray(chosen[i]).sum((0, 1))[held] for i in sorted(chosen)])
        differ = float(np.abs(np.asarray(report["expert_load"]) - want_load).sum()) / (ids[0, 0].size * f.mc.num_experts_per_tok * len(chosen))
        print(f"share of (token, expert) choices on which program and reference differ: {differ:.2e}")
        np.testing.assert_array_equal(np.asarray(report["expert_load"]), want_load)

    def test_the_step_reports_its_expert_counters(self, two_steps, ids):
        f, m = self.family, two_steps["metrics"]
        held = len(f.mc.held_expert_ids)
        assert m["expert_load"].shape == (held,)
        assert f.pairs_per_token[0] < float(m["expert_pairs_per_token"]) < f.pairs_per_token[1]  # the seed's draw, near what is expected
        assert 1.0 <= float(m["expert_load_max_over_mean"]) <= held
        self.check_counters(two_steps, ids)

    def test_the_shares_add_up_to_the_uncut_layer(self):
        """The share test. The routed experts as ``shares.count`` programs, each
        told its share (``held_experts``) and handed its rows of the expert
        leaves, the whole router and its bias: their routed outputs, with what
        every share computes alike (a shared expert) counted ONCE, add up to what
        the uncut reference gives for the whole layer, and every pair of every
        token is some share's."""
        f, s = self.family, self.family.shares
        mc = s.mc or f.mc
        n = mc.n_routed_experts
        whole = dict(f.bench_cfg(mc), held_experts=list(range(n)), **{s.experts_key: n})
        lp = {k: v.astype(jnp.float32) for k, v in f.ref.layer_leaves(f.weights.make_flat(11, whole), s.layer).items()}
        if s.bias:
            lp[f"mlp/gate/{BIAS}"] = 0.05 * jnp.cos(jnp.arange(float(n)))  # a bias that moves choices, and no weight
        h = jax.random.normal(jax.random.PRNGKey(3), (2, s.tokens, mc.hidden_size), jnp.float32)
        items = dict(f.ref.cfg_items(whole))
        want = f.ref.experts(lp, h, items)
        shared_once = s.shared_once(lp, h, items) if s.shared_once else 0.0
        total, pairs, each, parts = shared_once, 0, n // s.count, []
        for first in range(0, n, each):
            tree = weights.nest({k: (v[first:first + each] if "/experts/" in k else v)
                                 for k, v in lp.items() if k.startswith("mlp/")})["mlp"]
            share = mc.replace(held_experts=tuple(range(first, first + each)))
            y, load = moe.grouped_moe_mlp(tree, h, share, jnp.float32)
            total, pairs, parts = total + y, pairs + int(load.sum()), parts + [(tree, share, y)]
        assert _rel(total, want) < f.rtol
        assert pairs == 2 * s.tokens * mc.num_experts_per_tok
        self.check_shares(parts, lp, h, items, shared_once)

    def test_sharding_freeze_and_pipeline_rules(self, monkeypatch):
        r = self.family.rules
        for path, (ndim, axes) in r.specs.items():
            assert tuple(param_spec(path, ndim)) == axes, path
        tail = flatten_dict(trainable_mask(init_params(jax.random.PRNGKey(0), r.mc), r.mc, TrainConfig(
            model_preset=None, freeze_strategy="last_n_and_head", unfreeze_last_n_layers=r.unfreeze_last_n)))
        assert all(tail[k] for k in r.trained) and not any(tail[k] for k in r.held)
        # the pipeline's layer scan runs identical layers: a model that mixes kinds is refused, by what differs
        for overrides, named in r.scan_problems:
            (problem,) = layer_scan_problems(r.mc.replace(**overrides), seq_parallel=False)
            assert all(part in problem for part in named), problem
        # over a mesh's expert axis the layer still raises: the exchange is not written
        with pytest.raises(NotImplementedError, match="exchange"):
            transformer._grouped_experts({}, None, None, self.family.mc, compute_dtype=jnp.float32, mesh=SimpleNamespace(shape={"expert": 2}))
        self.check_rules(monkeypatch)

    def test_a_bfloat16_router_fails_the_tolerance(self, flat, ids, monkeypatch):
        """What the tolerance must not let through, the one part every family has: the router's products in bfloat16
        move the logits by ten tolerances and more. (The parts only one family has: its own ``..._fails_the_tolerance``.)"""
        monkeypatch.setattr(moe, "ROUTER_DTYPE", jnp.bfloat16)
        assert _logit_gap(self.family, flat, ids) > 10 * self.family.rtol

    def test_the_cells_step_compiles_for_v5e(self, topo, monkeypatch):
        """The family's cell as its traffic file states it, cut in depth, at the
        published widths, for a described (not attached) v5e: the step is what the
        chip's compiler accepts, the grouped products are in it, each expert layer
        keeps its routing and gathered rows (the backward pass holds no second
        router product, selection or sort: tests/test_moe_remat.py), and
        ``check_the_cells_step`` holds what the family's kernels must show."""
        self.before_the_cells_step(monkeypatch)
        step = compiled_cells_step(self.family.cell, topo, monkeypatch)
        assert "jit(gmm)" in step.text, "no grouped product kernel in the step"
        again = re.findall(r'op_name="[^"]*rematted_computation[^"]*/router/(dot_general|top_k|jit\(argsort\))', step.text)
        assert not again, again
        self.check_the_cells_step(step)


def compiled_cells_step(cell, topo, monkeypatch):
    """``cell``'s train step (a ``CellStep``) lowered and compiled for ONE described v5e, with what the families'
    checks read of it: the optimized program's text, its ``op_name``s, the Mosaic calls under a kernel's name."""
    from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.default_matmul_precision("default"):  # conftest.py sets ``highest`` for CPU numerics; the entry points run at the default
        setup = abstract_train_setup(
            {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, cell.preset, devices=topo.devices[:1], accum=2,
            seq=cell.seq, per_dp_batch=cell.rows, param_dtype="bfloat16", model_overrides=cell.overrides,
            train_kwargs=dict(freeze_strategy="none", remat_policy="full", attention_impl="flash", loss_chunk_size=1024))
        if cell.float32_moments:
            setup = dataclasses.replace(setup, state=setup.state.replace(opt_state=jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=x.sharding) if jnp.issubdtype(x.dtype, jnp.floating) else x,
                setup.state.opt_state)))
        lowered = setup.lower()
        compiled = lowered.compile()
    text = compiled.as_text()
    return SimpleNamespace(
        setup=setup, lowered=lowered, compiled=compiled, text=text, layers=setup.model_config.num_layers,
        names=re.findall(r'op_name="([^"]+)"', text), calls=lambda kernel: mosaic_calls(text, kernel))
