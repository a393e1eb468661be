"""Collective-byte accounting of the compiled sharded train step, per mesh.

The v5e-16 scaling projection (benchmarks/project_scaling.py) cannot be wall-clocked here (one real
chip), so its evidence is compiled-program facts: for each target mesh, the
optimized HLO's per-step collective bytes must match the analytic cost of the
parallelism strategy. ``observe/comm_accounting.py`` extracts the bytes (with
loop trip-count multipliers); these tests pin them against the expectations:

- DP        : gradient all-reduce on the data axis ~ 2 x (g-1)/g x trainable
              bytes per accumulation microbatch, and nothing else.
- FSDP      : param all-gathers on the fsdp axis, bounded by fwd+bwd per
              microbatch + optimizer re-gather; grad sync on fsdp (XLA's CPU
              partitioner emits it as all-reduce + slice; TPU lowers the same
              pattern to reduce-scatter — the accounted bytes are the upper
              bound of the two).
- TP        : activation psums on the tensor axis, ~2 per block per direction
              per microbatch (Megatron pairing).
- SP (ring) : K/V collective-permutes on the seq axis every attention step.
- PP        : exactly 2 x (M + S - 1) stage-boundary ppermutes per step
              (GPipe fwd + its transposed bwd), plus the output psum-scatter.
- EP        : dispatch/combine all-reduces on the expert axis.

Every collective must also *attribute* to a mesh axis (no "?" rows): an
unattributable replica group means the partitioner built groups that cross
axes in ways the design does not predict — exactly the regression this file
exists to catch.

Baseline being beaten: the reference pays one NCCL ring all-reduce of ALL
trainable grads per step on 4 GPUs (reference ``training.py:285``,
``deploy/pytorchjob.yaml:51-64``) with no sharding, no overlap accounting.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from llm_fine_tune_distributed_tpu.observe.comm_accounting import (
    account_compiled,
    account_text,
)
from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _leaf_bytes(leaf) -> int:
    return int(np.prod(leaf.shape)) * leaf.dtype.itemsize


def _bytes_where(flat: dict, axis: str) -> int:
    """Bytes of leaves whose sharding spec mentions ``axis``."""
    total = 0
    for leaf in flat.values():
        spec = getattr(leaf.sharding, "spec", ())
        flat_axes = set()
        for entry in spec:
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                flat_axes.add(a)
        if axis in flat_axes:
            total += _leaf_bytes(leaf)
    return total


def _ar(bytes_, g):
    return 2 * bytes_ * (g - 1) / g


# ------------------------------------------------------------------ unit: parser


def test_parser_exact_on_known_program(eight_devices):
    """A hand-built FSDP matmul step with a 3-trip scan: the parser must
    recover the exact collective set, axis attribution, and trip counts."""
    mesh = jax.make_mesh(
        (2, 4), ("data", "fsdp"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
    W = jax.ShapeDtypeStruct(
        (512, 512), jnp.float32, sharding=NamedSharding(mesh, P("fsdp", None))
    )
    xs = jax.ShapeDtypeStruct(
        (3, 16, 512),
        jnp.float32,
        sharding=NamedSharding(mesh, P(None, ("data", "fsdp"), None)),
    )

    def step(w, xs):
        def body(carry, x):
            g = jax.grad(lambda w, x: jnp.mean((x @ w) ** 2))(w, x)
            return carry + g, ()

        acc, _ = jax.lax.scan(body, jnp.zeros_like(w), xs)
        return w - 0.1 * acc

    rep = account_compiled(jax.jit(step).lower(W, xs).compile(), mesh)
    by = {}
    for c in rep.collectives:
        by.setdefault((c.kind, c.axes), []).append(c)

    # weight all-gather: loop-invariant, hoisted out (count 1), full W bytes
    (ag,) = by[("all-gather", ("fsdp",))]
    assert ag.count == 1
    assert ag.result_bytes == 512 * 512 * 4
    assert ag.wire_bytes == pytest.approx(512 * 512 * 4 * 3 / 4)
    # grad sync inside the scan: count 3 (known_trip_count multiplier)
    for c in by[("all-reduce", ("fsdp",))] + by[("all-reduce", ("data",))]:
        assert c.count == 3
    assert ("?",) not in {c.axes for c in rep.collectives}


def test_iota_replica_group_decode():
    """The [ng,gs]<=[dims]T(perm) notation decodes to real device groups."""
    from llm_fine_tune_distributed_tpu.observe.comm_accounting import (
        _parse_replica_groups,
    )

    assert _parse_replica_groups("replica_groups={{0,1},{2,3}}") == [[0, 1], [2, 3]]
    assert _parse_replica_groups("replica_groups=[2,4]<=[8]") == [
        [0, 1, 2, 3],
        [4, 5, 6, 7],
    ]
    assert _parse_replica_groups("replica_groups=[4,2]<=[2,4]T(1,0)") == [
        [0, 4],
        [1, 5],
        [2, 6],
        [3, 7],
    ]


@pytest.mark.slow
def test_trip_count_multiplier_scales_with_accum(eight_devices):
    """Doubling grad accumulation must ~double loop-body collective bytes —
    the direct check that the known_trip_count multiplier is applied."""
    w2 = abstract_train_setup({"data": 8}, accum=2).comm_report().total_wire_bytes()
    w4 = abstract_train_setup({"data": 8}, accum=4).comm_report().total_wire_bytes()
    assert 1.7 < w4 / w2 < 2.3


def test_adam_moments_are_sharded_like_their_params(eight_devices):
    """Left to the compiler ``optimizer.init``'s zeros come out replicated
    (nothing propagates into a constant), so every device of an fsdp mesh held
    all of Adam's moments. ``opt_state_shardings`` lays each moment out like
    the parameter it mirrors and replicates only what mirrors none."""
    s = abstract_train_setup({"data": 2, "fsdp": 4}, accum=2)
    moments = [
        (path[-1].key, leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(s.state.opt_state)
        if leaf.ndim
    ]
    assert len(moments) == 2 * len(s.state.trainable)  # mu and nu
    for key, leaf in moments:
        assert leaf.sharding == s.state.trainable[key].sharding, key
    assert _bytes_where({k: l for k, l in moments}, "fsdp") > 0
    count = [
        leaf for leaf in jax.tree.leaves(s.state.opt_state) if leaf.ndim == 0
    ]
    assert count and all(c.sharding.is_fully_replicated for c in count)


# ------------------------------------------------------------- per-mesh volumes


def test_dp_mesh_volume(eight_devices):
    """Pure DP: only gradient all-reduces, only on the data axis."""
    s = abstract_train_setup({"data": 8}, accum=2)
    rep = s.comm_report()
    assert {c.axes for c in rep.collectives} == {("data",)}
    assert set(rep.wire_bytes_by_kind()) == {"all-reduce"}
    # per-microbatch grad AR (the scan's carry sync; TPU's all-reduce-sinking
    # pass can only shrink this) + the embedding-gather grad scatter
    lo = _ar(s.trainable_bytes, 8)
    hi = 2 * _ar(s.trainable_bytes, 8) * 1.5
    assert lo <= rep.total_wire_bytes() <= hi


@pytest.mark.slow
def test_dp_fsdp_mesh_volume(eight_devices):
    s = abstract_train_setup({"data": 2, "fsdp": 4}, accum=2)
    rep = s.comm_report()
    assert ("?",) not in {c.axes for c in rep.collectives}

    sharded = _bytes_where(s.state.trainable, "fsdp") + _bytes_where(
        s.state.frozen, "fsdp"
    )
    ag = rep.filter(kind="all-gather", axes=("fsdp",))
    # params gathered >= once and <= (fwd+bwd) x accum + optimizer re-gather
    assert sharded * 3 / 4 <= ag.total_wire_bytes() <= sharded * 3 / 4 * (2 * 2 + 1)

    # grad sync on fsdp: all-reduce (CPU partitioner) or reduce-scatter (TPU)
    sync = rep.filter(kind="all-reduce", axes=("fsdp",)).total_wire_bytes()
    sync += rep.filter(kind="reduce-scatter", axes=("fsdp",)).total_wire_bytes()
    assert sync > 0
    # data-axis AR moves the fsdp-scattered grad shard per microbatch
    dp_ar = rep.filter(kind="all-reduce", axes=("data",)).total_wire_bytes()
    assert _ar(s.trainable_bytes / 4, 2) * 0.5 <= dp_ar <= _ar(s.trainable_bytes, 2) * 2 * 1.5


@pytest.mark.slow
def test_fsdp_tp_mesh_volume(eight_devices):
    s = abstract_train_setup({"fsdp": 4, "tensor": 2}, accum=2)
    rep = s.comm_report()
    assert ("?",) not in {c.axes for c in rep.collectives}

    # Megatron psums on tensor: >= 2 per block per microbatch (fwd), with bwd
    # and remat adding at most 3x more
    L = s.model_config.num_layers
    tp_ar = rep.filter(kind="all-reduce", axes=("tensor",))
    n_psums = sum(c.count for c in tp_ar.collectives)
    assert n_psums >= 2 * L * 2
    # activation psum bytes: [rows_local, seq, h] each, f32 activations
    dp = 4
    rows = s.batch["input_ids"].shape[1] // dp
    seq = s.batch["input_ids"].shape[2]
    h = s.model_config.hidden_size
    one = 2 * rows * seq * h * 4 * (2 - 1) / 2  # AR cost of one [rows,seq,h] f32
    assert tp_ar.total_wire_bytes() <= one * 2 * L * 2 * 4  # <= 4x fwd count
    ag = rep.filter(kind="all-gather", axes=("fsdp",))
    assert ag.total_wire_bytes() > 0


@pytest.mark.slow
def test_seq_mesh_has_ring_permutes(eight_devices):
    s = abstract_train_setup(
        {"fsdp": 2, "tensor": 2, "seq": 2},
        accum=2,
        train_kwargs={"attention_impl": "ring"},
    )
    rep = s.comm_report()
    assert ("?",) not in {c.axes for c in rep.collectives}
    perm = rep.filter(kind="collective-permute", axes=("seq",))
    L = s.model_config.num_layers
    # ring rotation: (seq_axis - 1) = 1 K/V rotation per attention, per layer,
    # per microbatch, fwd + bwd(remat recompute + transpose)
    n = sum(c.count for c in perm.collectives)
    assert n >= L * 2 * 2
    assert perm.total_wire_bytes() > 0


@pytest.mark.slow
def test_pipeline_mesh_exact_permute_schedule(eight_devices):
    M, S = 4, 2
    s = abstract_train_setup({"pipe": S, "fsdp": 4}, accum=M)
    rep = s.comm_report()
    assert ("?",) not in {c.axes for c in rep.collectives}

    perm = rep.filter(kind="collective-permute", axes=("pipe",))
    # GPipe: M + S - 1 ticks forward; jax.grad's transpose replays them
    # backward -> exactly 2(M + S - 1) boundary ppermutes per step
    assert sum(c.count for c in perm.collectives) == 2 * (M + S - 1)
    # each moves exactly one [mb_local, seq, h] boundary activation (dtype is
    # the compiled program's choice: bf16 on TPU, f32 where XLA keeps the
    # residual stream wide — infer the itemsize rather than assume)
    rows = s.batch["input_ids"].shape[1] // 4
    seq = s.batch["input_ids"].shape[2]
    h = s.model_config.hidden_size
    itemsize = perm.collectives[0].result_bytes // (rows * seq * h)
    assert itemsize in (2, 4)
    assert perm.total_wire_bytes() == pytest.approx(
        2 * (M + S - 1) * rows * seq * h * itemsize, rel=0.01
    )
    # last-stage output collection: psum-scatter + transpose's all-gather
    assert rep.filter(kind="reduce-scatter", axes=("pipe",)).total_wire_bytes() > 0
    assert rep.filter(kind="all-gather", axes=("pipe",)).total_wire_bytes() > 0


@pytest.mark.slow
def test_ep_mesh_volume(eight_devices):
    s = abstract_train_setup(
        {"data": 2, "expert": 4},
        preset="tiny_moe",
        accum=2,
        train_kwargs={"freeze_strategy": "none"},
    )
    rep = s.comm_report()
    assert ("?",) not in {c.axes for c in rep.collectives}
    # GShard einsum dispatch/combine: psums on the expert axis both directions
    ep_ar = rep.filter(kind="all-reduce", axes=("expert",))
    assert sum(c.count for c in ep_ar.collectives) >= 2 * 2  # >= dispatch+combine per microbatch
    assert ep_ar.total_wire_bytes() > 0
    # gradient sync still rides data
    assert rep.filter(kind="all-reduce", axes=("data",)).total_wire_bytes() > 0


@pytest.mark.slow
def test_pipe_ep_mesh_has_both_axes(eight_devices):
    """pipe x EP: the compiled schedule keeps expert parallelism ACTIVE
    inside stages — expert-axis psums appear alongside the pipe ppermutes
    (were experts gathered/replicated at shard_map entry, the expert axis
    would carry only the trivial top-k gathers)."""
    s = abstract_train_setup(
        {"pipe": 2, "expert": 2, "fsdp": 2},
        preset="tiny_moe",
        accum=4,
        train_kwargs={"freeze_strategy": "none"},
    )
    rep = s.comm_report()
    assert ("?",) not in {c.axes for c in rep.collectives}
    ep_ar = rep.filter(kind="all-reduce", axes=("expert",))
    assert sum(c.count for c in ep_ar.collectives) >= 2 * 4  # dispatch+combine per tick
    perm = rep.filter(kind="collective-permute", axes=("pipe",))
    assert sum(c.count for c in perm.collectives) == 2 * (4 + 2 - 1)
    # the expert weights are never all-gathered whole (EP's memory win): any
    # expert-axis gather traffic stays far below one full gather of the
    # stacked expert bytes
    expert_bytes = sum(
        int(np.prod(v.shape)) * v.dtype.itemsize
        for k, v in s.state.trainable.items()
        if "/experts/" in k and k.endswith(("w1", "w2", "w3"))
    )
    ag = rep.filter(kind="all-gather", axes=("expert",)).total_wire_bytes()
    assert ag < expert_bytes / 4, (ag, expert_bytes)


@pytest.mark.slow
def test_pipe_ring_mesh_has_both_rings(eight_devices):
    """pipe x ring: the compiled schedule carries BOTH permute families —
    stage-boundary ppermutes on pipe (exactly 2(M+S-1), now of seq-chunked
    activations) and K/V rotation permutes on seq (per layer per tick)."""
    M, S = 4, 2
    s = abstract_train_setup(
        {"pipe": S, "fsdp": 2, "seq": 2},
        accum=M,
        train_kwargs={"attention_impl": "ring"},
    )
    rep = s.comm_report()
    assert ("?",) not in {c.axes for c in rep.collectives}
    pipe_perm = rep.filter(kind="collective-permute", axes=("pipe",))
    assert sum(c.count for c in pipe_perm.collectives) == 2 * (M + S - 1)
    # boundary activations are seq-chunked: [mb_local, seq/2, h]
    rows = s.batch["input_ids"].shape[1] // 2
    seq_local = s.batch["input_ids"].shape[2] // 2
    h = s.model_config.hidden_size
    itemsize = pipe_perm.collectives[0].result_bytes // (rows * seq_local * h)
    assert itemsize in (2, 4)
    seq_perm = rep.filter(kind="collective-permute", axes=("seq",))
    L = s.model_config.num_layers
    # (seq-1)=1 K/V rotation per layer per tick, fwd + bwd replay
    assert sum(c.count for c in seq_perm.collectives) >= L * (M + S - 1)


# ------------------------------------------------------------- 16-device probe

_PROBE_16 = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

def ar(b, g):
    return 2 * b * (g - 1) / g

# dp x fsdp at v5e-16 scale
s = abstract_train_setup({"data": 2, "fsdp": 8}, accum=2)
rep = s.comm_report()
assert ("?",) not in {c.axes for c in rep.collectives}
ag = rep.filter(kind="all-gather", axes=("fsdp",)).total_wire_bytes()
assert ag > 0
sync = rep.filter(kind="all-reduce", axes=("fsdp",)).total_wire_bytes() + \
       rep.filter(kind="reduce-scatter", axes=("fsdp",)).total_wire_bytes()
assert sync > 0
print("PROBE16 dpxfsdp OK", int(rep.total_wire_bytes()))

# fsdp x tp at v5e-16 scale
s2 = abstract_train_setup({"fsdp": 8, "tensor": 2}, accum=2)
rep2 = s2.comm_report()
assert ("?",) not in {c.axes for c in rep2.collectives}
assert rep2.filter(kind="all-reduce", axes=("tensor",)).total_wire_bytes() > 0
print("PROBE16 fsdpxtp OK", int(rep2.total_wire_bytes()))
"""


@pytest.mark.slow
def test_16_device_meshes_account_clean():
    """The v5e-16-sized meshes (16 virtual devices need their own process)
    compile and account with full axis attribution."""
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE_16],
        capture_output=True,
        text=True,
        timeout=900,
        cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PROBE16 dpxfsdp OK" in proc.stdout
    assert "PROBE16 fsdpxtp OK" in proc.stdout


def test_projection_math():
    """project_step_time: compute term from the measured single-chip rate,
    comm term from wire bytes over the link model, efficiencies consistent."""
    from llm_fine_tune_distributed_tpu.observe.comm_accounting import (
        Collective,
        CommReport,
    )
    from llm_fine_tune_distributed_tpu.observe.scaling import project_step_time

    # one FSDP all-gather of 90 GB wire -> exactly 1 s at the 90 GB/s ring
    rep = CommReport([
        Collective(
            kind="all-gather", computation="main", result_bytes=0,
            group_size=16, axes=("fsdp",), count=1,
        )
    ])
    rep.collectives[0].result_bytes = int(90e9 * 16 / 15)  # wire = b*(g-1)/g
    proj = project_step_time(
        rep, {"fsdp": 16},
        single_chip_samples_per_sec=10.0, samples_per_step=160,
    )
    assert proj.compute_s == pytest.approx(1.0)      # 160 / (10 x 16)
    assert proj.exposed_comm_s == pytest.approx(1.0, rel=1e-6)
    assert proj.step_s == pytest.approx(2.0)
    assert proj.samples_per_sec == pytest.approx(80.0)
    assert proj.scaling_efficiency == pytest.approx(0.5)

    # full overlap hides all communication
    proj_ovl = project_step_time(
        rep, {"fsdp": 16},
        single_chip_samples_per_sec=10.0, samples_per_step=160,
        overlap_fraction=1.0,
    )
    assert proj_ovl.samples_per_sec == pytest.approx(160.0)

    # a data axis marked as DCN uses the slow link
    rep2 = CommReport([
        Collective(
            kind="collective-permute", computation="main",
            result_bytes=int(6.25e9), group_size=2, axes=("data",), count=1,
        )
    ])
    proj_dcn = project_step_time(
        rep2, {"data": 16},
        single_chip_samples_per_sec=10.0, samples_per_step=160,
        dcn_axes=("data",),
    )
    assert proj_dcn.exposed_comm_s == pytest.approx(1.0, rel=1e-6)
