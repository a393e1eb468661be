"""Deviceless compiles: the Pallas kernels of the main path, lowered by the
TPU's own compiler for a described (not attached) v5e at SmolLM3-3B widths.

Interpret mode cannot see what Mosaic refuses — a block whose last two dims
are neither full nor tile-aligned, a kernel over the VMEM it may use, a
kernel GSPMD cannot partition. These compiles can, at no chip time; what they
cannot say is whether the results are right or how long they take
(``chip_smoke.py`` checks the numbers on the chip).

This file holds the kernels' compiles and the four-chip ones. A family's whole
step at its cell's shapes is compiled in the family's own file
(``FamilySuite.test_the_cells_step_compiles_for_v5e``, ``tests/family_suite.py``).
The topology comes from ``conftest.py``'s module-scoped ``topo`` fixture: only one
process may load libtpu unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (the tier-1
command sets it), so it is never described at import, in a ``skipif`` or in
``parametrize`` arguments, and every compile happens in the test's own process.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from family_suite import IN_PASS_PROGRAMS
from llm_fine_tune_distributed_tpu.observe.xla import mosaic_programs
from llm_fine_tune_distributed_tpu.ops import flash_attention as fa
from llm_fine_tune_distributed_tpu.ops.int8_matmul import _w8a8_pallas

# SmolLM3-3B attention geometry
HQ, HKV, D = 16, 4, 128
# The softmax mixer's IN pass (PR 41: q/k norms, rope and the head-major layout, ops/rope.heads_in): one forward and one
# backward program a MODEL (a layer without rope runs the program of the layers with), and a budget for their serialized
# modules' bytes together, by cell at a microbatch's shape: (rows, seq, q heads, kv heads, head, table width, norm) ->
# bytes. A module carries its operations' call stacks, up to ten frames each, and a bare call like this test's is
# shallower than that: pytest's own frames get in and the same two kernels read 20.6 KB in a bare process and 25.8 KB
# under six of the suite's workers. So the text is counted with the stacks left out (``_without_call_stacks``: the
# innermost frame alone, the same under any runner) and held at the landed count plus a fifth, as the rule's is.
IN_PASS_SHAPES = {
    "trinity": ((2, 8192, 32, 4, 128, 128, True), 28_780),
    "mellum": ((4, 8192, 32, 4, 128, 128, False), 18_408),
    "smollm3": ((2, 1024, 16, 4, 128, 128, False), 15_740),
    "qwen3-next": ((2, 8192, 16, 2, 256, 64, True), 31_952),
}


@pytest.fixture(autouse=True)
def production_matmul_precision():
    """conftest.py sets ``highest`` for CPU numerics; the entry points run at
    the default, and that is the kernel these tests must compile."""
    with jax.default_matmul_precision("default"):
        yield


def _without_call_stacks():
    """While it lasts, a lowered operation's location is its innermost frame alone: a Mosaic module's text then does
    not depend on how deep the caller stood (a bare test under a runner's frames reads kilobytes more)."""
    return jax._src.config.include_full_tracebacks_in_locations(False)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel in the program"
    return compiled


def _qkv(b, s, hq=HQ, hkv=HKV):
    return (
        ((b, s, hq, D), jnp.bfloat16),
        ((b, s, hkv, D), jnp.bfloat16),
        ((b, s, hkv, D), jnp.bfloat16),
    )


# the largest sequence flash_unsupported_reason admits at these head shapes:
# the dk/dv kernel's VMEM budget at 6144 is 95 MiB of the 100 MiB cap
MAX_FLASH_SEQ = 6144

# one microbatch of each training cell of BENCHMARK.json (SmolLM3-3B: 2 rows
# of 1024; Mistral-7B: one row of 2048, 32 q heads on 8 kv heads) and the
# largest admitted sequence: (rows, seq, q heads, kv heads)
FLASH_SHAPES = [(2, 1024, HQ, HKV), (2, MAX_FLASH_SEQ, HQ, HKV), (1, 2048, 32, 8)]


@pytest.mark.parametrize("rows, seq, hq, hkv", FLASH_SHAPES)
def test_flash_forward_compiles_for_v5e(one_chip, rows, seq, hq, hkv):
    _compile(
        lambda q, k, v: fa.pallas_flash_attention(q, k, v), one_chip, *_qkv(rows, seq, hq, hkv)
    )


@pytest.mark.parametrize("rows, seq, hq, hkv", FLASH_SHAPES)
def test_flash_forward_backward_compiles_for_v5e(one_chip, rows, seq, hq, hkv):
    """The backward holds a kv head's whole query group in VMEM: this is the
    compile the default 16 MiB scoped budget refused at seq 4096."""

    def loss(q, k, v):
        return fa.pallas_flash_attention(q, k, v).astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *_qkv(rows, seq, hq, hkv))


def test_flash_with_wider_query_and_key_heads_compiles_for_v5e(one_chip):
    """Latent attention's shapes, one microbatch of the Moonlight cell: q/k
    heads of 192 (taken as they lie; a block pads to 256 lanes in VMEM) against v heads of 128,
    16 heads, 2 rows of 4096. At two lane registers a head the kernels' bodies
    need twice the room (``_vmem_budget``): with the one-register budget the
    chip's compiler refused dk/dv (38.3 MiB of scoped VMEM against 31)."""

    def loss(q, k, v):
        return fa.pallas_flash_attention(q, k, v).astype(jnp.float32).sum()

    shapes = (((2, 4096, 16, 192), jnp.bfloat16), ((2, 4096, 16, 192), jnp.bfloat16), ((2, 4096, 16, 128), jnp.bfloat16))
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *shapes)
    assert compiled.as_text().count("tpu_custom_call") >= 3  # fwd, dq, dk/dv


# the first chunk of each expert cell, one microbatch: (rows of the chunk, hidden, rows' dtype, tokens, choices, experts held)
SUM_SHAPES = [
    (98304, 2304, jnp.float32, 32768, 8, 16), (98304, 2304, jnp.bfloat16, 32768, 8, 16),  # Mellum: sum_rows forward, take_rows backward
    (16384, 2048, jnp.float32, 16384, 6, 8), (16384, 2048, jnp.bfloat16, 16384, 6, 8),     # Moonlight
]


@pytest.mark.parametrize("rows, hidden, dtype, tokens, k, held", SUM_SHAPES,
                         ids=["mellum-f32", "mellum-bf16", "moonlight-f32", "moonlight-bf16"])
def test_sum_kernel_compiles_for_v5e(one_chip, rows, hidden, dtype, tokens, k, held):
    """The kernel that sums a chunk's rows into their tokens, at both expert
    cells' shapes, inside the VMEM it asks for (its copies move whole blocks of
    8 float32 or 16 bfloat16 rows: Mosaic refuses a copy of one row of a tiled
    array; the plan around the call is XLA's)."""
    from llm_fine_tune_distributed_tpu.ops import moe

    shapes = (((rows, hidden), dtype), ((tokens, k), jnp.int32), ((held,), jnp.int32))
    assert moe.sum_kernel_refused(*(jax.ShapeDtypeStruct(s, d) for s, d in shapes)) is None
    assert moe._sum_vmem_bytes(hidden, k, held, dtype) <= 48 * 2**20
    text = _compile(lambda *a: moe._sum_into_tokens(*a, impl="kernel"), one_chip, *shapes).as_text()
    assert "sum_held_rows" in text and text.count("tpu_custom_call") >= 1


def test_flash_says_no_past_its_vmem_cap(monkeypatch):
    """One block past the largest sequence whose query group the resident
    dk/dv kernel holds, the streamed kernels take the call; what they cannot
    hold either is refused for a stated reason, before the compiler is asked
    (so needs no topology)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def call(seq, window=None):
        q, k, v = (jax.ShapeDtypeStruct(s, d) for s, d in _qkv(2, seq))
        return fa.flash_unsupported_reason(q, k, v, sliding_window=window), fa.program_label(q, k, v, sliding_window=window)

    assert call(MAX_FLASH_SEQ) == (None, "resident causal")
    assert call(MAX_FLASH_SEQ + 512) == (None, "streamed causal")
    assert call(1024, window=512) == (None, "streamed window 512")
    assert call(1024, window=4096) == (None, "resident causal")  # a window as long as the row is none
    assert call(1000)[0] == "seq 1000 is not a multiple of 128"
    assert "sees no key" in call(1024, window=0)[0]
    q, k, v = (jax.ShapeDtypeStruct(s, d) for s, d in _qkv(2, 1024))
    assert fa.flash_unsupported_reason(q, k, v, causal=False) == "non-causal mask"
    monkeypatch.setattr(fa, "_VMEM_CAP_BYTES", 8 * 1024 * 1024)
    assert "streamed backward needs" in call(MAX_FLASH_SEQ)[0] and "VMEM" in call(1024, window=512)[0]


# Mellum2-12B-A2.5B's attention, one row of the new cell: 32 query heads on 4
# kv heads (8 queries a kv head) at 8192 positions, where the resident dk/dv
# kernel would ask for 218 MiB
MELLUM_QKV = (((1, 8192, 32, D), jnp.bfloat16), ((1, 8192, 4, D), jnp.bfloat16), ((1, 8192, 4, D), jnp.bfloat16))


@pytest.mark.parametrize("window", [1024, None], ids=["window-1024", "global"])
def test_streamed_flash_forward_backward_compiles_for_v5e(one_chip, window):
    """The window layers' and the global layers' kernels, forward, dq and
    dk/dv, inside the 100 MiB a kernel may ask for, each under its own name."""

    def loss(q, k, v):
        return fa.pallas_flash_attention(q, k, v, sliding_window=window).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *MELLUM_QKV).as_text()
    kind = "window" if window else "causal"
    for kernel in ("fwd", "dq", "dkv"):
        assert f"flash_attention_{kind}_{kernel}" in text
    assert text.count("tpu_custom_call") >= 3
    band = fa._band(8192, 1024, window)  # the key says which shape was counted: another shape is another entry
    assert fa.GRID_TILES[f"flash_attention_{kind}_dkv", band] == ((15, 36) if window else (36, 36))


@pytest.mark.parametrize("cell", list(IN_PASS_SHAPES))
def test_in_pass_compiles_for_v5e_and_its_text_is_held(one_chip, cell):
    """The IN pass's two kernels at a microbatch of each claimed cell (Trinity: gate and q/k norms; Mellum: neither),
    of SmolLM3's (rows of 1024: one token block) and of Qwen3-Next's full layer (heads of two registers, a table of 64
    lanes: two rolls and a select), forward and backward through the ``custom_vjp``: Mosaic takes the narrow column
    blocks of the flat projections, the head-major output blocks, the lane rolls and the flag in SMEM. One program
    each way, and their text (what every start of a process traces and lowers again, warm cache or not) inside its
    budget."""
    from llm_fine_tune_distributed_tpu.ops import rope

    (b, s, heads, kv, d, width, norm), landed = IN_PASS_SHAPES[cell]
    shapes = [((b, s, heads * d), jnp.bfloat16), ((b, s, kv * d), jnp.bfloat16), ((b, s, kv * d), jnp.bfloat16),
              ((b, s, width), jnp.float32), ((b, s, width), jnp.float32)] + [((d,), jnp.float32)] * (2 * norm)

    def grads(xq, xk, xv, cos, sin, *w):
        def loss(xq, xk, xv, *w):
            out = rope.heads_in(xq, xk, xv, cos, sin, heads=heads, kv_heads=kv,
                                **(dict(q_weight=w[0], k_weight=w[1]) if w else {}))
            return sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in out)

        return jax.grad(loss, argnums=tuple(range(3 + len(w))))(xq, xk, xv, *w)

    with _without_call_stacks():
        lowered = jax.jit(grads).lower(*(jax.ShapeDtypeStruct(shape, t, sharding=one_chip) for shape, t in shapes))
    assert "tpu_custom_call" in lowered.compile().as_text()
    programs = mosaic_programs(lowered.as_text())
    assert {name: x["programs"] for name, x in programs.items()} == IN_PASS_PROGRAMS, programs
    assert sum(x["bytes"] for x in programs.values()) <= 1.2 * landed, programs


# EVA attention at the EvaByte cell's shapes (one row of 32,768 bytes, 32 heads of 128, windows of 2048 in chunks of 16):
# the Mosaic programs of one layer's aggregate, forward and backward, and the bytes of text they landed at (my deviceless
# lowering, PR 47: 94,720, of them ``eva_remote_fwd`` 8,300 with its two loop bodies, trips of four windows' summaries and of
# one, for PR 46's 6,260 with one; what every start of a process traces and lowers again, warm cache or not)
EVA_PROGRAMS = {"flash_attention_fwd": 1, "flash_attention_dq": 1, "flash_attention_dkv": 1,
                "eva_remote_fwd": 1, "eva_remote_dq": 1, "eva_remote_dkv": 1}
EVA_LANDED = 94_720


def test_eva_attention_compiles_for_v5e_and_its_text_is_held(one_chip):
    """The operator's kernels at the cell's shapes through the ``custom_vjp``: the resident flash kernels take the row
    cut into windows (512 rows of one block a head and window) under the room the operator asks for them, the remote
    kernels the summaries resident in VMEM beside a query block, the dynamic trip counts, the aliased outputs; one
    program each, their text inside its budget; and nothing of ``[T, T]`` or ``[T, T / 16]`` in the compiled program."""
    from llm_fine_tune_distributed_tpu.ops import eva_attention as eva

    b, h, t, d, window, chunk = 1, 32, 32768, D, 2048, 16
    scale = d ** -0.5

    def grads(q, k, v, phi, mu):
        def loss(q, k, v, phi, mu):
            ks, vs = eva.pool(k, v, phi, mu, chunk=chunk, scale=scale)
            return jnp.sum(eva._make_aggregate(window, chunk, scale, False)(q, k, v, ks, vs).astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, phi, mu)

    shapes = [((b, h, t, d), jnp.bfloat16)] * 3 + [((h, d), jnp.float32)] * 2
    with _without_call_stacks():
        lowered = jax.jit(grads).lower(*(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes))
    text = lowered.compile().as_text()
    assert sum("tpu_custom_call" in line for line in text.splitlines()) == 6
    produced = [ln.split(" = ", 1)[-1].split("(", 1)[0] for ln in text.splitlines()]
    assert not [x for x in produced if f",{t},{t}]" in x or f",{t},{t // chunk}]" in x]
    programs = mosaic_programs(lowered.as_text())
    assert {name: x["programs"] for name, x in programs.items()} == EVA_PROGRAMS, programs
    assert sum(x["bytes"] for x in programs.values()) <= 1.2 * EVA_LANDED, programs


def test_the_evabyte_cells_step_compiles_for_v5e(topo, monkeypatch):
    """The EvaByte cell's step as its traffic file states it (``benchmarks/step_memory.STEPS``: 8 frozen and 2 trained
    layers, one row of 32,768, remat full, loss in chunks of 1024): what the chip's compiler accepts, under the 15.0
    GiB line the cells are sized by, each layer's two forward kernels once (and once more recomputed in the two
    trained layers: ``keeps_flash_outputs`` says recompute there), the backward kernels in the two trained layers
    alone, the IN pass in every layer, and no buffer of ``[T, T]`` or ``[T, T / 16]``."""
    import dataclasses

    from benchmarks.step_memory import STEPS
    from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    preset, overrides, rows, accum, seq, recipe = STEPS["evabyte-6.5b-d10.sft-32k-eva-last2"]
    setup = abstract_train_setup(
        {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, preset, devices=topo.devices[:1], accum=accum, seq=seq,
        per_dp_batch=rows, param_dtype="bfloat16", train_kwargs=recipe, model_overrides=overrides)
    float32 = lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=x.sharding) if jnp.issubdtype(x.dtype, jnp.floating) else x  # noqa: E731
    compiled = dataclasses.replace(setup, state=setup.state.replace(opt_state=jax.tree.map(float32, setup.state.opt_state))).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < 15.0 * 2**30
    lines = [ln for ln in compiled.as_text().splitlines()]
    calls = lambda kernel: sum("tpu_custom_call" in ln and f"/{kernel}/" in ln for ln in lines)  # noqa: E731
    assert {k: calls(k) for k in ("flash_attention_fwd", "eva_remote_fwd", "attn_in_fwd")} == {
        "flash_attention_fwd": 12, "eva_remote_fwd": 12, "attn_in_fwd": 12}
    assert [calls(k) for k in ("flash_attention_dq", "flash_attention_dkv", "eva_remote_dq", "eva_remote_dkv", "attn_in_bwd")] == [2] * 5
    produced = [ln.split(" = ", 1)[-1].split("(", 1)[0] for ln in lines]
    assert not [x for x in produced if f",{seq},{seq}]" in x or f",{seq},{seq // 16}]" in x]


def test_flash_on_a_four_chip_mesh_compiles_for_v5e(topo, monkeypatch):
    """Mosaic kernels cannot be partitioned by GSPMD: on the default fsdp=4
    mesh the dispatcher must run the kernel per shard under a shard_map
    (before PR 21 this lowering raised NotImplementedError)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llm_fine_tune_distributed_tpu.config import MeshConfig
    from llm_fine_tune_distributed_tpu.ops.attention import attention
    from llm_fine_tune_distributed_tpu.runtime.mesh import make_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh(MeshConfig(data=1, fsdp=4, tensor=1, seq=1), topo.devices)
    rows = NamedSharding(mesh, P(("data", "fsdp")))

    def loss(q, k, v, pad):
        out = attention(q, k, v, impl="flash", padding_mask=pad, mesh=mesh)
        return out.astype(jnp.float32).sum()

    args = [jax.ShapeDtypeStruct(s, d, sharding=rows) for s, d in _qkv(8, 1024)]
    pad = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=rows)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args, pad).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3  # fwd, dq, dk/dv
    # the softmax mixer's IN pass (ops/rope.heads_in) hands the kernels head-major operands in ONE device's program:
    # under this mesh the kernel runs per shard inside a shard_map over [b, s, h, d] and the pass runs not at all
    from llm_fine_tune_distributed_tpu.models import transformer
    from llm_fine_tune_distributed_tpu.models.configs import get_preset

    config = get_preset("smollm3_3b")
    hid = jax.ShapeDtypeStruct((8, 1024, config.hidden_size), jnp.bfloat16)
    cos = jax.ShapeDtypeStruct((8, 1024, 128), jnp.float32)
    asked = dict(attention_impl="flash", scale=None, mask=None, cache_entry=None)
    why = transformer._why_not_head_major(hid, cos, config, config.layer(0), mesh=mesh, **asked)
    assert why.startswith("a mesh of 4 devices"), why
    assert transformer._why_not_head_major(hid, cos, config, config.layer(0), mesh=None, **asked) is None


def test_fsdp4_step_keeps_plain_all_gathers_on_a_ring_ordered_mesh(topo, monkeypatch):
    """``jax.make_mesh`` lays the fsdp axis of a 2x2 v5e host out as the ring
    of neighbours 0, 1, 3, 2. In that order (and not in 0, 1, 2, 3, which is
    what ``runtime/mesh.make_mesh`` used to build from a list of described
    devices, so that no deviceless compile saw it) XLA rewrote every weight
    all-gather + matmul of the step as a windowed einsum, kept 0.31 GiB of
    weight shards a layer live, and refused the flagship recipe at full depth
    (18.69 GB of a device's 15.75: PR 21). ``jit_train_step`` compiles a
    partitioned step without that rewrite. Four layers at SmolLM3-3B width:
    with the rewrite this program holds 225 collective-permutes and 3.27 GiB
    of temporaries, without it 7 and 2.04."""
    import re

    from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    setup = abstract_train_setup(
        {"data": 1, "fsdp": 4, "tensor": 1, "seq": 1}, "smollm3_3b",
        devices=topo.devices, accum=4, seq=1024, per_dp_batch=2, param_dtype="bfloat16",
        train_kwargs=dict(remat_policy="dots_no_batch", attention_impl="flash"),
        model_overrides=dict(num_layers=4),
    )
    # make_mesh orders described devices as it orders attached ones
    assert [d.id for d in setup.mesh.devices.flat] == [0, 1, 3, 2]
    compiled = setup.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the flash kernel is not in the step"
    assert "attn_in_fwd" not in text  # (the IN pass is one device's: under this mesh the XLA form stands)
    gathers = len(re.findall(r"= .*\ball-gather(-start)?\(", text))
    permutes = len(re.findall(r"= .*\bcollective-permute(-start)?\(", text))
    assert gathers > 100 and permutes < 20, (gathers, permutes)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.4 * 2**30


@pytest.mark.parametrize("block_len", [256, 16])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, block_len):
    """The int8 paged-decode kernel at SmolLM3 head shapes: the server's
    default ``--kv-block-len 256`` and the small block of the unit tests.
    (Until PR 21 its K/V blocks sliced the kv-head dim to 1 and Mosaic
    refused it; agreement with the XLA gather is checked on the chip.)"""
    num_blocks, b, nb = 64, 4, 4

    def decode(q, kp, vp, ks, vs, tables, lengths):
        return fa.paged_decode_attention(q, kp, vp, ks, vs, tables, lengths=lengths)

    _compile(
        decode, one_chip,
        ((b, 1, HQ, D), jnp.bfloat16),
        ((num_blocks, block_len, HKV, D), jnp.int8),
        ((num_blocks, block_len, HKV, D), jnp.int8),
        ((num_blocks, HKV), jnp.float32),
        ((num_blocks, HKV), jnp.float32),
        ((b, nb), jnp.int32),
        ((b,), jnp.int32),
    )


@pytest.mark.parametrize("m, k, n", [(2048, 2048, 11008), (2048, 11008, 2048)])
def test_int8_trunk_matmul_compiles_for_v5e(one_chip, m, k, n):
    """SmolLM3's MLP up- and down-projection at microbatch 2 x seq 1024."""
    _compile(
        lambda xq, xs, wq, ws: _w8a8_pallas(xq, xs, wq, ws, jnp.bfloat16),
        one_chip,
        ((m, k), jnp.int8), ((m,), jnp.float32),
        ((k, n), jnp.int8), ((n,), jnp.float32),
    )


# Granite 4.0-H Micro's microbatch: one row of 8192; 64 Mamba-2 heads of 64 with a state of 128; 32 / 8 attention heads of 64
SSD_PROGRAMS = {"ssd_scan_fwd": 1, "ssd_scan_bwd": 1}
SSD_LANDED = 29_652  # (PR 49: 28,740; PR 50's sweeps make each chunk's running sum and the row forms themselves: 912 bytes)


def test_the_state_space_sweeps_compile_for_v5e_and_their_text_is_held(one_chip):
    """The scan's two sweeps at the cell's shapes through the ``custom_vjp``: two heads of 64 a 128-lane block, the
    chunk's ``C B^T`` in VMEM scratch shared over the heads' grid axis, dB and dC resident over it, the transposed
    products; one program each, their text inside its budget (the landed count and a fifth, stacks left out as for
    the rule's kernels); nothing of ``[T, T]`` or ``[T, chunk]`` a head in the compiled program."""
    from llm_fine_tune_distributed_tpu.ops import ssd

    b, t, heads, p, n = 1, 8192, 64, 64, 128

    def grads(x, dt, a, bm, cm, d):
        loss = lambda *z: jnp.sum(ssd.ssd_scan(*z, impl="kernels").astype(jnp.float32) ** 2)  # noqa: E731
        return jax.grad(loss, argnums=range(6))(x, dt, a, bm, cm, d)

    shapes = [((b, t, heads, p), jnp.bfloat16), ((b, t, heads), jnp.float32), ((heads,), jnp.float32),
              ((b, t, 1, n), jnp.bfloat16), ((b, t, 1, n), jnp.bfloat16), ((heads,), jnp.float32)]
    with _without_call_stacks():
        lowered = jax.jit(grads).lower(*(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes))
    text = lowered.compile().as_text()
    assert sum("tpu_custom_call" in line for line in text.splitlines()) == 2
    produced = [ln.split(" = ", 1)[-1].split("(", 1)[0] for ln in text.splitlines()]
    assert not [x for x in produced if f",{t},{t}]" in x or f"{heads},{t},{ssd.CHUNK}]" in x]
    programs = mosaic_programs(lowered.as_text())
    assert {name: x["programs"] for name, x in programs.items()} == SSD_PROGRAMS, programs
    assert sum(x["bytes"] for x in programs.values()) <= 1.2 * SSD_LANDED, programs


def test_flash_at_heads_of_64_compiles_for_v5e(one_chip):
    """Granite's attention layers at a row of 8192: 4 queries a kv head at heads of 64 take the STREAMED kernels (the
    resident dk/dv kernel's query group pads to 128 lanes in VMEM and passes the cap, as at heads of 128), blocks of 64
    lanes as they lie; forward, dq and dk/dv."""
    def loss(q, k, v):
        return fa.pallas_flash_attention(q, k, v).astype(jnp.float32).sum()

    shapes = (((1, 8192, 32, 64), jnp.bfloat16), ((1, 8192, 8, 64), jnp.bfloat16), ((1, 8192, 8, 64), jnp.bfloat16))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *shapes).as_text()
    calls = lambda kernel: sum("tpu_custom_call" in ln and f"/{kernel}/" in ln for ln in text.splitlines())  # noqa: E731
    assert [calls(f"flash_attention_causal_{k}") for k in ("fwd", "dq", "dkv")] == [1, 1, 1]
    resident = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, ((2, 1024, 32, 64), jnp.bfloat16),
                        ((2, 1024, 8, 64), jnp.bfloat16), ((2, 1024, 8, 64), jnp.bfloat16)).as_text()
    assert sum("tpu_custom_call" in ln and "/flash_attention_fwd/" in ln for ln in resident.splitlines()) == 1
