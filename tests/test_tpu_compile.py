"""Deviceless compiles: the Pallas kernels of the main path, lowered by the
TPU's own compiler for a described (not attached) v5e at SmolLM3-3B widths.

Interpret mode cannot see what Mosaic refuses — a block whose last two dims
are neither full nor tile-aligned, a kernel over the VMEM it may use, a
kernel GSPMD cannot partition. These compiles can, at no chip time; what they
cannot say is whether the results are right or how long they take
(``chip_smoke.py`` checks the numbers on the chip).

Rules that keep this file safe under pytest-xdist (only one process may load
libtpu): the topology is described inside a module-scoped, non-autouse
fixture, never at import, in a ``skipif`` or in ``parametrize`` arguments;
every compile happens in the test's own process; all of them live in this
one file, so one worker loads the library once.
"""

from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from llm_fine_tune_distributed_tpu.observe.xla import mosaic_programs
from llm_fine_tune_distributed_tpu.ops import flash_attention as fa
from llm_fine_tune_distributed_tpu.ops.int8_matmul import _w8a8_pallas

# SmolLM3-3B attention geometry
HQ, HKV, D = 16, 4, 128
# The gated delta rule's kernels in the Qwen3-Next cell's step as they landed (PR 37): distinct Mosaic programs by
# kernel, and their serialized modules' bytes together (PR 36's tree read 125,980 in the same step, my chip run, PR 37).
RULE_PROGRAMS = {"gdn_rule_fwd": 1, "gdn_rule_bwd": 1}
RULE_MODULE_BYTES = 77_064
# The rule with a decay a CHANNEL (Kimi Delta Attention) in the Kimi cell's step as its two sweeps landed (PR 43), the
# same way: a warm start of that cell pays for this text (and no longer for the XLA form's Python loops over sub-blocks).
KDA_RULE_PROGRAMS = {"kda_rule_fwd": 1, "kda_rule_bwd": 1}
KDA_RULE_MODULE_BYTES = 112_700
# The mixer's two elementwise passes around the rule, the same way (PR 39; budget: 40 KB together).
MIXER_PROGRAMS = {"gdn_in_fwd": 1, "gdn_in_bwd": 1, "gdn_out_fwd": 1, "gdn_out_bwd": 1}
MIXER_MODULE_BYTES = 35_816
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
IN_PASS_PROGRAMS = {"attn_in_fwd": 1, "attn_in_bwd": 1}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a deviceless executable can be written to the persistent cache but not
    # read back without a chip: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


def _xla_remats(text):
    """The instructions XLA's own rematerialization added to an optimized program (it names them ``<name>.remat``)."""
    return re.findall(r"%[\w.\-]*\.remat[\w.\-]* = ", text)


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


def _sum_kernel_calls(text):
    """The paths (``op_name``) of the Mosaic calls that sum rows into tokens."""
    found = (re.search(r'op_name="([^"]*/sum_held_rows/pallas_call)"', ln) for ln in text.splitlines() if "tpu_custom_call" in ln)
    return [m.group(1) for m in found if m]


def _assert_two_sums_an_expert_layer(text, expert_layers):
    """One call forward (``sum_rows``) and one backward (``take_rows``'
    transpose) an expert layer for the first chunk, the same two again inside
    the overflow chunks' ``cond``, and none recomputed: the block's last
    operation is dead in the recompute."""
    calls = _sum_kernel_calls(text)
    assert len(calls) == 4 * expert_layers, calls
    first_chunk = [c for c in calls if "/cond/" not in c]
    assert len(first_chunk) == 2 * expert_layers and sum("transpose(" in c for c in first_chunk) == expert_layers
    assert not [c for c in calls if "rematted_computation" in c]
    assert all("/mlp/experts/" in c and "gmm" not in c and "flash_attention" not in c for c in calls)


def test_step_with_latent_attention_and_routed_experts_compiles_for_v5e(topo, monkeypatch):
    """The dense layer and one expert layer of Moonlight-16B-A3B at its
    published widths (this chip's share: 8 of 64 experts, an eighth of the
    vocabulary), every parameter trained, one chip: the flash kernels at
    192/128 and the grouped products are in the step, and the step is what the
    chip's compiler accepts. (With the held experts' load counted by
    ``bincount`` this program aborted the compiler: ops/moe.py.) The forward
    kernel is in it once a layer (tests/test_flash_remat.py)."""
    from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    setup = abstract_train_setup(
        {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, "moonlight_16b_a3b",
        devices=topo.devices[:1], accum=2, seq=4096, per_dp_batch=1, param_dtype="bfloat16",
        train_kwargs=dict(freeze_strategy="none", remat_policy="full", attention_impl="flash", loss_chunk_size=1024),
        model_overrides=dict(num_layers=2, vocab_size=20480, held_experts=tuple(range(8))),
    )
    text = setup.compile().as_text()
    mosaic_calls = lambda kernel: sum(  # noqa: E731
        "tpu_custom_call" in line and f"/{kernel}/" in line for line in text.splitlines()
    )
    # rows of 4096 at heads of 192/128 over a hidden size of 2048: each block
    # keeps the forward kernel's o and lse across its remat boundary, so the
    # forward kernel is in the step once a layer and not a second time in the
    # backward pass (under ``full`` too)
    assert mosaic_calls("flash_attention_fwd") == setup.model_config.num_layers
    assert mosaic_calls("flash_attention_dq") == mosaic_calls("flash_attention_dkv") == setup.model_config.num_layers
    assert "jit(gmm)" in text, "no grouped product kernel in the step"
    _assert_two_sums_an_expert_layer(text, setup.model_config.num_layers - 1)
    # and each expert layer keeps its routing and gathered rows: the backward
    # pass holds no second router product, selection or sort (tests/test_moe_remat.py)
    again = re.findall(r'op_name="[^"]*rematted_computation[^"]*/router/(dot_general|top_k|jit\(argsort\))', text)
    assert not again, again


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


def test_step_with_window_and_global_layers_and_softmax_experts_compiles_for_v5e(topo, monkeypatch):
    """One window layer and one global layer of Mellum2-12B-A2.5B at its
    published widths (this chip's share: 16 of 64 experts, a quarter of the
    vocabulary), every parameter trained, one row of 8192 a microbatch: both
    kinds of layer run the streamed flash kernels (no ``[8192, 8192]`` scores
    in the program), the window layer's forward kernel twice (recomputed: 1920
    against the hidden 2304) and the global layer's once (kept), and the
    grouped products and the kept routing are in the step."""
    from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    setup = abstract_train_setup(
        {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, "mellum2_12b_a2_5b",
        devices=topo.devices[:1], accum=2, seq=8192, per_dp_batch=1, param_dtype="bfloat16",
        train_kwargs=dict(freeze_strategy="none", remat_policy="full", attention_impl="flash", loss_chunk_size=1024),
        model_overrides=dict(num_layers=2, vocab_size=24576, held_experts=tuple(range(16)),
                             layer_types=("sliding_attention", "full_attention")),
    )
    text = setup.compile().as_text()
    mosaic_calls = lambda kernel: sum(  # noqa: E731
        "tpu_custom_call" in line and f"/{kernel}/" in line for line in text.splitlines()
    )
    assert mosaic_calls("flash_attention_window_fwd") == 2 and mosaic_calls("flash_attention_causal_fwd") == 1
    for kernel in ("window_dq", "window_dkv", "causal_dq", "causal_dkv"):
        assert mosaic_calls(f"flash_attention_{kernel}") == 1
    assert mosaic_calls("flash_attention_fwd") == 0  # no resident kernel at 8 queries a kv head and 8192
    assert not re.search(r"\[[0-9,]*8192,8192\]", text), "a [seq, seq] buffer in the step"
    assert "jit(gmm)" in text, "no grouped product kernel in the step"
    _assert_two_sums_an_expert_layer(text, setup.model_config.num_layers)
    again = re.findall(r'op_name="[^"]*rematted_computation[^"]*/router/(dot_general|top_k|jit\(argsort\))', text)
    assert not again, again


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


def test_step_with_gated_window_and_global_layers_compiles_for_v5e(topo, monkeypatch):
    """The leading dense layer, one window layer and the global layer of
    Trinity-Mini (``afmoe``) at its published widths (this chip's share: 16 of
    128 experts, an eighth of the vocabulary), every parameter trained but the
    selection bias, one row of 8192 a microbatch. The window of 2048 is a band
    three blocks of 1024 wide (Mellum's 1024: two): both kinds of layer run the
    streamed flash kernels behind the gate and the q/k norms, each forward
    kernel ONCE (``o`` and ``lse`` kept on the window layers too: 2048 x 1.75 =
    3584 keys' worth against the hidden 2048); the post-norm sits on the expert
    layers' output; the grouped products, the sums of rows into tokens and the
    kept routing are in the step; and the block's three scopes are on its
    operations. Between the projections and the flash kernels stands the IN
    pass (PR 41): ``attn_in_fwd`` in each layer's forward and recomputed pass,
    ``attn_in_bwd`` once, ONE program each for the window layers with rope and
    the global layer without, and the flash kernels read q, k and v as the
    pass wrote them: no transpose, no copy, no fusion between."""
    from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    setup = abstract_train_setup(
        {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, "trinity_mini",
        devices=topo.devices[:1], accum=2, seq=8192, per_dp_batch=1, param_dtype="bfloat16",
        train_kwargs=dict(freeze_strategy="none", remat_policy="full", attention_impl="flash", loss_chunk_size=1024),
        model_overrides=dict(num_layers=3, first_k_dense_replace=1, vocab_size=25024, held_experts=tuple(range(16)),
                             layer_types=("sliding_attention", "sliding_attention", "full_attention"),
                             no_rope_layers=(1, 1, 0)),
    )
    lowered = setup.lower()
    text = lowered.compile().as_text()
    mosaic_calls = lambda kernel: sum(  # noqa: E731
        "tpu_custom_call" in line and f"/{kernel}/" in line for line in text.splitlines()
    )
    for kernel in ("fwd", "dq", "dkv"):
        assert mosaic_calls(f"flash_attention_window_{kernel}") == 2, kernel  # layers 0 and 1, the forward kernel kept
        assert mosaic_calls(f"flash_attention_causal_{kernel}") == 1, kernel
    assert mosaic_calls("flash_attention_fwd") == 0  # no resident kernel at 8 queries a kv head and 8192
    band = fa._band(8192, 1024, 2048)
    assert band.steps == 3 and fa.GRID_TILES["flash_attention_window_fwd", band] == (21, 36)
    assert "jit(gmm)" in text, "no grouped product kernel in the step"
    # the sums of rows into tokens: forward and backward an expert layer as everywhere, and here a THIRD, recomputed:
    # the expert layer's output is no longer the block's last operation, the output norm's backward reads it
    # (64 MiB a layer and microbatch to keep instead: not kept), and the same three again behind the overflow's cond
    sums = _sum_kernel_calls(text)
    first_chunk = [c for c in sums if "/cond/" not in c]
    assert len(sums) == 6 * 2 and len(first_chunk) == 3 * 2, sums
    assert sum("transpose(" in c and "rematted_computation" not in c for c in first_chunk) == 2
    assert sum("rematted_computation" in c for c in first_chunk) == 2
    again = re.findall(r'op_name="[^"]*rematted_computation[^"]*/router/(dot_general|top_k|jit\(argsort\))', text)
    assert not again, again
    names = re.findall(r'op_name="([^"]+)"', text)
    for inside in ("attn/attn_gate", "attn/attn_in", "attn/out_norm", "mlp/out_norm"):
        assert any(f"/{inside}/" in name for name in names), inside
    assert not any("/qk_norm/" in name for name in names)  # the norms are inside the pass: the scope is the XLA form's
    assert any("layer2" in name and "mlp/out_norm" in name for name in names)  # the post-norm of an EXPERT layer
    # the IN pass: forward kernel in the forward and the recomputed pass, backward kernel once, every layer
    passes = sorted(re.findall(
        r'op_name="[^"]*?/(transpose\(jvp\(layer\d\)\)|jvp\(layer\d\))/(?:[^"]*?/)?(rematted_computation/)?attn/attn_in/'
        r'jit\((attn_in_\w+)\)/\3/pallas_call"', "\n".join(line for line in text.splitlines() if "tpu_custom_call" in line)))
    assert passes == sorted(found for i in range(3) for found in (
        (f"jvp(layer{i})", "", "attn_in_fwd"), (f"transpose(jvp(layer{i}))", "rematted_computation/", "attn_in_fwd"),
        (f"transpose(jvp(layer{i}))", "", "attn_in_bwd"))), passes
    programs = {name: x for name, x in mosaic_programs(lowered.as_text()).items() if name.startswith("attn_in")}
    assert {name: x["programs"] for name, x in programs.items()} == IN_PASS_PROGRAMS, programs  # (rope or none: data)
    # (30,048 B landed, plus a fifth; a whole step stands deeper than the ten frames a location keeps: the same anywhere)
    assert sum(x["bytes"] for x in programs.values()) <= 36_000, programs
    # what the forward flash kernels read as q, k, v IS what the pass wrote: get-tuple-elements of its call
    defined = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = \S+ ([\w\-]+)\(", text, flags=re.M))
    reads = re.findall(r"= \S+ \S+ custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\".*?"
                       r'op_name="[^"]*/flash_attention_(?:window|causal)_fwd/pallas_call"', text)
    assert len(reads) == 3
    for operands in reads:
        q_k_v = [name.split("*/")[-1].strip() for name in operands.split(",")][-3:]
        assert all(name.startswith("%jit_attn_in_fwd_") and defined[name] == "get-tuple-element" for name in q_k_v), q_k_v


def test_step_with_linear_and_full_layers_compiles_for_v5e(topo, monkeypatch):
    """The step of ``qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams`` as
    its traffic file states it: one period of Qwen3-Next-80B-A3B at its
    published widths (three Gated DeltaNet layers, one gated full-attention
    layer; this chip's share: 32 of 512 experts, an eighth of the vocabulary),
    every parameter trained, 2 rows of 8192 a microbatch, two microbatches.
    The compiler's own count has to fit beside the state (15.49 GiB a program
    may use; at 4 rows a microbatch it refused the step, 17.89 G of 15.75 G,
    until PR 39's passes took the float32 copies out: 14.13 GiB now, PERF.md);
    the full layer runs the streamed flash kernels at heads of 256, 8 queries
    a kv head (no resident kernel: its dk/dv would ask 300 MiB), its forward
    kernel once (``o`` and ``lse`` kept: 8192 against the hidden 2048); each
    linear layer's rule is the Pallas kernels of ``ops/gated_delta.py``, the
    forward sweep ONCE (the block keeps its ``o`` and its per-step states, PR
    44: none in the recomputed pass) and the backward sweep once, and XLA adds
    no rematerialization of its own (no ``.remat`` instruction); grouped
    products, the sums of rows into tokens and the kept routing are in the
    step. What the rule's kernels cost every start of a process is
    held too (``RULE_PROGRAMS``, ``RULE_MODULE_BYTES``): PR 36's kernels, 126 KB
    of modules here, added 10.9 s to every warm ``setup_s`` and were refused.
    Around the rule the mixer's elementwise work is two fused passes (PR 39:
    ``gdn_in_*`` under ``gdn_conv``, ``gdn_out_*`` under ``gdn_gate_norm``,
    counted like the sweeps and their text held like the rule's), and between
    the projections and ``out_proj`` nothing else touches a whole activation."""
    from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    setup = abstract_train_setup(
        {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, "qwen3_next_80b_a3b",
        devices=topo.devices[:1], accum=2, seq=8192, per_dp_batch=2, param_dtype="bfloat16",
        train_kwargs=dict(freeze_strategy="none", remat_policy="full", attention_impl="flash", loss_chunk_size=1024),
        model_overrides=dict(num_layers=4, vocab_size=18992, held_experts=tuple(range(32))),
    )
    state = setup.state.replace(opt_state=jax.tree.map(  # Adam's moments float32, as the cell holds them
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=x.sharding) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        setup.state.opt_state))
    lowered = dataclasses.replace(setup, state=state).lower()
    compiled = lowered.compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < 15.49 * 2**30
    text = compiled.as_text()
    mosaic_calls = lambda kernel: sum(  # noqa: E731
        "tpu_custom_call" in line and f"/{kernel}/" in line for line in text.splitlines()
    )
    for kernel in ("causal_fwd", "causal_dq", "causal_dkv"):
        assert mosaic_calls(f"flash_attention_{kernel}") == 1, kernel
    assert mosaic_calls("flash_attention_fwd") == 0 and mosaic_calls("flash_attention_window_fwd") == 0
    assert "jit(gmm)" in text, "no grouped product kernel in the step"
    _assert_two_sums_an_expert_layer(text, setup.model_config.num_layers)
    again = re.findall(r'op_name="[^"]*rematted_computation[^"]*/router/(dot_general|top_k|jit\(argsort\))', text)
    assert not again, again
    # each linear layer's rule is the kernels (PR 37): the forward sweep in the forward pass and NOT recomputed (its o
    # and states are kept, PR 44), the backward sweep once; the full layer has none, XLA's triangular solve is out of
    # the step and XLA rematerializes nothing of its own
    sweeps = sorted(re.findall(
        r'op_name="[^"]*?/(transpose\(jvp\(layer\d\)\)|jvp\(layer\d\))/(?:[^"]*?/)?(rematted_computation/)?linear_attn/gdn_scan/'
        r'jit\((gdn_rule_\w+)\)/\3/pallas_call"', "\n".join(line for line in text.splitlines() if "tpu_custom_call" in line)))
    assert sweeps == sorted(
        sweep for i in range(3) for sweep in ((f"jvp(layer{i})", "", "gdn_rule_fwd"), (f"transpose(jvp(layer{i}))", "", "gdn_rule_bwd"))), sweeps
    assert "triangular" not in text.lower() and not _xla_remats(text)
    # the two passes around it (PR 39), the same: forward kernels in the forward and the recomputed pass, backward
    # kernels once, none in the full layer
    passes = sorted(re.findall(
        r'op_name="[^"]*?/(transpose\(jvp\(layer\d\)\)|jvp\(layer\d\))/(?:[^"]*?/)?(rematted_computation/)?linear_attn/(gdn_conv|gdn_gate_norm)/'
        r'jit\((gdn_(?:in|out)_\w+)\)/\4/pallas_call"', "\n".join(line for line in text.splitlines() if "tpu_custom_call" in line)))
    assert passes == sorted(
        found for i in range(3) for scope, way in (("gdn_conv", "in"), ("gdn_gate_norm", "out")) for found in (
            (f"jvp(layer{i})", "", scope, f"gdn_{way}_fwd"), (f"transpose(jvp(layer{i}))", "rematted_computation/", scope, f"gdn_{way}_fwd"),
            (f"transpose(jvp(layer{i}))", "", scope, f"gdn_{way}_bwd"))), passes
    # what the passes removed: of the instructions under linear_attn that yield a whole [2, 8192, >= 2048] activation
    # (fused computations' insides apart) none is a pad, a slice, a concatenation, a copy, a transpose or a conversion:
    # each is a kernel, or a fusion that is a projection's product or the sum of the products' input cotangents, or (PR
    # 44) the ONE pass ``jax.checkpoint`` puts on the producer of a float residual it saves (``reduce_precision``, a
    # layer's kept ``o`` of the rule; the flash kernel's kept ``o`` passes the same under ``attn``)
    whole, computation = [], ""
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            computation = head.group(1)
        found = re.match(r'\s*(?:ROOT )?%[\w.\-]+ = \w+\[2,8192,(\d+)\]\S* ([\w\-]+)\(.*op_name="([^"]*/linear_attn[^"]*)"', line)
        if found and not computation.startswith("fused_computation") and int(found.group(1)) >= 2048:
            whole.append((found.group(2), found.group(3).rsplit("/", 1)[1]))
    assert whole and {opcode for opcode, _ in whole} <= {"custom-call", "get-tuple-element", "fusion", "bitcast", "reduce-precision"}, set(whole)
    assert {last for opcode, last in whole if opcode == "fusion"} <= {"dot_general", "add_any"}, set(whole)
    assert [last for opcode, last in whole if opcode == "reduce-precision"] == ["reduce_precision"] * 3, set(whole)
    # at the landed count (13.47 GiB at PR 39; the parent's 14.15 held the XLA form's float32 copies and padded
    # cotangents; PR 41's IN pass leaves it where it was: the full layer's gate is a product of its own; 13.56 since
    # the three linear blocks keep 192 MiB each of the rule's o and states, PR 44)
    assert compiled.memory_analysis().peak_memory_in_bytes <= 13.57 * 2**30
    # the full layer's IN pass (PR 41): forward kernel in the forward and the recomputed pass, backward kernel once
    in_pass = mosaic_programs(lowered.as_text())
    assert {name: in_pass[name]["programs"] for name in IN_PASS_PROGRAMS} == IN_PASS_PROGRAMS
    assert (mosaic_calls("attn_in_fwd"), mosaic_calls("attn_in_bwd")) == (2, 1)
    # what a start of the process pays for the rule again, warm cache or not: the text of its kernels, traced and
    # lowered before the cache is even asked (PERF.md, PR 37, step 0: the step's lower() follows the serialized
    # modules' bytes, .compile() on a hit does not move). Held at the landed value plus a fifth.
    programs = mosaic_programs(lowered.as_text())
    for prefixes, count, landed in ((("gdn_rule",), RULE_PROGRAMS, RULE_MODULE_BYTES), (("gdn_in", "gdn_out"), MIXER_PROGRAMS, MIXER_MODULE_BYTES)):
        found = {name: x for name, x in programs.items() if name.startswith(prefixes)}
        assert {name: x["programs"] for name, x in found.items()} == count, found
        assert sum(x["bytes"] for x in found.values()) <= 1.2 * landed, found


def test_step_with_kda_and_latent_layers_compiles_for_v5e(topo, monkeypatch):
    """One period of Kimi-Linear-48B-A3B at its published widths (Kimi Delta Attention, KDA, latent attention without
    rope, KDA; this chip's share: 8 of 256 experts, an eighth of the vocabulary), every parameter trained but the
    selection bias, the cell's 2 rows of 8192 a microbatch, two microbatches. The compiler's own count stays under the
    cell's memory line (15.0 GiB for the five layers: these four hold 0.1 G of state less) and at its landed value
    (11.79 GiB since the rule's kernels, PR 43; 14.576 while the XLA form held a row's ``U``, ``W``, ``P`` and decayed
    operands of all chunks); the latent layer takes the
    RESIDENT flash kernels at q/k 192 against v 128, one query a kv head, on a row of 8192 (``dispatch_summary()`` says
    which set), its forward kernel once (``o`` and ``lse`` kept); each KDA layer's rule is the two Pallas sweeps for a
    decay a channel (``kda_rule_fwd`` ONCE, its ``o`` and per-step states kept across the block's remat since PR 44,
    ``kda_rule_bwd`` once; XLA's triangular solve is out of the step and it rematerializes nothing of its own) between the two fused passes' kernels, the out pass with its sigmoid gate;
    ``kda_gates`` is on the step's operations; ``CALLS`` names the kernel form; and the sweeps' Mosaic programs and
    serialized bytes are held where they landed (``KDA_RULE_PROGRAMS``, ``KDA_RULE_MODULE_BYTES``), as the scalar rule's
    are in the Qwen3-Next step."""
    from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup
    from llm_fine_tune_distributed_tpu.ops import gated_delta
    from llm_fine_tune_distributed_tpu.ops.attention import dispatch_summary

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gated_delta, "CALLS", {})
    setup = abstract_train_setup(
        {"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, "kimi_linear_48b_a3b",
        devices=topo.devices[:1], accum=2, seq=8192, per_dp_batch=2, param_dtype="bfloat16",
        train_kwargs=dict(freeze_strategy="none", remat_policy="full", attention_impl="flash", loss_chunk_size=1024),
        model_overrides=dict(num_layers=4, first_k_dense_replace=0, vocab_size=20480, held_experts=tuple(range(8)),
                             layer_types=("linear_attention", "linear_attention", "full_attention", "linear_attention")),
    )
    state = setup.state.replace(opt_state=jax.tree.map(  # Adam's moments float32, as the cell holds them
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=x.sharding) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        setup.state.opt_state))
    lowered = dataclasses.replace(setup, state=state).lower()
    compiled = lowered.compile()
    assert compiled.memory_analysis().peak_memory_in_bytes <= 11.9 * 2**30 < 15.0 * 2**30
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    mosaic_calls = lambda kernel: sum(f"/{kernel}/" in line for line in calls)  # noqa: E731
    for kernel in ("fwd", "dq", "dkv"):
        assert mosaic_calls(f"flash_attention_{kernel}") == 1, kernel  # the resident set, the forward kernel kept
        assert mosaic_calls(f"flash_attention_causal_{kernel}") == 0, kernel
    assert "resident causal" in dispatch_summary()
    sweeps = sorted(re.findall(
        r'op_name="[^"]*?/(transpose\(jvp\(layer\d\)\)|jvp\(layer\d\))/(?:[^"]*?/)?(rematted_computation/)?linear_attn/gdn_scan/'
        r'jit\((\w+_rule_\w+)\)/\3/pallas_call"', "\n".join(calls)))
    assert sweeps == sorted(
        sweep for i in (0, 1, 3) for sweep in ((f"jvp(layer{i})", "", "kda_rule_fwd"), (f"transpose(jvp(layer{i}))", "", "kda_rule_bwd"))), sweeps
    assert "triangular" not in text.lower() and not _xla_remats(text)
    passes = sorted(re.findall(
        r'op_name="[^"]*?/(transpose\(jvp\(layer\d\)\)|jvp\(layer\d\))/(?:[^"]*?/)?(rematted_computation/)?linear_attn/(gdn_conv|gdn_gate_norm)/'
        r'jit\((gdn_(?:in|out)_\w+)\)/\4/pallas_call"', "\n".join(calls)))
    assert passes == sorted(
        found for i in (0, 1, 3) for scope, way in (("gdn_conv", "in"), ("gdn_gate_norm", "out")) for found in (
            (f"jvp(layer{i})", "", scope, f"gdn_{way}_fwd"), (f"transpose(jvp(layer{i}))", "rematted_computation/", scope, f"gdn_{way}_fwd"),
            (f"transpose(jvp(layer{i}))", "", scope, f"gdn_{way}_bwd"))), passes
    names = re.findall(r'op_name="([^"]+)"', text)
    for inside in ("linear_attn/kda_gates", "linear_attn/gdn_scan", "attn/"):
        assert any(f"/{inside}" in name for name in names), inside
    assert "jit(gmm)" in text, "no grouped product kernel in the step"
    assert {form for _, form in gated_delta.CALLS.values()} == {"chunked 64, a decay a channel in sub-blocks of 16: kernels"}
    assert set(gated_delta.CALLS) == {(2, 8192, 32, 32, 128, 128, "by channel")}
    # what a start of the process pays for the sweeps, warm cache or not (the Qwen3-Next step's test: why): their text
    found = {name: x for name, x in mosaic_programs(lowered.as_text()).items() if name.endswith(("_rule_fwd", "_rule_bwd"))}
    assert {name: x["programs"] for name, x in found.items()} == KDA_RULE_PROGRAMS, found
    assert sum(x["bytes"] for x in found.values()) <= 1.2 * KDA_RULE_MODULE_BYTES, found


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
