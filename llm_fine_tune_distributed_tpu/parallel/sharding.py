"""Sharding rules: param-path -> PartitionSpec over the (data, fsdp, tensor, seq) mesh.

This module is the TPU-native replacement for the reference's entire
parallelism story (DDP-only, ``ddp_backend="nccl"`` reference
``training.py:285``) and its aspired FSDP next step (external-doc article):

- DP    : params replicated; batch split over (data, fsdp); gradients psum'd
          by XLA (the analog of NCCL bucketed all-reduce,
          ``docs/architecture-diagram.md:119-135``).
- FSDP  : each param's largest dim additionally sharded over ``fsdp``
          (ZeRO-3); XLA turns the gradient psum into reduce-scatter +
          all-gather automatically.
- TP    : Megatron-style — attention q/k/v and MLP gate/up shard their output
          dim over ``tensor``; o_proj and down shard their input dim, so each
          block needs exactly two psums (inserted by XLA from the annotations).
- seq   : reserved for ring attention (parallel/ring_attention.py).

Rules are by HF param path, so they apply to every model in models/configs.py.
"""

from __future__ import annotations

import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llm_fine_tune_distributed_tpu.utils.tree import map_with_path

# (path regex, spec builder) — first match wins. Specs are (dim0, dim1) for
# matrices, (dim0,) for vectors. None = replicated on that dim.
# "tensor-column": output dim over tensor; "tensor-row": input dim over tensor.
# NF4-quantized kernels (ops/nf4.py) keep the base kernel's orientation:
# packed [in/8, out] and absmax [in/block, out] shard like kernel [in, out]
# (_validate_spec drops any axis the smaller dims no longer divide).
# int8 weight-only inference kernels (ops/int8.py) keep the base [in, out]
# orientation; their 1-D scales fall through to the replicated default.
_QK = r"kernel(_nf4|_absmax|_absmax_q|_int8)?$"
_MATRIX_RULES = [
    # attention projections
    (re.compile(r".*self_attn/(q_proj|k_proj|v_proj)/" + _QK), ("fsdp", "tensor")),
    (re.compile(r".*self_attn/o_proj/" + _QK), ("tensor", "fsdp")),
    # latent attention: the latent (and the one rope key) is shared by all
    # heads, so its down-projection keeps its output whole; the up-projection
    # to the heads' k and v shards by head like q
    (re.compile(r".*self_attn/kv_a_proj_with_mqa/" + _QK), ("fsdp", None)),
    (re.compile(r".*self_attn/kv_b_proj/" + _QK), ("fsdp", "tensor")),
    # a linear-attention mixer: its input projections' columns hold parts of
    # different kinds side by side ([q | k | v | z], [b | a]), so only the
    # input dim shards; out_proj like o_proj without the tensor axis; the
    # convolution's [taps, channels] and the per-head vectors stay whole
    (re.compile(r".*linear_attn/(in_proj_qkvz|in_proj_ba)/" + _QK), ("fsdp", None)),
    (re.compile(r".*linear_attn/out_proj/" + _QK), (None, "fsdp")),
    # a Kimi Delta Attention mixer: projections of their own for q, k, v, beta and the low-rank pairs of the
    # decay and of the output gate, the input dim of the first of a pair and the output dim of the second
    (re.compile(r".*linear_attn/(q_proj|k_proj|v_proj|b_proj|f_a_proj|g_a_proj)/" + _QK), ("fsdp", None)),
    (re.compile(r".*linear_attn/(f_b_proj|g_b_proj)/" + _QK), (None, "fsdp")),
    # a Mamba-2 mixer: in_proj's columns hold [z | x | B | C | dt] side by side, so only its input dim shards;
    # out_proj like the linear mixers'; the convolution's [taps, channels] and the per-head vectors stay whole
    (re.compile(r".*mamba/in_proj/" + _QK), ("fsdp", None)),
    (re.compile(r".*mamba/out_proj/" + _QK), (None, "fsdp")),
    (re.compile(r".*mlp/shared_expert_gate/" + _QK), ("fsdp", None)),
    # MLP (and the shared experts beside routed ones: the same SwiGLU)
    (re.compile(r".*mlp/(shared_experts/)?(gate_proj|up_proj)/" + _QK), ("fsdp", "tensor")),
    (re.compile(r".*mlp/(shared_experts/)?down_proj/" + _QK), ("tensor", "fsdp")),
    # embeddings: [vocab, hidden] — shard vocab over tensor, hidden over fsdp
    (re.compile(r".*embed_tokens/weight$"), ("tensor", "fsdp")),
    (re.compile(r".*lm_head/kernel$"), ("fsdp", "tensor")),
    # LoRA adapters: A [in, r] shard in-dim like the base kernel's in-dim;
    # B [r, out] shard out-dim. Conservative: fsdp only (r is tiny).
    (re.compile(r".*/lora_a$"), ("fsdp", None)),
    (re.compile(r".*/lora_b$"), (None, "fsdp")),
    # Stacked adapter pools (infer/adapters.py): same orientation with a
    # leading [max_adapters] pool dim. lora_scale_pool is 1-D -> replicated.
    (re.compile(r".*/lora_a_pool$"), (None, "fsdp", None)),
    (re.compile(r".*/lora_b_pool$"), (None, None, "fsdp")),
    # MoE (ops/moe.py): stacked expert weights shard the expert dim over the
    # "expert" axis (expert parallelism) plus the usual fsdp/tensor dims;
    # the router gate [h, E] is tiny — fsdp on the input dim only.
    # NF4-quantized experts ([E, in/8, out] packed + [E, in/block, out]
    # absmax) keep the same orientation; _validate_spec drops any dim the
    # packed shapes no longer divide.
    # The grouped layer (mlp/experts, mlp/gate) lays its leaves out alike.
    (re.compile(r".*(block_sparse_moe|mlp)/experts/(w1|w3)(_nf4|_absmax|_absmax_q)?$"),
     ("expert", "fsdp", "tensor")),
    (re.compile(r".*(block_sparse_moe|mlp)/experts/w2(_nf4|_absmax|_absmax_q)?$"),
     ("expert", "tensor", "fsdp")),
    (re.compile(r".*(block_sparse_moe|mlp)/gate/kernel$"), ("fsdp", None)),
]


def param_spec(path: str, ndim: int) -> P:
    """PartitionSpec for one param."""
    if ndim <= 1:
        # norms / biases / scalars: replicated (tiny).
        return P()
    for pat, dims in _MATRIX_RULES:
        if pat.match(path):
            return P(*dims)
    return P()


def param_sharding_rules(params, mesh: Mesh):
    """Pytree of NamedSharding matching ``params``' structure.

    Falls back to replication for any dim whose size does not divide the mesh
    axis (e.g. tiny test models on an 8-way fsdp axis).
    """

    def rule(path: str, leaf) -> NamedSharding:
        spec = param_spec(path, getattr(leaf, "ndim", 0))
        spec = _validate_spec(spec, getattr(leaf, "shape", ()), mesh)
        return NamedSharding(mesh, spec)

    return map_with_path(rule, params)


def _validate_spec(spec: P, shape, mesh: Mesh) -> P:
    fixed = []
    for i, axis in enumerate(spec):
        if axis is None:
            fixed.append(None)
            continue
        if axis == "expert" and axis not in mesh.shape:
            # the one axis that is legitimately optional (meshes built before
            # MoE support have 4 axes): replicate the expert dim. Any OTHER
            # unknown axis is a bug in the rules and raises below.
            fixed.append(None)
            continue
        size = mesh.shape[axis]
        if i < len(shape) and shape[i] % size == 0:
            fixed.append(axis)
        else:
            fixed.append(None)
    while fixed and fixed[-1] is None:
        fixed.pop()
    return P(*fixed)


def shard_params(params, mesh: Mesh):
    """Place a (host-local) params pytree onto the mesh per the rules.

    Works on process-spanning meshes too: ``jax.device_put`` cannot target
    another process's devices, so when the mesh is not fully addressable
    each leaf is assembled with ``make_array_from_callback`` — every process
    holds the full host copy (same checkpoint on every host) and contributes
    its local shards. This is the multi-host inference load path
    (``Generator(mesh=...)`` with tensor spanning hosts)."""
    shardings = param_sharding_rules(params, mesh)
    if len(mesh.devices.flat) == len([d for d in mesh.devices.flat if d.process_index == jax.process_index()]):
        return jax.device_put(params, shardings)
    return jax.tree.map(
        lambda x, sh: global_array_from_host(np.asarray(x), sh), params, shardings
    )


def global_array_from_host(host_array: np.ndarray, sharding: NamedSharding):
    """Global jax.Array over a (possibly multi-process) mesh from a host
    array every process holds in full: each process contributes the shards
    its devices own."""
    return jax.make_array_from_callback(
        host_array.shape, sharding, lambda idx: host_array[idx]
    )


def mesh_fully_addressable(mesh: Mesh) -> bool:
    """True when every mesh device belongs to this process (single-controller
    placement via ``jax.device_put`` is legal); False on a process-spanning
    mesh, where leaves must be assembled as global arrays."""
    pid = jax.process_index()
    return all(d.process_index == pid for d in mesh.devices.flat)


def place_tree(tree, shardings):
    """Place a host-local pytree under a matching pytree of NamedShardings,
    choosing ``device_put`` or global-array assembly per the mesh's
    addressability (the same split ``shard_params`` makes for weights)."""
    meshes = {sh.mesh for sh in jax.tree.leaves(shardings)}
    if all(mesh_fully_addressable(m) for m in meshes):
        return jax.device_put(tree, shardings)
    return jax.tree.map(
        lambda x, sh: global_array_from_host(np.asarray(x), sh), tree, shardings
    )


# KV cache / paged block pool leaves, by leaf name. Dense rows and paged
# blocks share the layout [rows|blocks, len, num_kv_heads, head_dim]: the
# kv-head dim shards over ``tensor`` so each chip holds the heads its
# (column-sharded) k/v projections produce — decode attention then needs no
# resharding between projection, cache write, and the gather/softmax.
# int8 pools carry sibling per-block scales [blocks, num_kv_heads] that
# shard the same head dim. _validate_spec drops the tensor axis when it
# does not divide num_kv_heads (head replication — see make_tp_mesh).
_KV_LEAF_DIMS = {
    "k": (None, None, "tensor", None),
    "v": (None, None, "tensor", None),
    "k_scale": (None, "tensor"),
    "v_scale": (None, "tensor"),
}


def kv_cache_spec(path: str, shape, mesh: Mesh) -> P:
    name = path.rsplit("/", 1)[-1]
    dims = _KV_LEAF_DIMS.get(name)
    if dims is None or len(dims) != len(shape):
        return P()
    return _validate_spec(P(*dims), shape, mesh)


def kv_cache_shardings(cache, mesh: Mesh):
    """Pytree of NamedSharding for a dense KV cache or paged block pool
    (``models/transformer.init_cache`` / ``init_paged_cache`` layout)."""
    return map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, kv_cache_spec(path, getattr(leaf, "shape", ()), mesh)
        ),
        cache,
    )


def batch_spec(mesh: Mesh, seq_axis: bool = False) -> P:
    """Batch arrays [batch, seq, ...]: batch over (data, fsdp), optionally
    sequence over seq (ring attention)."""
    if seq_axis and mesh.shape["seq"] > 1:
        return P(("data", "fsdp"), "seq")
    return P(("data", "fsdp"))


def logical_batch_sharding(mesh: Mesh, seq_axis: bool = False) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(mesh, seq_axis))
