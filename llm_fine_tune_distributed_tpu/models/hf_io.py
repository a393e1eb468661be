"""Two-way HF safetensors <-> params-pytree bridge.

Parity targets in the reference:
- load base weights from an HF checkpoint (reference ``training.py:97-102``);
- export the fine-tuned model as safetensors that the inference CLI loads
  (``trainer.save_model`` -> ``best_model/``, reference ``training.py:310-311``,
  consumed by ``ask_tuned_model.py:15-35``).

Because the params pytree mirrors HF module paths, the mapping is purely
mechanical: torch ``Linear.weight [out, in]`` <-> JAX ``kernel [in, out]``
(transpose); embeddings/norms/biases copy through unchanged.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, Optional

import numpy as np

from llm_fine_tune_distributed_tpu.config import ModelConfig

# Leaves stored transposed relative to torch (Linear weights).
_KERNEL_LEAF = "kernel"


def _flatten(tree, prefix=()):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flatten(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out


def _unflatten(flat: Dict[tuple, np.ndarray]):
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return tree


# DeepSeek-V3 names of the grouped layer's stacked expert leaves (ops/moe.py)
_DEEPSEEK_EXPERT = {"w1": "gate_proj", "w3": "up_proj", "w2": "down_proj"}


def _rope_pairs(config: ModelConfig, path: tuple):
    """For a latent-attention projection whose output holds rope dimensions:
    the column permutation from DeepSeek's stored layout (each rotated pair
    adjacent) to the layout the model rotates in (halves apart, what HF's
    DeepseekV3 attention makes of q_pe and k_pe before it rotates), else
    None. A dot product of q and k is the same under any permutation both
    share, so only the two projections that produce rope dimensions move."""
    if not config.kv_lora_rank or config.mla_use_nope or len(path) < 3 or path[-3] != "self_attn":
        return None  # (without rope nothing is rotated, and q and the shared key keep the stored order alike)
    dr = config.qk_rope_head_dim
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    if path[-2] == "q_proj":
        width = config.qk_nope_head_dim + dr
        head = np.concatenate([np.arange(config.qk_nope_head_dim), config.qk_nope_head_dim + halves])
        return (np.arange(config.num_heads)[:, None] * width + head[None, :]).reshape(-1)
    if path[-2] == "kv_a_proj_with_mqa":
        return np.concatenate([np.arange(config.kv_lora_rank), config.kv_lora_rank + halves])
    return None


def _linear_columns(config: ModelConfig, path: tuple):
    """For the two input projections of a linear-attention mixer: the column
    permutation from HF's stored layout to the one the model slices. HF's
    ``Qwen3NextGatedDeltaNet`` interleaves by KEY head: ``in_proj_qkvz`` holds,
    for key head g, ``[q_g | k_g | v of its r value heads | z of its r value
    heads]`` and ``in_proj_ba`` ``[b of its r value heads | a of them]``; the
    model keeps ``[q | k | v | z]`` and ``[b | a]``, each part by head, so that
    every part is one slice. Else None."""
    if len(path) < 3 or path[-3] != "linear_attn" or path[-2] not in ("in_proj_qkvz", "in_proj_ba"):
        return None
    hk, hv = config.linear_num_key_heads, config.linear_num_value_heads
    dk, dv, r = config.linear_key_head_dim, config.linear_value_head_dim, hv // hk
    g = np.arange(hk)[:, None]
    if path[-2] == "in_proj_ba":
        parts = [g * 2 * r + off + np.arange(r)[None, :] for off in (0, r)]
    else:
        width = 2 * dk + 2 * r * dv
        parts = [g * width + off + np.arange(n)[None, :]
                 for off, n in ((0, dk), (dk, dk), (2 * dk, r * dv), (2 * dk + r * dv, r * dv))]
    return np.concatenate([part.reshape(-1) for part in parts])


# the shared expert beside routed ones: DeepSeek-V3's name is the tree's; Qwen3-Next's differs
_QWEN_SHARED = ("shared_experts", "shared_expert")

# HF afmoe (arcee-ai Trinity) names that differ from the tree's, stored name -> tree's name (dotted, below a
# layer): the two norms around the feed-forward, the router and its selection bias. Its attention gate is a
# ``self_attn.gate_proj`` of its own, which the tree keeps inside ``q_proj`` as ``[q | gate]`` by head (the leaf
# Qwen3-Next stores that way): joined on load, split on save (``_split_gate``). Its experts are DeepSeek-V3's names.
_AFMOE_NAMES = {
    "pre_mlp_layernorm": "pre_feedforward_layernorm",
    "post_mlp_layernorm": "post_feedforward_layernorm",
    "mlp.router.gate": "mlp.gate",
    "mlp.expert_bias": "mlp.gate.e_score_correction_bias",
}
_AFMOE_GATE = ("self_attn", "gate_proj", _KERNEL_LEAF)


def _afmoe(config: Optional[ModelConfig]) -> bool:
    """Whether a checkpoint of ``config`` carries HF afmoe's names: the one family whose blocks have an output
    gate on softmax attention AND four norms (Qwen3-Next has the gate and two norms, Gemma2 four norms and no gate)."""
    return config is not None and config.attention_output_gate and config.sandwich_norms


def _afmoe_renamed(name: str, to_stored: bool) -> str:
    """A dotted name with afmoe's parts renamed, whole parts only (``mlp.gate`` and not ``mlp.gate_proj``); the
    bias before the gate it lies under."""
    name += "."
    for stored, ours in reversed(_AFMOE_NAMES.items()):
        name = name.replace(f".{ours}.", f".{stored}.") if to_stored else name.replace(f".{stored}.", f".{ours}.")
    return name[:-1]


def _split_gate(kernel: np.ndarray, heads: int):
    """``q_proj``'s ``[hidden, heads x (q | gate)]`` -> (q, gate), each ``[hidden, heads x d]``."""
    h = kernel.shape[0]
    both = kernel.reshape(h, heads, 2, -1)
    return both[:, :, 0].reshape(h, -1), both[:, :, 1].reshape(h, -1)


def _join_gate(q: np.ndarray, gate: np.ndarray, heads: int) -> np.ndarray:
    h = q.shape[0]
    return np.stack([q.reshape(h, heads, -1), gate.reshape(h, heads, -1)], axis=2).reshape(h, -1)


# HF kimi_linear (moonshotai Kimi Linear) names that differ from the tree's. Both kinds of mixer are a layer's
# ``self_attn`` there; the tree keeps a Kimi Delta Attention mixer under ``linear_attn`` (the other linear mixer's
# subtree) with the parts both have under one name. HF keeps a convolution each for q, k and v, ``[channels, 1,
# taps]``; the tree ONE ``conv1d/weight [taps, q | k | v channels]`` leaf, the one the in pass reads
# (``ops/gated_delta.mixer_in``). ``A_log`` is stored ``[1, 1, heads, 1]``. The experts are a layer's
# ``block_sparse_moe`` with Mixtral's ``w1``/``w3``/``w2`` under each expert's GLOBAL id, the router its ``gate``
# with DeepSeek-V3's ``e_score_correction_bias``; a dense layer keeps ``mlp``.
_KIMI_MIXER = {"norm": "o_norm", "out_proj": "o_proj"}                   # tree's name -> stored name, below the mixer
_KIMI_EXPERT = {"gate_proj": "w1", "up_proj": "w3", "down_proj": "w2"}   # DeepSeek-V3's name -> stored name
_KIMI_LAYER = re.compile(r"^(model\.layers\.(\d+))\.(self_attn|linear_attn|mlp|block_sparse_moe)\.(.*)$")


def _kimi(config: Optional[ModelConfig]) -> bool:
    return config is not None and bool(config.linear_decay_rank)


def _kimi_to_stored(state: Dict[str, np.ndarray], config: ModelConfig) -> Dict[str, np.ndarray]:
    """A state dict under the tree's dotted names (torch layout) -> under HF kimi_linear's."""
    kd = config.linear_num_key_heads * config.linear_key_head_dim
    out = {}
    for name, arr in state.items():
        m = _KIMI_LAYER.match(name)
        if m and m.group(3) == "linear_attn":
            base, part = f"{m.group(1)}.self_attn.", m.group(4)
            if part == "conv1d.weight":
                for which, lo, hi in (("q", 0, kd), ("k", kd, 2 * kd), ("v", 2 * kd, arr.shape[0])):
                    out[f"{base}{which}_conv1d.weight"] = np.ascontiguousarray(arr[lo:hi])
                continue
            if part == "A_log":
                arr = arr.reshape(1, 1, -1, 1)
            head, _, tail = part.partition(".")
            name = base + _KIMI_MIXER.get(head, head) + (f".{tail}" if tail else "")
        elif m and m.group(3) == "mlp" and m.group(4).split(".")[0] in ("gate", "experts", "shared_experts"):
            parts = m.group(4).split(".")
            if parts[0] == "experts":
                parts[2] = _KIMI_EXPERT[parts[2]]
            name = f"{m.group(1)}.block_sparse_moe." + ".".join(parts)
        out[name] = arr
    return out


def _kimi_from_stored(state: Dict[str, np.ndarray], config: ModelConfig) -> Dict[str, np.ndarray]:
    """``_kimi_to_stored`` backwards: which layers' ``self_attn`` is a Kimi Delta Attention mixer is the config's to say."""
    ours_mixer = {v: k for k, v in _KIMI_MIXER.items()}
    ours_expert = {v: k for k, v in _KIMI_EXPERT.items()}
    out, convs = {}, {}
    for name, arr in state.items():
        m = _KIMI_LAYER.match(name)
        if m and m.group(3) == "self_attn" and config.layer(int(m.group(2))).attention == "kda":
            base, part = f"{m.group(1)}.linear_attn.", m.group(4)
            if part.endswith("_conv1d.weight"):
                convs.setdefault(base, {})[part[0]] = np.asarray(arr)
                continue
            if part == "A_log":
                arr = np.asarray(arr).reshape(-1)
            head, _, tail = part.partition(".")
            name = base + ours_mixer.get(head, head) + (f".{tail}" if tail else "")
        elif m and m.group(3) == "block_sparse_moe":
            parts = m.group(4).split(".")
            if parts[0] == "experts":
                parts[2] = ours_expert[parts[2]]
            name = f"{m.group(1)}.mlp." + ".".join(parts)
        out[name] = arr
    for base, parts in convs.items():
        out[base + "conv1d.weight"] = np.concatenate([parts[which] for which in "qkv"], axis=0)
    return out


# HF granitemoehybrid (IBM Granite 4.0-H) names that differ from the tree's (as remembered: no network here). A Mamba-2
# layer's mixer is the layer's ``mamba`` (``in_proj``, ``conv1d`` with its bias, ``dt_bias``, ``A_log``, ``D``, ``norm``,
# ``out_proj``: the tree's names). The feed-forward is ``shared_mlp`` with ONE ``input_linear.weight [2 f, hidden]``, the
# gate's rows then the up projection's, and ``output_linear``; the tree keeps ``mlp`` with the three leaves
# ``_dense_mlp`` reads, so the leaf is cut in two here.
_GRANITE_MLP = re.compile(r"^(model\.layers\.\d+)\.(mlp|shared_mlp)\.(.*)$")


def _granite(config: Optional[ModelConfig]) -> bool:
    return config is not None and "mamba" in config.layer_types


def _granite_to_stored(state: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """A state dict under the tree's dotted names (torch layout) -> under HF granitemoehybrid's."""
    out = {}
    for name, arr in state.items():
        m = _GRANITE_MLP.match(name)
        if m and m.group(3) == "gate_proj.weight":
            out[f"{m.group(1)}.shared_mlp.input_linear.weight"] = np.concatenate([arr, state[f"{m.group(1)}.mlp.up_proj.weight"]], axis=0)
        elif m and m.group(3) == "down_proj.weight":
            out[f"{m.group(1)}.shared_mlp.output_linear.weight"] = arr
        elif not (m and m.group(3) == "up_proj.weight"):
            out[name] = arr
    return out


def _granite_from_stored(state: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    out = {}
    for name, arr in state.items():
        m = _GRANITE_MLP.match(name)
        if m and m.group(3) == "input_linear.weight":
            half = arr.shape[0] // 2
            out[f"{m.group(1)}.mlp.gate_proj.weight"], out[f"{m.group(1)}.mlp.up_proj.weight"] = arr[:half], arr[half:]
        elif m and m.group(3) == "output_linear.weight":
            out[f"{m.group(1)}.mlp.down_proj.weight"] = arr
        else:
            out[name] = arr
    return out


# HF evabyte (EvaByte/EvaByte) stores EVA attention's two leaves a head as ``[1, heads, 1, 1, d]`` (as remembered: they
# broadcast against ``[batch, heads, windows, chunks, d]``); the tree keeps them ``[heads, d]``. Every other name of
# the checkpoint is a Llama block's, and ``lm_head.weight`` is ``[num_pred_heads x vocab, hidden]``, head by head.
_EVA_LEAVES = ("adaptive_phi", "adaptive_mu_k")


def pytree_to_hf_state_dict(params, config: Optional[ModelConfig] = None) -> Dict[str, np.ndarray]:
    """params pytree -> {hf_name: numpy array (torch layout)}. ``config`` is
    needed for a model with latent attention or held experts (the rope
    columns' stored order, the experts' global ids)."""
    state = {}
    for path, leaf in _flatten(params).items():
        arr = np.asarray(leaf)
        leaf_name = path[-1]
        if len(path) >= 3 and path[-3] == "mlp" and path[-2] == "experts" and leaf_name in _DEEPSEEK_EXPERT:
            # the grouped layer's [E_held, in, out] -> DeepSeek-V3's
            # `mlp.experts.<global id>.{gate,up,down}_proj.weight [out, in]`
            if config is None:
                raise ValueError("exporting a model of routed experts with shared experts needs its config")
            base = ".".join(path[:-1])
            for row, expert in enumerate(config.held_expert_ids):
                state[f"{base}.{expert}.{_DEEPSEEK_EXPERT[leaf_name]}.weight"] = np.ascontiguousarray(arr[row].T)
            continue
        if leaf_name == _KERNEL_LEAF and "kv_a_proj_with_mqa" in path and config is None:
            raise ValueError("exporting a model with latent attention needs its config")
        pairs = None
        if config is not None and leaf_name == _KERNEL_LEAF:
            pairs = _rope_pairs(config, path)
            if pairs is None:
                pairs = _linear_columns(config, path)
        if pairs is not None:
            arr = arr[:, np.argsort(pairs)]  # back to the stored order
        if "linear_attn" in path and config is None:
            raise ValueError("exporting a model with linear-attention layers needs its config")
        if config is not None and config.shared_expert_gate and _QWEN_SHARED[0] in path:
            path = tuple(_QWEN_SHARED[1] if part == _QWEN_SHARED[0] else part for part in path)
        if _afmoe(config):
            if path[-3:] == ("self_attn", "q_proj", _KERNEL_LEAF):
                base = ".".join(path[:-2])
                for name, part in zip(("q_proj", "gate_proj"), _split_gate(arr, config.num_heads)):
                    state[f"{base}.{name}.weight"] = np.ascontiguousarray(part.T)
                continue
            path = tuple(_afmoe_renamed(".".join(path), to_stored=True).split("."))
        if leaf_name in _EVA_LEAVES:
            state[".".join(path)] = np.ascontiguousarray(arr[None, :, None, None, :])
            continue
        if path[-2:] == ("conv1d", "weight"):
            # [taps, channels] -> torch Conv1d's [channels, 1, taps]
            state[".".join(path)] = np.ascontiguousarray(arr.T[:, None, :])
            continue
        if len(path) >= 2 and path[-2] == "experts" and leaf_name in ("w1", "w2", "w3"):
            # Stacked MoE expert weights [E, in, out] (ops/moe.py) -> HF
            # Mixtral's per-expert Linears `...experts.<i>.w<n>.weight [out, in]`
            base = ".".join(path[:-1])
            for i in range(arr.shape[0]):
                state[f"{base}.{i}.{leaf_name}.weight"] = np.ascontiguousarray(arr[i].T)
            continue
        if leaf_name == _KERNEL_LEAF:
            hf_name = ".".join(path[:-1]) + ".weight"
            arr = arr.T
        elif leaf_name in ("lora_a", "lora_b", "lora_scale"):
            continue  # adapters exported separately (parallel/lora.py)
        else:
            hf_name = ".".join(path)
        state[hf_name] = np.ascontiguousarray(arr)
    if _granite(config):
        return _granite_to_stored(state)
    return _kimi_to_stored(state, config) if _kimi(config) else state


def hf_state_dict_to_pytree(state: Dict[str, np.ndarray], config: ModelConfig, dtype=None):
    """{hf_name: array} -> params pytree (transposing Linear weights).

    Handles tied embeddings: if the checkpoint carries no ``lm_head.weight``
    and the config ties embeddings, none is created; if the config does NOT
    tie but the checkpoint omits lm_head (HF stores tied models without it),
    raises.
    """
    # Names whose final '.weight' is a torch-layout matrix needing transpose.
    def needs_transpose(name: str) -> bool:
        return name.endswith(".weight") and any(
            part in name
            for part in (
                "q_proj", "k_proj", "v_proj", "o_proj",
                "gate_proj", "up_proj", "down_proj", "lm_head",
                "block_sparse_moe.gate", "kv_a_proj_with_mqa", "kv_b_proj", "mlp.gate.",
                "in_proj_qkvz", "in_proj_ba", "linear_attn.out_proj", "shared_expert_gate",
                "linear_attn.b_proj", "f_a_proj", "f_b_proj", "g_a_proj", "g_b_proj",
                "mamba.in_proj", "mamba.out_proj",
            )
        )

    if _kimi(config):
        state = _kimi_from_stored(state, config)
    if _granite(config):
        state = _granite_from_stored(state)
    deepseek_re = re.compile(r"^(.*\.mlp\.experts)\.(\d+)\.(gate_proj|up_proj|down_proj)\.weight$")
    stacked_name = {v: k for k, v in _DEEPSEEK_EXPERT.items()}
    held_row = {expert: row for row, expert in enumerate(config.held_expert_ids)}
    held: Dict[tuple, Dict[int, np.ndarray]] = {}
    expert_re = re.compile(r"^(.*\.experts)\.(\d+)\.(w[123])\.weight$")
    experts: Dict[tuple, Dict[int, np.ndarray]] = {}
    flat: Dict[tuple, np.ndarray] = {}
    for name, arr in state.items():
        arr = np.asarray(arr)
        if dtype is not None:
            arr = arr.astype(dtype)
        m = deepseek_re.match(name)
        if m:
            # DeepSeek-V3 per-expert Linear [out, in] -> row of the stacked
            # [E_held, in, out] leaf; experts held elsewhere are passed over
            if int(m.group(2)) in held_row:
                key = tuple(m.group(1).split(".")) + (stacked_name[m.group(3)],)
                held.setdefault(key, {})[held_row[int(m.group(2))]] = np.ascontiguousarray(arr.T)
            continue
        m = expert_re.match(name)
        if m:
            # HF Mixtral per-expert Linear [out, in] -> row of the stacked
            # [E, in, out] leaf (ops/moe.py layout)
            key = tuple(m.group(1).split(".")) + (m.group(3),)
            experts.setdefault(key, {})[int(m.group(2))] = np.ascontiguousarray(arr.T)
            continue
        name = name.replace(f".mlp.{_QWEN_SHARED[1]}.", f".mlp.{_QWEN_SHARED[0]}.")
        name = _afmoe_renamed(name, to_stored=False)  # (no other family stores one of these names)
        if needs_transpose(name):
            path = tuple(name[: -len(".weight")].split(".")) + (_KERNEL_LEAF,)
            arr = np.ascontiguousarray(arr.T)
            pairs = _rope_pairs(config, path)
            if pairs is None:
                pairs = _linear_columns(config, path)
            if pairs is not None:
                arr = np.ascontiguousarray(arr[:, pairs])
        else:
            path = tuple(name.split("."))
            if path[-2:] == ("conv1d", "weight"):  # torch Conv1d's [channels, 1, taps] -> [taps, channels]
                arr = np.ascontiguousarray(arr[:, 0, :].T)
            elif path[-1] in _EVA_LEAVES:
                arr = np.ascontiguousarray(arr.reshape(config.num_heads, -1))
        flat[path] = arr
    for path in [p for p in flat if p[-3:] == _AFMOE_GATE]:  # afmoe's gate_proj into q_proj, [q | gate] by head
        q_path = path[:-2] + ("q_proj", _KERNEL_LEAF)
        flat[q_path] = _join_gate(flat[q_path], flat.pop(path), config.num_heads)
    for key, rows in experts.items():
        n = config.num_experts or (max(rows) + 1)
        missing = [i for i in range(n) if i not in rows]
        if missing:
            raise ValueError(
                f"checkpoint is missing expert tensors {missing} for "
                f"{'.'.join(key)} (expected {n} experts)"
            )
        if max(rows) + 1 > n:
            raise ValueError(
                f"checkpoint has {max(rows) + 1} experts for {'.'.join(key)} "
                f"but config.num_experts={n}"
            )
        flat[key] = np.stack([rows[i] for i in range(n)])

    for key, rows in held.items():
        missing = [e for e, row in held_row.items() if row not in rows]
        if missing:
            raise ValueError(f"checkpoint is missing held experts {missing} for {'.'.join(key)}")
        flat[key] = np.stack([rows[i] for i in range(len(held_row))])

    if config.tie_word_embeddings:
        flat.pop(("lm_head", _KERNEL_LEAF), None)
    elif ("lm_head", _KERNEL_LEAF) not in flat:
        embed = flat.get(("model", "embed_tokens", "weight"))
        if embed is None:
            raise ValueError("checkpoint has neither lm_head nor embed_tokens")
        flat[("lm_head", _KERNEL_LEAF)] = np.ascontiguousarray(embed.T)
    return _unflatten(flat)


# ---------------------------------------------------------------------------
# safetensors files
# ---------------------------------------------------------------------------


def load_safetensors_dir(path: str) -> Dict[str, np.ndarray]:
    """Read one or many ``*.safetensors`` files (sharded HF checkpoints use
    ``model.safetensors.index.json``)."""
    from safetensors.numpy import load_file

    if os.path.isfile(path):
        return load_file(path)
    index = os.path.join(path, "model.safetensors.index.json")
    state: Dict[str, np.ndarray] = {}
    if os.path.exists(index):
        with open(index) as f:
            weight_map = json.load(f)["weight_map"]
        for shard in sorted(set(weight_map.values())):
            state.update(load_file(os.path.join(path, shard)))
        return state
    single = os.path.join(path, "model.safetensors")
    if os.path.exists(single):
        return load_file(single)
    shards = sorted(
        f for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if not shards:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    for shard in shards:
        state.update(load_file(os.path.join(path, shard)))
    return state


def load_hf_checkpoint(path: str, config: ModelConfig, dtype=np.float32):
    """Load an HF checkpoint directory (or single file) into a params pytree."""
    state = load_safetensors_dir(path)
    # torch bf16 arrives as uint16 view through safetensors.numpy on some
    # versions; normalize via ml_dtypes if needed.
    state = {k: _as_float(v) for k, v in state.items()}
    return hf_state_dict_to_pytree(state, config, dtype=dtype)


def _as_float(arr: np.ndarray) -> np.ndarray:
    if arr.dtype == np.uint16:
        import ml_dtypes

        return arr.view(ml_dtypes.bfloat16)
    return arr


_MAX_SHARD_BYTES = 4 * 1024**3


def save_hf_checkpoint(
    params,
    path: str,
    *,
    metadata: Optional[Dict[str, str]] = None,
    save_dtype=None,
    config: Optional[ModelConfig] = None,
):
    """Write params as HF-layout safetensors under ``path`` (sharding files at
    4GB like HF does). Produces ``model.safetensors`` or shards + index."""
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    state = pytree_to_hf_state_dict(params, config)
    if save_dtype is not None:
        state = {k: v.astype(save_dtype) for k, v in state.items()}

    total = sum(v.nbytes for v in state.values())
    meta = {"format": "pt", **(metadata or {})}
    if total <= _MAX_SHARD_BYTES:
        save_file(state, os.path.join(path, "model.safetensors"), metadata=meta)
        return

    shards: list = [{}]
    sizes = [0]
    for name, arr in state.items():
        if sizes[-1] + arr.nbytes > _MAX_SHARD_BYTES and shards[-1]:
            shards.append({})
            sizes.append(0)
        shards[-1][name] = arr
        sizes[-1] += arr.nbytes

    n = len(shards)
    weight_map = {}
    for i, shard in enumerate(shards):
        fname = f"model-{i + 1:05d}-of-{n:05d}.safetensors"
        save_file(shard, os.path.join(path, fname), metadata=meta)
        for name in shard:
            weight_map[name] = fname
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {"total_size": total}, "weight_map": weight_map}, f, indent=2)
