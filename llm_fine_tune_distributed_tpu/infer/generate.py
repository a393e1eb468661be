"""Autoregressive generation: jitted prefill + ``lax.while_loop`` KV-cache
decode — the TPU-native replacement for the reference's ``model.generate``
call (reference ``ask_tuned_model.py:55-65``). The whole decode loop is ONE
XLA program; prompt lengths are bucketed so recompiles are rare.

Layout invariant: decoded token *t* is written at cache slot
``prompt_len + t``, so cache-slot index == logical position and the causal
mask over the fixed-size buffer needs no separate validity tracking (pad
slots written during prefill sit at positions > query position until
overwritten, hence always masked).
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from llm_fine_tune_distributed_tpu.config import ModelConfig
from llm_fine_tune_distributed_tpu.infer.sampling import (
    GenerationConfig,
    rejection_sample_step_traced,
    sample_token,
    sample_token_traced,
)
from llm_fine_tune_distributed_tpu.models.transformer import (
    forward,
    init_cache,
    init_paged_cache,
    insert_cache_row,
    unembed,
)
from llm_fine_tune_distributed_tpu.observe.xla import CompileLedger, instrument

_PROMPT_BUCKET = 256


def _prompt_prefill(params, prompt_ids, prompt_lens, *, mc, dtype, act, mesh,
                    buf_len, gen, rng):
    """Shared prompt-ingest for every decode builder: cache init + prefill
    forward + per-row last-position logits + seen-set init + first sampled
    token. Returns ``(first [b], cache, seen [b, V], valid [b, pb], rng)``
    — the single source for the padding/seen semantics all decode paths
    must agree on."""
    b, pb = prompt_ids.shape
    rows = jnp.arange(b)
    cache = init_cache(mc, b, buf_len, dtype=dtype)
    hidden, cache = forward(
        params, prompt_ids, mc, cache=cache, cache_pos=0,
        compute_dtype=dtype, output_hidden=True, activation_sharding=act,
    )
    last_h = jnp.take_along_axis(
        hidden, (prompt_lens - 1)[:, None, None], axis=1
    )[:, 0]
    logits0 = unembed(params, last_h, mc, compute_dtype=dtype, mesh=mesh)
    valid = jnp.arange(pb)[None, :] < prompt_lens[:, None]
    safe_ids = jnp.where(valid, prompt_ids, prompt_ids[:, :1])
    seen = jnp.zeros((b, mc.vocab_size), bool).at[rows[:, None], safe_ids].set(True)
    rng, sub = jax.random.split(rng)
    first = sample_token(sub if gen.do_sample else None, logits0, seen, gen)
    seen = seen.at[rows, first].set(True)
    return first, cache, seen, valid, rng


def make_tp_mesh(tp: int, model_config: Optional[ModelConfig] = None):
    """Tensor-parallel inference mesh over the first ``tp`` devices of the
    GLOBAL pool (the `--tp` flag of ask_tuned_model.py / smollm3-serve).

    Under ``jax.distributed`` the pool spans processes, so ``tp`` may exceed
    the local device count — a llama3_70b int8 (~70 GB) becomes servable on
    a 2-host v5e-8 with ``--tp 8``. The Generator detects the
    process-spanning mesh and switches to global-array placement/inputs.

    With ``model_config`` the KV-head geometry is validated UP FRONT instead
    of failing deep inside ``shard_params`` with a bare shape error: when
    ``tp`` does not divide ``num_kv_heads`` (GQA presets with few KV heads)
    the KV cache falls back to head REPLICATION — correct but each chip
    holds the full cache — and a warning says so at mesh build time."""
    import warnings

    import jax as _jax

    from llm_fine_tune_distributed_tpu.config import MeshConfig
    from llm_fine_tune_distributed_tpu.runtime.mesh import make_mesh

    if tp > len(_jax.devices()):
        raise ValueError(
            f"--tp {tp} exceeds the {len(_jax.devices())} visible devices "
            f"across {_jax.process_count()} process(es); start more hosts "
            "under jax.distributed (MASTER_ADDR/PORT, WORLD_SIZE/RANK)"
        )
    if model_config is not None and tp > 1:
        if model_config.num_kv_heads % tp != 0:
            warnings.warn(
                f"--tp {tp} does not divide num_kv_heads="
                f"{model_config.num_kv_heads}: KV-cache leaves fall back to "
                "head replication (every chip holds the full cache; weights "
                f"still shard {tp}-way). For a sharded cache pick a tp that "
                f"divides {model_config.num_kv_heads}.",
                stacklevel=2,
            )
    return make_mesh(MeshConfig(data=1, fsdp=1, tensor=tp, seq=1, expert=1, pipe=1))


class LatentAttentionNotServed(NotImplementedError):
    """A model with a kind of layer whose cache ``infer/`` does not have
    (``ModelConfig.layer(i).attention`` other than ``"heads"``) was handed to
    the serving path. It trains (``models/transformer.forward`` without a
    cache). Latent attention's cache, one latent and one rope key a token, is
    a third layout (ROADMAP.md, Reach C); a linear-attention layer's is a
    state and the convolution's last inputs, not keys and values (Reach D).
    (The name is the first kind's; both are refused here.)"""

    _LACKS = {
        "latent": "latent attention: it is supported on the training path only; serving it needs a latent KV "
                  "cache layout and a decode path that infer/ lacks",
        "linear": "linear-attention layers: it is supported on the training path only; serving it needs a "
                  "recurrent state (and the convolution's last inputs) as a cache entry, which infer/ lacks",
    }
    _LACKS["kda"] = _LACKS["linear"]  # Kimi Delta Attention: the same kind of state, a decay a channel
    _LACKS["eva"] = ("EVA attention: it is supported on the training path only; serving it needs a cache of the "
                     "current window's keys and values beside a growing list of pooled summaries, and a decode path "
                     "for its several next-token heads, which infer/ lacks")
    _LACKS["ssd"] = ("state-space (Mamba-2) layers: it is supported on the training path only; serving it needs each "
                     "layer's state and the convolution's last inputs as a cache entry (continuous batching of states), "
                     "which infer/ lacks")

    def __init__(self, name: str, kind: str = "latent"):
        super().__init__(f"model {name!r} has {self._LACKS[kind]}")


def unserved_layer_kind(config: ModelConfig):
    """The first kind of layer of ``config`` that has no cache here, or None."""
    kinds = {config.layer(i).attention for i in range(config.num_layers)}
    return next((kind for kind in ("latent", "linear", "kda", "eva", "ssd") if kind in kinds), None)


class Generator:
    """Generation engine over a params pytree — single-chip by default, or
    sharded over a device mesh.

    With ``mesh`` (tensor/expert axes live), weights shard per the training
    rules (parallel/sharding.py: Megatron column/row TP, stacked experts
    over ``expert``) and the KV cache follows the kv-head sharding by
    propagation — so llama3_70b / mixtral presets that exceed one chip's
    HBM are servable. Single-chip is the degenerate ``mesh=None`` case; the
    reference's analog is ``device_map="auto"`` multi-GPU loading
    (reference ``ask_tuned_model.py:26-30``)."""

    def __init__(
        self,
        params,
        model_config: ModelConfig,
        tokenizer,
        compute_dtype=jnp.bfloat16,
        eos_token_ids: Optional[Sequence[int]] = None,
        mesh=None,
        draft_params=None,
        draft_config: Optional[ModelConfig] = None,
    ):
        """``draft_params``/``draft_config``: an optional SMALL model sharing
        this tokenizer's vocab. With both set and
        ``GenerationConfig.speculative_lookup > 0``, speculation drafts with
        the draft MODEL instead of prompt-lookup — the draft generalizes
        beyond repetition-heavy outputs (prompt-lookup's limit), at the cost
        of running the small model K steps per verify."""
        for served in (model_config, draft_config):
            if served is not None and (kind := unserved_layer_kind(served)) is not None:
                raise LatentAttentionNotServed(served.name, kind)
        self.mesh = mesh
        self._act_sharding = None
        self._multihost = False
        if (draft_params is None) != (draft_config is None):
            raise ValueError("draft_params and draft_config come together")
        if draft_config is not None and draft_config.vocab_size != model_config.vocab_size:
            raise ValueError(
                f"draft vocab {draft_config.vocab_size} != target vocab "
                f"{model_config.vocab_size} — speculation verifies token ids"
            )
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from llm_fine_tune_distributed_tpu.parallel.sharding import shard_params

            self._multihost = any(
                d.process_index != jax.process_index() for d in mesh.devices.flat
            )
            params = shard_params(params, mesh)
            if draft_params is not None:
                draft_params = shard_params(draft_params, mesh)
            # batch-1 decode activations are tiny: keep them replicated and
            # let the weight shardings drive the per-block psums. Passing
            # the sharding also hands `forward` the mesh (embed/unembed
            # vocab-sharded lookups, MoE expert dispatch).
            self._act_sharding = NamedSharding(mesh, P())
        self._draft_params = draft_params
        self._draft_config = draft_config
        self.params = params
        self.config = model_config
        self.tokenizer = tokenizer
        self.compute_dtype = compute_dtype
        eos = eos_token_ids
        if eos is None:
            eos = [tokenizer.eos_token_id] if tokenizer.eos_token_id is not None else []
        self.eos_token_ids = tuple(int(e) for e in eos)
        self._jit_cache = {}
        # every jitted program this Generator dispatches registers its
        # compilations here (observe/xla.py); engines sharing the Generator
        # share the ledger, so a fleet's shared jit cache is counted once
        self.compile_ledger = CompileLedger()
        # sequential-forward count + draft acceptance rate of the last
        # speculative run (telemetry; None when the last call took the plain
        # batch path). The per-row arrays attribute each LIVE row's own
        # proposed/accepted draft counts so the window batcher can report
        # per-request numbers instead of pinning the batch-global rate on
        # every rider (infer/batching.py).
        self.last_spec_steps: Optional[int] = None
        self.last_acceptance_rate: Optional[float] = None
        self.last_row_draft_proposed: Optional[np.ndarray] = None
        self.last_row_draft_accepted: Optional[np.ndarray] = None

    @property
    def has_draft(self) -> bool:
        """True when a draft model is attached (speculation drafts with it)."""
        return self._draft_params is not None

    @property
    def draft_params(self):
        """Draft-model params pytree (operand for the engine draft step)."""
        return self._draft_params

    # ------------------------------------------------------------- jit build

    def _build_batch(self, batch: int, prompt_bucket: int, gen: GenerationConfig):
        """Compile one (batch, prompt_bucket, generation-config)
        specialization with per-row prompt lengths (ragged batches).

        Right-padded prompts prefill the whole bucket; row *i*'s decoded
        token *t* is written at cache slot ``len_i + t`` (vector ``cache_pos``
        — progressively overwriting that row's pad slots), so the cache
        slot == logical position invariant holds per row and un-overwritten
        pad slots sit at positions > any query, hence always masked. Greedy
        decode of a batched row is bit-identical to running that prompt
        alone (the single-prompt path IS the batch-of-1 case); SAMPLED rows
        draw from a batched RNG stream, so row i > 0 sees different (still
        seeded/deterministic) noise than a solo run would.
        """
        mc = self.config
        dtype = self.compute_dtype
        mesh, act = self.mesh, self._act_sharding
        buf_len = prompt_bucket + gen.max_new_tokens
        eos = jnp.asarray(self.eos_token_ids, jnp.int32) if self.eos_token_ids else None

        def step_logits(params, token_ids, cache, cache_pos):
            hidden, cache = forward(
                params, token_ids, mc, cache=cache, cache_pos=cache_pos,
                compute_dtype=dtype, output_hidden=True, activation_sharding=act,
            )
            logits = unembed(params, hidden[:, -1], mc, compute_dtype=dtype, mesh=mesh)
            return logits, cache

        @jax.jit
        def run(params, prompt_ids, prompt_lens, rng):
            b = prompt_ids.shape[0]
            first, cache, seen, _, rng = _prompt_prefill(
                params, prompt_ids, prompt_lens, mc=mc, dtype=dtype, act=act,
                mesh=mesh, buf_len=buf_len, gen=gen, rng=rng,
            )
            out = jnp.zeros((b, gen.max_new_tokens), jnp.int32)
            out = out.at[:, 0].set(first)
            done = jnp.isin(first, eos) if eos is not None else jnp.zeros((b,), bool)

            def cond(c):
                t, _, _, _, done, _ = c
                return (t < gen.max_new_tokens) & ~done.all()

            def body(c):
                t, cache, out, seen, done, rng = c
                last = jax.lax.dynamic_index_in_dim(out, t - 1, axis=1)
                logits, cache = step_logits(
                    params, last, cache, prompt_lens + (t - 1)
                )
                rng, sub = jax.random.split(rng)
                nxt = sample_token(sub, logits, seen, gen)
                hit_eos = jnp.isin(nxt, eos) if eos is not None else jnp.zeros((b,), bool)
                nxt = jnp.where(done, nxt * 0 + (eos[0] if eos is not None else 0), nxt)
                out = out.at[:, t].set(nxt)
                seen = seen.at[jnp.arange(b), nxt].set(True)
                return (t + 1, cache, out, seen, done | hit_eos, rng)

            t, cache, out, seen, done, rng = jax.lax.while_loop(
                cond, body, (jnp.int32(1), cache, out, seen, done, rng)
            )
            return out, t

        return run

    def _build_spec(
        self, batch: int, prompt_bucket: int, gen: GenerationConfig,
        with_draft: bool = False,
    ):
        """Compile the speculative decoder (any batch size).

        ``with_draft=False``: prompt-lookup proposals (bigram match in each
        row's own context — zero extra model cost, pays off on
        repetition-heavy outputs). ``with_draft=True``: DRAFT-MODEL
        proposals (``draft_params``/``draft_config`` from the constructor) —
        K greedy tokens from the small model per step, which speculates on
        any text at the cost of K small forwards. Verification is identical
        for both sources, so the output guarantees below hold unchanged.

        Each step feeds every row's ``[cur, d_1..d_K]`` (K =
        ``gen.speculative_lookup`` drafts found by matching that row's newest
        bigram earlier in its own context) through ONE forward — rows carry
        independent positions (vector ``cache_pos``), so they desynchronize
        freely as their acceptance counts diverge; the loop runs until every
        row is done.

        GREEDY verify accepts the longest prefix of drafts that match the
        model's own greedy choices — algorithmically plain greedy decode
        (bit-exact in f32, tests/test_generate.py; bf16 near-ties at the
        chunked verify may resolve differently, as in any chunked-verify
        speculative decoder).

        SAMPLED verify is rejection sampling against the warped target
        distribution q (Leviathan et al. / SpecInfer, specialized to the
        deterministic prompt-lookup proposal): accept draft d with
        probability q(d); on rejection draw from the renormalized residual
        q with d removed — which makes the emitted token exactly
        q-distributed at every position, so the OUTPUT DISTRIBUTION equals
        plain sampling's (pinned statistically by tests/test_generate.py).
        Draft tokens outside the top-k/top-p support have q = 0 and always
        reject.

        Pays off when the OUTPUT repeats n-grams from the context
        (extractive QA, code, summaries); on low-repetition text the
        K+1-wide verify is pure overhead — hence opt-in, default off.
        Rollback is free under the slot == position invariant: the next
        step's writes start at the last accepted position, overwriting every
        slot a rejected draft touched before any query can see it.
        """
        mc = self.config
        dtype = self.compute_dtype
        mesh, act = self.mesh, self._act_sharding
        dmc = self._draft_config if with_draft else None
        K = gen.speculative_lookup
        max_new = gen.max_new_tokens
        buf_len = prompt_bucket + max_new + K + 1
        eos = jnp.asarray(self.eos_token_ids, jnp.int32) if self.eos_token_ids else None

        def is_eos(tok):
            return jnp.isin(tok, eos) if eos is not None else jnp.zeros_like(tok, bool)

        import dataclasses

        greedy_gen = dataclasses.replace(gen, do_sample=False)

        def _run(params, dparams, prompt_ids, prompt_lens, rng):
            b, pb = prompt_ids.shape
            rows = jnp.arange(b)
            first, cache, seen, valid, rng = _prompt_prefill(
                params, prompt_ids, prompt_lens, mc=mc, dtype=dtype, act=act,
                mesh=mesh, buf_len=buf_len, gen=gen, rng=rng,
            )

            if dmc is not None:
                # the draft model sees the full prompt too; its cache stays
                # position-synced with accepted history via the re-ingest
                # window each step
                dcache = init_cache(dmc, b, buf_len, dtype=dtype)
                _, dcache = forward(
                    dparams, prompt_ids, dmc, cache=dcache, cache_pos=0,
                    compute_dtype=dtype, output_hidden=True,
                    activation_sharding=act,
                )
            else:
                dcache = jnp.zeros((), jnp.int32)  # placeholder carry slot

            # per-row token history: prompt + generated, in logical positions
            ids_buf = jnp.zeros((b, buf_len), jnp.int32)
            ids_buf = ids_buf.at[:, :pb].set(jnp.where(valid, prompt_ids, 0))
            ids_buf = ids_buf.at[rows, prompt_lens].set(first)
            done = is_eos(first)
            n_gen = jnp.ones((b,), jnp.int32)

            def lookup_draft(ids_buf, pos, dcache, seen):
                """Prompt-lookup proposal: continuation of the most recent
                earlier occurrence of each row's newest bigram."""
                l0 = ids_buf[rows, pos - 2]
                l1 = ids_buf[rows, pos - 1]
                j = jnp.arange(buf_len - 1)
                match = (
                    (ids_buf[:, :-1] == l0[:, None])
                    & (ids_buf[:, 1:] == l1[:, None])
                    & (j[None, :] < (pos - 2)[:, None])
                )
                j_star = jnp.max(jnp.where(match, j[None, :], -1), axis=1)
                # garbage drafts are harmless: acceptance re-derives every
                # token from the model's own choice
                start = jnp.clip(j_star + 2, 0, buf_len - K)
                draft = jax.vmap(
                    lambda buf, s: jax.lax.dynamic_slice(buf, (s,), (K,))
                )(ids_buf, start)  # [b, K]
                return draft, dcache

            def model_draft(ids_buf, pos, dcache, seen):
                """Draft-model proposal: K continuations from the small
                model, drawn with the TARGET's greedy sampler semantics
                (repetition penalty over a speculatively-updated seen set) —
                so a perfect draft achieves 100% acceptance. A (K+1)-wide
                re-ingest window first replays the ACCEPTED tokens since the
                last step into the draft cache (overwriting any
                rejected-draft K/V — same slot==position rollback the
                target uses), and its last logits give d_0."""
                start = jnp.maximum(pos - (K + 1), 0)
                win = jax.vmap(
                    lambda buf, s: jax.lax.dynamic_slice(buf, (s,), (K + 1,))
                )(ids_buf, start)
                dh, dcache = forward(
                    dparams, win, dmc, cache=dcache, cache_pos=start,
                    compute_dtype=dtype, output_hidden=True,
                    activation_sharding=act,
                )
                idx = pos - 1 - start  # window index of token pos-1
                cur_h = jnp.take_along_axis(dh, idx[:, None, None], axis=1)[:, 0]
                spec_seen = seen

                def propose(logits, spec_seen):
                    # deterministic proposal even under sampled verify (the
                    # rejection sampler assumes a deterministic proposal,
                    # like prompt-lookup): greedy with the target's penalty
                    d = sample_token(None, logits, spec_seen, greedy_gen)
                    return d, spec_seen.at[rows, d].set(True)

                d0, spec_seen = propose(
                    unembed(dparams, cur_h, dmc, compute_dtype=dtype, mesh=mesh),
                    spec_seen,
                )
                dbuf = jnp.zeros((b, K), jnp.int32).at[:, 0].set(d0)

                def dstep(i, c):
                    dcache, dbuf, spec_seen = c
                    prev = dbuf[rows, i - 1]
                    dh, dcache = forward(
                        dparams, prev[:, None], dmc, cache=dcache,
                        cache_pos=pos + i - 1, compute_dtype=dtype,
                        output_hidden=True, activation_sharding=act,
                    )
                    nxt, spec_seen = propose(
                        unembed(dparams, dh[:, -1], dmc, compute_dtype=dtype, mesh=mesh),
                        spec_seen,
                    )
                    return (dcache, dbuf.at[:, i].set(nxt), spec_seen)

                if K > 1:
                    dcache, dbuf, _ = jax.lax.fori_loop(
                        1, K, dstep, (dcache, dbuf, spec_seen)
                    )
                return dbuf, dcache

            draft_fn = model_draft if dmc is not None else lookup_draft

            def body(c):
                n_gen, cache, dcache, ids_buf, seen, done, n_steps, row_steps, rng = c
                pos = prompt_lens + n_gen  # [b] position of each next token
                alive = (n_gen < max_new) & ~done

                draft, dcache = draft_fn(ids_buf, pos, dcache, seen)

                cur = ids_buf[rows, pos - 1]
                inputs = jnp.concatenate([cur[:, None], draft], axis=1)  # [b, K+1]
                hidden, new_cache = forward(
                    params, inputs, mc, cache=cache, cache_pos=pos - 1,
                    compute_dtype=dtype, output_hidden=True, activation_sharding=act,
                )
                logits_all = unembed(params, hidden, mc, compute_dtype=dtype, mesh=mesh)

                # --- sequential verify (evolving repetition-penalty set).
                # Position i's token is ALWAYS valid when emitted (its logits
                # condition only on accepted tokens); `active` gates whether
                # position i+1 may still consume the next draft. All per-row.
                def verify(i, v):
                    seen, ids_buf, n_acc, active, done, rng = v
                    d = draft[:, jnp.minimum(i, K - 1)]
                    if gen.do_sample:
                        from llm_fine_tune_distributed_tpu.infer.sampling import (
                            rejection_sample_step,
                        )

                        rng, sub = jax.random.split(rng)
                        tok, keep_going = rejection_sample_step(
                            sub, logits_all[:, i], seen, d, gen, bonus=i >= K,
                        )
                    else:
                        tok = sample_token(None, logits_all[:, i], seen, gen)
                        # token i+1 is valid only if draft i matched the
                        # greedy choice (slot K has no draft to validate)
                        keep_going = (i >= K) | (d == tok)
                    take = active & ~done & (n_gen + i < max_new)
                    seen = jnp.where(
                        take[:, None], seen.at[rows, tok].set(True), seen
                    )
                    ids_buf = jnp.where(
                        take[:, None], ids_buf.at[rows, pos + i].set(tok), ids_buf
                    )
                    n_acc = n_acc + take.astype(jnp.int32)
                    done = done | (take & is_eos(tok))
                    active = active & keep_going
                    return (seen, ids_buf, n_acc, active, done, rng)

                seen, ids_buf, n_acc, _, done, rng = jax.lax.fori_loop(
                    0, K + 1, verify,
                    (seen, ids_buf, jnp.zeros((b,), jnp.int32), alive, done, rng),
                )
                return (
                    n_gen + n_acc, new_cache, dcache, ids_buf, seen, done,
                    n_steps + 1, row_steps + alive.astype(jnp.int32), rng,
                )

            def cond(c):
                n_gen, _, _, _, _, done, _, _, _ = c
                return jnp.any((n_gen < max_new) & ~done)

            n_gen, cache, dcache, ids_buf, seen, done, n_steps, row_steps, rng = (
                jax.lax.while_loop(
                    cond, body,
                    (n_gen, cache, dcache, ids_buf, seen, done, jnp.int32(1),
                     jnp.zeros((b,), jnp.int32), rng),
                )
            )
            out = jax.vmap(
                lambda buf, s: jax.lax.dynamic_slice(buf, (s,), (max_new,))
            )(ids_buf, prompt_lens)
            # n_steps counts sequential forwards (prefill + spec steps);
            # row_steps counts the steps each row was still generating — a
            # row's accepted drafts total n_gen - 1 - row_steps
            return out, n_gen, n_steps, row_steps

        if with_draft:
            return jax.jit(_run)
        return jax.jit(
            lambda params, prompt_ids, prompt_lens, rng: _run(
                params, None, prompt_ids, prompt_lens, rng
            )
        )

    def _build_stream(self, prompt_bucket: int, gen: GenerationConfig, chunk: int):
        """Compile the STREAMING decode pair: a prefill program plus a
        fixed-``chunk`` continuation program whose cache/state round-trips
        through the host, so tokens can be surfaced every ``chunk`` steps
        instead of after the whole ``max_new_tokens`` while_loop.

        The cache buffer carries ``chunk`` slack slots so the final
        continuation may overrun ``max_new_tokens`` harmlessly (the host
        trims); per-chunk host sync costs ~one dispatch latency per chunk —
        the price of first-token latency dropping from O(max_new) to
        O(chunk) decode steps."""
        mc = self.config
        dtype = self.compute_dtype
        mesh, act = self.mesh, self._act_sharding
        buf_len = prompt_bucket + gen.max_new_tokens + chunk
        eos = jnp.asarray(self.eos_token_ids, jnp.int32) if self.eos_token_ids else None

        def step_logits(params, token_ids, cache, cache_pos):
            hidden, cache = forward(
                params, token_ids, mc, cache=cache, cache_pos=cache_pos,
                compute_dtype=dtype, output_hidden=True, activation_sharding=act,
            )
            logits = unembed(params, hidden[:, -1], mc, compute_dtype=dtype, mesh=mesh)
            return logits, cache

        @jax.jit
        def prefill(params, prompt_ids, prompt_lens, rng):
            first, cache, seen, _, rng = _prompt_prefill(
                params, prompt_ids, prompt_lens, mc=mc, dtype=dtype, act=act,
                mesh=mesh, buf_len=buf_len, gen=gen, rng=rng,
            )
            return first, cache, seen, rng

        @jax.jit
        def decode_chunk(params, cache, prompt_lens, t0, last, seen, rng):
            b = last.shape[0]

            def body(i, c):
                cache, toks, last, seen, rng = c
                # token t0+i consumes token t0+i-1 sitting at slot len+t0+i-1
                logits, cache = step_logits(
                    params, last[:, None], cache, prompt_lens + t0 + i - 1
                )
                rng, sub = jax.random.split(rng)
                nxt = sample_token(sub, logits, seen, gen)
                seen = seen.at[jnp.arange(b), nxt].set(True)
                toks = toks.at[:, i].set(nxt)
                return (cache, toks, nxt, seen, rng)

            toks0 = jnp.zeros((b, chunk), jnp.int32)
            cache, toks, last, seen, rng = jax.lax.fori_loop(
                0, chunk, body, (cache, toks0, last, seen, rng)
            )
            return toks, cache, last, seen, rng

        return prefill, decode_chunk

    # --------------------------------------------------- continuous batching

    # Per-slot decode state consumed by infer/engine.py. The KV cache is ONE
    # shared [slots, buf_len] buffer; each slot additionally carries:
    #   last [S] i32     last emitted token (next step's input)
    #   pos  [S] i32     logical position of `last` == its cache slot
    #   seen [S, V] bool repetition-penalty set
    #   rng  [S, 2] u32  per-slot PRNG key chain, seeded from the REQUEST's
    #                    seed at insert — sampling is deterministic in
    #                    (request, seed) regardless of slot index/co-residents
    #   adapter_idx [S] i32  pool slot of the request's LoRA adapter
    #                    (infer/adapters.py; 0 = identity/base model) — the
    #                    forward batch-gathers each row's low-rank delta, so
    #                    tenants co-batch in ONE dispatch
    #   + one [S] array per traced sampling knob (sample_token_traced), so
    #     mixed-config traffic co-batches in one compiled step.
    # Liveness stays HOST-side (the engine passes a [S] bool mask): freeing a
    # slot costs no device op. Dead rows still run through the forward (the
    # batch shape is static) but their pos/seen/rng are frozen and their
    # writes land in their own row at a fixed slot — harmless, since a reused
    # slot rewrites every cache position before any query can attend to it
    # (slot == position invariant; see insert_cache_row).

    def _fresh_slot_state(self, slots: int):
        mc = self.config
        return {
            "last": jnp.zeros((slots,), jnp.int32),
            "pos": jnp.zeros((slots,), jnp.int32),
            "seen": jnp.zeros((slots, mc.vocab_size), bool),
            "rng": jnp.zeros((slots, 2), jnp.uint32),
            "temperature": jnp.ones((slots,), jnp.float32),
            "top_p": jnp.ones((slots,), jnp.float32),
            "top_k": jnp.full((slots,), mc.vocab_size, jnp.int32),
            "repetition_penalty": jnp.ones((slots,), jnp.float32),
            "do_sample": jnp.zeros((slots,), bool),
            "adapter_idx": jnp.zeros((slots,), jnp.int32),
        }

    def _place_replicated(self, tree):
        """Mesh placement for per-slot host-visible state: every leaf lives
        replicated on the mesh (they are small and read host-side every
        tick). No-op without a mesh."""
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec as P

        from llm_fine_tune_distributed_tpu.parallel.sharding import place_tree

        rep = NamedSharding(self.mesh, P())
        return place_tree(tree, jax.tree.map(lambda _: rep, tree))

    def _pin_kv(self, tree):
        """Traced: constrain a cache/pool pytree to the resident KV
        shardings (kv-head dim over ``tensor``), so every program's output
        cache layout equals its input layout — the threaded buffers sit at a
        sharding fixed point from the first compile, which is what makes the
        sharded engines zero-recompile after warmup. Identity without a
        mesh."""
        if self.mesh is None:
            return tree
        from llm_fine_tune_distributed_tpu.parallel.sharding import (
            kv_cache_shardings,
        )

        return jax.lax.with_sharding_constraint(
            tree, kv_cache_shardings(tree, self.mesh)
        )

    def _pin_state(self, state):
        """Traced: constrain the per-slot state dict replicated (its leaves
        are host-read every tick). Identity without a mesh."""
        if self.mesh is None:
            return state
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, rep), state
        )

    def init_slot_state(self, slots: int, buf_len: int):
        """Fresh (cache, state) for a ``slots``-wide persistent decode.
        Under a mesh both land sharded/placed (cache: kv-head dim over
        ``tensor``; state: replicated) so the engines' first dispatch
        already sees the steady-state layout."""
        cache = init_cache(
            self.config, slots, buf_len, dtype=self.compute_dtype,
            mesh=self.mesh,
        )
        return cache, self._place_replicated(self._fresh_slot_state(slots))

    def _instrument(self, key, fn, aot: bool = True):
        """Ledger-wrap a freshly built program: ``key`` is the jit-cache
        key, whose head is the program name and whose tail is the shape
        bucket — exactly the dedup signature the ledger wants. aot=True
        (engine hot paths, array-only call sites) compiles ahead-of-time
        for exact compile seconds + cost analysis; aot=False (call sites
        passing python scalars / donated buffers) times the first call."""
        return instrument(
            key[0], fn, self.compile_ledger, shapes=str(key[1:]), aot=aot
        )

    def slot_step(self, slots: int, buf_len: int):
        """Jitted one-token decode step for ALL slots (cached per shape)."""
        key = ("slot_step", slots, buf_len)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_slot_step(slots, buf_len)
            )
        return self._jit_cache[key]

    def slot_prefill(self, bucket: int, buf_len: int):
        """Jitted prefill-insert (cached per prompt bucket)."""
        key = ("slot_prefill", bucket, buf_len)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_slot_prefill(bucket, buf_len)
            )
        return self._jit_cache[key]

    def _build_slot_step(self, slots: int, buf_len: int):
        """One decode step over the whole slot array: feed every slot's last
        token at its own cache position (vector cache_pos), sample every
        slot's next token with its own traced knobs and its own RNG key.
        Greedy slots follow exactly the static sampler's arithmetic, so a
        greedy slot's token stream is bit-identical to a solo
        ``generate_ids`` run of the same prompt (row-independent ops; pinned
        by tests/test_engine.py)."""
        mc = self.config
        dtype = self.compute_dtype
        mesh, act = self.mesh, self._act_sharding

        @jax.jit
        def step(params, cache, state, live):
            last, pos = state["last"], state["pos"]
            hidden, cache = forward(
                params, last[:, None], mc, cache=cache, cache_pos=pos,
                compute_dtype=dtype, output_hidden=True, activation_sharding=act,
                adapter_idx=state["adapter_idx"],
            )
            logits = unembed(params, hidden[:, -1], mc, compute_dtype=dtype, mesh=mesh)
            split = jax.vmap(jax.random.split)(state["rng"])  # [S, 2, 2]
            tok = sample_token_traced(
                split[:, 1], logits, state["seen"],
                temperature=state["temperature"], top_p=state["top_p"],
                top_k=state["top_k"],
                repetition_penalty=state["repetition_penalty"],
                do_sample=state["do_sample"],
            )
            tok = jnp.where(live, tok, last)
            rows = jnp.arange(slots)
            seen = jnp.where(
                live[:, None], state["seen"].at[rows, tok].set(True), state["seen"]
            )
            new_state = dict(
                state,
                last=tok,
                pos=jnp.where(live, jnp.minimum(pos + 1, buf_len - 1), pos),
                seen=seen,
                rng=jnp.where(live[:, None], split[:, 0], state["rng"]),
            )
            return self._pin_kv(cache), self._pin_state(new_state), tok

        return step

    def _build_slot_prefill(self, bucket: int, buf_len: int):
        """Prefill ONE prompt (padded to ``bucket``) in a private batch-1
        cache, sample its first token, and scatter the K/V row + slot state
        into the shared buffers at ``slot`` — live neighbors are untouched
        (row-scoped dynamic_update_slice writes only). The first token is
        computed exactly as ``_prompt_prefill`` computes it (pad keys sit at
        positions above the last real query, hence masked — logits are
        independent of the bucket size)."""
        mc = self.config
        dtype = self.compute_dtype
        mesh, act = self.mesh, self._act_sharding

        @jax.jit
        def prefill(params, cache, state, prompt_ids, prompt_len, slot, knobs, seed_key):
            small = init_cache(mc, 1, bucket, dtype=dtype)
            hidden, small = forward(
                params, prompt_ids, mc, cache=small, cache_pos=0,
                compute_dtype=dtype, output_hidden=True, activation_sharding=act,
                adapter_idx=knobs["adapter_idx"][None],
            )
            lens = prompt_len[None]  # [1]
            last_h = jnp.take_along_axis(
                hidden, (lens - 1)[:, None, None], axis=1
            )[:, 0]
            logits0 = unembed(params, last_h, mc, compute_dtype=dtype, mesh=mesh)
            valid = jnp.arange(bucket)[None, :] < lens[:, None]
            safe_ids = jnp.where(valid, prompt_ids, prompt_ids[:, :1])
            seen_row = jnp.zeros((1, mc.vocab_size), bool).at[0, safe_ids[0]].set(True)
            key, sub = jax.random.split(seed_key)
            first = sample_token_traced(
                sub[None], logits0, seen_row,
                temperature=knobs["temperature"][None],
                top_p=knobs["top_p"][None],
                top_k=knobs["top_k"][None],
                repetition_penalty=knobs["repetition_penalty"][None],
                do_sample=knobs["do_sample"][None],
            )
            seen_row = seen_row.at[0, first[0]].set(True)
            cache = insert_cache_row(cache, small, slot)
            state = dict(
                state,
                last=state["last"].at[slot].set(first[0]),
                pos=state["pos"].at[slot].set(prompt_len),
                seen=jax.lax.dynamic_update_slice(state["seen"], seen_row, (slot, 0)),
                rng=jax.lax.dynamic_update_slice(state["rng"], key[None], (slot, 0)),
                temperature=state["temperature"].at[slot].set(knobs["temperature"]),
                top_p=state["top_p"].at[slot].set(knobs["top_p"]),
                top_k=state["top_k"].at[slot].set(knobs["top_k"]),
                repetition_penalty=state["repetition_penalty"].at[slot].set(
                    knobs["repetition_penalty"]
                ),
                do_sample=state["do_sample"].at[slot].set(knobs["do_sample"]),
                adapter_idx=state["adapter_idx"].at[slot].set(knobs["adapter_idx"]),
            )
            return self._pin_kv(cache), self._pin_state(state), first[0]

        return prefill

    # ------------------------------------------------- paged continuous decode

    # Block-paged variants of the slot programs (PagedContinuousBatchingEngine,
    # infer/engine.py). The per-slot state dict is IDENTICAL to
    # init_slot_state's; only the KV layout changes: one global block pool
    # (models/transformer.init_paged_cache) addressed through per-slot block
    # tables, so (a) a decode step's attention gathers nb*block_len positions
    # — the engine slices tables to the live occupancy bucket, so cost tracks
    # occupancy, not the buffer ceiling — and (b) prompts prefill in bounded
    # chunks (cache_pos = chunk start) interleaved with decode, writing
    # straight into the slot's blocks instead of a private buffer + row copy.

    def init_paged_state(
        self, slots: int, num_blocks: int, block_len: int, kv_quant: str = "none"
    ):
        """Fresh (pool, state) for a paged ``slots``-wide persistent decode.
        ``kv_quant="int8"`` builds the quantized pool layout (int8 codes +
        per-block absmax scale pools) — the step/prefill programs detect it
        from the pool pytree, so no program variants are needed here."""
        pool = init_paged_cache(
            self.config, num_blocks, block_len, dtype=self.compute_dtype,
            kv_quant=kv_quant, mesh=self.mesh,
        )
        return pool, self._place_replicated(self._fresh_slot_state(slots))

    def paged_step(self, slots: int, nb: int, block_len: int):
        """Jitted one-token paged decode step (cached per table width)."""
        key = ("paged_step", slots, nb, block_len)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_paged_step(slots, nb, block_len)
            )
        return self._jit_cache[key]

    def paged_prefill_chunk(self, chunk: int, nb: int, block_len: int):
        """Jitted ingest-only prefill chunk (all but a prompt's last chunk)."""
        key = ("paged_chunk", chunk, nb, block_len)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_paged_prefill(chunk, nb, block_len, final=False)
            )
        return self._jit_cache[key]

    def paged_prefill_final(self, bucket: int, nb: int, block_len: int):
        """Jitted final prefill chunk: ingest + first-token sample + slot
        state scatter (cached per pad bucket)."""
        key = ("paged_final", bucket, nb, block_len)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_paged_prefill(bucket, nb, block_len, final=True)
            )
        return self._jit_cache[key]

    def paged_block_gather(self, n: int):
        """Jitted gather of ``n`` pool blocks (host-tier spill). Cached per
        power-of-two block-count bucket ``n`` — the engine pads its id list
        with NULL_BLOCK rows it slices off host-side, so any spill size
        reuses a handful of compiled programs (zero post-warmup recompiles,
        the SERVE_COMPILES contract)."""
        key = ("paged_block_gather", n)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_paged_block_gather()
            )
        return self._jit_cache[key]

    def paged_block_scatter(self, n: int):
        """Jitted scatter of ``n`` host blocks back into the pool (host-tier
        restore). Same bucketing contract as ``paged_block_gather``; the
        engine pads with NULL_BLOCK ids and ALL-ZERO rows, so pad writes
        land in block 0 as zeros — which for the int8 pool layout preserves
        the null block's zero-codes/zero-scales invariant, and for bf16 only
        rewrites garbage that is always masked."""
        key = ("paged_block_scatter", n)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_paged_block_scatter()
            )
        return self._jit_cache[key]

    def _build_paged_block_gather(self):
        """Tree-mapped row gather over the pool pytree: every pool leaf is
        block-major (``[num_blocks, ...]`` — int8 code pools and their scale
        siblings alike), so one ``leaf[ids]`` per leaf lifts a whole block
        (codes + scales as a unit) into ``n`` leading rows ready for one
        host transfer."""

        @jax.jit
        def gather(pool, ids):
            return jax.tree.map(lambda leaf: leaf[ids], pool)

        return gather

    def _build_paged_block_scatter(self):
        """Inverse of the gather: writes ``updates`` (one leading row per
        block id, same treedef as the pool) into the pool rows ``ids``.
        Functional like every other pool program — the engine re-points its
        pool reference at the result."""

        @jax.jit
        def scatter(pool, ids, updates):
            return self._pin_kv(
                jax.tree.map(
                    lambda leaf, upd: leaf.at[ids].set(upd.astype(leaf.dtype)),
                    pool,
                    updates,
                )
            )

        return scatter

    def _build_paged_step(self, slots: int, nb: int, block_len: int):
        """One decode step over the slot array against the block pool. Same
        sampling semantics as ``_build_slot_step`` bit for bit — only the KV
        addressing differs: each slot's last token's K/V scatters to pool
        cell (table[pos // L], pos % L) and attention runs over the slot's
        gathered nb*L-position view (gathered index == logical position, so
        the mask rule is the dense one). Dead rows carry all-null tables
        (engine-side) so their frozen-position writes land in null-block
        garbage, never in a block reassigned to a live slot."""
        mc = self.config
        dtype = self.compute_dtype
        mesh, act = self.mesh, self._act_sharding

        @jax.jit
        def step(params, pool, state, live, tables):
            last, pos = state["last"], state["pos"]
            hidden, pool = forward(
                params, last[:, None], mc, cache=pool, cache_pos=pos,
                block_tables=tables, compute_dtype=dtype, output_hidden=True,
                activation_sharding=act, adapter_idx=state["adapter_idx"],
            )
            logits = unembed(params, hidden[:, -1], mc, compute_dtype=dtype, mesh=mesh)
            split = jax.vmap(jax.random.split)(state["rng"])  # [S, 2, 2]
            tok = sample_token_traced(
                split[:, 1], logits, state["seen"],
                temperature=state["temperature"], top_p=state["top_p"],
                top_k=state["top_k"],
                repetition_penalty=state["repetition_penalty"],
                do_sample=state["do_sample"],
            )
            tok = jnp.where(live, tok, last)
            rows = jnp.arange(slots)
            seen = jnp.where(
                live[:, None], state["seen"].at[rows, tok].set(True), state["seen"]
            )
            new_state = dict(
                state,
                last=tok,
                # no ceiling clamp: the engine's per-request budget keeps a
                # live row's positions inside its allocated blocks
                pos=jnp.where(live, pos + 1, pos),
                seen=seen,
                rng=jnp.where(live[:, None], split[:, 0], state["rng"]),
            )
            return self._pin_kv(pool), self._pin_state(new_state), tok

        return step

    def _build_paged_prefill(
        self, bucket: int, nb: int, block_len: int, final: bool
    ):
        """One prefill chunk of one prompt, written THROUGH the slot's block
        table (batch 1, ``cache_pos`` = the chunk's first logical position).
        Chunk queries attend to every logical position <= their own — shared
        prefix blocks and earlier chunks included — so chunking (and prefix
        reuse) does not change any real token's logits vs. a monolithic
        prefill; pad keys of the last chunk sit at positions above every real
        query, hence masked (``_prompt_prefill``'s argument, paged).

        ``final=False``: ingest only (returns the pool). ``final=True``:
        additionally samples the first token from the last PROMPT position's
        logits with the request's traced knobs + seed-keyed RNG, and scatters
        the slot's state — ``seen`` arrives precomputed from the FULL prompt
        (host-side), since this program only sees the prompt's tail."""
        mc = self.config
        dtype = self.compute_dtype
        mesh, act = self.mesh, self._act_sharding

        if not final:

            @jax.jit
            def ingest(params, pool, table, chunk_ids, chunk_start, adapter_idx):
                _, pool = forward(
                    params, chunk_ids, mc, cache=pool, cache_pos=chunk_start,
                    block_tables=table, compute_dtype=dtype, output_hidden=True,
                    activation_sharding=act, adapter_idx=adapter_idx[None],
                )
                return self._pin_kv(pool)

            return ingest

        @jax.jit
        def final_chunk(
            params, pool, state, table, chunk_ids, chunk_start, prompt_len,
            seen_row, slot, knobs, seed_key,
        ):
            hidden, pool = forward(
                params, chunk_ids, mc, cache=pool, cache_pos=chunk_start,
                block_tables=table, compute_dtype=dtype, output_hidden=True,
                activation_sharding=act, adapter_idx=knobs["adapter_idx"][None],
            )
            idx = prompt_len - 1 - chunk_start  # last prompt token, in-chunk
            last_h = jnp.take_along_axis(
                hidden, jnp.reshape(idx, (1, 1, 1)), axis=1
            )[:, 0]
            logits0 = unembed(params, last_h, mc, compute_dtype=dtype, mesh=mesh)
            key, sub = jax.random.split(seed_key)
            first = sample_token_traced(
                sub[None], logits0, seen_row,
                temperature=knobs["temperature"][None],
                top_p=knobs["top_p"][None],
                top_k=knobs["top_k"][None],
                repetition_penalty=knobs["repetition_penalty"][None],
                do_sample=knobs["do_sample"][None],
            )
            seen_row = seen_row.at[0, first[0]].set(True)
            state = dict(
                state,
                last=state["last"].at[slot].set(first[0]),
                pos=state["pos"].at[slot].set(prompt_len),
                seen=jax.lax.dynamic_update_slice(state["seen"], seen_row, (slot, 0)),
                rng=jax.lax.dynamic_update_slice(state["rng"], key[None], (slot, 0)),
                temperature=state["temperature"].at[slot].set(knobs["temperature"]),
                top_p=state["top_p"].at[slot].set(knobs["top_p"]),
                top_k=state["top_k"].at[slot].set(knobs["top_k"]),
                repetition_penalty=state["repetition_penalty"].at[slot].set(
                    knobs["repetition_penalty"]
                ),
                do_sample=state["do_sample"].at[slot].set(knobs["do_sample"]),
                adapter_idx=state["adapter_idx"].at[slot].set(knobs["adapter_idx"]),
            )
            return self._pin_kv(pool), self._pin_state(state), first[0]

        return final_chunk

    # ----------------------------------------- speculative continuous decode

    # Fused verify-tick programs for the continuous engines (infer/engine.py
    # with ``speculative_k > 0``): every tick, each live slot's
    # ``[last, d_1..d_K]`` goes through ONE target forward at that slot's own
    # vector cache_pos, and a K+1-position sequential verify
    # (rejection_sample_step_traced, per-slot traced knobs) accepts a
    # variable per-slot prefix. A slot with ``n_draft == 0`` reduces exactly
    # to the plain step: position 0 is its bonus sample, positions 1..K are
    # never taken — so mixed spec/non-spec traffic shares the fused program
    # and greedy non-spec slots stay bit-identical to solo decode.
    #
    # RNG discipline: every live slot consumes EXACTLY K+2 subkeys per tick
    # (one chain key + one per verify position), independent of its own or
    # any neighbor's draft/acceptance counts — so a sampled request's stream
    # depends only on (request seed, engine K), never on co-residents.
    #
    # EOS/budget are settled HOST-side: the device reports the emitted run
    # ``toks [S, K+1]`` / ``n_emit [S]`` (EOS gates further takes within the
    # tick) and the engine truncates, finishes, and releases. Positions a
    # rejected draft wrote are rolled back for free: dense, they sit above
    # the slot's new position (masked) until the next tick's writes cover
    # them (slot == position invariant); paged, the engine slices tables
    # wide enough for pos+K and budgets K+1 spare positions per slot so
    # verify writes land in the slot's own blocks (never a neighbor's — see
    # PagedContinuousBatchingEngine._plan).

    def spec_slot_step(self, slots: int, buf_len: int, k: int):
        """Jitted fused draft-verify step, dense cache (cached per shape)."""
        key = ("spec_slot_step", slots, buf_len, k)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_spec_slot_step(slots, buf_len, k)
            )
        return self._jit_cache[key]

    def spec_paged_step(self, slots: int, nb: int, block_len: int, k: int):
        """Jitted fused draft-verify step, paged pool (cached per table width)."""
        key = ("spec_paged_step", slots, nb, block_len, k)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_spec_paged_step(slots, nb, block_len, k)
            )
        return self._jit_cache[key]

    def _build_spec_verify(self, slots: int, K: int):
        """The shared verify tail of both fused spec steps: logits for all
        K+1 positions of every slot -> (emitted run, per-slot counts, state
        advance pieces). Factored so dense and paged steps cannot drift."""
        eos = jnp.asarray(self.eos_token_ids, jnp.int32) if self.eos_token_ids else None

        def is_eos(tok):
            return jnp.isin(tok, eos) if eos is not None else jnp.zeros_like(tok, bool)

        def verify_all(state, live, drafts, n_draft, logits_all, splits):
            rows = jnp.arange(slots)
            seen = state["seen"]
            toks = jnp.full((slots, K + 1), -1, jnp.int32)
            last = state["last"]
            n_emit = jnp.zeros((slots,), jnp.int32)
            active = live
            done = jnp.zeros((slots,), bool)

            def verify(i, c):
                seen, toks, last, n_emit, active, done = c
                d = drafts[:, jnp.minimum(i, K - 1)]
                tok, accepted = rejection_sample_step_traced(
                    splits[:, i + 1], logits_all[:, i], seen, d,
                    temperature=state["temperature"], top_p=state["top_p"],
                    top_k=state["top_k"],
                    repetition_penalty=state["repetition_penalty"],
                    do_sample=state["do_sample"], bonus=i >= n_draft,
                )
                take = active & ~done
                seen = jnp.where(
                    take[:, None], seen.at[rows, tok].set(True), seen
                )
                toks = toks.at[:, i].set(jnp.where(take, tok, -1))
                last = jnp.where(take, tok, last)
                n_emit = n_emit + take.astype(jnp.int32)
                done = done | (take & is_eos(tok))
                # position i+1's draft is only consumable if position i
                # accepted ITS draft (a bonus/replacement token ends the run)
                active = active & accepted & (i < n_draft)
                return (seen, toks, last, n_emit, active, done)

            seen, toks, last, n_emit, _, _ = jax.lax.fori_loop(
                0, K + 1, verify, (seen, toks, last, n_emit, active, done)
            )
            return seen, toks, last, n_emit

        return verify_all

    def _build_spec_slot_step(self, slots: int, buf_len: int, K: int):
        """Fused draft-verify decode step over the dense shared cache.

        The forward writes positions pos..pos+K per row (vector cache_pos,
        multi-token row — models/transformer.py's existing per-row scatter);
        position pos is ``last``'s K/V rewrite-in-place (same values), pos+i
        holds draft i-1. Rejected-draft writes need no cleanup: they sit at
        positions > the slot's advanced ``pos`` (always masked) and the next
        tick's writes start at the new pos, covering them before any query
        climbs past. Writes past ``buf_len`` (only possible on a slot's
        final tick before the host finishes it at budget) are dropped by the
        scatter's out-of-bounds rule — never clipped onto live cells.
        """
        mc = self.config
        dtype = self.compute_dtype
        mesh, act = self.mesh, self._act_sharding
        verify_all = self._build_spec_verify(slots, K)

        @jax.jit
        def step(params, cache, state, live, drafts, n_draft):
            last, pos = state["last"], state["pos"]
            inputs = jnp.concatenate([last[:, None], drafts], axis=1)  # [S, K+1]
            hidden, cache = forward(
                params, inputs, mc, cache=cache, cache_pos=pos,
                compute_dtype=dtype, output_hidden=True, activation_sharding=act,
                adapter_idx=state["adapter_idx"],
            )
            logits_all = unembed(params, hidden, mc, compute_dtype=dtype, mesh=mesh)
            splits = jax.vmap(lambda r: jax.random.split(r, K + 2))(state["rng"])
            seen, toks, new_last, n_emit = verify_all(
                state, live, drafts, n_draft, logits_all, splits
            )
            new_state = dict(
                state,
                last=new_last,
                pos=jnp.where(live, jnp.minimum(pos + n_emit, buf_len - 1), pos),
                seen=seen,
                rng=jnp.where(live[:, None], splits[:, 0], state["rng"]),
            )
            return self._pin_kv(cache), self._pin_state(new_state), toks, n_emit

        return step

    def _build_spec_paged_step(self, slots: int, nb: int, block_len: int, K: int):
        """Fused draft-verify decode step against the block pool. Verify
        writes route through the slot's block table exactly like decode
        writes (cell = (table[p // L], p % L)); the engine widens each
        slot's block budget by K+1 positions and slices tables to cover
        pos+K, so every live-slot write lands in the slot's OWN blocks —
        rejected-draft cells are overwritten by the next tick before any
        query position reaches them, and dead rows' writes fall into the
        null block (all-null tables, engine-side)."""
        mc = self.config
        dtype = self.compute_dtype
        mesh, act = self.mesh, self._act_sharding
        verify_all = self._build_spec_verify(slots, K)

        @jax.jit
        def step(params, pool, state, live, tables, drafts, n_draft):
            last, pos = state["last"], state["pos"]
            inputs = jnp.concatenate([last[:, None], drafts], axis=1)  # [S, K+1]
            hidden, pool = forward(
                params, inputs, mc, cache=pool, cache_pos=pos,
                block_tables=tables, compute_dtype=dtype, output_hidden=True,
                activation_sharding=act, adapter_idx=state["adapter_idx"],
            )
            logits_all = unembed(params, hidden, mc, compute_dtype=dtype, mesh=mesh)
            splits = jax.vmap(lambda r: jax.random.split(r, K + 2))(state["rng"])
            seen, toks, new_last, n_emit = verify_all(
                state, live, drafts, n_draft, logits_all, splits
            )
            new_state = dict(
                state,
                last=new_last,
                # no ceiling clamp: the engine's K+1-widened block budget
                # keeps a live row's positions inside its allocation
                pos=jnp.where(live, pos + n_emit, pos),
                seen=seen,
                rng=jnp.where(live[:, None], splits[:, 0], state["rng"]),
            )
            return self._pin_kv(pool), self._pin_state(new_state), toks, n_emit

        return step

    # Draft-model programs for the engines: the draft keeps its OWN dense
    # per-slot cache (small model — a dense [slots, buf_len] buffer is cheap
    # even under the paged target engine, so the draft skips paging). Each
    # tick one jitted program re-ingests the (K+1)-wide accepted-token window
    # (resyncing the draft cache under the same slot == position rollback the
    # solo path uses — at most K+1 tokens advance per tick, so the window
    # always covers what changed) and rolls K greedy proposals with the
    # TARGET's repetition-penalty semantics over a speculative seen copy.

    def init_draft_slot_cache(self, slots: int, buf_len: int):
        """Fresh dense per-slot cache for the attached draft model."""
        if self._draft_config is None:
            raise ValueError("no draft model attached")
        return init_cache(
            self._draft_config, slots, buf_len, dtype=self.compute_dtype,
            mesh=self.mesh,
        )

    def draft_slot_prefill(self, bucket: int):
        """Jitted draft-cache prompt ingest + row insert (cached per bucket)."""
        key = ("draft_slot_prefill", bucket)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_draft_slot_prefill(bucket)
            )
        return self._jit_cache[key]

    def draft_slot_step(self, slots: int, K: int):
        """Jitted per-tick K-token draft proposal (cached per shape)."""
        key = ("draft_slot_step", slots, K)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._instrument(
                key, self._build_draft_slot_step(slots, K)
            )
        return self._jit_cache[key]

    def _build_draft_slot_prefill(self, bucket: int):
        dmc = self._draft_config
        dtype = self.compute_dtype
        act = self._act_sharding

        @jax.jit
        def prefill(dparams, dcache, prompt_ids, slot):
            small = init_cache(dmc, 1, bucket, dtype=dtype)
            _, small = forward(
                dparams, prompt_ids, dmc, cache=small, cache_pos=0,
                compute_dtype=dtype, output_hidden=True,
                activation_sharding=act,
            )
            return self._pin_kv(insert_cache_row(dcache, small, slot))

        return prefill

    def _build_draft_slot_step(self, slots: int, K: int):
        """K greedy proposals per slot from the draft model.

        ``window [S, K+1]`` holds each slot's context tokens at positions
        start..start+K (``start = max(pos - K, 0)``, so ``last`` sits at
        window index pos-start); the re-ingest forward writes them at their
        true positions, then K-1 single-token draft forwards extend at
        pos+1..pos+K-1. Window cells past a short context (pos < K) write
        garbage ABOVE pos — overwritten by the draft extension before any
        draft query passes them, masked meanwhile. Non-live rows get
        window=0/start=0 from the engine; their garbage stays in their own
        dcache row and their proposals are discarded (n_draft = 0)."""
        dmc = self._draft_config
        dtype = self.compute_dtype
        mesh, act = self.mesh, self._act_sharding

        @jax.jit
        def draft(dparams, dcache, state, window, start):
            pos = state["pos"]
            rows = jnp.arange(slots)
            dh, dcache = forward(
                dparams, window, dmc, cache=dcache, cache_pos=start,
                compute_dtype=dtype, output_hidden=True,
                activation_sharding=act,
            )
            idx = jnp.clip(pos - start, 0, K)  # stale dead-row pos: clamp
            cur_h = jnp.take_along_axis(dh, idx[:, None, None], axis=1)[:, 0]
            rp = state["repetition_penalty"][:, None]

            def propose(logits, spec_seen):
                # greedy with the TARGET's penalty over the speculative seen
                # set — a perfect draft then matches the target's greedy
                # verify choice exactly (100% acceptance on self-draft)
                pl = jnp.where(
                    spec_seen,
                    jnp.where(logits > 0, logits / rp, logits * rp),
                    logits,
                )
                d = jnp.argmax(pl, axis=-1).astype(jnp.int32)
                return d, spec_seen.at[rows, d].set(True)

            d0, spec_seen = propose(
                unembed(dparams, cur_h, dmc, compute_dtype=dtype, mesh=mesh),
                state["seen"],
            )
            dbuf = jnp.zeros((slots, K), jnp.int32).at[:, 0].set(d0)

            def dstep(i, c):
                dcache, dbuf, spec_seen = c
                prev = dbuf[rows, i - 1]
                dh, dcache = forward(
                    dparams, prev[:, None], dmc, cache=dcache, cache_pos=pos + i,
                    compute_dtype=dtype, output_hidden=True,
                    activation_sharding=act,
                )
                nxt, spec_seen = propose(
                    unembed(dparams, dh[:, -1], dmc, compute_dtype=dtype, mesh=mesh),
                    spec_seen,
                )
                return (dcache, dbuf.at[:, i].set(nxt), spec_seen)

            if K > 1:
                dcache, dbuf, _ = jax.lax.fori_loop(
                    1, K, dstep, (dcache, dbuf, spec_seen)
                )
            return self._pin_kv(dcache), dbuf

        return draft

    def generate_stream(
        self,
        prompt_ids: Sequence[int],
        gen: Optional[GenerationConfig] = None,
        seed: int = 0,
        chunk: int = 8,
    ):
        """Yield generated token ids in ``chunk``-sized lists as they decode.

        Greedy streams are the exact plain-decode token sequence (same
        sampler, same evolving repetition set); the stream ends at EOS or
        ``max_new_tokens``. The serving layer turns this into SSE
        (``/v1/stream``); a CLI can print chunks as they arrive instead of
        staring at a silent ~20s ``max_new_tokens=3768`` generation."""
        gen = gen or GenerationConfig()
        prompt = [int(t) for t in prompt_ids]
        if not prompt:
            raise ValueError("generate_stream needs a non-empty prompt")
        if chunk < 1:
            raise ValueError(f"stream chunk must be >= 1, got {chunk}")
        bucket = -(-len(prompt) // _PROMPT_BUCKET) * _PROMPT_BUCKET
        key = ("stream", bucket, gen, chunk)
        if key not in self._jit_cache:
            s_prefill, s_decode = self._build_stream(bucket, gen, chunk)
            sig = str(key[1:])
            self._jit_cache[key] = (
                instrument("stream_prefill", s_prefill, self.compile_ledger,
                           shapes=sig, aot=False),
                instrument("stream_decode", s_decode, self.compile_ledger,
                           shapes=sig, aot=False),
            )
        prefill, decode_chunk = self._jit_cache[key]

        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(prompt)] = prompt
        lens = jnp.asarray([len(prompt)], jnp.int32)
        last, cache, seen, rng = prefill(
            self.params, jnp.asarray(padded), lens, jax.random.PRNGKey(seed)
        )
        first = int(np.asarray(last)[0])
        if first in self.eos_token_ids:
            return
        yield [first]
        emitted = 1
        while emitted < gen.max_new_tokens:
            toks, cache, last, seen, rng = decode_chunk(
                self.params, cache, lens, jnp.int32(emitted), last, seen, rng
            )
            row = np.asarray(toks)[0].tolist()
            row = row[: gen.max_new_tokens - emitted]  # trim the slack overrun
            out = []
            hit_eos = False
            for t in row:
                if t in self.eos_token_ids:
                    hit_eos = True
                    break
                out.append(int(t))
            emitted += len(row)
            if out:
                yield out
            if hit_eos:
                return

    def generate_batch(
        self,
        prompts: Sequence[Sequence[int]],
        gen: Optional[GenerationConfig] = None,
        seed: int = 0,
        live_rows: Optional[int] = None,
    ) -> List[List[int]]:
        """Generate continuations for a ragged batch of prompts in ONE device
        program — the weight stream (the batch-1 decode bottleneck) is read
        once per step for the whole batch.

        ``live_rows``: rows past this index are filler (the batching engine
        pads to a power-of-two batch by duplicating a prompt) and are excluded
        from the speculative-acceptance telemetry; generation output is
        unaffected."""
        gen = gen or GenerationConfig()
        prompts = [list(p) for p in prompts]
        if not prompts or any(not p for p in prompts):
            raise ValueError("generate_batch needs >= 1 non-empty prompt")
        longest = max(len(p) for p in prompts)
        bucket = -(-longest // _PROMPT_BUCKET) * _PROMPT_BUCKET
        # speculation, any batch size: rows draft (from their own contexts,
        # or via the attached draft model) and desynchronize freely; greedy
        # verifies by exact match, sampled by rejection sampling
        speculate = gen.speculative_lookup > 0
        with_draft = speculate and self._draft_params is not None
        if speculate:
            key = ("specd" if with_draft else "spec", len(prompts), bucket, gen)
            if key not in self._jit_cache:
                self._jit_cache[key] = self._instrument(
                    key,
                    self._build_spec(
                        len(prompts), bucket, gen, with_draft=with_draft
                    ),
                    aot=False,
                )
        else:
            key = ("batch", len(prompts), bucket, gen)
            if key not in self._jit_cache:
                self._jit_cache[key] = self._instrument(
                    key, self._build_batch(len(prompts), bucket, gen), aot=False
                )
        run = self._jit_cache[key]

        padded = np.zeros((len(prompts), bucket), np.int32)
        lens = np.zeros((len(prompts),), np.int32)
        for i, p in enumerate(prompts):
            padded[i, : len(p)] = p
            lens[i] = len(p)
        key = jax.random.PRNGKey(seed)
        if self._multihost:
            # a process-spanning mesh needs GLOBAL input arrays; every
            # process must call with the same prompts/seed (the coordinator
            # in infer/multihost.py guarantees this for the serving path)
            from jax.sharding import NamedSharding, PartitionSpec as P

            from llm_fine_tune_distributed_tpu.parallel.sharding import (
                global_array_from_host,
            )

            rep = NamedSharding(self.mesh, P())
            inputs = (
                global_array_from_host(padded, rep),
                global_array_from_host(lens, rep),
                global_array_from_host(np.asarray(key), rep),
            )
        else:
            inputs = (jnp.asarray(padded), jnp.asarray(lens), key)
        if with_draft:
            res = run(self.params, self._draft_params, *inputs)
        else:
            res = run(self.params, *inputs)
        out, n = res[0], res[1]
        if speculate:
            # acceptance telemetry: prefill emitted 1 per row and each of a
            # row's row_steps spec steps drafted K and emitted 1 + accepted.
            # Aggregate over live rows only — padded filler rows (ADVICE r3)
            # would otherwise skew the per-request acceptance rate.
            nl = len(prompts) if live_rows is None else min(live_rows, len(prompts))
            n_vec = np.asarray(n)[:nl]
            row_steps = np.asarray(res[3])[:nl]
            self.last_spec_steps = int(res[2])
            # per-row attribution: row i drafted K per spec step it was still
            # generating in, and each emitted token beyond prefill's first
            # and the per-step mandatory one is an accepted draft
            self.last_row_draft_proposed = row_steps * gen.speculative_lookup
            self.last_row_draft_accepted = np.maximum(n_vec - 1 - row_steps, 0)
            drafted = int(self.last_row_draft_proposed.sum())
            accepted = int(self.last_row_draft_accepted.sum())
            self.last_acceptance_rate = max(accepted, 0) / max(drafted, 1)
        else:
            self.last_spec_steps = None
            self.last_acceptance_rate = None
            self.last_row_draft_proposed = None
            self.last_row_draft_accepted = None
        out = np.asarray(out)
        results: List[List[int]] = []
        for r, row in enumerate(out):
            toks = row.tolist()
            if speculate:
                # slots past the accepted count hold rejected-draft leftovers
                toks = toks[: int(np.asarray(n)[r])]
            for i, tok in enumerate(toks):
                if tok in self.eos_token_ids:
                    toks = toks[:i]
                    break
            results.append(toks)
        return results

    # -------------------------------------------------------------- generate

    def generate_ids(
        self,
        prompt_ids: Sequence[int],
        gen: Optional[GenerationConfig] = None,
        seed: int = 0,
    ) -> List[int]:
        """Generate continuation token ids for one prompt (= batch of 1)."""
        return self.generate_batch([prompt_ids], gen, seed)[0]

    def encode_chat(self, messages: List[dict], **template_kwargs) -> List[int]:
        """ChatML conversation -> prompt token ids (generation prompt added).

        Shared by ``chat`` and the serving path (infer/server.py submits the
        ids through the batching engine) so prompt construction cannot
        diverge between the CLI and the server."""
        return self.tokenizer.apply_chat_template(
            messages, tokenize=True, add_generation_prompt=True, **template_kwargs
        )

    def decode_reply(self, ids: Sequence[int]) -> str:
        """Generated ids -> assistant reply text (shared with the server)."""
        return self.tokenizer.decode(list(ids), skip_special_tokens=True).strip()

    def chat(
        self,
        messages: List[dict],
        gen: Optional[GenerationConfig] = None,
        seed: int = 0,
        **template_kwargs,
    ) -> str:
        """ChatML conversation -> assistant reply text.

        The reference recovers the assistant turn by scanning the decoded full
        text for ``<|im_start|>assistant`` markers (reference
        ``ask_tuned_model.py:69-92``) because HF returns prompt+completion;
        here only the generated ids are decoded, which is the same extraction
        without the string fragility.
        """
        ids = self.generate_ids(self.encode_chat(messages, **template_kwargs), gen, seed)
        return self.decode_reply(ids)


# ---------------------------------------------------------------------------
# model-directory loading (the inference-side artifact contract)
# ---------------------------------------------------------------------------


def load_model_dir(path: str, dtype=None) -> Tuple[dict, ModelConfig]:
    """Load a model directory (``best_model/`` emitted by the trainer, or any
    local HF Llama-family checkpoint) into (params, ModelConfig).

    Mirrors the reference inference entry (``ask_tuned_model.py:15-35``):
    ``config.json`` describes the architecture; weights come from
    ``*.safetensors``. ``dtype=None`` keeps the checkpoint's stored dtype
    (bf16 for trainer-emitted ``best_model/`` — upcasting a 3B model to f32
    would not fit a 16GB chip beside its KV cache).
    """
    from llm_fine_tune_distributed_tpu.models.configs import load_model_config
    from llm_fine_tune_distributed_tpu.models.hf_io import load_hf_checkpoint

    model_config = load_model_config(path)
    params = load_hf_checkpoint(path, model_config, dtype=dtype)
    return params, model_config


def load_tokenizer_dir(path: str):
    """Tokenizer saved beside the weights.

    Resolution order: the hermetic byte tokenizer's marker file (written by
    its ``save_pretrained``), then HF tokenizer files, else raise — a silent
    byte-tokenizer fallback against a 128k-vocab model would emit garbage.
    """
    from llm_fine_tune_distributed_tpu.data.tokenizer import (
        ByteChatMLTokenizer,
        load_tokenizer,
    )

    if os.path.exists(os.path.join(path, ByteChatMLTokenizer.MARKER_FILE)):
        return load_tokenizer("byte-chatml")
    has_hf_tok = any(
        os.path.exists(os.path.join(path, f))
        for f in ("tokenizer.json", "tokenizer_config.json", "tokenizer.model")
    )
    if not has_hf_tok:
        raise FileNotFoundError(
            f"no tokenizer files under {path} (expected tokenizer.json / "
            f"tokenizer_config.json / tokenizer.model, or the byte-chatml marker)"
        )
    return load_tokenizer(path)
