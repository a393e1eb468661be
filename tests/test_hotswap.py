"""Live deployment: zero-downtime checkpoint hot-swap (ISSUE 10).

Pins the train→serve loop end to end: the trainer-side publish protocol
(train/publish.py — atomic weights-then-manifest commit, keep-last-K
retention), the serving-side watcher/manager (infer/deploy.py — frozen-
fingerprint verification, rolling swaps, instant rollback), and the
engine tick-boundary swap itself (infer/engine.py):

- an identity swap is greedy bit-identical on both slot engines, with the
  warm jit caches intact (zero recompiles after warmup);
- a request in flight across a swap completes on the OLD generation;
- the paged prefix cache flushes on a real weight change (and only then)
  and rebuilds under post-swap traffic;
- rollback restores the prior outputs bit-for-bit and the poller does not
  immediately redeploy the generation that was rolled back;
- a worker crash with a swap staged recovers into a consistent single
  application of that swap;
- 16 concurrent clients across a rolling fleet swap lose zero requests.
"""

import os
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
from llm_fine_tune_distributed_tpu.infer.batching import GenerationConfig
from llm_fine_tune_distributed_tpu.infer.deploy import (
    CheckpointWatcher,
    HotSwapManager,
)
from llm_fine_tune_distributed_tpu.infer.engine import (
    ContinuousBatchingEngine,
    PagedContinuousBatchingEngine,
)
from llm_fine_tune_distributed_tpu.infer.fleet import EngineFleet
from llm_fine_tune_distributed_tpu.infer.generate import Generator
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import forward, init_params
from llm_fine_tune_distributed_tpu.train.checkpoints import frozen_fingerprint
from llm_fine_tune_distributed_tpu.train.publish import (
    CheckpointPublisher,
    MANIFEST_NAME,
    atomic_write_bytes,
    list_published,
    load_manifest,
    load_weights,
    parse_step,
    step_dir_name,
    weights_digest,
)
from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict, unflatten_dict

GREEDY = GenerationConfig(max_new_tokens=6, do_sample=False)
LONG = GenerationConfig(max_new_tokens=32, do_sample=False)


@pytest.fixture(scope="module")
def generator():
    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    return Generator(
        params, mc, ByteChatMLTokenizer(), compute_dtype=jnp.float32,
        eos_token_ids=[],
    )


def _make(generator, kind, **kw):
    kw.setdefault("restart_backoff_s", 0.01)
    kw.setdefault("restart_backoff_max_s", 0.02)
    if kind == "paged":
        return PagedContinuousBatchingEngine(
            generator, slots=4, buf_len=96, prompt_bucket=16,
            block_len=16, prefill_chunk=32, **kw,
        )
    return ContinuousBatchingEngine(
        generator, slots=4, buf_len=96, prompt_bucket=16, **kw
    )


def _prompt(text="hello world"):
    return ByteChatMLTokenizer().encode(text)


def _split(generator, n_trainable=2):
    """(trainable, frozen_fp) pretending the first couple of kernels are
    the fine-tuned set — the same flat {path: leaf} shape the trainer's
    TrainState carries."""
    flat = flatten_dict(generator.params)
    keys = sorted(k for k in flat if k.endswith("kernel"))[:n_trainable]
    trainable = {k: np.asarray(flat[k]) for k in keys}
    frozen = {k: v for k, v in flat.items() if k not in trainable}
    return trainable, frozen_fingerprint(frozen)


# ------------------------------------------------------- publish protocol


def test_atomic_write_replaces_never_tears(tmp_path):
    p = str(tmp_path / "blob.bin")
    atomic_write_bytes(p, b"first")
    atomic_write_bytes(p, b"second")
    assert open(p, "rb").read() == b"second"
    # no temp litter after successful replaces
    assert os.listdir(tmp_path) == ["blob.bin"]


def test_manifest_is_the_commit_point(tmp_path):
    pub = CheckpointPublisher(str(tmp_path), keep_last=3)
    trainable = {"a/kernel": np.ones((2, 2), np.float32)}
    path = pub.publish(7, trainable, frozen_fp={"b": np.zeros(4, np.float32)})
    assert parse_step(os.path.basename(path)) == 7
    assert list_published(str(tmp_path)) == [(7, path)]
    manifest = load_manifest(path)
    assert manifest["step"] == 7
    assert manifest["weight_fingerprint"] == weights_digest(trainable)
    loaded = load_weights(path, manifest)
    assert set(loaded) == {"a/kernel"}
    np.testing.assert_array_equal(loaded["a/kernel"], trainable["a/kernel"])
    # a dir whose manifest is gone is invisible, weights notwithstanding
    os.unlink(os.path.join(path, MANIFEST_NAME))
    assert list_published(str(tmp_path)) == []


def test_torn_manifest_reads_as_no_publish(tmp_path):
    pub = CheckpointPublisher(str(tmp_path), keep_last=3)
    pub.publish(1, {"w": np.ones(3, np.float32)}, frozen_fp={})
    path = pub.publish(2, {"w": np.full(3, 2.0, np.float32)}, frozen_fp={})
    # tear step 2's manifest mid-write: the watcher must fall back to 1
    with open(os.path.join(path, MANIFEST_NAME), "w") as f:
        f.write('{"schema": 1, "step": 2, "weights_fi')
    watcher = CheckpointWatcher(str(tmp_path), verify_frozen=False)
    dep = watcher.check()
    assert dep is not None and dep["step"] == 1


def test_unloadable_weights_skipped(tmp_path):
    pub = CheckpointPublisher(str(tmp_path), keep_last=3)
    pub.publish(1, {"w": np.ones(3, np.float32)}, frozen_fp={})
    path = pub.publish(2, {"w": np.full(3, 2.0, np.float32)}, frozen_fp={})
    os.unlink(os.path.join(path, "trainable.npz"))
    watcher = CheckpointWatcher(str(tmp_path), verify_frozen=False)
    dep = watcher.check()
    assert dep is not None and dep["step"] == 1


def test_retention_keeps_last_k(tmp_path):
    pub = CheckpointPublisher(str(tmp_path), keep_last=3)
    for step in range(1, 6):
        pub.publish(step, {"w": np.full(2, float(step), np.float32)},
                    frozen_fp={})
    steps = [s for s, _ in list_published(str(tmp_path))]
    assert steps == [3, 4, 5]
    # the evicted dirs are gone entirely, not just de-listed
    assert not os.path.exists(str(tmp_path / step_dir_name(1)))
    # the newest publish is still fully loadable after retention
    watcher = CheckpointWatcher(str(tmp_path), verify_frozen=False)
    assert watcher.check()["step"] == 5


def test_identical_payload_same_fingerprint():
    w = {"a": np.arange(6, dtype=np.float32).reshape(2, 3)}
    assert weights_digest(w) == weights_digest({k: v.copy() for k, v in w.items()})
    changed = {"a": w["a"] + 1e-3}
    assert weights_digest(w) != weights_digest(changed)


# --------------------------------------------------- engine tick-boundary


@pytest.mark.parametrize("kind", ["continuous", "paged"])
def test_identity_swap_bit_identical_zero_recompiles(generator, kind, tmp_path):
    engine = _make(generator, kind)
    prompt = _prompt()
    before = engine.submit(prompt, GREEDY)
    # the ledger is shared on the Generator (all engines, all tests), so
    # the zero-recompile claim is a DELTA across the swap: everything this
    # traffic needs is compiled now, and the swap must add nothing
    compiles0 = engine.stats_snapshot()["compile"]["total_compiles"]

    trainable, frozen_fp = _split(generator)
    pub = CheckpointPublisher(str(tmp_path))
    pub.publish(1, trainable, frozen_fp=frozen_fp)
    watcher = CheckpointWatcher(str(tmp_path), base_params=generator.params)
    mgr = HotSwapManager(engine, watcher)
    res = mgr.poll_once()
    assert res is not None and res["step"] == 1
    assert engine.weight_generation == 1
    assert mgr.poll_once() is None  # nothing newer: idempotent

    after = engine.submit(prompt, GREEDY)
    assert after == before  # same values in, same greedy tokens out
    # the swap re-pointed values only — shapes unchanged, caches warm
    comp = engine.stats_snapshot()["compile"]
    assert comp["total_compiles"] == compiles0, comp
    snap = engine.stats_snapshot()
    assert snap["weight_swaps"] == 1
    assert snap["weight_generation"] == 1
    # the apply landed on the flight-recorder timeline
    kinds = [e["kind"] for e in engine.recorder.events()]
    assert "weight_swap_begin" in kinds and "weight_swap" in kinds


@pytest.mark.parametrize("kind", ["continuous", "paged"])
def test_inflight_request_finishes_on_old_generation(generator, kind):
    engine = _make(generator, kind)
    trainable, _ = _split(generator)
    prompt = _prompt("stream across the swap boundary")

    req_box = {}
    started = threading.Event()

    def run():
        it = engine.stream(prompt, LONG, timeout=60)
        toks = []
        for t in it:
            toks.append(t)
            started.set()
        req_box["tokens"] = toks

    th = threading.Thread(target=run)
    th.start()
    assert started.wait(30)
    res = engine.request_weight_swap(
        {k: v + 0.25 for k, v in trainable.items()},
        fingerprint="changed", step=1, timeout=60,
    )
    th.join(60)
    assert not th.is_alive()
    # the stream got every token it asked for — nothing dropped mid-swap
    assert len(req_box["tokens"]) == LONG.max_new_tokens
    assert res["weight_generation"] == 1
    # a request admitted AFTER the swap settles stamped with the new one
    done = engine.submit_full(prompt, GREEDY)
    assert done.weight_generation == 1


def test_prefix_cache_flushes_on_real_change_then_rebuilds(generator):
    engine = _make(generator, "paged")
    trainable, _ = _split(generator)
    # long shared prompt: > block_len so full blocks land in the cache
    # (but within the 96-position buffer alongside GREEDY's new tokens)
    prompt = _prompt("the quick brown fox jumps over the lazy dog")

    def reused_delta(fn):
        a = engine.stats_snapshot()["prefix_tokens_reused"]
        fn()
        return engine.stats_snapshot()["prefix_tokens_reused"] - a

    engine.submit(prompt, GREEDY)  # seeds the cache
    assert reused_delta(lambda: engine.submit(prompt, GREEDY)) > 0

    # the FIRST swap always flushes: boot weights carry no publish digest,
    # so the resident fingerprint is unknown and stale KV cannot be ruled
    # out (engine.request_weight_swap docstring)
    engine.request_weight_swap(
        {k: np.asarray(v) for k, v in trainable.items()},
        fingerprint="fp-same", step=1, timeout=60,
    )
    assert reused_delta(lambda: engine.submit(prompt, GREEDY)) == 0
    assert reused_delta(lambda: engine.submit(prompt, GREEDY)) > 0

    # identity republish (same fingerprint): the cache SURVIVES the swap
    engine.request_weight_swap(
        {k: np.asarray(v) for k, v in trainable.items()},
        fingerprint="fp-same", step=2, timeout=60,
    )
    assert reused_delta(lambda: engine.submit(prompt, GREEDY)) > 0

    # real change: stale KV must not serve — hit rate drops to zero...
    engine.request_weight_swap(
        {k: v + 0.25 for k, v in trainable.items()},
        fingerprint="fp-new", step=3, timeout=60,
    )
    assert reused_delta(lambda: engine.submit(prompt, GREEDY)) == 0
    # ...and the very next identical prompt rebuilds against new weights
    assert reused_delta(lambda: engine.submit(prompt, GREEDY)) > 0
    flushes = [
        e for e in engine.recorder.events()
        if e["kind"] == "prefix_cache_invalidated"
    ]
    assert len(flushes) == 2 and all(f["entries"] > 0 for f in flushes)


def test_rollback_restores_prior_outputs(generator, tmp_path):
    fleet = EngineFleet(
        [_make(generator, "paged") for _ in range(2)], routing="prefix"
    )
    prompt = _prompt()
    base = fleet.submit(prompt, GREEDY)

    # the fine-tuned set here is the tied table (lookup and output head): a
    # uniform shift of a projection is normed away and leaves the tiny
    # model's greedy output where it was
    flat = flatten_dict(generator.params)
    head = "model/embed_tokens/weight"
    trainable = {head: np.asarray(flat[head])}
    frozen_fp = frozen_fingerprint({k: v for k, v in flat.items() if k != head})
    pub = CheckpointPublisher(str(tmp_path))
    pub.publish(1, trainable, frozen_fp=frozen_fp)
    watcher = CheckpointWatcher(str(tmp_path), base_params=generator.params)
    mgr = HotSwapManager(fleet, watcher)
    assert mgr.poll_once()["step"] == 1
    assert fleet.submit(prompt, GREEDY) == base  # same values

    # step 2: a seeded random change of the table's rows, shown ON THE LOGITS
    # to move the first greedy token (by a margin no summation order closes)
    # before the fleet is asked. With a tied table the tiny model echoes its
    # last token (a row's largest product is with itself), and noise alone
    # keeps it echoing: one randomly chosen other row is made that row's
    # multiple, so that it wins
    rng = np.random.RandomState(2)
    table = trainable[head] + rng.normal(0.0, 0.02, trainable[head].shape).astype(np.float32)
    winner = int(rng.choice([t for t in range(256) if t != base[0]]))
    table[winner] = 4.0 * table[base[0]]
    moved = {head: table}
    logits, _ = forward(
        unflatten_dict({**flat, **moved}), jnp.asarray([prompt], jnp.int32),
        generator.config, compute_dtype=jnp.float32,
    )
    last = np.asarray(logits[0, -1])
    assert int(last.argmax()) != base[0] and last.max() - last[base[0]] > 0.1
    pub.publish(2, moved, frozen_fp=frozen_fp)
    res = mgr.poll_once()
    assert res["step"] == 2 and res["cache_invalidated"]
    changed = fleet.submit(prompt, GREEDY)
    assert changed != base

    rb = mgr.rollback()
    assert rb["kind"] == "rollback" and rb["step"] == 1
    assert fleet.submit(prompt, GREEDY) == base  # bit-identical restore
    # every replica advanced IN LOCKSTEP (a rollback is a forward swap)
    assert [e.weight_generation for e in fleet.replicas] == [3, 3]
    snap = fleet.stats_snapshot()
    assert snap["weight_rollbacks"] == len(fleet.replicas)
    assert snap["weight_generation"] == 3
    # the poller must NOT redeploy the generation the rollback fled
    assert mgr.poll_once() is None
    # a manager that never swapped has nothing buffered to restore
    with pytest.raises(RuntimeError):
        HotSwapManager(_make(generator, "continuous"), watcher).rollback()


def test_crash_during_swap_recovers_consistent(generator):
    engine = _make(generator, "continuous")
    trainable, _ = _split(generator)
    prompt = _prompt("crash mid drain")

    started = threading.Event()
    errors = []

    def run():
        try:
            it = engine.stream(prompt, LONG, timeout=60)
            for _ in it:
                started.set()
        except Exception as e:  # the injected crash fails this in-flight
            started.set()
            errors.append(e)

    th = threading.Thread(target=run)
    th.start()
    assert started.wait(30)
    # the NEXT decode tick — which is the swap's drain tick — blows up
    engine.faults.fail_decode_next(1)
    res = engine.request_weight_swap(
        {k: v + 0.25 for k, v in trainable.items()},
        fingerprint="post-crash", step=1, timeout=60,
    )
    th.join(60)
    # the staged swap survived the in-process restart and applied EXACTLY
    # once, on the rebuilt worker, at a (trivially) drained boundary
    assert res["weight_generation"] == 1
    assert engine.weight_generation == 1
    assert engine.healthy
    assert engine.stats_snapshot()["weight_swaps"] == 1
    # and the engine serves the post-swap weights
    assert engine.submit(prompt, GREEDY)


def test_swap_rejected_on_terminal_engine(generator):
    engine = _make(generator, "continuous", circuit_threshold=1)
    trainable, _ = _split(generator)
    engine.faults.fail_decode_next(10)
    with pytest.raises(Exception):
        engine.submit(_prompt(), GREEDY)
    deadline = 50
    while engine.healthy and deadline:
        import time
        time.sleep(0.05)
        deadline -= 1
    assert not engine.healthy
    with pytest.raises(Exception):
        engine.request_weight_swap(
            {k: np.asarray(v) for k, v in trainable.items()}, timeout=5
        )


def test_swap_under_concurrent_load_drops_nothing(generator, tmp_path):
    """16 clients hammer a 2-replica fleet while a rolling identity-valued
    swap lands: zero failed requests, both replicas on the new generation,
    zero post-warmup recompiles."""
    fleet = EngineFleet(
        [_make(generator, "paged") for _ in range(2)], routing="prefix"
    )
    prompts = [_prompt(f"client {i} says hi") for i in range(16)]
    for p in prompts:  # compile every prompt bucket the load will use
        fleet.submit(p, GREEDY)
    compiles0 = fleet.replicas[0].stats_snapshot()["compile"]["total_compiles"]

    trainable, frozen_fp = _split(generator)
    pub = CheckpointPublisher(str(tmp_path))
    pub.publish(1, trainable, frozen_fp=frozen_fp)
    mgr = HotSwapManager(
        fleet, CheckpointWatcher(str(tmp_path), base_params=generator.params)
    )

    errors = []
    done = []

    def client(i):
        try:
            for _ in range(3):
                out = fleet.submit(prompts[i], GREEDY, timeout=120)
                assert len(out) == GREEDY.max_new_tokens
            done.append(i)
        except Exception as e:  # noqa: BLE001 — the assertion below reports
            errors.append((i, e))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    swap_res = mgr.poll_once()  # rolling swap rides under the load
    for t in threads:
        t.join(180)
    assert not errors, errors
    assert len(done) == 16
    assert swap_res is not None and swap_res["step"] == 1
    assert [e.weight_generation for e in fleet.replicas] == [1, 1]
    snap = fleet.stats_snapshot()
    assert snap["requests_failed"] == 0
    # the rolling swap added zero compiles (shared ledger: one read covers
    # both replicas — the jit caches live on the Generator)
    comp = fleet.replicas[0].stats_snapshot()["compile"]
    assert comp["total_compiles"] == compiles0, comp


# ----------------------------------------- quantized-resident swap (ISSUE 12)


@pytest.fixture(scope="module")
def int8_generator():
    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    from llm_fine_tune_distributed_tpu.ops.int8 import maybe_quantize

    return Generator(
        maybe_quantize(params, "int8"), mc, ByteChatMLTokenizer(),
        compute_dtype=jnp.float32, eos_token_ids=[],
    )


def _quantized_kernel_paths(generator):
    """Flat paths the trainer would publish (plain .../kernel) whose
    resident form is quantized (kernel_int8 / kernel_nf4 siblings)."""
    mc = get_preset("tiny")
    base = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    flat = flatten_dict(base)
    return flat, sorted(
        k for k in flat
        if "/layers/0/" in k and k.endswith("/kernel") and "gate" not in k
    )


def test_swap_requantizes_into_resident_int8(int8_generator):
    """A trainer publishes plain bf16 kernels; the int8-serving engine
    re-quantizes them into the resident format at the drain boundary —
    shapes preserved, so the swap keeps the zero-recompile guarantee, and
    the resident codes are exactly quantize_int8 of the published array."""
    from llm_fine_tune_distributed_tpu.ops.int8 import quantize_int8

    engine = _make(int8_generator, "paged", kv_quant="int8")
    prompt = _prompt()
    assert engine.submit(prompt, GREEDY)
    engine.mark_compile_warm()

    flat, qkeys = _quantized_kernel_paths(int8_generator)
    published = {k: np.asarray(flat[k]) * 1.5 for k in qkeys[:2]}
    res = engine.request_weight_swap(
        published, fingerprint="fp-requant", step=1, timeout=60
    )
    assert res["weight_generation"] == 1
    assert engine.compile_ledger.recompiles_after_warmup == 0

    resident = flatten_dict(engine._params)
    for path, arr in published.items():
        want = quantize_int8(jnp.asarray(arr))
        np.testing.assert_array_equal(
            np.asarray(resident[f"{path}_int8"]), np.asarray(want["int8"])
        )
        np.testing.assert_allclose(
            np.asarray(resident[f"{path}_int8_scale"]),
            np.asarray(want["int8_scale"]), rtol=1e-6,
        )
    assert engine.submit(prompt, GREEDY)  # still serving on the new codes


def test_swap_rejects_unreconcilable_published_leaf(int8_generator):
    """A published leaf that cannot be re-quantized into the resident
    layout fails the swap with a message naming --quantize-weights; the
    engine keeps the old generation and stays healthy."""
    engine = _make(int8_generator, "paged", kv_quant="int8")
    prompt = _prompt()
    assert engine.submit(prompt, GREEDY)
    _, qkeys = _quantized_kernel_paths(int8_generator)
    with pytest.raises(RuntimeError, match="--quantize-weights int8"):
        engine.request_weight_swap(
            {qkeys[0]: np.zeros((8, 8), np.float32)},
            fingerprint="fp-bad", step=1, timeout=60,
        )
    assert engine.weight_generation == 0
    assert engine.healthy
    assert engine.submit(prompt, GREEDY)


def test_swap_requantizes_into_resident_nf4():
    """Same translation for an NF4-resident server: the published bf16
    kernel lands as packed NF4 codes at the resident block size."""
    from llm_fine_tune_distributed_tpu.ops.int8 import maybe_quantize
    from llm_fine_tune_distributed_tpu.ops.nf4 import quantize_nf4

    mc = get_preset("tiny")
    base = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    gen = Generator(
        maybe_quantize(base, "nf4"), mc, ByteChatMLTokenizer(),
        compute_dtype=jnp.float32, eos_token_ids=[],
    )
    engine = _make(gen, "continuous")
    prompt = _prompt()
    assert engine.submit(prompt, GREEDY)

    flat = flatten_dict(init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32))
    path = sorted(
        k for k in flat
        if "/layers/0/" in k and k.endswith("/kernel") and "gate" not in k
    )[0]
    arr = np.asarray(flat[path]) * 1.5
    res = engine.request_weight_swap(
        {path: arr}, fingerprint="fp-nf4", step=1, timeout=60
    )
    assert res["weight_generation"] == 1
    resident = flatten_dict(engine._params)
    want = quantize_nf4(jnp.asarray(arr))
    np.testing.assert_array_equal(
        np.asarray(resident[f"{path}_nf4"]), np.asarray(want["nf4"])
    )
    assert engine.submit(prompt, GREEDY)
