"""Native C++ runtime: prefetching batch pipeline + heartbeat failure
detector (native/loader.cc, native/heartbeat.cc). The reference gets these
capabilities from torch DataLoader workers and Kubernetes restart policy
(SURVEY.md §2.3, §5.3); here they are first-party and therefore tested."""

import time

import numpy as np
import pytest

from llm_fine_tune_distributed_tpu.runtime import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native build unavailable: {native.build_error()}"
)


def _arrays(n=64, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "input_ids": rng.randint(0, 1000, (n, seq)).astype(np.int32),
        "loss_mask": np.ones((n, seq), np.int32),
        "attention_mask": np.ones((n, seq), np.int32),
    }


def _make(arrays, **kw):
    from llm_fine_tune_distributed_tpu.data.native_loader import NativeBatchLoader

    defaults = dict(per_device_batch_size=2, grad_accum_steps=2, data_parallel_size=2)
    defaults.update(kw)
    return NativeBatchLoader(arrays, **defaults)


def test_shapes_and_steps():
    arrays = _arrays()
    loader = _make(arrays)
    assert loader.steps_per_epoch == 64 // 8
    batches = list(loader.epoch(0))
    assert len(batches) == 8
    for b in batches:
        assert b["input_ids"].shape == (2, 4, 16)  # [accum, bs*dp/hosts, seq]
    loader.close()


def test_epoch_covers_every_sample_once():
    arrays = _arrays()
    loader = _make(arrays)
    seen = []
    for b in loader.epoch(3):
        seen.extend(b["input_ids"].reshape(-1, 16).tolist())
    rows = {tuple(r) for r in seen}
    all_rows = {tuple(r) for r in arrays["input_ids"].tolist()}
    assert rows == all_rows
    loader.close()


def test_deterministic_across_instances():
    arrays = _arrays()
    a, b = _make(arrays, seed=7), _make(arrays, seed=7)
    assert np.array_equal(a.epoch_order(5), b.epoch_order(5))
    ba = [x["input_ids"] for x in a.epoch(2)]
    bb = [x["input_ids"] for x in b.epoch(2)]
    for x, y in zip(ba, bb):
        assert np.array_equal(x, y)
    assert not np.array_equal(a.epoch_order(0), a.epoch_order(1))  # reshuffles
    a.close(); b.close()


def test_host_shards_are_disjoint_and_complete():
    """Two 'hosts' with the same seed see disjoint halves of each global batch
    — the DistributedSampler property (reference
    docs/single-vs-distributed-comparison.md:395-407)."""
    arrays = _arrays()
    h0 = _make(arrays, process_index=0, process_count=2)
    h1 = _make(arrays, process_index=1, process_count=2)
    for b0, b1 in zip(h0.epoch(0), h1.epoch(0)):
        r0 = {tuple(r) for r in b0["input_ids"].reshape(-1, 16).tolist()}
        r1 = {tuple(r) for r in b1["input_ids"].reshape(-1, 16).tolist()}
        assert not (r0 & r1)
        assert len(r0) == len(r1) == 4
    h0.close(); h1.close()


def test_matches_python_loader_unshuffled():
    """With shuffle off the two engines must emit identical batches."""
    from llm_fine_tune_distributed_tpu.data.loader import SFTBatchLoader

    arrays = _arrays()
    kw = dict(
        per_device_batch_size=2, grad_accum_steps=2, data_parallel_size=2,
        shuffle=False,
    )
    nat = _make(arrays, shuffle=False)
    py = SFTBatchLoader(arrays, **kw)
    for bn, bp in zip(nat.epoch(0), py.epoch(0)):
        for k in ("input_ids", "loss_mask", "attention_mask"):
            assert np.array_equal(bn[k], np.asarray(bp[k], np.int32)), k
    nat.close()


def test_packed_keys_ride_native_pipeline():
    """Packed batches (segment_ids/positions + float32 masks) gather through
    the C++ pipeline with exact parity to the Python loader — dtypes
    included (no more Python-loader fallback for packing)."""
    from llm_fine_tune_distributed_tpu.data.loader import SFTBatchLoader

    rng = np.random.RandomState(1)
    n, seq = 32, 16
    arrays = {
        "input_ids": rng.randint(0, 1000, (n, seq)).astype(np.int32),
        "loss_mask": rng.randint(0, 2, (n, seq)).astype(np.float32),
        "attention_mask": rng.randint(0, 2, (n, seq)).astype(np.float32),
        "segment_ids": rng.randint(0, 4, (n, seq)).astype(np.int32),
        "positions": rng.randint(0, seq, (n, seq)).astype(np.int32),
        "lengths": np.full((n,), seq, np.int32),  # stripped by both engines
    }
    kw = dict(per_device_batch_size=2, grad_accum_steps=2, data_parallel_size=2)
    nat = _make(arrays, shuffle=False)
    py = SFTBatchLoader(arrays, shuffle=False, **kw)
    n_batches = 0
    for bn, bp in zip(nat.epoch(0), py.epoch(0)):
        assert set(bn) == set(bp) == {
            "input_ids", "loss_mask", "attention_mask", "segment_ids", "positions"
        }
        for k in bn:
            assert bn[k].dtype == bp[k].dtype, k
            assert np.array_equal(bn[k], np.asarray(bp[k])), k
        n_batches += 1
    assert n_batches == nat.steps_per_epoch
    # shuffled epochs still cover every row exactly once
    seen = []
    for b in nat.epoch(1):
        seen.extend(b["input_ids"].reshape(-1, seq).tolist())
    assert {tuple(r) for r in seen} == {tuple(r) for r in arrays["input_ids"].tolist()}
    nat.close()


def test_heartbeat_detects_dead_and_alive():
    from llm_fine_tune_distributed_tpu.runtime.failure import FailureDetector

    # Coordinator (rank 0) + one worker (rank 1) of an expected world of 3:
    # rank 2 never starts and must show up dead.
    coord = FailureDetector(rank=0, world_size=3, port=0, interval_ms=50, timeout_ms=400)
    w1 = FailureDetector(
        rank=1, world_size=3, coordinator_host="127.0.0.1", port=coord.port,
        interval_ms=50, timeout_ms=400,
    )
    try:
        deadline = time.time() + 5.0
        while time.time() < deadline and coord.dead_ranks() != [2]:
            time.sleep(0.05)
        assert coord.dead_ranks() == [2]
        assert coord.rank_age_ms(0) >= 0
        assert coord.rank_age_ms(1) >= 0
        assert coord.rank_age_ms(2) == -1

        # Kill rank 1's beater; it must go dead within the timeout.
        w1.stop()
        deadline = time.time() + 5.0
        while time.time() < deadline and 1 not in coord.dead_ranks():
            time.sleep(0.05)
        assert 1 in coord.dead_ranks()
    finally:
        w1.stop()
        coord.stop()


def test_workers_report_no_dead_ranks():
    from llm_fine_tune_distributed_tpu.runtime.failure import FailureDetector

    coord = FailureDetector(rank=0, world_size=2, port=0, interval_ms=50)
    w = FailureDetector(rank=1, world_size=2, port=coord.port, interval_ms=50)
    try:
        assert w.dead_ranks() == []  # only the coordinator judges liveness
    finally:
        w.stop()
        coord.stop()
