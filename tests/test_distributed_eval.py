"""Distributed eval: validation work is sharded over the
data-parallel axes — per-device FLOPs shrink ~1/dp — while the token-weighted
eval loss stays equal to the single-device result, and the whole sweep runs
as one staged scan program."""

import numpy as np
import pytest

import jax

from llm_fine_tune_distributed_tpu.config import MeshConfig

from tests.test_train_e2e import make_config, qa_parquet  # noqa: F401 (fixture)


@pytest.fixture(scope="module")
def trainers(qa_parquet, tmp_path_factory):  # noqa: F811
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    tmp = tmp_path_factory.mktemp("eval_out")
    solo = SFTTrainer(
        make_config(tmp / "solo", data_dir, dataset_file,
                    mesh=MeshConfig(data=1, fsdp=1, tensor=1, seq=1))
    )
    sharded = SFTTrainer(
        make_config(tmp / "shard", data_dir, dataset_file,
                    mesh=MeshConfig(data=2, fsdp=4, tensor=1, seq=1))
    )
    return solo, sharded


def test_eval_loss_equal_across_meshes(trainers):
    solo, sharded = trainers
    l1 = solo.evaluate()
    l8 = sharded.evaluate()
    assert np.isfinite(l1)
    # same params (same init seed), same data -> same token-weighted loss up
    # to reduction order (~1e-4 on this jax/XLA's f32 cross-device reduce;
    # a real weighting bug would shift the ~6.3 loss by orders more)
    assert l8 == pytest.approx(l1, abs=5e-4)
    # staged slabs were built exactly once and reused
    assert solo._staged_eval is not None
    again = sharded.evaluate()
    assert again == pytest.approx(l8, abs=0)


def test_eval_work_shards_over_dp(trainers):
    """Per-device validation work on the dp=8 mesh is ~1/dp: each device
    holds (and, under SPMD, computes on) only its shard of the staged
    batches, and the compiled program carries the cross-device all-reduce
    that sums (ce, tokens)."""
    solo, sharded = trainers
    solo.evaluate()
    sharded.evaluate()

    def rows_per_device(trainer):
        ids = trainer._staged_eval["input_ids"]  # [nb, bs, seq]
        shard = ids.addressable_shards[0].data
        return shard.shape[0] * shard.shape[1]

    r1, r8 = rows_per_device(solo), rows_per_device(sharded)
    # 10 val rows: solo stages 5x2 rows on one device; the dp=8 mesh pads to
    # 16 and gives each device 2 — a 1/5 cut (1/dp up to tail padding)
    assert r8 * 4 <= r1, f"per-device eval rows {r8} vs single-device {r1}"

    compiled = sharded._eval_all.lower(
        sharded.state, sharded._staged_eval
    ).compile().as_text()
    assert "all-reduce" in compiled, (
        "sharded eval program has no cross-device reduction — the "
        "(ce, tokens) sums are not being psum'd"
    )
