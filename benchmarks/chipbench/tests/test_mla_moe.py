"""The cell of kind ``sft_moe`` (``moonlight-16b-a3b-ep8-d6.sft-4k-allparams``):
the hand-worked figures of ``flops_mla_moe.py``, the readers of
``readers/moe.py`` on a synthetic trace, the cell's rehearsal on a CPU, and its
control (the router in bfloat16), which has to come out not correct.

``JAX_PLATFORMS=cpu python -m pytest benchmarks/chipbench/tests -q`` (not part
of tier-1).
"""

import json
import os
import sys

import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import flops_mla_moe, run  # noqa: E402
from benchmarks.chipbench.readers import moe, scopes  # noqa: E402

CELL = "moonlight-16b-a3b-ep8-d6.sft-4k-allparams"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return run.load_json(BENCH, "configs", "moonlight-16b-a3b-ep8-d6.json")


def test_flops_match_the_hand_worked_figures():
    cfg = config()
    assert flops_mla_moe.matrix_params(cfg) == {
        "attention": 13_762_560, "dense_mlp": 69_206_016, "shared_experts": 17_301_504, "router": 131_072,
        "expert": 8_650_752, "head": 41_943_040}
    assert flops_mla_moe.attention_flops_per_token(cfg, 4096) == 20_971_520
    need = flops_mla_moe.train_flops_per_token(cfg, 4096, 0.75)
    assert need["forward"] == 752_484_352 and need["backward"] == 1_504_968_704
    assert need["total"] == 2_257_453_056 and need["attention"] == 377_487_360
    # the experts' work follows the pairs the step counted
    more = flops_mla_moe.train_flops_per_token(cfg, 4096, 1.0)
    assert more["total"] - need["total"] == 6 * 5 * 0.25 * 8_650_752
    assert flops_mla_moe.flash_fwd_cost(4, 4096, cfg) == {"flops": 343_597_383_680, "bytes": 335_544_320}
    assert flops_mla_moe.grouped_product_cost(12_288, 2048, 1408, 8) == {"flops": 70_866_960_384, "bytes": 131_072_000}


def test_the_router_columns_of_each_share_sum_to_zero():
    import jax

    from benchmarks.chipbench import weights_mla_moe

    drawn = jax.random.normal(jax.random.key(3), (256, 64), jnp.float32) * 0.02
    made = weights_mla_moe.zero_sum_by_share(drawn, 8)
    assert float(jnp.abs(made.reshape(256, 8, 8).sum(-1)).max()) < 1e-6  # whatever the common direction, it cancels
    assert abs(float(made.std()) / 0.02 - 1) < 0.02  # the scores keep their spread
    assert weights_mla_moe.zero_sum_by_share(drawn, 1) is drawn and weights_mla_moe.zero_sum_by_share(drawn, 7) is drawn
    tiny = dict(run.load_json(BENCH, "configs", "tiny.json"),
                **run.load_json(BENCH, "traffic", "sft-4k-allparams.json")["rehearsal_config"])
    gate = weights_mla_moe.make_flat(5, tiny, only=["model/layers/1/mlp/gate/kernel"])["model/layers/1/mlp/gate/kernel"]
    share = tiny["n_routed_experts"]
    sums = gate.astype(jnp.float32).reshape(gate.shape[0], -1, share).sum(-1)
    assert float(jnp.abs(sums).max()) < 2e-3  # bfloat16 rounding of `share` entries of 0.02


def test_the_configuration_states_its_cut():
    cfg, entry = config(), [c for c in run.load_json(ROOT, "BENCHMARK.json")["configs"]
                            if c["name"] == "moonlight-16b-a3b-ep8-d6"][0]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 163840}
    assert cfg["router_experts"] == 64 and cfg["held_experts"] == list(range(8)) and cfg["num_experts_per_tok"] == 6
    assert cfg["source"] == entry["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every published number under its key, but the three that are reduced
        with open(catalog) as f:
            row = [json.loads(line) for line in f if '"Moonlight-16B-A3B"' in line][0]
        assert row["source_url"] == cfg["source"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "missing") != v} == set(cfg["reduced"])


# the paths a device operation carries on the chip (tf_op), one of each kind
META = {
    "%fusion.1": {"tf_op": "jit(train_step)/while/body/closed_call/jvp(layer1)/mlp/router/dot_general:"},
    "%sort.2": {"tf_op": "jit(train_step)/while/body/closed_call/jvp(layer1)/mlp/router/sort:"},
    "%custom-call.3": {"tf_op": "jit(train_step)/while/body/closed_call/jvp(layer1)/mlp/experts/ragged_dot_general:"},
    "%custom-call.4": {"tf_op": "jit(train_step)/while/body/closed_call/transpose(jvp(layer1))/jvp(layer1)/checkpoint/mlp/experts/ragged_dot_general:"},
    "%gather.5": {"tf_op": "jit(train_step)/while/body/closed_call/jvp(layer1)/mlp/experts/gather:"},
    "%cumsum.10": {"tf_op": "jit(train_step)/while/body/closed_call/jvp(layer1)/mlp/experts/jit(gmm)/cumsum:"},
    "%custom-call.11": {"tf_op": "jit(train_step)/while/body/closed_call/jvp(layer1)/mlp/experts/jit(gmm)/pallas_call:"},
    "%fusion.6": {"tf_op": "jit(train_step)/while/body/closed_call/jvp(layer1)/mlp/shared_expert/dot_general:"},
    "%fusion.7": {"tf_op": "jit(train_step)/while/body/closed_call/jvp(layer0)/mlp/dot_general:"},
    "%fusion.8": {"tf_op": "jit(train_step)/while/body/closed_call/jvp(layer1)/attn/dot_general:"},
}
SECONDS = {"%fusion.1": 0.01, "%sort.2": 0.02, "%custom-call.3": 0.05, "%custom-call.4": 0.20, "%gather.5": 0.02,
           "%cumsum.10": 0.01, "%custom-call.11": 0.05,
           "%fusion.6": 0.04, "%fusion.7": 0.30, "%fusion.8": 0.30}
COUNTS = {k: 2.0 for k in SECONDS}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(scopes, "_metadata", lambda path, mtime: META)
    red = {"busy_s": 1.0, "window_s": 1.0, "op_seconds": SECONDS, "op_counts": COUNTS}
    return {"trace": red, "peaks": PEAKS, "config": config(), "microbatch": 4, "seq_len": 4096,
            "expert_pairs_per_token": 0.75, "expert_load_max_over_mean": 1.3}


def spec(name):
    return run.load_json(BENCH, "metrics", name + ".json")


def test_readers_on_a_synthetic_trace(traced):
    parts = moe.seconds_by_part(SECONDS, COUNTS, META, ("ragged_dot", "gmm"))
    assert parts == {"router": (0.03, 4.0), "product": (pytest.approx(0.30), 6.0), "experts": (pytest.approx(0.03), 4.0),
                     "shared_expert": (0.04, 2.0)}
    here = __file__  # any file that exists: the metadata is the fixture's
    assert moe.moe_time_pct(traced, spec("moe_busy_pct.train"), xplane_path=here) == pytest.approx(40.0)
    assert moe.moe_time_pct(traced, spec("moe_dispatch_busy_pct.train"), xplane_path=here) == pytest.approx(6.0)
    # 6 grouped products of 12,288 pairs: 70.87 GFLOP each at 197 TFLOP/s = 0.3597 ms, over 0.30 s
    # (the cumsum inside jit(gmm) is the kernel's wrapper: dispatch, not a product)
    bound = 70_866_960_384 / 197e12
    got = moe.expert_gmm_roofline_pct(traced, spec("expert_gmm_roofline_pct"), xplane_path=here)
    assert got == pytest.approx(100.0 * 6 * bound / 0.30)
    assert moe.step_counter(traced, spec("expert_pairs_per_token.train")) == 0.75
    assert moe.step_counter(traced, spec("expert_load_max_over_mean.train")) == 1.3


def test_flash_roofline_counts_both_head_widths(traced):
    name = "%custom-call.9 = bf16[4,16,4096,128] custom-call(...), flash_attention_fwd"
    traced["trace"] = dict(traced["trace"], op_seconds={name: 0.02}, op_counts={name: 6.0})
    got = moe.mla_flash_fwd_roofline_pct(traced, spec("mla_flash_fwd_roofline_pct"))
    assert got == pytest.approx(100.0 * 6 * (343_597_383_680 / 197e12) / 0.02)
    dense = dict(traced, config={"head_dim": 128})  # a configuration without latent attention: nothing to read
    assert moe.mla_flash_fwd_roofline_pct(dense, spec("mla_flash_fwd_roofline_pct")) is None


def test_readers_find_nothing_in_a_program_without_the_scopes(monkeypatch):
    """The parent's trace: scopes of PR 24, no expert scope. Every reader of
    this file returns None and raises nothing."""
    pb = os.path.join(BENCH, "testdata", "scoped.xplane.pb")
    from benchmarks.chipbench import trace

    sources = {"trace": trace.reduce_planes(trace.read_planes(pb)), "peaks": PEAKS, "config": {"head_dim": 128},
               "microbatch": 2, "seq_len": 1024}
    for name in ("moe_busy_pct.train", "moe_dispatch_busy_pct.train", "expert_gmm_roofline_pct"):
        reader = getattr(moe, spec(name)["reader"].rsplit(".", 1)[1])
        assert reader(sources, spec(name), xplane_path=pb) is None
    assert moe.mla_flash_fwd_roofline_pct(sources, spec("mla_flash_fwd_roofline_pct")) is None
    assert moe.step_counter(sources, spec("expert_pairs_per_token.train")) is None
    assert moe.moe_time_pct({"trace": None}, spec("moe_busy_pct.train")) is None


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def run_cell(capsys, seed, trace_on="0", entry=run):
    code = entry.main(["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", trace_on, "--rehearse", "1"])
    assert code == 0
    return last_line(capsys)


@pytest.mark.parametrize("seed", [11, 2**31 + 12])
def test_the_cell_rehearses_and_its_control_is_not_correct(capsys, seed):
    from benchmarks.chipbench.tools import control
    from llm_fine_tune_distributed_tpu.ops import moe as program_moe

    line = run_cell(capsys, seed)
    assert line["correct"] is True and line["failed"] == 0 and "train_tokens_per_s" in line["metrics"]
    try:
        line = run_cell(capsys, seed, entry=control)
    finally:
        program_moe.ROUTER_DTYPE = jnp.float32  # the control set it for this process
    assert line["correct"] is False
    assert "first_grad_worst_leaf_rel_err" in {c["name"] for c in line["checks"] if not c["ok"]}


def test_the_traced_rehearsal_reports_the_counters(capsys):
    line = run_cell(capsys, 7, trace_on="1")
    assert line["correct"] is True
    assert 0.3 < line["metrics"]["expert_pairs_per_token.train"]["value"] < 1.5
    assert line["metrics"]["expert_load_max_over_mean.train"]["value"] >= 1.0
    assert line["metrics"]["recompiles_in_window.train"]["value"] == 0
