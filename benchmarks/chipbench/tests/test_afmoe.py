"""The cell of kind ``sft_afmoe``
(``trinity-mini-26b-a3b-ep8-d5.sft-8k-gated-swa-allparams``): the hand-worked
figures of ``flops_afmoe.py``, the configuration's stated cut, the two new
metrics' reader on a synthetic trace and over a program that has nothing for
it to read, the cell's rehearsal on a CPU, and its control (the router in
``float8_e4m3fn``) and a planted fault (half the batch left out), which have to
come out not correct.

``JAX_PLATFORMS=cpu python -m pytest benchmarks/chipbench/tests -q`` (not part
of tier-1).
"""

import json
import math
import os
import sys

import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import flops, flops_afmoe, run  # noqa: E402
from benchmarks.chipbench.readers import gdn, moe, scopes, swa  # noqa: E402

CONFIG = "trinity-mini-26b-a3b-ep8-d5"
CELL = CONFIG + ".sft-8k-gated-swa-allparams"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("attn_gate_time_pct.train", "out_norm_time_pct.train")
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]


def config():
    return run.load_json(BENCH, "configs", CONFIG + ".json")


def spec(name):
    return run.load_json(BENCH, "metrics", name + ".json")


def test_flops_match_the_hand_worked_figures():
    cfg = config()
    assert flops_afmoe.matrix_params(cfg) == {
        "mixer": 27_262_976, "dense_mlp": 37_748_736, "router": 262_144, "expert": 6_291_456,
        "shared_experts": 6_291_456, "head": 51_249_152}
    assert [flops_afmoe.window_of(cfg, i) for i in range(5)] == [2048, 2048, 2048, None, 2048]
    assert flops_afmoe.pairs_a_head(8192, 2048) == 14_680_064 and flops_afmoe.pairs_a_head(8192, None) == 33_554_432
    assert flops_afmoe.attention_flops_per_token(cfg, 8192, 0) == 29_360_128
    assert flops_afmoe.attention_flops_per_token(cfg, 8192, 3) == 67_108_864
    need = flops_afmoe.train_flops_per_token(cfg, 8192, 1.0)
    assert need["forward"] == 737_935_360 and need["backward"] == 1_475_870_720 and need["total"] == 2_213_806_080
    assert need["attention"] == 553_648_128 and need["experts"] == 150_994_944
    # the flash forward kernels through the accepted readers' count, at this configuration's window and heads
    assert flops_afmoe.flash_fwd_cost(2, 8192, cfg, 2048) == {"flops": 481_036_337_152, "bytes": 301_989_888}
    assert flops_afmoe.flash_fwd_cost(2, 8192, cfg, None) == {"flops": 1_099_511_627_776, "bytes": 301_989_888}
    assert cfg["n_routed_experts"] == cfg["num_experts"] == len(cfg["held_experts"])  # what readers/moe.py reads


def test_the_configuration_states_its_cut():
    cfg, bench = config(), run.load_json(ROOT, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert cfg["reduced"] == entry["reduced"] == REDUCED
    assert cfg["published"] == {"num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128, "vocab_size": 200192}
    assert [cfg[k] for k in REDUCED] == [5, 1, 16, 25024]
    assert cfg["router_experts"] == 128 and cfg["held_experts"] == list(range(16)) and cfg["num_experts_per_tok"] == 8
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["sliding_window"], cfg["route_scale"]) == (6144, 1024, 2048, 2.826)
    assert cfg["layer_types"][:5] == ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"] and len(cfg["layer_types"]) == 32
    for said in ("64-chip", "8 pipeline stages", "shared by 8 chips", "705,474,304", "BEFORE post_mlp_layernorm"):
        assert said in cfg["stands_for"], said
    assert {"embed_scale", "four_norms", "qk_norm", "attention_gate", "rope_layers", "expert_bias", "router_aux_loss",
            "embed_std", "router_kernel", "n_routed_experts", "param_dtype"} <= set(cfg["assumed"])
    assert cfg["source"] == entry["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every published key as published, but the four that are reduced
        with open(catalog) as f:
            row = [json.loads(line) for line in f if '"Trinity-Mini"' in line][0]
        assert row["source_url"] == cfg["source"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "missing") != v} == set(cfg["reduced"])
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "sft-8k-gated-swa-allparams"
    assert bench["workloads"][-1] == cell and bench["configs"][-1] == entry  # appended, nothing put in the middle
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert set(NEW_METRICS) <= listed
    assert {"window_flash_fwd_roofline_pct", "global_flash_fwd_roofline_pct", "window_attn_time_pct.train",
            "global_attn_time_pct.train", "flash_band_tiles_pct", "flash_time_pct.train", "expert_gmm_roofline_pct",
            "moe_busy_pct.train", "moe_dispatch_busy_pct.train", "expert_pairs_per_token.train",
            "expert_load_max_over_mean.train", "train_mfu_pct", "scoped_time_pct.train"} <= listed
    assert not {"flash_fwd_roofline_pct", "mla_flash_fwd_roofline_pct", "gdn_scan_fwd_roofline_pct",
                "frozen_fwd_time_pct.train"} & listed
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW_METRICS)
    for m in bench["per_layer"][-2:]:
        assert m["workloads"][0] == CELL and m["moves"] == "train_tokens_per_s" and m["better"] == "lower"
        # (the gate's share is read in the Qwen3-Next cell too: its full layer has carried the scope since PR 32)
        assert m["workloads"][1:] == (["qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams"] if "gate" in m["name"] else [])
        assert spec(m["name"])["reader"] == "readers.gdn.scope_share_pct"  # a data file over the reader that is there
    mix = run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    assert mix["microbatch"] * mix["accum"] * mix["seq_len"] == 32_768 and mix["kind"] == "sft_afmoe"
    assert mix["control"] == {"router_dtype": "float8_e4m3fn"} and mix["recipe"]["loss_chunk_size"] == 1024


# the paths a device operation carries on the chip (tf_op)
LAYER = "jit(train_step)/while/body/closed_call/"
META = {
    "%fusion.1": {"tf_op": LAYER + "jvp(layer1)/attn/dot_general:"},
    "%fusion.2": {"tf_op": LAYER + "jvp(layer1)/attn/qk_norm/mul:"},
    "%custom-call.3 flash_attention_window_fwd": {"tf_op": LAYER + "jvp(layer1)/attn/jit(forward)/flash_attention_window_fwd/pallas_call:"},
    "%fusion.4": {"tf_op": LAYER + "jvp(layer1)/attn/attn_gate/mul:"},
    "%fusion.5": {"tf_op": LAYER + "transpose(jvp(layer1))/attn/attn_gate/mul:"},
    "%fusion.6": {"tf_op": LAYER + "jvp(layer1)/attn/out_norm/mul:"},
    "%fusion.7": {"tf_op": LAYER + "jvp(layer1)/mlp/out_norm/mul:"},
    "%fusion.8": {"tf_op": LAYER + "transpose(jvp(layer3))/jvp(layer3)/checkpoint/rematted_computation/mlp/out_norm/rsqrt:"},
    "%custom-call.9 flash_attention_causal_fwd": {"tf_op": LAYER + "jvp(layer3)/attn/jit(forward)/flash_attention_causal_fwd/pallas_call:"},
    "%fusion.10": {"tf_op": LAYER + "jvp(layer3)/attn/attn_gate/logistic:"},
    "%fusion.11": {"tf_op": "jit(train_step)/optimizer/sub:"},
}
SECONDS = {"%fusion.1": 0.10, "%fusion.2": 0.02, "%custom-call.3 flash_attention_window_fwd": 0.08, "%fusion.4": 0.01,
           "%fusion.5": 0.02, "%fusion.6": 0.03, "%fusion.7": 0.04, "%fusion.8": 0.05,
           "%custom-call.9 flash_attention_causal_fwd": 0.10, "%fusion.10": 0.01, "%fusion.11": 0.54}
COUNTS = {k: 6.0 for k in SECONDS}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(scopes, "_metadata", lambda path, mtime: META)
    red = {"busy_s": 1.0, "window_s": 1.0, "op_seconds": SECONDS, "op_counts": COUNTS}
    return {"trace": red, "peaks": PEAKS, "config": config(), "microbatch": 2, "seq_len": 8192,
            "flash_grid_tiles": {"flash_attention_window_fwd seq=8192 block=1024 window=2048": [21, 36],
                                 "flash_attention_window_dq seq=8192 block=1024 window=2048": [21, 36]}}


def test_readers_on_a_synthetic_trace(traced):
    here = __file__  # any file that exists: the metadata is the fixture's
    assert gdn.scope_share_pct(traced, spec("attn_gate_time_pct.train"), xplane_path=here) == pytest.approx(4.0)
    assert gdn.scope_share_pct(traced, spec("out_norm_time_pct.train"), xplane_path=here) == pytest.approx(12.0)
    # the accepted readers the cell joins: the window layers' and the global layer's attention, by layer_types
    assert swa.attn_kind_time_pct(traced, spec("window_attn_time_pct.train"), xplane_path=here) == pytest.approx(26.0)
    assert swa.attn_kind_time_pct(traced, spec("global_attn_time_pct.train"), xplane_path=here) == pytest.approx(11.0)
    # their kernels' rooflines at THIS configuration's window (2048) and heads: 6 calls of 2 rows each
    window = flops.roofline_seconds(flops_afmoe.flash_fwd_cost(2, 8192, config(), 2048), PEAKS)["seconds"]
    assert swa.flash_kind_fwd_roofline_pct(traced, spec("window_flash_fwd_roofline_pct")) == pytest.approx(100 * 6 * window / 0.08)
    whole = flops.roofline_seconds(flops_afmoe.flash_fwd_cost(2, 8192, config(), None), PEAKS)["seconds"]
    assert swa.flash_kind_fwd_roofline_pct(traced, spec("global_flash_fwd_roofline_pct")) == pytest.approx(100 * 6 * whole / 0.10)
    # a band three blocks wide on eight: 1 + 2 + 6 x 3 = 21 tiles of the triangle's 36
    assert swa.flash_band_tiles_pct(traced, spec("flash_band_tiles_pct")) == pytest.approx(100 * 21 / 36)


def test_the_program_counts_a_band_of_three_blocks():
    from llm_fine_tune_distributed_tpu.ops import flash_attention

    band = flash_attention._band(8192, 1024, 2048)
    assert (band.blocks, band.steps, band.tiles) == (8, 3, 21)
    # of a block's three tiles the middle one is whole: no test in it; Mellum's band of two has none
    assert [flash_attention._is_cut(band, d) for d in range(3)] == [True, False, True]
    assert not any(not flash_attention._is_cut(flash_attention._band(8192, 1024, 1024), d) for d in range(2))


def test_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's trace: no ``attn_gate`` and no ``out_norm`` scope. The new
    metrics' reader returns None and raises nothing."""
    pb = os.path.join(BENCH, "testdata", "scoped.xplane.pb")
    from benchmarks.chipbench import trace

    red = trace.reduce_planes(trace.read_planes(pb))
    for cfg in ({"head_dim": 128}, config()):
        sources = {"trace": red, "peaks": PEAKS, "config": cfg, "microbatch": 2, "seq_len": 1024}
        for name in NEW_METRICS:
            assert gdn.scope_share_pct(sources, spec(name), xplane_path=pb) is None
        assert moe.expert_gmm_roofline_pct(sources, spec("expert_gmm_roofline_pct"), xplane_path=pb) is None
    assert gdn.scope_share_pct({"trace": None, "config": config(), "peaks": PEAKS}, spec(NEW_METRICS[0])) is None


def test_a_program_without_the_afmoe_block_refuses_the_cell(monkeypatch):
    """What the parent commit does with the cell once the benchmark's files are laid over it: exit at once, by name."""
    from benchmarks.chipbench import kind_sft_afmoe
    from llm_fine_tune_distributed_tpu.models import configs

    monkeypatch.setattr(configs, "PRESETS", {k: v for k, v in configs.PRESETS.items() if "trinity" not in k})
    with pytest.raises(SystemExit, match="afmoe"):
        kind_sft_afmoe.model_config(config())


def test_the_cells_model_is_the_published_one_cut_to_the_share():
    from benchmarks.chipbench import kind_sft_afmoe, weights_afmoe
    from llm_fine_tune_distributed_tpu.models.configs import get_preset

    mc = kind_sft_afmoe.model_config(config())
    want = get_preset("trinity_mini").replace(
        name="afmoe", num_layers=5, first_k_dense_replace=1, vocab_size=25024, held_experts=tuple(range(16)))
    assert mc == want and mc.num_params == 705_474_304
    assert [(mc.layer(i).rope, mc.layer(i).window, mc.layer(i).feed_forward) for i in range(5)] == [
        (True, 2048, "dense"), (True, 2048, "grouped_experts"), (True, 2048, "grouped_experts"),
        (False, None, "grouped_experts"), (True, 2048, "grouped_experts")]
    shapes = weights_afmoe.leaf_shapes(config())
    assert sum(math.prod(s) for s in shapes.values()) == mc.num_params


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def run_cell(capsys, seed, trace_on="0", entry=run):
    code = entry.main(["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", trace_on, "--rehearse", "1"])
    assert code == 0
    return last_line(capsys)


def test_the_cell_rehearses_and_its_control_is_not_correct(capsys):
    from benchmarks.chipbench.tools import control
    from llm_fine_tune_distributed_tpu.ops import moe as program_moe

    seed = 2**31 + 12
    line = run_cell(capsys, seed)
    assert line["correct"] is True and line["failed"] == 0 and "train_tokens_per_s" in line["metrics"]
    try:
        line = run_cell(capsys, seed, entry=control)
        assert program_moe.ROUTER_DTYPE == jnp.float8_e4m3fn
    finally:
        program_moe.ROUTER_DTYPE = jnp.float32  # the control set it for this process
    assert line["correct"] is False and line["failed"] == 0  # wrong, and every loss finite
    assert "first_grad_worst_leaf_rel_err" in {c["name"] for c in line["checks"] if not c["ok"]}


def test_half_the_batch_left_out_is_not_correct(capsys):
    from benchmarks.chipbench.tools import fault as planted

    code = planted.main(["--fault", "half_batch", "--workload", CELL, "--seed", "7", "--seconds", "2", "--trace", "0",
                         "--rehearse", "1"])
    line = last_line(capsys)
    assert code == 0 and line["correct"] is False and line["failed"] == 0
    assert "loss_step1_abs_gap" in {c["name"] for c in line["checks"] if not c["ok"]}


def test_the_traced_rehearsal_prints_every_metric_a_cpu_can_read(capsys):
    """Every per-layer metric the cell lists is in a traced run's line, but
    those read from a device trace (a CPU's trace holds no device plane): the
    counters, the ledger's, the set-up spans' and the grids' tiles are."""
    line = run_cell(capsys, 7, trace_on="1")
    assert line["correct"] is True
    bench = run.load_json(ROOT, "BENCHMARK.json")
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    from_a_device_trace = {m["name"] for m in listed if m["source"] == "device_trace"}
    missing = {m["name"] for m in listed} - set(line["metrics"])
    assert missing <= from_a_device_trace | {"train_mfu_pct", "train_peak_hbm_gib", "flash_band_tiles_pct"}, missing
    assert 0.5 < line["metrics"]["expert_pairs_per_token.train"]["value"] < 1.6  # 4 of 16 chosen, 4 held: 1 expected
    assert line["metrics"]["recompiles_in_window.train"]["value"] == 0
    assert line["metrics"]["train_step_trace_s"]["value"] > 0
