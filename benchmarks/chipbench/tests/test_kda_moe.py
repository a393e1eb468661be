"""The cell of kind ``sft_kda_moe``
(``kimi-linear-48b-a3b-ep32-d5.sft-8k-kda-mla-allparams``): the hand-worked
figures of ``flops_kda_moe.py``, the configuration's stated cut, the two new
metrics' readers on a synthetic trace and over a program that has nothing for
them to read, the cell's rehearsal on a CPU, and its control (the router in
``float8_e4m3fn``, the rule's state in bfloat16), a planted fault (half the
batch left out) and the mechanism taken out (the decay as one scalar a head),
which have to come out not correct.

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

from benchmarks.chipbench import flops, flops_kda_moe, run  # noqa: E402
from benchmarks.chipbench.readers import gdn, kda, moe, scopes  # noqa: E402

CONFIG = "kimi-linear-48b-a3b-ep32-d5"
CELL = CONFIG + ".sft-8k-kda-mla-allparams"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("kda_scan_fwd_roofline_pct", "kda_gates_time_pct.train")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


def config():
    return run.load_json(BENCH, "configs", CONFIG + ".json")


def spec(name):
    return run.load_json(BENCH, "metrics", name + ".json")


def test_flops_match_the_hand_worked_figures():
    cfg = config()
    assert flops_kda_moe.matrix_params(cfg) == {
        "kda_mixer": 39_460_864, "mla_mixer": 29_114_368, "dense_mlp": 63_700_992, "router": 589_824,
        "expert": 7_077_888, "shared_experts": 7_077_888, "head": 47_185_920}
    assert flops_kda_moe.kda_layers(cfg) == (0, 1, 2, 4)
    assert flops_kda_moe.rule_flops_per_token(cfg) == 32 * 7 * 128 * 128 == 3_670_016
    assert flops_kda_moe.conv_flops_per_token(cfg) == 98_304
    assert flops_kda_moe.attention_flops_per_token(cfg, 8192) == 83_886_080
    need = flops_kda_moe.train_flops_per_token(cfg, 8192, 0.25)
    assert need["forward"] == 770_146_304 and need["backward"] == 1_540_292_608 and need["total"] == 2_310_438_912
    assert need["linear_layers"] == 992_280_576 and need["attention"] == 251_658_240 and need["experts"] == 42_467_328
    # the recurrence, whatever implements it: 7 x 128 x 128 a token and head; q, k, v, g at 128 wide, beta, o once
    assert flops_kda_moe.kda_scan_fwd_cost(2, 8192, cfg) == {"flops": 60_129_542_144, "bytes": 807_403_520}
    assert flops.roofline_seconds(flops_kda_moe.kda_scan_fwd_cost(2, 8192, cfg), PEAKS)["seconds"] == pytest.approx(807_403_520 / 819e9)
    # the latent layer's kernel through the accepted reader's count, at this configuration's heads: 192 and 128
    assert flops_kda_moe.flash_fwd_cost(2, 8192, cfg) == {"flops": 2 * 32 * 320 * 8192 * 8192, "bytes": 2 * 8192 * 32 * 640 * 2}
    assert cfg["n_routed_experts"] == cfg["num_experts"] == len(cfg["held_experts"])  # what readers/moe.py reads


def test_the_configuration_states_its_cut():
    cfg, bench = config(), run.load_json(ROOT, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert cfg["reduced"] == entry["reduced"] == REDUCED
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert [cfg[k] for k in REDUCED] == [5, 8, 20480]
    assert cfg["router_experts"] == 256 and cfg["held_experts"] == list(range(8)) and cfg["num_experts_per_token"] == 8
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["num_shared_experts"]) == (2304, 9216, 1024, 1)
    assert (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["mla_use_nope"]) == (512, 128, 64, 128, True)
    lin = cfg["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["kda_layers"][:4] == [1, 2, 3, 5] and lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]  # as published, 1-based
    for said in ("224-chip", "7 pipeline stages", "shared by 32 chips", "602,434,432", "9.64 GB", "first five"):
        assert said in cfg["stands_for"], said
    assert {"kda_projections", "kda_beta", "kda_decay_gate", "kda_output_gate", "A_log", "dt_bias", "mla", "router",
            "e_score_correction_bias", "router_aux_loss", "embed_std", "router_kernel", "n_routed_experts", "param_dtype"} <= set(cfg["assumed"])
    assert cfg["source"] == entry["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every published key as published, but the three that are reduced
        with open(catalog) as f:
            row = [json.loads(line) for line in f if '"Kimi-Linear-48B-A3B-Instruct"' in line][0]
        assert row["source_url"] == cfg["source"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "missing") != v} == set(cfg["reduced"])
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "sft-8k-kda-mla-allparams"
    assert bench["workloads"][-1] == cell and bench["configs"][-1] == entry  # appended, nothing put in the middle
    assert all(len(x["why"]) <= 200 for x in (cell, entry))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert set(NEW_METRICS) <= listed
    assert {"linear_attn_time_pct.train", "gdn_scan_time_pct.train", "gdn_chunked_calls_pct", "flash_time_pct.train",
            "mla_flash_fwd_roofline_pct", "expert_gmm_roofline_pct", "moe_busy_pct.train", "moe_dispatch_busy_pct.train",
            "expert_pairs_per_token.train", "expert_load_max_over_mean.train", "train_mfu_pct", "scoped_time_pct.train",
            "device_idle_pct.train", "recompiles_in_window.train", "train_step_trace_s", "setup_spanned_pct"} <= listed
    assert not {"gdn_scan_fwd_roofline_pct", "flash_fwd_roofline_pct", "attn_in_time_pct.train", "attn_gate_time_pct.train",
                "window_flash_fwd_roofline_pct", "frozen_fwd_time_pct.train"} & listed
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW_METRICS)
    for m in bench["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
    assert spec(NEW_METRICS[0])["reader"] == "readers.kda.kda_scan_fwd_roofline_pct"
    assert spec(NEW_METRICS[1])["reader"] == "readers.gdn.scope_share_pct"  # a data file over the reader that is there
    assert cell["name"] in [m for m in bench["end_to_end"] if m["name"] == "train_tokens_per_s"][0]["workloads"]
    mix = run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    assert mix["microbatch"] * mix["accum"] * mix["seq_len"] == 32_768 and mix["kind"] == "sft_kda_moe"
    assert mix["control"] == {"router_dtype": "float8_e4m3fn", "state_dtype": "bfloat16"} and mix["recipe"]["loss_chunk_size"] == 1024


# the paths a device operation carries on the chip (tf_op)
LAYER = "jit(train_step)/while/body/closed_call/"
SCAN = "linear_attn/gdn_scan/"
META = {
    "%fusion.1": {"tf_op": LAYER + "jvp(layer1)/linear_attn/dot_general:"},
    "%fusion.2": {"tf_op": LAYER + "jvp(layer1)/linear_attn/kda_gates/dot_general:"},
    "%fusion.3": {"tf_op": LAYER + "transpose(jvp(layer1))/linear_attn/kda_gates/softplus:"},
    "%fusion.4": {"tf_op": LAYER + "jvp(layer1)/" + SCAN + "cumsum:"},
    "%fusion.5": {"tf_op": LAYER + "jvp(layer1)/" + SCAN + "while/body/dot_general:"},
    "%fusion.6": {"tf_op": LAYER + "transpose(jvp(layer1))/" + SCAN + "while/body/dot_general:"},
    "%fusion.7": {"tf_op": LAYER + "transpose(jvp(layer1))/jvp(layer1)/checkpoint/rematted_computation/" + SCAN + "cumsum:"},
    "%custom-call.8 flash_attention_fwd": {"tf_op": LAYER + "jvp(layer3)/attn/jit(forward)/flash_attention_fwd/pallas_call:"},
    "%fusion.9": {"tf_op": "jit(train_step)/optimizer/sub:"},
}
SECONDS = {"%fusion.1": 0.10, "%fusion.2": 0.02, "%fusion.3": 0.03, "%fusion.4": 0.01, "%fusion.5": 0.09,
           "%fusion.6": 0.20, "%fusion.7": 0.05, "%custom-call.8 flash_attention_fwd": 0.10, "%fusion.9": 0.40}
COUNTS = {**{k: 6.0 for k in SECONDS}, "%fusion.5": 6.0 * 128}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(scopes, "_metadata", lambda path, mtime: META)
    red = {"busy_s": 1.0, "window_s": 1.0, "op_seconds": SECONDS, "op_counts": COUNTS}
    return {"trace": red, "peaks": PEAKS, "config": config(), "microbatch": 2, "seq_len": 8192,
            "gdn_calls": {"2 8192 32 32 128 128 by channel": [4, "chunked 64, a decay a channel in sub-blocks of 16: xla (no kernels for it yet)"]}}


def test_readers_on_a_synthetic_trace(traced):
    here = __file__  # any file that exists: the metadata is the fixture's
    assert gdn.scope_share_pct(traced, spec("kda_gates_time_pct.train"), xplane_path=here) == pytest.approx(5.0)
    # the forward calls under gdn_scan: 6 calls (the operation outside the loop), 0.01 + 0.09 s of forward time
    bound = flops.roofline_seconds(flops_kda_moe.kda_scan_fwd_cost(2, 8192, config()), PEAKS)["seconds"]
    assert kda.kda_scan_fwd_roofline_pct(traced, spec("kda_scan_fwd_roofline_pct"), xplane_path=here) == pytest.approx(100 * 6 * bound / 0.10)
    # the accepted readers the cell joins
    assert gdn.scope_share_pct(traced, spec("linear_attn_time_pct.train"), xplane_path=here) == pytest.approx(50.0)
    assert gdn.scope_share_pct(traced, spec("gdn_scan_time_pct.train"), xplane_path=here) == pytest.approx(35.0)
    assert gdn.gdn_chunked_calls_pct(traced, spec("gdn_chunked_calls_pct")) == 100.0
    latent = flops.roofline_seconds(flops_kda_moe.flash_fwd_cost(2, 8192, config()), PEAKS)["seconds"]
    assert moe.mla_flash_fwd_roofline_pct(traced, spec("mla_flash_fwd_roofline_pct")) == pytest.approx(100 * 6 * latent / 0.10)


def test_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's trace: no ``kda_gates`` and no ``gdn_scan`` scope, and another configuration. The new metrics'
    readers return None and raise nothing."""
    pb = os.path.join(BENCH, "testdata", "scoped.xplane.pb")
    from benchmarks.chipbench import trace

    red = trace.reduce_planes(trace.read_planes(pb))
    for cfg in ({"head_dim": 128}, {"linear_num_value_heads": 32}, config()):
        sources = {"trace": red, "peaks": PEAKS, "config": cfg, "microbatch": 2, "seq_len": 1024}
        assert gdn.scope_share_pct(sources, spec("kda_gates_time_pct.train"), xplane_path=pb) is None
        assert kda.kda_scan_fwd_roofline_pct(sources, spec("kda_scan_fwd_roofline_pct"), xplane_path=pb) is None
    assert kda.kda_scan_fwd_roofline_pct({"trace": None, "config": config(), "peaks": PEAKS}, spec(NEW_METRICS[0])) is None


def test_a_program_without_the_mixer_refuses_the_cell(monkeypatch):
    """What the parent commit does with the cell once the benchmark's files are laid over it: exit at once, by name."""
    from benchmarks.chipbench import kind_sft_kda_moe
    from llm_fine_tune_distributed_tpu.models import configs

    monkeypatch.setattr(configs, "PRESETS", {k: v for k, v in configs.PRESETS.items() if "kimi" not in k})
    with pytest.raises(SystemExit, match="Kimi Delta Attention"):
        kind_sft_kda_moe.model_config(config())


def test_the_cells_model_is_the_published_one_cut_to_the_share():
    from benchmarks.chipbench import kind_sft_kda_moe, weights_kda_moe
    from llm_fine_tune_distributed_tpu.models.configs import get_preset

    mc = kind_sft_kda_moe.model_config(config())
    full = get_preset("kimi_linear_48b_a3b")
    want = full.replace(name="kimi_linear", num_layers=5, vocab_size=20480, held_experts=tuple(range(8)), layer_types=full.layer_types[:5])
    assert mc == want and mc.num_params == 602_434_432
    assert [(mc.layer(i).attention, mc.layer(i).rope, mc.layer(i).feed_forward) for i in range(5)] == [
        ("kda", False, "dense"), ("kda", False, "grouped_experts"), ("kda", False, "grouped_experts"),
        ("latent", False, "grouped_experts"), ("kda", False, "grouped_experts")]
    shapes = weights_kda_moe.leaf_shapes(config())
    assert sum(math.prod(s) for s in shapes.values()) == mc.num_params


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def run_cell(capsys, seed, trace_on="0", entry=run):
    code = entry.main(["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", trace_on, "--rehearse", "1"])
    assert code == 0
    return last_line(capsys)


def test_the_cell_rehearses_and_its_control_is_not_correct(capsys):
    from benchmarks.chipbench.tools import control
    from llm_fine_tune_distributed_tpu.ops import gated_delta, moe as program_moe

    seed = 2**31 + 12
    line = run_cell(capsys, seed)
    assert line["correct"] is True and line["failed"] == 0 and "train_tokens_per_s" in line["metrics"]
    try:
        line = run_cell(capsys, seed, entry=control)
        assert program_moe.ROUTER_DTYPE == jnp.float8_e4m3fn and gated_delta.STATE_DTYPE == jnp.bfloat16
    finally:
        program_moe.ROUTER_DTYPE, gated_delta.STATE_DTYPE = jnp.float32, jnp.float32  # the control set them for this process
    assert line["correct"] is False and line["failed"] == 0  # wrong, and every loss finite


def test_half_the_batch_left_out_and_a_scalar_decay_are_not_correct(capsys):
    from benchmarks.chipbench.tools import fault as planted, fault_kda

    code = planted.main(["--fault", "half_batch", "--workload", CELL, "--seed", "7", "--seconds", "2", "--trace", "0",
                         "--rehearse", "1"])
    line = last_line(capsys)
    assert code == 0 and line["correct"] is False and line["failed"] == 0
    assert "loss_step1_abs_gap" in {c["name"] for c in line["checks"] if not c["ok"]}
    line = run_cell(capsys, 7, entry=fault_kda)  # the mechanism the cell exists for, taken out
    assert line["correct"] is False and line["failed"] == 0


def test_the_traced_rehearsal_prints_every_metric_a_cpu_can_read(capsys):
    """Every per-layer metric the cell lists is in a traced run's line, but those read from a device trace (a CPU's
    trace holds no device plane): the counters, the ledger's, the set-up spans' and the rule's forms are."""
    line = run_cell(capsys, 7, trace_on="1")
    assert line["correct"] is True
    bench = run.load_json(ROOT, "BENCHMARK.json")
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    from_a_device_trace = {m["name"] for m in listed if m["source"] == "device_trace"}
    missing = {m["name"] for m in listed} - set(line["metrics"])
    assert missing <= from_a_device_trace | {"train_mfu_pct", "train_peak_hbm_gib"}, missing
    assert 0.5 < line["metrics"]["expert_pairs_per_token.train"]["value"] < 1.6  # 4 of 16 chosen, 4 held: 1 expected
    assert line["metrics"]["recompiles_in_window.train"]["value"] == 0
    assert line["metrics"]["gdn_chunked_calls_pct"]["value"] == 100.0
    assert line["metrics"]["train_step_trace_s"]["value"] > 0
