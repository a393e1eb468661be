"""The cell of kind ``sft_swa_moe`` (``mellum2-12b-a2.5b-ep4-d4.sft-8k-allparams``):
the hand-worked figures of ``flops_swa_moe.py``, the configuration's stated
cut, the readers of ``readers/swa.py`` on a synthetic trace and over a program
that has nothing for them to read, the cell's rehearsal on a CPU, and its
control (the router in ``float8_e4m3fn``) and two planted faults (half the
batch left out, a state left unchanged), which have to come out not correct.

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

from benchmarks.chipbench import flops_swa_moe, run  # noqa: E402
from benchmarks.chipbench.readers import moe, scopes, swa  # noqa: E402

CONFIG = "mellum2-12b-a2.5b-ep4-d4"
CELL = CONFIG + ".sft-8k-allparams"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("window_flash_fwd_roofline_pct", "global_flash_fwd_roofline_pct", "window_attn_time_pct.train",
               "global_attn_time_pct.train", "flash_band_tiles_pct")


def config():
    return run.load_json(BENCH, "configs", CONFIG + ".json")


def spec(name):
    return run.load_json(BENCH, "metrics", name + ".json")


def test_flops_match_the_hand_worked_figures():
    cfg = config()
    assert flops_swa_moe.matrix_params(cfg) == {
        "attention": 21_233_664, "router": 147_456, "expert": 6_193_152, "head": 56_623_104}
    assert flops_swa_moe.pairs_a_head(8192, 1024) == 7_864_320 and flops_swa_moe.pairs_a_head(8192, None) == 33_554_432
    assert [flops_swa_moe.attention_flops_per_token(cfg, 8192, i) for i in range(4)] == [15_728_640] * 3 + [67_108_864]
    need = flops_swa_moe.train_flops_per_token(cfg, 8192, 2.0)
    assert need["forward"] == 497_680_384 and need["backward"] == 995_360_768
    assert need["total"] == 1_493_041_152 and need["attention"] == 342_884_352 and need["experts"] == 297_271_296
    assert flops_swa_moe.flash_fwd_cost(2, 8192, cfg, 1024) == {"flops": 257_698_037_760, "bytes": 301_989_888}
    assert flops_swa_moe.flash_fwd_cost(2, 8192, cfg, None) == {"flops": 1_099_511_627_776, "bytes": 301_989_888}
    # the grouped products are counted by the accepted reader, from the keys it reads
    assert cfg["n_routed_experts"] == cfg["num_experts"] == len(cfg["held_experts"])


def test_the_configuration_states_its_cut():
    cfg, bench = config(), run.load_json(ROOT, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304}
    assert cfg["router_experts"] == 64 and cfg["held_experts"] == list(range(16)) and cfg["num_experts_per_tok"] == 8
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (2304, 32, 4, 128)
    assert (cfg["sliding_window"], cfg["moe_intermediate_size"]) == (1024, 896)
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
        "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert cfg["source"] == entry["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every published key as published, but the three that are reduced
        with open(catalog) as f:
            row = [json.loads(line) for line in f if '"Mellum2-12B-A2.5B-Instruct"' in line][0]
        assert row["source_url"] == cfg["source"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "missing") != v} == set(cfg["reduced"])
    # the cell is listed wherever a reader has something to read for it, and alone under the new metrics
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert set(NEW_METRICS) <= listed and not {"flash_fwd_roofline_pct", "mla_flash_fwd_roofline_pct"} & listed
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]


# the paths a device operation carries on the chip (tf_op) and the names of the streamed kernels' events
LAYER = "jit(train_step)/while/body/closed_call/"
META = {
    "%fusion.1": {"tf_op": LAYER + "jvp(layer0)/attn/dot_general:"},
    "%custom-call.2 flash_attention_window_fwd": {"tf_op": LAYER + "jvp(layer1)/attn/jit(forward)/flash_attention_window_fwd/pallas_call:"},
    "%custom-call.3 flash_attention_window_dkv": {"tf_op": LAYER + "transpose(jvp(layer2))/attn/jit(backward)/flash_attention_window_dkv/pallas_call:"},
    "%custom-call.4 flash_attention_causal_fwd": {"tf_op": LAYER + "jvp(layer3)/attn/jit(forward)/flash_attention_causal_fwd/pallas_call:"},
    "%fusion.5": {"tf_op": LAYER + "transpose(jvp(layer3))/jvp(layer3)/checkpoint/rematted_computation/attn/mul:"},
    "%fusion.6": {"tf_op": LAYER + "jvp(layer3)/mlp/router/dot_general:"},
    "%fusion.7": {"tf_op": "jit(train_step)/optimizer/sub:"},
}
SECONDS = {"%fusion.1": 0.05, "%custom-call.2 flash_attention_window_fwd": 0.03,
           "%custom-call.3 flash_attention_window_dkv": 0.07, "%custom-call.4 flash_attention_causal_fwd": 0.10,
           "%fusion.5": 0.02, "%fusion.6": 0.30, "%fusion.7": 0.43}
COUNTS = {k: 3.0 for k in SECONDS}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(scopes, "_metadata", lambda path, mtime: META)
    red = {"busy_s": 1.0, "window_s": 1.0, "op_seconds": SECONDS, "op_counts": COUNTS}
    return {"trace": red, "peaks": PEAKS, "config": config(), "microbatch": 2, "seq_len": 8192,
            "flash_grid_tiles": {f"flash_attention_{name} seq=8192 block=1024 window={window}": tiles for name, window, tiles in (
                ("window_fwd", 1024, [15, 36]), ("window_dq", 1024, [15, 36]), ("window_dkv", 1024, [15, 36]),
                ("causal_fwd", None, [36, 36]))}}


def test_readers_on_a_synthetic_trace(traced):
    here = __file__  # any file that exists: the metadata is the fixture's
    assert swa.attn_seconds_by_kind(SECONDS, META, config()["layer_types"]) == {
        "sliding_attention": pytest.approx(0.15), "full_attention": pytest.approx(0.12)}
    assert swa.attn_kind_time_pct(traced, spec("window_attn_time_pct.train"), xplane_path=here) == pytest.approx(15.0)
    assert swa.attn_kind_time_pct(traced, spec("global_attn_time_pct.train"), xplane_path=here) == pytest.approx(12.0)
    # 3 window calls of 257.7 GFLOP at 197 TFLOP/s = 1.308 ms each, over 0.03 s; 3 global calls of 1.0995 TFLOP over 0.10 s
    got = swa.flash_kind_fwd_roofline_pct(traced, spec("window_flash_fwd_roofline_pct"))
    assert got == pytest.approx(100.0 * 3 * (257_698_037_760 / 197e12) / 0.03)
    got = swa.flash_kind_fwd_roofline_pct(traced, spec("global_flash_fwd_roofline_pct"))
    assert got == pytest.approx(100.0 * 3 * (1_099_511_627_776 / 197e12) / 0.10)
    assert swa.flash_band_tiles_pct(traced, spec("flash_band_tiles_pct")) == pytest.approx(100.0 * 45 / 108)
    # the accepted flash_time_pct.train finds the streamed kernels by the name they share
    from benchmarks.chipbench.readers import train

    assert train.kernel_time_pct(traced, spec("flash_time_pct.train")) == pytest.approx(20.0)


def test_the_kind_hands_over_one_entry_a_kernel_and_shape(monkeypatch):
    """The program keys what it counted by kernel and band, so a second shape
    traced in the process (a rehearsal, a neighbour) is a second entry."""
    from benchmarks.chipbench import kind_sft_swa_moe
    from llm_fine_tune_distributed_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "GRID_TILES", {
        ("flash_attention_window_dkv", fa._band(8192, 1024, 1024)): (15, 36),
        ("flash_attention_window_dkv", fa._band(512, 128, 160)): (9, 10),
        ("flash_attention_causal_dkv", fa._band(8192, 1024, None)): (36, 36)})
    tiles = kind_sft_swa_moe.grid_tiles()
    assert tiles == {"flash_attention_window_dkv seq=8192 block=1024 window=1024": [15, 36],
                     "flash_attention_window_dkv seq=512 block=128 window=160": [9, 10],
                     "flash_attention_causal_dkv seq=8192 block=1024 window=None": [36, 36]}
    assert swa.flash_band_tiles_pct({"flash_grid_tiles": tiles}, spec("flash_band_tiles_pct")) == pytest.approx(100.0 * 24 / 46)


def test_readers_find_nothing_in_a_program_without_the_kernels_or_the_counter():
    """The parent's trace and sources: no streamed kernel, a configuration
    without ``layer_types``, no tile counter. Every reader of this file
    returns None and raises nothing."""
    pb = os.path.join(BENCH, "testdata", "scoped.xplane.pb")
    from benchmarks.chipbench import trace

    red = trace.reduce_planes(trace.read_planes(pb))
    dense = {"trace": red, "peaks": PEAKS, "config": {"head_dim": 128}, "microbatch": 2, "seq_len": 1024}
    mixed = dict(dense, config=config())  # the new configuration over a trace with none of its kernels
    for sources in (dense, mixed, {"trace": None, "config": config()}):
        for name in ("window_flash_fwd_roofline_pct", "global_flash_fwd_roofline_pct"):
            assert swa.flash_kind_fwd_roofline_pct(sources, spec(name)) is None
        assert swa.flash_band_tiles_pct(sources, spec("flash_band_tiles_pct")) is None
    for name in ("window_attn_time_pct.train", "global_attn_time_pct.train"):
        assert swa.attn_kind_time_pct(dense, spec(name), xplane_path=pb) is None
        assert swa.attn_kind_time_pct({"trace": None, "config": config()}, spec(name)) is None
    assert moe.step_counter(dense, spec("expert_pairs_per_token.train")) is None


def test_a_program_without_layers_of_several_kinds_refuses_the_cell(monkeypatch):
    """What the parent commit does with the cell: exit at once, by name."""
    import dataclasses

    from benchmarks.chipbench import kind_sft_swa_moe
    from llm_fine_tune_distributed_tpu import config as program_config

    fields = dataclasses.fields
    monkeypatch.setattr(dataclasses, "fields", lambda cls: [f for f in fields(cls) if f.name != "layer_types"]
                        if cls is program_config.ModelConfig else fields(cls))
    with pytest.raises(SystemExit, match="layer_types"):
        kind_sft_swa_moe.model_config(config())


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
    assert line["correct"] is False and line["failed"] == 0  # wrong, and every loss finite
    assert "first_grad_worst_leaf_rel_err" in {c["name"] for c in line["checks"] if not c["ok"]}


@pytest.mark.parametrize("fault, fails", [("half_batch", "loss_step1_abs_gap"),
                                          ("unchanged_state", "param_change_worst_leaf_gap")])
def test_a_planted_fault_is_not_correct(capsys, fault, fails):
    """What the two limits that no lower precision moves are held against,
    planted on the program's side only (``tools/fault.py``): half of each
    microbatch's rows left out of the loss, and a step that returns the
    parameters it was given (which reads 1.0)."""
    from benchmarks.chipbench.tools import fault as planted

    code = planted.main(["--fault", fault, "--workload", CELL, "--seed", "7", "--seconds", "2", "--trace", "0",
                         "--rehearse", "1"])
    line = last_line(capsys)
    assert code == 0 and line["correct"] is False and line["failed"] == 0
    failed = {c["name"]: c["value"] for c in line["checks"] if not c["ok"]}
    assert fails in failed
    if fault == "unchanged_state":
        assert failed == {fails: 1.0}  # nothing else moves: the first step's readings are a sound step's


@pytest.mark.parametrize("shape, kept", [((1, 4, 8), 16), ((2, 1, 8), 8)], ids=["rows", "microbatches"])
def test_half_batch_leaves_out_half_of_what_a_step_has(shape, kept):
    import numpy as np

    from benchmarks.chipbench.tools import fault as planted

    put = planted._half_batch(lambda self, batch: batch)
    batch = {"input_ids": np.zeros(shape, np.int32), "loss_mask": np.ones(shape, np.float32)}
    assert put(None, batch)["loss_mask"].sum() == kept and batch["loss_mask"].sum() == 2 * kept  # the caller's is whole
    with pytest.raises(SystemExit, match="two rows"):
        put(None, {"loss_mask": np.ones((1, 1, 8), np.float32)})


def test_the_traced_rehearsal_reports_the_counters(capsys):
    line = run_cell(capsys, 7, trace_on="1")
    assert line["correct"] is True
    assert 0.5 < line["metrics"]["expert_pairs_per_token.train"]["value"] < 1.6  # 4 of 16 chosen, 4 held: 1 expected
    assert line["metrics"]["expert_load_max_over_mean.train"]["value"] >= 1.0
    assert line["metrics"]["recompiles_in_window.train"]["value"] == 0
