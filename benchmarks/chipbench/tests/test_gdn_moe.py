"""The cell of kind ``sft_gdn_moe``
(``qwen3-next-80b-a3b-ep16-d4.sft-8k-linear-allparams``): the hand-worked
figures of ``flops_gdn_moe.py``, the configuration's stated cut, the readers
of ``readers/gdn.py`` on a synthetic trace and over a program that has nothing
for them to read, the cell's rehearsal on a CPU, and its control (the router in
``float8_e5m2``, the rule's carried state in bfloat16) and two planted
faults (half the batch left out, a state left unchanged), which have to come
out not correct.

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

from benchmarks.chipbench import flops_gdn_moe, run  # noqa: E402
from benchmarks.chipbench.readers import gdn, scopes, swa  # noqa: E402

CONFIG = "qwen3-next-80b-a3b-ep16-d4"
CELL = CONFIG + ".sft-8k-linear-allparams"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("linear_attn_time_pct.train", "gdn_scan_time_pct.train", "gdn_scan_fwd_roofline_pct", "gdn_chunked_calls_pct")


def config():
    return run.load_json(BENCH, "configs", CONFIG + ".json")


def spec(name):
    return run.load_json(BENCH, "metrics", name + ".json")


def test_flops_match_the_hand_worked_figures():
    cfg = config()
    assert flops_gdn_moe.matrix_params(cfg) == {
        "linear_mixer": 33_685_504, "full_mixer": 27_262_976, "router": 1_048_576, "shared_expert": 3_147_776,
        "expert": 3_145_728, "head": 38_895_616}
    assert flops_gdn_moe.linear_layers(cfg) == 3
    assert flops_gdn_moe.rule_flops_per_token(cfg) == 3_145_728 and flops_gdn_moe.conv_flops_per_token(cfg) == 65_536
    assert flops_gdn_moe.attention_flops_per_token(cfg, 8192) == 67_108_864
    need = flops_gdn_moe.train_flops_per_token(cfg, 8192, 0.625)
    assert need["forward"] == 460_472_320 and need["backward"] == 920_944_640 and need["total"] == 1_381_416_960
    assert need["linear_layers"] == 635_240_448 and need["attention"] == 201_326_592 and need["experts"] == 47_185_920
    assert flops_gdn_moe.gdn_scan_fwd_cost(4, 8192, cfg) == {"flops": 103_079_215_104, "bytes": 813_694_976}
    # the full layer's kernel through the accepted reader's count: 16 heads of 256 over 2, half the square
    assert flops_gdn_moe.flash_fwd_cost(2, 8192, cfg, None) == {
        "flops": 2 * 16 * 4 * 256 * 8192 * 8192 // 2, "bytes": 2 * 8192 * (2 * 16 + 2 * 2) * 256 * 2}
    assert cfg["n_routed_experts"] == cfg["num_experts"] == len(cfg["held_experts"])  # what readers/moe.py reads


def test_the_configuration_states_its_cut():
    cfg, bench = config(), run.load_json(ROOT, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert cfg["router_experts"] == 512 and cfg["held_experts"] == list(range(32)) and cfg["num_experts_per_tok"] == 10
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 16, 2, 256)
    assert cfg["layer_types"][:8] == (["linear_attention"] * 3 + ["full_attention"]) * 2 and len(cfg["layer_types"]) == 48
    assert "192-chip" in cfg["stands_for"] and "625,667,136" in cfg["stands_for"]
    assert {"router_aux_loss", "mtp_layer", "A_log_and_dt_bias", "embed_std", "param_dtype"} <= set(cfg["assumed"])
    assert cfg["source"] == entry["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every published key as published, but the three that are reduced
        with open(catalog) as f:
            row = [json.loads(line) for line in f if '"Qwen3-Next-80B-A3B-Instruct"' in line][0]
        assert row["source_url"] == cfg["source"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "missing") != v} == set(cfg["reduced"])
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "sft-8k-linear-allparams"
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert set(NEW_METRICS) <= listed and "global_flash_fwd_roofline_pct" in listed and "train_mfu_pct" in listed
    assert not {"flash_fwd_roofline_pct", "mla_flash_fwd_roofline_pct", "window_flash_fwd_roofline_pct"} & listed
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
    mix = run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    assert mix["microbatch"] * mix["accum"] * mix["seq_len"] == 32_768 and mix["kind"] == "sft_gdn_moe"


# the paths a device operation carries on the chip (tf_op)
LAYER = "jit(train_step)/while/body/closed_call/"
META = {
    "%fusion.1": {"tf_op": LAYER + "jvp(layer0)/linear_attn/dot_general:"},
    "%fusion.2": {"tf_op": LAYER + "jvp(layer0)/linear_attn/gdn_conv/mul:"},
    "%fusion.3": {"tf_op": LAYER + "jvp(layer1)/linear_attn/gdn_scan/unit_lower_inverse/dot_general:"},
    "%while.4": {"tf_op": ""},  # the scan's own event carries no path on the chip (my chip run, PR 32)
    "%fusion.12": {"tf_op": LAYER + "jvp(layer1)/linear_attn/gdn_scan/jit(_solve_triangular)/triangular_solve:"},
    "%fusion.13": {"tf_op": LAYER + "jvp(layer0)/linear_attn/gdn_scan/exp:"},
    "%fusion.13.clone": {"tf_op": LAYER + "jvp(layer0)/linear_attn/gdn_scan/exp:"},
    "%broadcast.14": {"tf_op": LAYER + "jvp(layer0)/linear_attn/gdn_scan/eq:"},  # hoisted out of the accumulation loop
    "%fusion.5": {"tf_op": LAYER + "jvp(layer1)/linear_attn/gdn_scan/closed_call/while/body/closed_call/checkpoint/dot_general:"},
    "%while.6": {"tf_op": ""},
    "%fusion.7": {"tf_op": LAYER + "transpose(jvp(layer2))/jvp(layer2)/checkpoint/rematted_computation/linear_attn/gdn_scan/exp:"},
    "%fusion.8": {"tf_op": LAYER + "jvp(layer2)/linear_attn/gdn_gate_norm/mul:"},
    "%custom-call.9 flash_attention_causal_fwd": {"tf_op": LAYER + "jvp(layer3)/attn/jit(forward)/flash_attention_causal_fwd/pallas_call:"},
    "%fusion.10": {"tf_op": LAYER + "jvp(layer3)/attn/attn_gate/mul:"},
    "%fusion.11": {"tf_op": "jit(train_step)/optimizer/sub:"},
}
SECONDS = {"%fusion.1": 0.10, "%fusion.2": 0.02, "%fusion.3": 0.03, "%while.4": 0.01, "%fusion.5": 0.06, "%while.6": 0.09,
           "%fusion.7": 0.04, "%fusion.8": 0.05, "%custom-call.9 flash_attention_causal_fwd": 0.10, "%fusion.10": 0.01,
           "%fusion.11": 0.40, "%fusion.12": 0.0, "%fusion.13": 0.0, "%fusion.13.clone": 0.0, "%broadcast.14": 0.0}
# 6 calls of the rule a layer, 128 chunks a call; one operation runs once a step and not once a microbatch
COUNTS = {**{k: 6.0 for k in SECONDS}, "%fusion.5": 768.0, "%broadcast.14": 3.0}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(scopes, "_metadata", lambda path, mtime: META)
    red = {"busy_s": 1.0, "window_s": 1.0, "op_seconds": SECONDS, "op_counts": COUNTS}
    return {"trace": red, "peaks": PEAKS, "config": config(), "microbatch": 2, "seq_len": 8192,
            "gdn_calls": {"2 8192 16 32 128 128": [6, "chunked 64"], "2 160 2 4 16 16": [2, "token by token"]}}


def test_readers_on_a_synthetic_trace(traced):
    here = __file__  # any file that exists: the metadata is the fixture's
    assert gdn.scope_share_pct(traced, spec("linear_attn_time_pct.train"), xplane_path=here) == pytest.approx(30.0)
    assert gdn.scope_share_pct(traced, spec("gdn_scan_time_pct.train"), xplane_path=here) == pytest.approx(13.0)
    # forward under gdn_scan: 0.03 + 0.06 = 0.09 s (the whiles' own events carry no path) for 12 calls: layer 1's
    # two operations outside the loop ran 6 times each, layer 0's three 6, 6 and 3 times (the count most share);
    # the body's 768 events are not calls; a call of 2 rows reads and writes 406,847,488 bytes, which bind
    secs, calls = gdn.seconds_under(SECONDS, COUNTS, META, "gdn_scan", forward_only=True)
    assert secs == pytest.approx(0.09) and calls == 12.0
    got = gdn.gdn_scan_fwd_roofline_pct(traced, spec("gdn_scan_fwd_roofline_pct"), xplane_path=here)
    assert got == pytest.approx(100.0 * 12 * (406_847_488 / 819e9) / 0.09)
    assert gdn.gdn_chunked_calls_pct(traced, spec("gdn_chunked_calls_pct")) == pytest.approx(75.0)
    # the accepted readers that the cell joins find the full layer's kernel by the streamed kernel's name
    from benchmarks.chipbench.readers import train

    assert train.kernel_time_pct(traced, spec("flash_time_pct.train")) == pytest.approx(10.0)
    got = swa.flash_kind_fwd_roofline_pct(traced, spec("global_flash_fwd_roofline_pct"))
    assert got == pytest.approx(100.0 * 6 * (2 * 16 * 4 * 256 * 8192 * 8192 / 2 / 197e12) / 0.10)


def test_readers_find_nothing_in_a_program_without_the_scopes_or_the_counter():
    """The parent's trace and sources: no ``linear_attn`` scope, a
    configuration without linear layers, no counter of calls. Every reader of
    this file returns None and raises nothing."""
    pb = os.path.join(BENCH, "testdata", "scoped.xplane.pb")
    from benchmarks.chipbench import trace

    red = trace.reduce_planes(trace.read_planes(pb))
    dense = {"trace": red, "peaks": PEAKS, "config": {"head_dim": 128}, "microbatch": 2, "seq_len": 1024}
    mixed = dict(dense, config=config())  # the new configuration over a trace with none of its scopes
    for sources in (dense, mixed):
        for name in ("linear_attn_time_pct.train", "gdn_scan_time_pct.train"):
            assert gdn.scope_share_pct(sources, spec(name), xplane_path=pb) is None
        assert gdn.gdn_scan_fwd_roofline_pct(sources, spec("gdn_scan_fwd_roofline_pct"), xplane_path=pb) is None
        assert gdn.gdn_chunked_calls_pct(sources, spec("gdn_chunked_calls_pct")) is None
    none = {"trace": None, "config": config(), "peaks": PEAKS}
    assert gdn.scope_share_pct(none, spec("gdn_scan_time_pct.train")) is None
    assert gdn.gdn_scan_fwd_roofline_pct(none, spec("gdn_scan_fwd_roofline_pct")) is None


def test_a_program_without_linear_layers_refuses_the_cell(monkeypatch):
    """What the parent commit does with the cell: exit at once, by name."""
    import dataclasses

    from benchmarks.chipbench import kind_sft_gdn_moe
    from llm_fine_tune_distributed_tpu import config as program_config

    fields = dataclasses.fields
    monkeypatch.setattr(dataclasses, "fields", lambda cls: [f for f in fields(cls) if not f.name.startswith("linear_")]
                        if cls is program_config.ModelConfig else fields(cls))
    with pytest.raises(SystemExit, match="linear-attention"):
        kind_sft_gdn_moe.model_config(config())


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
        assert gated_delta.STATE_DTYPE == jnp.bfloat16 and program_moe.ROUTER_DTYPE == jnp.float8_e5m2
    finally:
        program_moe.ROUTER_DTYPE = gated_delta.STATE_DTYPE = jnp.float32  # the control set them for this process
    assert line["correct"] is False and line["failed"] == 0  # wrong, and every loss finite
    assert "first_grad_worst_leaf_rel_err" in {c["name"] for c in line["checks"] if not c["ok"]}


@pytest.mark.parametrize("fault, fails", [("half_batch", "loss_step1_abs_gap"),
                                          ("unchanged_state", "param_change_worst_leaf_gap")])
def test_a_planted_fault_is_not_correct(capsys, fault, fails):
    from benchmarks.chipbench.tools import fault as planted

    code = planted.main(["--fault", fault, "--workload", CELL, "--seed", "7", "--seconds", "2", "--trace", "0",
                         "--rehearse", "1"])
    line = last_line(capsys)
    assert code == 0 and line["correct"] is False and line["failed"] == 0
    failed = {c["name"]: c["value"] for c in line["checks"] if not c["ok"]}
    assert fails in failed
    if fault == "unchanged_state":
        assert failed == {fails: 1.0}


def test_the_traced_rehearsal_reports_the_counters(capsys):
    line = run_cell(capsys, 7, trace_on="1")
    assert line["correct"] is True
    assert line["metrics"]["gdn_chunked_calls_pct"]["value"] == 100.0
    assert 0.5 < line["metrics"]["expert_pairs_per_token.train"]["value"] < 1.6  # 4 of 16 chosen, 4 held: 1 expected
    assert line["metrics"]["recompiles_in_window.train"]["value"] == 0
