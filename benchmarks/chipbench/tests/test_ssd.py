"""The cell of kind ``sft_ssd`` (``granite-4.0-h-micro.sft-8k-ssd-tied-last2``): the hand-worked figures of
``flops_ssd.py``, the configuration as published with nothing cut, the cell added by new files and appended entries
alone, the new metrics' readers on a synthetic trace and over a program that has nothing for them to read, the cell's
rehearsal on a CPU, and its control (the int8 frozen trunk) and the planted faults (a state that never forgets, the
norm before the gate, a residual multiplier of 1), which have to come out not correct.

``JAX_PLATFORMS=cpu python -m pytest benchmarks/chipbench/tests -q`` (not part of tier-1).
"""

import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import flops, flops_ssd, run  # noqa: E402
from benchmarks.chipbench.readers import gdn, scopes, ssd  # noqa: E402

CONFIG = "granite-4.0-h-micro"
CELL = CONFIG + ".sft-8k-ssd-tied-last2"
PARENT = "7a0ee9582fe9b552c2fee116dbb7421c4b61c9d5"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("ssd_scan_time_pct.train", "ssd_in_time_pct.train", "ssd_gate_norm_time_pct.train", "ssd_scan_fwd_roofline_pct",
               "ssd_kernel_calls_pct")


def config():
    return run.load_json(BENCH, "configs", CONFIG + ".json")


def spec(name):
    return run.load_json(BENCH, "metrics", name + ".json")


def test_flops_match_the_hand_worked_figures():
    cfg = config()
    assert flops_ssd.layer_matrix_params(cfg, "mamba") == 76_152_832 and flops_ssd.layer_matrix_params(cfg, "attention") == 60_817_408
    assert flops_ssd.scan_flops_per_token(cfg) == 64 * (5 * 64 * 128 + 2 * 64) == 2_629_632
    assert flops_ssd.mixer_flops_per_token(cfg, "mamba", 8192) == 2_664_448 and flops_ssd.mixer_flops_per_token(cfg, "attention", 8192) == 33_554_432
    need = flops_ssd.recipe_train_flops_per_token(cfg, {"unfreeze_last_n_layers": 2}, 8192)
    assert need == {"forward": 6_610_722_816, "backward": 7_556_513_792, "total": 14_167_236_608}
    cost = flops_ssd.ssd_scan_fwd_cost(1, 8192, cfg)
    assert cost == {"flops": 8192 * 2_629_632, "bytes": 8192 * (2 * (2 * 4096 + 256) + 4 * 64)}
    assert flops.roofline_seconds(cost, PEAKS)["bound"] == "memory"  # 0.109 ms of operations under 0.172 ms of bytes


def test_the_configuration_is_the_published_one_whole_and_the_cell_is_appended():
    cfg, bench = config(), run.load_json(ROOT, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert cfg["reduced"] == entry["reduced"] == [] and "nothing cut" in cfg["stands_for"]
    for said in ("3,191,396,096", "357,886,848", "2,833,509,248", "76,182,976", "60,821,504"):
        assert said in cfg["stands_for"], said
    assert {"head_dim", "initializer_range", "in_proj_columns", "xbc_cut", "conv", "conv_init", "dt", "decay", "A_log_D_dt_bias_init",
            "gate_norm_order", "skip", "mlp", "multipliers", "checkpoint_names"} <= set(cfg["assumed"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every published key as published
        with open(catalog) as f:
            row = [json.loads(line) for line in f if f'"{CONFIG}"' in line][0]
        assert row["source_url"] == cfg["source"] == entry["source"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "missing") != v} == set()
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "sft-8k-ssd-tied-last2"
    assert all(len(x["why"]) <= 200 for x in (cell, entry))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert {"train_mfu_pct", "train_peak_hbm_gib", "device_idle_pct.train", "frozen_fwd_time_pct.train",
            "frozen_bwd_time_pct.train", "tail_time_pct.train", "loss_head_time_pct.train", "optimizer_time_pct.train",
            "remat_time_pct.train", "scoped_time_pct.train", "recompiles_in_window.train", "linear_attn_time_pct.train",
            "flash_time_pct.train", "global_flash_fwd_roofline_pct", "attn_in_time_pct.train", "attn_in_fused_calls_pct",
            "train_step_trace_s", "setup_spanned_pct"} <= listed
    # a row of 8192 at 4 queries a kv head takes the STREAMED causal kernels, which ``global_flash_fwd_roofline_pct`` reads by
    # name; heads of 64 keep the XLA hand-over, whose time ``attn_in_time_pct.train`` reads under the scope it shares with
    # the fused pass and whose calls ``attn_in_fused_calls_pct`` reads as 0 of 4 (``ops/rope.CALLS``)
    assert not {"flash_fwd_roofline_pct", "gdn_scan_time_pct.train"} & listed
    new = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == list(NEW_METRICS)  # (appended in this order: held against the parent commit below)
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s" and m["unit"] == "%"
    assert [spec(n)["reader"] for n in NEW_METRICS] == [
        "readers.gdn.scope_share_pct"] * 3 + ["readers.ssd.ssd_scan_fwd_roofline_pct", "readers.ssd.ssd_kernel_calls_pct"]
    assert cell["name"] in [m for m in bench["end_to_end"] if m["name"] == "train_tokens_per_s"][0]["workloads"]
    mix = run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    assert (mix["microbatch"], mix["accum"], mix["seq_len"], mix["kind"]) == (1, 2, 8192, "sft_ssd")
    assert mix["control"] == {"recipe": {"frozen_compute": "int8"}}
    assert (mix["recipe"]["remat_policy"], mix["recipe"]["loss_chunk_size"], mix["recipe"]["unfreeze_last_n_layers"]) == ("full", 1024, 2)


def test_the_cell_is_added_by_new_files_and_entries_alone():
    """Against the parent commit: no file the benchmark had is edited or gone, and in BENCHMARK.json what was there is
    there still, entry for entry, with the new cell's name appended to the lists it joins."""
    parent = subprocess.run(["git", "rev-parse", "--verify", "-q", PARENT], cwd=ROOT, capture_output=True, text=True)
    if parent.returncode != 0:
        pytest.skip("not a git checkout that holds the parent commit")
    had = subprocess.run(["git", "ls-tree", "-r", "--name-only", PARENT, "benchmarks/chipbench"], cwd=ROOT,
                         capture_output=True, text=True, check=True).stdout.split()
    changed = subprocess.run(["git", "diff", "--name-only", PARENT, "--", "benchmarks/chipbench"], cwd=ROOT,
                             capture_output=True, text=True, check=True).stdout.split()
    assert not set(changed) & set(had), set(changed) & set(had)
    old = json.loads(subprocess.run(["git", "show", PARENT + ":BENCHMARK.json"], cwd=ROOT, capture_output=True, text=True, check=True).stdout)
    new = run.load_json(ROOT, "BENCHMARK.json")
    assert (new["command"], new["paths"], new["run_seconds"]) == (old["command"], old["paths"], old["run_seconds"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[group], new[group]):
            assert {k: v for k, v in now.items() if k != "workloads"} == {k: v for k, v in was.items() if k != "workloads"}
            assert now.get("workloads", [])[: len(was.get("workloads", []))] == was.get("workloads", [])
            # (what a list gained is cells the parent did not have: this PR's, and whatever later PRs append after it)
            assert not set(now.get("workloads", [])[len(was.get("workloads", [])):]) & {w["name"] for w in old["workloads"]}
    assert [c["name"] for c in new["configs"]][len(old["configs"])] == CONFIG and [w["name"] for w in new["workloads"]][len(old["workloads"])] == CELL
    assert [m["name"] for m in new["per_layer"]][len(old["per_layer"]):len(old["per_layer"]) + 5] == list(NEW_METRICS)


# the paths a device operation carries on the chip (tf_op)
LAYER = "jit(train_step)/while/body/closed_call/"
META = {
    "%fusion.1": {"tf_op": LAYER + "layer0/linear_attn/dot_general:"},
    "%fusion.2": {"tf_op": LAYER + "layer0/linear_attn/ssd_in/mul:"},
    "%fusion.3": {"tf_op": LAYER + "transpose(jvp(layer39))/linear_attn/ssd_gate_norm/mul:"},
    "%custom-call.4 ssd_scan_fwd": {"tf_op": LAYER + "layer0/linear_attn/ssd_scan/jit(ssd_scan_fwd)/ssd_scan_fwd/pallas_call:"},
    "%fusion.5": {"tf_op": LAYER + "layer0/linear_attn/ssd_scan/cumsum:"},
    "%custom-call.6 ssd_scan_bwd": {"tf_op": LAYER + "transpose(jvp(layer0))/linear_attn/ssd_scan/jit(ssd_scan_bwd)/ssd_scan_bwd/pallas_call:"},
    "%custom-call.7 ssd_scan_fwd": {"tf_op": LAYER + "transpose(jvp(layer0))/jvp(layer0)/checkpoint/rematted_computation/linear_attn/ssd_scan/jit(ssd_scan_fwd)/ssd_scan_fwd/pallas_call:"},
    "%fusion.8": {"tf_op": "jit(train_step)/optimizer/sub:"},
}
SECONDS = {"%fusion.1": 0.30, "%fusion.2": 0.02, "%fusion.3": 0.03, "%custom-call.4 ssd_scan_fwd": 0.05, "%fusion.5": 0.01,
           "%custom-call.6 ssd_scan_bwd": 0.13, "%custom-call.7 ssd_scan_fwd": 0.05, "%fusion.8": 0.41}
COUNTS = {k: 5.0 for k in SECONDS}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(scopes, "_metadata", lambda path, mtime: META)
    red = {"busy_s": 1.0, "window_s": 1.0, "op_seconds": SECONDS, "op_counts": COUNTS}
    return {"trace": red, "peaks": PEAKS, "config": config(), "microbatch": 1, "seq_len": 8192,
            "ssd_calls": {"[1, 8192, 64, 64, 128, 1]": [72, "chunked 128: kernels"]}}


def test_readers_on_a_synthetic_trace(traced):
    here = __file__  # any file that exists: the metadata is the fixture's
    assert gdn.scope_share_pct(traced, spec("ssd_in_time_pct.train"), xplane_path=here) == pytest.approx(2.0)
    assert gdn.scope_share_pct(traced, spec("ssd_gate_norm_time_pct.train"), xplane_path=here) == pytest.approx(3.0)
    assert gdn.scope_share_pct(traced, spec("ssd_scan_time_pct.train"), xplane_path=here) == pytest.approx(24.0)
    # every FORWARD operation under ssd_scan, the recomputed sweep among them: 0.05 + 0.01 + 0.05 s, 5 first and 5 recomputed calls
    bound = flops.roofline_seconds(flops_ssd.ssd_scan_fwd_cost(1, 8192, config()), PEAKS)["seconds"]
    assert ssd.ssd_scan_fwd_roofline_pct(traced, spec("ssd_scan_fwd_roofline_pct"), xplane_path=here) == pytest.approx(100 * 10 * bound / 0.11)
    assert ssd.ssd_kernel_calls_pct(traced, spec("ssd_kernel_calls_pct")) == 100.0
    traced["ssd_calls"]["[2, 128, 8, 16, 32, 1]"] = [24, "chunked 128: xla (a state of 32 is no multiple of 128)"]
    assert ssd.ssd_kernel_calls_pct(traced, spec("ssd_kernel_calls_pct")) == 75.0


def test_readers_find_nothing_in_a_program_without_the_scopes_and_counters():
    """The parent's trace and sources: no ``ssd_*`` scope, no counter of the scan, another configuration. The new
    metrics' readers return None and raise nothing."""
    pb = os.path.join(BENCH, "testdata", "scoped.xplane.pb")
    from benchmarks.chipbench import trace

    red = trace.reduce_planes(trace.read_planes(pb))
    for cfg in ({"head_dim": 128}, config()):
        sources = {"trace": red, "peaks": PEAKS, "config": cfg, "microbatch": 1, "seq_len": 1024}
        for name in NEW_METRICS[:3]:
            assert gdn.scope_share_pct(sources, spec(name), xplane_path=pb) is None
        assert ssd.ssd_scan_fwd_roofline_pct(sources, spec("ssd_scan_fwd_roofline_pct"), xplane_path=pb) is None
        assert ssd.ssd_kernel_calls_pct(sources, spec("ssd_kernel_calls_pct")) is None
    assert ssd.ssd_scan_fwd_roofline_pct({"trace": None, "config": config(), "peaks": PEAKS}, spec(NEW_METRICS[3])) is None


def test_a_program_without_the_mixer_refuses_the_cell(monkeypatch):
    """What the parent commit does with the cell once the benchmark's files are laid over it: exit at once, by name."""
    from benchmarks.chipbench import kind_sft_ssd
    from llm_fine_tune_distributed_tpu.models import configs

    monkeypatch.setattr(configs, "PRESETS", {k: v for k, v in configs.PRESETS.items() if "granite" not in k})
    with pytest.raises(SystemExit, match="no state-space layer"):
        kind_sft_ssd.model_config(config())


def test_the_cells_model_is_the_published_one_whole():
    from benchmarks.chipbench import kind_sft_ssd, weights_ssd
    from llm_fine_tune_distributed_tpu.models.configs import get_preset

    mc = kind_sft_ssd.model_config(config())
    assert mc == get_preset("granite_4_0_h_micro").replace(name="granitemoehybrid", head_dim=64) and mc.num_params == 3_191_396_096
    shapes = weights_ssd.leaf_shapes(config())
    assert sum(math.prod(s) for s in shapes.values()) == mc.num_params
    trained = sum(math.prod(s) for k, s in shapes.items() if k.startswith(("model/layers/38/", "model/layers/39/", "model/embed_tokens")))
    assert trained == 357_886_848 and mc.num_params - trained == 2_833_509_248


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def run_cell(capsys, seed, trace_on="0", entry=run, extra=()):
    code = entry.main([*extra, "--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", trace_on, "--rehearse", "1"])
    assert code == 0
    return last_line(capsys)


def test_the_cell_rehearses_and_its_control_is_not_correct(capsys):
    from benchmarks.chipbench.tools import control

    seed = 2**31 + 49
    line = run_cell(capsys, seed)
    assert line["correct"] is True and line["failed"] == 0 and "train_tokens_per_s" in line["metrics"]
    line = run_cell(capsys, seed, entry=control)
    assert line["correct"] is False and line["failed"] == 0  # wrong, and every loss finite
    # (the int8 trunk cuts the table's gradient off below the trunk: its lookup part is gone, and with it a third of the norm)
    assert {"grad_norm_rel_gap", "first_grad_worst_leaf_rel_err"} <= {c["name"] for c in line["checks"] if not c["ok"]}


@pytest.mark.parametrize("fault, fails", [
    ("no_decay", "first_grad_worst_leaf_rel_err"), ("norm_before_gate", "first_grad_worst_leaf_rel_err"),
    ("unit_residual", "grad_norm_rel_gap")])
def test_a_fault_planted_in_the_program_alone_is_not_correct(capsys, fault, fails):
    from benchmarks.chipbench.tools import fault_ssd
    from llm_fine_tune_distributed_tpu.models import transformer
    from llm_fine_tune_distributed_tpu.ops import ssd as ops_ssd

    was = ops_ssd.ssd_scan, ops_ssd.gated_norm, transformer._residual
    line = run_cell(capsys, 2**31 + 50, entry=fault_ssd, extra=("--fault", fault))
    assert line["correct"] is False and line["failed"] == 0
    assert fails in {c["name"] for c in line["checks"] if not c["ok"]}
    assert (ops_ssd.ssd_scan, ops_ssd.gated_norm, transformer._residual) == was  # the tool put back what it took


def test_the_traced_rehearsal_prints_every_metric_a_cpu_can_read(capsys):
    line = run_cell(capsys, 7, trace_on="1")
    assert line["correct"] is True
    bench = run.load_json(ROOT, "BENCHMARK.json")
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    from_a_device_trace = {m["name"] for m in listed if m["source"] == "device_trace"}
    missing = {m["name"] for m in listed} - set(line["metrics"])
    assert missing <= from_a_device_trace | {"train_mfu_pct", "train_peak_hbm_gib"}, missing  # (no kernel is built on a CPU)
    assert line["metrics"]["recompiles_in_window.train"]["value"] == 0
    assert line["metrics"]["ssd_kernel_calls_pct"]["value"] == 0.0  # a CPU: the XLA form, and CALLS says why
    assert line["metrics"]["attn_in_fused_calls_pct"]["value"] == 0.0  # heads of 16 here, of 64 on the chip: the XLA hand-over
