"""The cell of kind ``sft_eva`` (``evabyte-6.5b-d10.sft-32k-eva-last2``): the
hand-worked figures of ``flops_eva.py``, the configuration's stated cut, the
cell added by new files and appended entries alone, the new metrics' readers on
a synthetic trace and over a program that has nothing for them to read, the
cell's rehearsal on a CPU, and its control (the int8 frozen trunk) and the three
planted faults (no summaries, the own window's summaries too, the first head's
loss alone), which have to come out not correct.

``JAX_PLATFORMS=cpu python -m pytest benchmarks/chipbench/tests -q`` (not part
of tier-1).
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

from benchmarks.chipbench import flops, flops_eva, run  # noqa: E402
from benchmarks.chipbench.readers import eva, gdn, scopes  # noqa: E402

CONFIG = "evabyte-6.5b-d10"
CELL = CONFIG + ".sft-32k-eva-last2"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("eva_agg_time_pct.train", "eva_pool_time_pct.train", "eva_agg_fwd_roofline_pct", "eva_kernel_calls_pct",
               "eva_tiles_pct")


def config():
    return run.load_json(BENCH, "configs", CONFIG + ".json")


def spec(name):
    return run.load_json(BENCH, "metrics", name + ".json")


def test_flops_match_the_hand_worked_figures():
    cfg = config()
    assert flops_eva.pairs_a_head(32768, 2048, 16) == {"local": 33_570_816, "remote": 31_457_280}
    assert flops_eva.pairs_a_head(2048, 2048, 16) == {"local": 2048 * 2049 // 2, "remote": 0}
    assert flops_eva.mixer_flops_per_token(cfg, 32768) == 32_514_048 + 24_576
    assert flops.layer_matrix_params(cfg) == {"qkv": 50_331_648, "o": 16_777_216, "mlp": 135_266_304}
    need = flops_eva.recipe_train_flops_per_token(cfg, {"unfreeze_last_n_layers": 2}, 32768)
    assert need == {"forward": 4_393_861_120, "backward": 1_690_435_584, "total": 6_084_296_704}
    cost = flops_eva.eva_agg_fwd_cost(1, 32768, cfg)
    assert cost == {"flops": 32 * 512 * 65_028_096, "bytes": 32 * 128 * 2 * (4 * 32768 + 2 * 2048)}
    assert flops.roofline_seconds(cost, PEAKS)["bound"] == "compute"


def test_the_configuration_states_its_cut_and_the_cell_is_appended():
    cfg, bench = config(), run.load_json(ROOT, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 32}
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"], cfg["vocab_size"]) == (4096, 11008, 32, 320)
    assert (cfg["window_size"], cfg["chunk_size"], cfg["num_pred_heads"], cfg["num_hidden_layers"]) == (2048, 16, 8, 10)
    for said in ("three pipeline stages", "11, 11 and 10", "layers 22 to 31", "2,035,716,096", "415,268,864", "6,488,330,240"):
        assert said in cfg["stands_for"], said
    assert {"rope_placement", "pooling_weights", "pooled_key", "pooled_value", "summaries_seen", "phi_mu_init",
            "head_layout", "head_targets", "loss_weights", "head_dim"} <= set(cfg["assumed"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every published key as published, but the one that is reduced
        with open(catalog) as f:
            row = [json.loads(line) for line in f if '"EvaByte"' in line][0]
        assert row["source_url"] == cfg["source"] == entry["source"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "missing") != v} == {"num_hidden_layers"}
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "sft-32k-eva-last2"
    # (that they were APPENDED is held against the parent commit below, not by their place: the next PR appends too)
    assert all(len(x["why"]) <= 200 for x in (cell, entry))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert {"train_mfu_pct", "train_peak_hbm_gib", "device_idle_pct.train", "frozen_fwd_time_pct.train",
            "frozen_bwd_time_pct.train", "tail_time_pct.train", "loss_head_time_pct.train", "optimizer_time_pct.train",
            "remat_time_pct.train", "scoped_time_pct.train", "recompiles_in_window.train", "attn_in_time_pct.train",
            "attn_in_fused_calls_pct", "flash_time_pct.train", "train_step_trace_s", "setup_spanned_pct"} <= listed
    # (the causal kernel's roofline counts half the square of the whole row: not this cell's local source)
    assert not {"flash_fwd_roofline_pct", "moe_busy_pct.train", "gdn_scan_time_pct.train"} & listed
    new = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s" and m["unit"] == "%"
    assert [spec(n)["reader"] for n in NEW_METRICS] == [
        "readers.gdn.scope_share_pct", "readers.gdn.scope_share_pct",  # data files over the reader that is there
        "readers.eva.eva_agg_fwd_roofline_pct", "readers.eva.eva_kernel_calls_pct", "readers.eva.eva_tiles_pct"]
    assert cell["name"] in [m for m in bench["end_to_end"] if m["name"] == "train_tokens_per_s"][0]["workloads"]
    mix = run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    assert (mix["microbatch"], mix["accum"], mix["seq_len"], mix["kind"]) == (1, 1, 32_768, "sft_eva")
    assert mix["control"] == {"recipe": {"frozen_compute": "int8"}}
    assert (mix["recipe"]["remat_policy"], mix["recipe"]["loss_chunk_size"], mix["recipe"]["unfreeze_last_n_layers"]) == ("full", 1024, 2)


def test_the_cell_is_added_by_new_files_and_entries_alone():
    """Against the parent commit: no file the benchmark had is edited or gone, and in BENCHMARK.json what was there is
    there still, entry for entry, with the new cell's name appended to the lists it joins."""
    parent = subprocess.run(["git", "rev-parse", "--verify", "-q", "3b0b41ed1b083871ba17a9974ffdb1b026624d79"], cwd=ROOT,
                            capture_output=True, text=True)
    if parent.returncode != 0:
        pytest.skip("not a git checkout that holds the parent commit")
    had = subprocess.run(["git", "ls-tree", "-r", "--name-only", parent.stdout.strip(), "benchmarks/chipbench"], cwd=ROOT,
                         capture_output=True, text=True, check=True).stdout.split()
    changed = subprocess.run(["git", "diff", "--name-only", parent.stdout.strip(), "--", "benchmarks/chipbench"], cwd=ROOT,
                             capture_output=True, text=True, check=True).stdout.split()
    assert not set(changed) & set(had), set(changed) & set(had)
    old = json.loads(subprocess.run(["git", "show", parent.stdout.strip() + ":BENCHMARK.json"], cwd=ROOT, capture_output=True,
                                    text=True, check=True).stdout)
    new = run.load_json(ROOT, "BENCHMARK.json")
    assert (new["command"], new["paths"], new["run_seconds"]) == (old["command"], old["paths"], old["run_seconds"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[group], new[group]):
            assert {k: v for k, v in now.items() if k != "workloads"} == {k: v for k, v in was.items() if k != "workloads"}
            assert now.get("workloads", [])[: len(was.get("workloads", []))] == was.get("workloads", [])
            assert now.get("workloads", [])[len(was.get("workloads", [])):] in ([], [CELL])
    assert (len(new["configs"]), len(new["workloads"]), len(new["per_layer"])) == (
        len(old["configs"]) + 1, len(old["workloads"]) + 1, len(old["per_layer"]) + 5)


# the paths a device operation carries on the chip (tf_op)
LAYER = "jit(train_step)/while/body/closed_call/"
META = {
    "%fusion.1": {"tf_op": LAYER + "layer0/attn/dot_general:"},
    "%fusion.2": {"tf_op": LAYER + "layer0/attn/eva_pool/reduce_sum:"},
    "%fusion.3": {"tf_op": LAYER + "transpose(jvp(layer9))/attn/eva_pool/mul:"},
    "%custom-call.4 flash_attention_fwd": {"tf_op": LAYER + "layer0/attn/eva_agg/jit(forward)/flash_attention_fwd/pallas_call:"},
    "%custom-call.5 eva_remote_fwd": {"tf_op": LAYER + "layer0/attn/eva_agg/jit(forward)/eva_remote_fwd/pallas_call:"},
    "%custom-call.6 eva_remote_dkv": {"tf_op": LAYER + "transpose(jvp(layer9))/attn/eva_agg/jit(backward)/eva_remote_dkv/pallas_call:"},
    "%custom-call.7 flash_attention_fwd": {"tf_op": LAYER + "transpose(jvp(layer9))/jvp(layer9)/checkpoint/rematted_computation/attn/eva_agg/jit(forward)/flash_attention_fwd/pallas_call:"},
    "%fusion.8": {"tf_op": "jit(train_step)/optimizer/sub:"},
}
SECONDS = {"%fusion.1": 0.30, "%fusion.2": 0.01, "%fusion.3": 0.02, "%custom-call.4 flash_attention_fwd": 0.06,
           "%custom-call.5 eva_remote_fwd": 0.04, "%custom-call.6 eva_remote_dkv": 0.12,
           "%custom-call.7 flash_attention_fwd": 0.05, "%fusion.8": 0.40}
COUNTS = {k: 5.0 for k in SECONDS}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(scopes, "_metadata", lambda path, mtime: META)
    red = {"busy_s": 1.0, "window_s": 1.0, "op_seconds": SECONDS, "op_counts": COUNTS}
    return {"trace": red, "peaks": PEAKS, "config": config(), "microbatch": 1, "seq_len": 32768,
            "eva_calls": {"[1, 32, 32768, 128] window 2048 chunk 16": [12, "kernels"]},
            "eva_grid_tiles": {"flash_attention_fwd (32768, 2048, 16)": [16, 16], "eva_remote_fwd (32768, 2048, 16)": [240, 240]}}


def test_readers_on_a_synthetic_trace(traced):
    here = __file__  # any file that exists: the metadata is the fixture's
    assert gdn.scope_share_pct(traced, spec("eva_pool_time_pct.train"), xplane_path=here) == pytest.approx(3.0)
    assert gdn.scope_share_pct(traced, spec("eva_agg_time_pct.train"), xplane_path=here) == pytest.approx(27.0)
    # the forward calls under eva_agg: 5 calls, 0.06 + 0.04 s of forward time (neither backward nor recomputed)
    bound = flops.roofline_seconds(flops_eva.eva_agg_fwd_cost(1, 32768, config()), PEAKS)["seconds"]
    assert eva.eva_agg_fwd_roofline_pct(traced, spec("eva_agg_fwd_roofline_pct"), xplane_path=here) == pytest.approx(100 * 5 * bound / 0.10)
    assert eva.eva_kernel_calls_pct(traced, spec("eva_kernel_calls_pct")) == 100.0
    assert eva.eva_tiles_pct(traced, spec("eva_tiles_pct")) == 100.0
    traced["eva_calls"]["[2, 4, 160, 16] window 32 chunk 4"] = [4, "xla (backend is cpu)"]
    assert eva.eva_kernel_calls_pct(traced, spec("eva_kernel_calls_pct")) == 75.0


def test_readers_find_nothing_in_a_program_without_the_scopes_and_counters():
    """The parent's trace and sources: no ``eva_pool`` and no ``eva_agg`` scope, no counter of the operator, another
    configuration. The new metrics' readers return None and raise nothing."""
    pb = os.path.join(BENCH, "testdata", "scoped.xplane.pb")
    from benchmarks.chipbench import trace

    red = trace.reduce_planes(trace.read_planes(pb))
    for cfg in ({"head_dim": 128}, config()):
        sources = {"trace": red, "peaks": PEAKS, "config": cfg, "microbatch": 1, "seq_len": 1024}
        for name in NEW_METRICS[:2]:
            assert gdn.scope_share_pct(sources, spec(name), xplane_path=pb) is None
        assert eva.eva_agg_fwd_roofline_pct(sources, spec("eva_agg_fwd_roofline_pct"), xplane_path=pb) is None
        assert eva.eva_kernel_calls_pct(sources, spec("eva_kernel_calls_pct")) is None
        assert eva.eva_tiles_pct(sources, spec("eva_tiles_pct")) is None
    assert eva.eva_agg_fwd_roofline_pct({"trace": None, "config": config(), "peaks": PEAKS}, spec(NEW_METRICS[2])) is None


def test_a_program_without_the_mixer_refuses_the_cell(monkeypatch):
    """What the parent commit does with the cell once the benchmark's files are laid over it: exit at once, by name."""
    from benchmarks.chipbench import kind_sft_eva
    from llm_fine_tune_distributed_tpu.models import configs

    monkeypatch.setattr(configs, "PRESETS", {k: v for k, v in configs.PRESETS.items() if "evabyte" not in k})
    with pytest.raises(SystemExit, match="no EVA attention"):
        kind_sft_eva.model_config(config())


def test_the_cells_model_is_the_published_one_cut_in_depth():
    from benchmarks.chipbench import kind_sft_eva, weights_eva
    from llm_fine_tune_distributed_tpu.models.configs import get_preset

    mc = kind_sft_eva.model_config(config())
    assert mc == get_preset("evabyte_6_5b").replace(name="evabyte", num_layers=10, head_dim=128) and mc.num_params == 2_035_716_096
    shapes = weights_eva.leaf_shapes(config())
    assert sum(math.prod(s) for s in shapes.values()) == mc.num_params
    trained = sum(math.prod(s) for k, s in shapes.items() if k.startswith(("model/layers/8/", "model/layers/9/", "lm_head")))
    assert trained == 415_268_864


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def run_cell(capsys, seed, trace_on="0", entry=run, extra=()):
    code = entry.main([*extra, "--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", trace_on, "--rehearse", "1"])
    assert code == 0
    return last_line(capsys)


def test_the_cell_rehearses_and_its_control_is_not_correct(capsys):
    from benchmarks.chipbench.tools import control

    seed = 2**31 + 46
    line = run_cell(capsys, seed)
    assert line["correct"] is True and line["failed"] == 0 and "train_tokens_per_s" in line["metrics"]
    line = run_cell(capsys, seed, entry=control)
    assert line["correct"] is False and line["failed"] == 0  # wrong, and every loss finite
    assert {c["name"] for c in line["checks"] if not c["ok"]} == {"first_grad_worst_leaf_rel_err"}  # (int8 rounding: the error by leaf alone)


@pytest.mark.parametrize("fault, fails", [
    ("no_summaries", "first_grad_worst_leaf_rel_err"), ("own_window_summaries", "first_grad_worst_leaf_rel_err"),
    ("first_head_only", "param_change_worst_leaf_gap")])
def test_a_fault_planted_in_the_program_alone_is_not_correct(capsys, fault, fails):
    from benchmarks.chipbench.tools import fault_eva
    from llm_fine_tune_distributed_tpu.ops import eva_attention
    from llm_fine_tune_distributed_tpu.train import step

    line = run_cell(capsys, 2**31 + 47, entry=fault_eva, extra=("--fault", fault))
    assert line["correct"] is False and line["failed"] == 0
    assert fails in {c["name"] for c in line["checks"] if not c["ok"]}
    assert eva_attention._windows_seen(3) == 3 and step.heads_ahead.__module__ == step.__name__  # the tool put back what it took


def test_the_traced_rehearsal_prints_every_metric_a_cpu_can_read(capsys):
    line = run_cell(capsys, 7, trace_on="1")
    assert line["correct"] is True
    bench = run.load_json(ROOT, "BENCHMARK.json")
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    from_a_device_trace = {m["name"] for m in listed if m["source"] == "device_trace"}
    missing = {m["name"] for m in listed} - set(line["metrics"])
    assert missing <= from_a_device_trace | {"train_mfu_pct", "train_peak_hbm_gib", "eva_tiles_pct"}, missing  # (no kernel is built on a CPU)
    assert line["metrics"]["recompiles_in_window.train"]["value"] == 0
    assert line["metrics"]["eva_kernel_calls_pct"]["value"] == 0.0  # a CPU: the XLA form, and CALLS says why
