"""Tests of the yardstick itself, at a size a CPU test run can hold
(``--rehearse 1``: configs/tiny.json and each mix's ``rehearsal`` sizes):

- the hand-worked FLOP figures and the trace reduction (self-tests);
- the control of each kind of cell comes out NOT correct (the int8 frozen
  trunk for training, the int8 KV pool for serving), while the program's own
  path comes out correct;
- a run whose timed path is broken underneath (a train step that returns its
  state unchanged; a served token altered where it is produced) comes out
  ``correct: false`` through the rest of the harness;
- a configuration, a mix, a per-layer metric and a cell can be added as new
  files plus entries, with no edit to a file that is there;
- the serving mix's schedule (ramp, window, tail) and warm-up list.

The serving cell is parked (``parked/<cell>.json``; ``run.py`` finds it by
name): these tests are what runs it until a later benchmark PR admits it.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/chipbench/tests -q``
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import flops, run, trace, traffic  # noqa: E402

SFT, SERVE = "smollm3-3b.sft-1k-full", "smollm3-3b.chat-steady"


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def config(name):
    return run.load_json(BENCH, "configs", name + ".json")


def mix(name):
    return run.load_json(BENCH, "traffic", name + ".json")


# ------------------------------------------------------------- self-tests


def test_flops_match_the_hand_worked_figures():
    smol = flops.recipe_train_flops_per_token(config("smollm3-3b"), mix("sft-1k-full")["recipe"], 1024)
    assert smol == {"forward": 6_300_893_184, "backward": 7_289_700_352, "total": 13_590_593_536}
    mistral = flops.recipe_train_flops_per_token(config("mistral-7b-d16"), mix("sft-2k-full")["recipe"], 2048)
    assert mistral == {"forward": 7_509_901_312, "backward": 2_285_895_680, "total": 9_795_796_992}
    cost = flops.flash_fwd_cost(2, 1024, 16, 4, 128)
    assert cost == {"flops": 2 * 2 * 16 * 128 * 1024 * 1024, "bytes": 2 * 1024 * 128 * 40 * 2}
    peaks = run.load_json(BENCH, "peaks.json")["TPU v5 lite"]
    assert flops.roofline_seconds(cost, peaks)["bound"] == "compute"


def test_mfu_is_read_at_the_median_step_not_over_a_trace_write_out():
    from benchmarks.chipbench.readers import train

    sources = {"peaks": run.load_json(BENCH, "peaks.json")["TPU v5 lite"], "chips": 1,
               "flops_per_token": {"total": 13_590_593_536}, "microbatch": 2, "accum": 16, "seq_len": 1024,
               "step_ends_s": [3.133, 6.266, 9.399, 30.5]}  # call 16: the fourth step held the trace's write-out
    assert train.train_mfu_pct(sources, {}) == pytest.approx(72.15, abs=0.01)
    assert train.train_mfu_pct(dict(sources, step_ends_s=[]), {}) is None


def test_trace_reduction_on_hand_made_planes():
    # device: a while over [0,20) that holds fusions [0,10) and [12,18), then a kernel at
    # [40,50) ns -> busy 30; the while's self time is 4; the host span covers [0,60)
    planes = [
        ("/device:TPU:0", [("XLA Ops", [("%while.5 = (s32[]) while(...)", 0.0, 20.0),
                                        ("%fusion.1 = bf16[2] fusion(...)", 0.0, 10.0),
                                        ("%fusion.2 = bf16[2] fusion(...)", 12.0, 6.0),
                                        ("%flash_attention_fwd.3 = bf16[2] custom-call(...)", 40.0, 10.0)]),
                           ("Steps", [("step", 0.0, 1000.0)])]),
        ("/host:CPU", [("main", [("chipbench/train_step", 0.0, 60.0), ("other", 0.0, 999.0)])]),
    ]
    red = trace.reduce_planes(planes, chips=1)
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["window_s"] == pytest.approx(60e-9)
    ops = dict(map(tuple, red["device_ops"]))
    assert ops["fusion"] == pytest.approx(16e-9) and ops["while"] == pytest.approx(4e-9)
    assert red["idle_gaps"] == [["chipbench/train_step", pytest.approx(30e-9)]]
    assert trace.kernel_seconds(red, "flash_attention") == (pytest.approx(10e-9), 1)


def test_trace_reduction_on_the_recorded_trace():
    path = os.path.join(BENCH, "testdata", "small.xplane.pb")
    red = trace.reduce_planes(trace.read_planes(path), chips=1)
    expect = run.load_json(BENCH, "testdata", "small.expected.json")
    assert red["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["device_ops"][0][0] == expect["top_op"]
    assert any(name.startswith("chipbench/") for name, _ in red["idle_gaps"])


def test_serve_schedule_has_ramp_window_and_tail_and_every_seed_the_same_work():
    m = mix("chat-steady")
    a, b = (traffic.serve_schedule(m, 128256, seed, 30.0) for seed in (1, 2**31 + 5))
    arr = m["arrivals"]
    longest_answer_s = m["output_len"]["max"] * 0.107  # a decode tick took 107 ms on the chip (PERF.md)
    assert arr["ramp_s"] >= longest_answer_s and arr["tail_s"] >= longest_answer_s
    for sched in (a, b):
        assert [r["due"] for r in sched] == sorted(r["due"] for r in sched)
        assert {r["measured"] for r in sched if 0 <= r["due"] < 30.0} == {True}
        assert not any(r["measured"] for r in sched if r["due"] < 0 or r["due"] >= 30.0)
        assert min(r["due"] for r in sched) >= -arr["ramp_s"] and max(r["due"] for r in sched) > 30.0
        assert sum(r["measured"] for r in sched) == round(arr["rate_per_s"] * 30.0)
    # the arrangement is fixed by the mix: seeds differ by their token ids alone
    assert [(r["due"], len(r["prompt"]), r["max_new"]) for r in a] == [(r["due"], len(r["prompt"]), r["max_new"]) for r in b]
    assert any((x["prompt"][:8] != y["prompt"][:8]).any() for x, y in zip(a, b))


def test_warmup_sends_each_program_once():
    m = mix("chat-steady")
    eng = m["engine"]
    reqs = [(len(p), n) for p, n in traffic.warmup_requests(m, 128256)]
    assert len(reqs) == len(set(reqs))
    last = {((p - 1) % eng["prefill_chunk"]) // eng["prompt_bucket"] for p, _ in reqs}
    assert last >= set(range(eng["prefill_chunk"] // eng["prompt_bucket"]))  # every final-chunk pad bucket
    assert any(p > eng["prefill_chunk"] for p, _ in reqs)  # the whole-chunk program
    blocks = {(p + n - 2) // eng["block_len"] + 1 for p, n in reqs}  # blocks in use at the last decode tick
    assert blocks >= {1, 2, 3, 5, 9}  # one in every power-of-two bucket up to 16 blocks (3072 + 512 tokens)


# ------------------------------------------------- controls and broken paths


def run_cell(capsys, name, seed, seconds="2", entry=run):
    code = entry.main(["--workload", name, "--seed", str(seed), "--seconds", seconds, "--trace", "0", "--rehearse", "1"])
    assert code == 0
    return last_line(capsys)


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_sft_passes_and_its_control_fails(capsys, seed):
    from benchmarks.chipbench.tools import control

    assert run_cell(capsys, SFT, seed)["correct"] is True
    line = run_cell(capsys, SFT, seed, entry=control)
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert {"first_grad_worst_leaf_gap", "first_grad_worst_leaf_rel_err"} <= failed


def test_sft_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    from llm_fine_tune_distributed_tpu.train import step as step_mod

    real = step_mod.build_train_step

    def broken(*a, **kw):
        inner = real(*a, **kw)

        def train_step(state, batch):
            _, metrics = inner(state, batch)
            return state, metrics

        return train_step

    monkeypatch.setattr(step_mod, "build_train_step", broken)
    line = run_cell(capsys, SFT, 21)
    assert line["correct"] is False
    assert {c["name"] for c in line["checks"] if not c["ok"]} >= {"param_change_worst_leaf_gap"}


@pytest.mark.parametrize("seed", [31, 2**31 + 32, 33])
def test_serve_passes_and_its_control_fails(capsys, seed):
    from benchmarks.chipbench.tools import control

    assert run_cell(capsys, SERVE, seed, "4")["correct"] is True
    line = run_cell(capsys, SERVE, seed, "4", entry=control)
    assert line["correct"] is False
    assert "served_token_logit_gap_mean_sq" in {c["name"] for c in line["checks"] if not c["ok"]}


def test_serve_token_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch):
    from llm_fine_tune_distributed_tpu.infer import engine as engine_mod

    real = engine_mod.ContinuousBatchingEngine._emit_token

    def altered(self, slot, req, tok, from_decode=True):
        return real(self, slot, req, (tok + 1) % 512 if from_decode else tok, from_decode)

    monkeypatch.setattr(engine_mod.ContinuousBatchingEngine, "_emit_token", altered)
    line = run_cell(capsys, SERVE, 41, "4")
    assert line["correct"] is False


# ------------------------------------------------------ driven by data


def test_a_cell_is_added_by_new_files_and_entries_alone(tmp_path):
    """A throwaway configuration, mix, per-layer metric (with a reader module
    of its own) and cell: new files plus BENCHMARK.json entries, nothing else."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "outputs", "__pycache__", ".chipbench_trace", "_*", "tests", "data", "docs"))
    before = {p: p.read_bytes() for p in (root / "benchmarks/chipbench").rglob("*") if p.is_file()}
    bench_dir = root / "benchmarks/chipbench"
    cfg = config("tiny")
    cfg["num_hidden_layers"] = 8
    (bench_dir / "configs/throwaway.json").write_text(json.dumps(cfg))
    m = mix("sft-1k-full")
    m["rehearsal"]["seq_len"] = 64
    (bench_dir / "traffic/sft-throwaway.json").write_text(json.dumps(m))
    shutil.copy(bench_dir / f"limits/{SFT}.json", bench_dir / "limits/throwaway.sft-throwaway.json")
    (bench_dir / "readers/throwaway.py").write_text(
        "def steps_in_window(sources, spec):\n    return sources.get('steps')\n")
    (bench_dir / "metrics/steps_in_window.json").write_text(json.dumps(
        {"layer": "train step", "unit": "count", "moves": "train_tokens_per_s", "kinds": ["sft"],
         "source": "program_counter", "reader": "readers.throwaway.steps_in_window"}))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "throwaway", "source": "none", "file": "benchmarks/chipbench/configs/throwaway.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "throwaway.sft-throwaway", "config": "throwaway", "traffic": "sft-throwaway",
                           "chips": 1, "why": "test"})
    for metric in b["end_to_end"]:
        if metric["name"] == "train_tokens_per_s":
            metric["workloads"].append("throwaway.sft-throwaway")
    b["per_layer"].append({"name": "steps_in_window", "unit": "count", "better": "higher", "source": "program_counter",
                           "layer": "train step", "moves": "train_tokens_per_s", "workloads": ["throwaway.sft-throwaway"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    out = subprocess.run(
        [sys.executable, "benchmarks/chipbench/run.py", "--workload", "throwaway.sft-throwaway", "--seed", "5",
         "--seconds", "2", "--trace", "1", "--rehearse", "1"],
        cwd=root, capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"]["steps_in_window"]["value"] >= 1
    assert all(p.read_bytes() == data for p, data in before.items())


def test_no_accelerator_means_no_result(tmp_path):
    out = subprocess.run(
        [sys.executable, "benchmarks/chipbench/run.py", "--workload", SFT, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
