"""Step watchdog (runtime/watchdog.py): the single-process wedged-link
detector. Born from a real failure: a flagship run wedged PERMANENTLY
between two train steps with a healthy-looking process — nothing restarted
it, resume never ran."""

import time

import pytest

from llm_fine_tune_distributed_tpu.runtime.watchdog import StepWatchdog


def test_trips_after_silence_and_rearms():
    wd = StepWatchdog(timeout_s=0.3, action="warn", poll_s=0.05)
    try:
        wd.poke(1)
        time.sleep(0.15)
        assert wd.trips == 0  # still inside the window
        time.sleep(0.6)
        assert wd.trips >= 1  # silence tripped it
        first = wd.trips
        wd.poke(2)
        time.sleep(0.15)
        assert wd.trips == first  # poke re-armed
    finally:
        wd.stop()


def test_pause_suppresses_and_resume_rearms():
    wd = StepWatchdog(timeout_s=0.2, action="warn", poll_s=0.05)
    try:
        wd.pause()
        time.sleep(0.5)
        assert wd.trips == 0  # paused: long silence is fine (slow save/export)
        wd.resume()
        time.sleep(0.1)
        assert wd.trips == 0  # resume re-timestamps
        time.sleep(0.5)
        assert wd.trips >= 1  # armed again
    finally:
        wd.stop()


def test_abort_action_fires_hook_instead_of_exit():
    fired = []
    wd = StepWatchdog(
        timeout_s=0.2, action="abort", poll_s=0.05, on_trip=lambda: fired.append(1)
    )
    try:
        time.sleep(0.6)
        assert fired == [1]  # abort path taken exactly once (thread exits)
    finally:
        wd.stop()


def test_rejects_unknown_action():
    with pytest.raises(ValueError, match="warn|abort"):
        StepWatchdog(timeout_s=1, action="explode")


def test_trainer_runs_clean_with_watchdog(tmp_path):
    """A normal training run with the watchdog armed never false-trips —
    the loop pokes per step and pauses around sync saves."""
    from test_train_e2e import make_config  # noqa: F401
    import json

    import numpy as np

    from llm_fine_tune_distributed_tpu.data.convert import convert_jsonl_to_parquet
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    jsonl = tmp_path / "qa.jsonl"
    with open(jsonl, "w") as f:
        for i in range(48):
            f.write(json.dumps({
                "topic": "Knots", "question": f"q {i}?",
                "answer": f"a {i}: pull the loop.",
            }) + "\n")
    convert_jsonl_to_parquet(str(jsonl), str(tmp_path / "qa_dataset.parquet"), verbose=False)
    cfg = make_config(
        tmp_path / "out", tmp_path, "qa_dataset.parquet", epochs=1,
        save_steps=5, use_native_loader=False,
        watchdog_timeout_s=300.0, watchdog_action="abort",
    )
    trainer = SFTTrainer(cfg)
    summary = trainer.train()  # abort would os._exit(42) and fail the test
    assert np.isfinite(summary["final_train_loss"])


def test_start_paused_arms_on_first_poke():
    """Trainer usage: disarmed through resume fast-forward + first compile,
    armed from the first step's poke (r5 review finding)."""
    wd = StepWatchdog(timeout_s=0.2, action="warn", poll_s=0.05, start_paused=True)
    try:
        time.sleep(0.5)
        assert wd.trips == 0  # startup silence never trips
        wd.poke(1)
        time.sleep(0.5)
        assert wd.trips >= 1  # armed after the first poke
    finally:
        wd.stop()
