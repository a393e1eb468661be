"""Training control plane + train→serve lineage (ISSUE 14).

Fast tier: anomaly sentinels (non-finite hard sentinel, EWMA-band spike /
explosion detectors, publish-window gate), the TrainTelemetry boundary
hooks, the primary-host-only HTTP plane on an ephemeral port, crash-safe
atomic history flushes, the non-primary no-write guarantee, StepProfiler
and watchdog flight events, manifest lineage keys, and HotSwapManager's
generation→run_id lineage records over a real tiny engine.

Slow tier: a short CPU training run serving live /metrics +
/v1/train/status while stepping; an injected non-finite loss landing as a
flight event + anomaly counter and flipping the publish manifest's
``anomaly_clean`` (or suppressing the publish under
``publish_require_clean``); and the full train→publish→serve→deploy→
``GET /v1/lineage`` round trip.
"""

import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llm_fine_tune_distributed_tpu.observe.trainplane import (
    ANOMALY_KINDS,
    TRAIN_COUNTERS,
    AnomalySentinels,
    TrainControlPlane,
    TrainTelemetry,
    hparams_digest,
    new_run_id,
    trainer_exposition,
)

from tests.test_train_e2e import make_config, qa_parquet  # noqa: F401


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        body = r.read().decode()
        ctype = r.headers.get("Content-Type", "")
    return body, ctype


def _get_json(port, path):
    body, _ = _get(port, path)
    return json.loads(body)


# ------------------------------------------------------------- sentinels


def test_non_finite_fires_from_observation_one():
    s = AnomalySentinels()
    assert s.observe(1, loss=float("nan")) == ["non_finite"]
    assert s.observe(2, grad_norm=float("inf")) == ["non_finite"]
    snap = s.snapshot()
    assert snap["counts"]["non_finite"] == 2
    assert snap["last_step"]["non_finite"] == 2
    assert snap["last_anomaly_step"] == 2


def test_loss_spike_needs_warmup_then_fires():
    s = AnomalySentinels(band_sigma=6.0, warmup=8)
    rng = np.random.RandomState(0)
    for i in range(1, 21):
        assert s.observe(i, loss=1.0 + 0.01 * rng.randn()) == []
    assert s.observe(21, loss=100.0) == ["loss_spike"]
    # the anomalous value was NOT folded into the band: a normal value
    # right after is still normal, and a repeat spike still fires
    assert s.observe(22, loss=1.0) == []
    assert s.observe(23, loss=100.0) == ["loss_spike"]
    assert s.snapshot()["counts"]["loss_spike"] == 2


def test_wild_value_before_warmup_does_not_fire():
    # the first loss of a run IS wild (and the band is meaningless until
    # warmed) — it must seed the band, not fire it
    s = AnomalySentinels(warmup=8)
    assert s.observe(1, loss=50.0) == []
    assert s.snapshot()["total"] == 0


def test_grad_explosion_band():
    s = AnomalySentinels(warmup=4)
    for i in range(1, 6):
        assert s.observe(i, grad_norm=0.5) == []
    assert s.observe(6, grad_norm=500.0) == ["grad_explosion"]


def test_flat_warmup_does_not_make_noise_anomalous():
    # perfectly constant warmup -> zero variance; the std floor must keep
    # ordinary jitter from reading as a 6-sigma event
    s = AnomalySentinels(warmup=4)
    for i in range(1, 8):
        assert s.observe(i, loss=2.0) == []
    assert s.observe(8, loss=2.001) == []


def test_clean_since_is_the_publish_gate():
    s = AnomalySentinels()
    s.observe(10, loss=float("nan"))
    assert not s.clean_since(5)
    assert not s.clean_since(10)
    assert s.clean_since(11)


def test_band_sigma_must_be_positive():
    with pytest.raises(ValueError):
        AnomalySentinels(band_sigma=0.0)


# ------------------------------------------------------------- telemetry


def test_on_step_feeds_flight_status_and_eval_counter():
    t = TrainTelemetry(hparams={"lr": 1e-4})
    t.update(total_steps=100, epochs=2)
    assert t.on_step(5, {"loss": 1.5, "grad_norm": 0.3, "learning_rate": 1e-4}) == []
    t.on_step(10, {"loss": 1.4, "eval_loss": 1.3, "steps_per_second": 2.0})
    st = t.status()
    assert st["step"] == 10
    assert st["loss"] == 1.4
    assert st["counters"]["evals"] == 1
    assert st["eta_s"] == pytest.approx(45.0)
    kinds = [e["kind"] for e in t.recorder.events()]
    assert kinds.count("step") == 2
    assert "eval" in kinds


def test_anomaly_rides_flight_and_window_gate():
    t = TrainTelemetry(hparams={}, anomaly_window_steps=10)
    assert t.on_step(3, {"loss": float("nan")}) == ["non_finite"]
    assert [e for e in t.recorder.events() if e["kind"] == "anomaly"]
    assert not t.publish_clean(3)
    assert not t.publish_clean(12)  # step 3 still inside the 10-step window
    assert t.publish_clean(13)


def test_publish_notes_and_skip_counterpart():
    t = TrainTelemetry(hparams={})
    t.note_publish(8, clean=True, fingerprint="abc")
    t.note_publish(16, clean=False, skipped=True)
    st = t.status()
    assert st["counters"]["publishes"] == 1
    assert st["counters"]["publishes_skipped_dirty"] == 1
    assert st["publishes"][0]["anomaly_clean"] is True
    assert st["publishes"][1]["skipped"] is True
    kinds = [e["kind"] for e in t.recorder.events()]
    assert "publish" in kinds and "publish_skipped_dirty" in kinds


def test_hparams_digest_is_order_insensitive_and_discriminating():
    a = hparams_digest({"lr": 1e-4, "bs": 8})
    b = hparams_digest({"bs": 8, "lr": 1e-4})
    c = hparams_digest({"bs": 8, "lr": 2e-4})
    assert a == b != c
    assert len(a) == 16
    assert new_run_id() != new_run_id()


# ------------------------------------------------------------ exposition


def test_exposition_seeds_every_anomaly_kind():
    text = trainer_exposition(TrainTelemetry(hparams={}), memory={})
    for kind in ANOMALY_KINDS:
        assert f'training_anomalies_total{{kind="{kind}"}} 0' in text
    assert text.count("# TYPE training_anomalies_total counter") == 1


def test_exposition_counts_match_sentinels():
    t = TrainTelemetry(hparams={})
    t.on_step(1, {"loss": float("inf")})
    text = trainer_exposition(t, memory={})
    assert 'training_anomalies_total{kind="non_finite"} 1' in text


# ------------------------------------------------------------ HTTP plane


def test_control_plane_endpoints(tmp_path):
    t = TrainTelemetry(hparams={"x": 1})
    t.update(total_steps=20, epochs=1)
    t.on_step(4, {"loss": 2.0, "grad_norm": 0.1})
    plane = TrainControlPlane(t, 0)
    try:
        assert plane.start()
        assert plane.port > 0
        body, ctype = _get(plane.port, "/metrics")
        assert ctype.startswith("text/plain")
        assert "\ntraining_loss 2\n" in body
        st = _get_json(plane.port, "/v1/train/status")
        assert st["run_id"] == t.run_id
        assert st["step"] == 4
        fl = _get_json(plane.port, "/v1/train/flight?limit=1")
        assert len(fl["events"]) == 1
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(plane.port, "/v1/train/flight?limit=0")
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(plane.port, "/nope")
        assert e.value.code == 404
        # profiling disabled (no profile_dir): POST is a 404, not a crash
        req = urllib.request.Request(
            f"http://127.0.0.1:{plane.port}/v1/train/profile",
            data=b"{}", method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 404
    finally:
        plane.stop()
    # idempotent stop
    plane.stop()


def test_control_plane_noop_off_primary(monkeypatch):
    import llm_fine_tune_distributed_tpu.observe.trainplane as tp

    monkeypatch.setattr(tp, "is_primary_host", lambda: False)
    plane = TrainControlPlane(TrainTelemetry(hparams={}), 0)
    assert plane.start() is False
    assert plane._server is None
    plane.stop()


# --------------------------------------------- metric sinks / history


def test_non_primary_host_writes_nothing(tmp_path, monkeypatch):
    import llm_fine_tune_distributed_tpu.observe.metrics as metrics_mod

    monkeypatch.setattr(metrics_mod, "is_primary_host", lambda: False)
    ml = metrics_mod.MetricLogger(str(tmp_path), stdout=False)
    ml.log(1, 0.1, {"loss": 1.0})
    ml.save_history(str(tmp_path / "training_history.json"))
    ml.close()
    # history still accumulates in memory (every host computes it)...
    assert len(ml.history) == 1
    # ...but NOTHING hits disk off the primary host
    assert os.listdir(tmp_path) == []


def test_save_history_is_atomic_and_litter_free(tmp_path):
    from llm_fine_tune_distributed_tpu.observe.metrics import MetricLogger

    ml = MetricLogger(str(tmp_path), stdout=False)
    path = str(tmp_path / "training_history.json")
    ml.log(1, 0.1, {"loss": 2.0})
    ml.save_history(path)
    ml.log(2, 0.2, {"loss": 1.5})
    ml.save_history(path)  # boundary reflush: replace, never truncate+write
    with open(path) as f:
        hist = json.load(f)
    assert [h["step"] for h in hist] == [1, 2]
    assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]
    ml.close()


# ------------------------------------------- watchdog / profiler flights


def test_watchdog_records_trip_and_rearm_events():
    from llm_fine_tune_distributed_tpu.observe.tracing import FlightRecorder
    from llm_fine_tune_distributed_tpu.runtime.watchdog import StepWatchdog

    rec = FlightRecorder(64)
    wd = StepWatchdog(timeout_s=0.15, action="warn", poll_s=0.03, recorder=rec)
    try:
        wd.poke(1)
        deadline = 5.0
        import time as _time

        t0 = _time.monotonic()
        while wd.trips == 0 and _time.monotonic() - t0 < deadline:
            _time.sleep(0.02)
        assert wd.trips >= 1
        trips = [e for e in rec.events() if e["kind"] == "watchdog_trip"]
        assert trips and trips[0]["last_step"] == 1
        wd.pause()
        wd.poke(2)  # paused->armed boundary: exactly here a rearm lands
        rearms = [e for e in rec.events() if e["kind"] == "watchdog_rearm"]
        assert rearms and rearms[-1]["step"] == 2
        n = len(rearms)
        wd.poke(3)  # already armed: the hot-path poke records NOTHING
        assert len([e for e in rec.events() if e["kind"] == "watchdog_rearm"]) == n
    finally:
        wd.stop()


def test_step_profiler_flight_events(tmp_path, monkeypatch):
    from llm_fine_tune_distributed_tpu.observe.profiler import StepProfiler
    from llm_fine_tune_distributed_tpu.observe.tracing import FlightRecorder

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop", None)))
    rec = FlightRecorder(16)
    prof = StepProfiler(str(tmp_path), start_step=2, num_steps=2, recorder=rec)
    for step in (1, 2, 3, 4, 5):
        prof.step(step)
    prof.close()
    assert [c[0] for c in calls] == ["start", "stop"]
    kinds = [e["kind"] for e in rec.events()]
    assert kinds == ["profile_start", "profile_stop"]
    assert rec.events()[0]["step"] == 2
    assert rec.events()[1]["step"] == 4


def test_step_profiler_close_stops_midflight(tmp_path, monkeypatch):
    from llm_fine_tune_distributed_tpu.observe.profiler import StepProfiler
    from llm_fine_tune_distributed_tpu.observe.tracing import FlightRecorder

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append("stop"))
    rec = FlightRecorder(16)
    prof = StepProfiler(str(tmp_path), start_step=1, num_steps=100, recorder=rec)
    prof.step(1)
    prof.close()  # run ended inside the window: close must stop the trace
    assert calls == ["start", "stop"]
    assert [e["kind"] for e in rec.events()] == ["profile_start", "profile_stop"]


def test_profiler_disabled_without_dir():
    from llm_fine_tune_distributed_tpu.observe.profiler import (
        StepProfiler,
        device_memory_report,
    )

    prof = StepProfiler(None)
    prof.step(3)  # no-op, no trace machinery touched
    prof.close()
    report = device_memory_report()
    assert isinstance(report, dict)  # {} on CPU, per-device dicts on TPU


# -------------------------------------------------------- manifest lineage


def test_manifest_carries_lineage_stamps(tmp_path):
    from llm_fine_tune_distributed_tpu.train.publish import (
        CheckpointPublisher,
        load_manifest,
    )

    pub = CheckpointPublisher(str(tmp_path))
    trainable = {"a/kernel": np.ones((2, 2), np.float32)}
    path = pub.publish(
        5, trainable, frozen_fp={"b": np.zeros(2, np.float32)},
        metrics={"eval_loss": 1.25},
        run_id="runabc", hparams_digest="d1" * 8, anomaly_clean=False,
    )
    m = load_manifest(path)
    assert m["run_id"] == "runabc"
    assert m["hparams_digest"] == "d1" * 8
    assert m["anomaly_clean"] is False
    assert m["metrics"]["eval_loss"] == 1.25


def test_manifest_lineage_keys_stay_optional(tmp_path):
    from llm_fine_tune_distributed_tpu.train.publish import (
        CheckpointPublisher,
        load_manifest,
    )

    pub = CheckpointPublisher(str(tmp_path))
    path = pub.publish(
        1, {"a/kernel": np.ones((2, 2), np.float32)},
        frozen_fp={"b": np.zeros(2, np.float32)},
    )
    m = load_manifest(path)  # pre-lineage manifests must keep loading
    assert m is not None
    assert "run_id" not in m and "anomaly_clean" not in m


# --------------------------------------------------- serve-side lineage


@pytest.fixture(scope="module")
def generator():
    from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
    from llm_fine_tune_distributed_tpu.infer.generate import Generator
    from llm_fine_tune_distributed_tpu.models.configs import get_preset
    from llm_fine_tune_distributed_tpu.models.transformer import init_params

    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    return Generator(
        params, mc, ByteChatMLTokenizer(), compute_dtype=jnp.float32,
        eos_token_ids=[],
    )


def _split(generator, n_trainable=2):
    from llm_fine_tune_distributed_tpu.train.checkpoints import frozen_fingerprint
    from llm_fine_tune_distributed_tpu.utils.tree import flatten_dict

    flat = flatten_dict(generator.params)
    keys = sorted(k for k in flat if k.endswith("kernel"))[:n_trainable]
    trainable = {k: np.asarray(flat[k]) for k in keys}
    frozen = {k: v for k, v in flat.items() if k not in trainable}
    return trainable, frozen_fingerprint(frozen)


def test_lineage_maps_generation_to_run(generator, tmp_path):
    from llm_fine_tune_distributed_tpu.infer.deploy import (
        CheckpointWatcher,
        HotSwapManager,
    )
    from llm_fine_tune_distributed_tpu.infer.engine import ContinuousBatchingEngine
    from llm_fine_tune_distributed_tpu.train.publish import CheckpointPublisher

    engine = ContinuousBatchingEngine(
        generator, slots=4, buf_len=96, prompt_bucket=16,
        restart_backoff_s=0.01, restart_backoff_max_s=0.02,
    )
    trainable, frozen_fp = _split(generator)
    pub = CheckpointPublisher(str(tmp_path))
    pub.publish(
        3, trainable, frozen_fp=frozen_fp, metrics={"eval_loss": 0.9},
        run_id="run-lineage", hparams_digest="hp" * 8, anomaly_clean=True,
    )
    watcher = CheckpointWatcher(str(tmp_path), base_params=generator.params)
    mgr = HotSwapManager(engine, watcher)
    res = mgr.poll_once()
    assert res["run_id"] == "run-lineage"
    assert res["anomaly_clean"] is True

    lin = mgr.lineage()
    gen = str(res["weight_generation"])
    assert lin["resident_generation"] == res["weight_generation"]
    rec = lin["generations"][gen]
    assert rec["run_id"] == "run-lineage"
    assert rec["hparams_digest"] == "hp" * 8
    assert rec["step"] == 3
    assert rec["anomaly_clean"] is True
    assert rec["metrics"]["eval_loss"] == 0.9
    assert lin["history"][-1]["kind"] == "deploy"

    # a second publish displaces the first; the rollback then lands as its
    # own lineage record pointing back at the ORIGINAL run identity
    pub.publish(
        6, {k: v + 0.5 for k, v in trainable.items()}, frozen_fp=frozen_fp,
        metrics={"eval_loss": 0.8},
        run_id="run-lineage", hparams_digest="hp" * 8, anomaly_clean=True,
    )
    res2 = mgr.poll_once()
    assert res2["step"] == 6
    back = mgr.rollback()
    assert back["kind"] == "rollback"
    assert back["step"] == 3
    assert back["run_id"] == "run-lineage"
    lin = mgr.lineage()
    assert [r["kind"] for r in lin["history"]] == ["deploy", "deploy", "rollback"]
    assert lin["generations"][str(back["weight_generation"])]["step"] == 3


def test_lineage_without_manifest_is_recorded_unknown(generator, tmp_path):
    from llm_fine_tune_distributed_tpu.infer.deploy import (
        CheckpointWatcher,
        HotSwapManager,
    )
    from llm_fine_tune_distributed_tpu.infer.engine import ContinuousBatchingEngine
    from llm_fine_tune_distributed_tpu.train.publish import CheckpointPublisher

    engine = ContinuousBatchingEngine(
        generator, slots=4, buf_len=96, prompt_bucket=16,
        restart_backoff_s=0.01, restart_backoff_max_s=0.02,
    )
    trainable, frozen_fp = _split(generator)
    CheckpointPublisher(str(tmp_path)).publish(
        1, trainable, frozen_fp=frozen_fp,  # pre-lineage publish: no stamps
    )
    mgr = HotSwapManager(
        engine, CheckpointWatcher(str(tmp_path), base_params=generator.params)
    )
    res = mgr.poll_once()
    assert res["run_id"] is None
    rec = mgr.lineage()["generations"][str(res["weight_generation"])]
    assert rec["run_id"] is None and rec["anomaly_clean"] is None


# ----------------------------------------------------- trainer e2e (slow)


def _wait_plane(trainer, timeout=120.0):
    import time as _time

    t0 = _time.monotonic()
    while _time.monotonic() - t0 < timeout:
        plane = getattr(trainer, "train_plane", None)
        if plane is not None and plane.port > 0 and plane._server is not None:
            return plane
        _time.sleep(0.05)
    raise AssertionError("control plane never came up")


def test_startup_phases_ride_status_and_the_log(qa_parquet, tmp_path, capsys, monkeypatch):  # noqa: F811
    """Where the time from process start to the first optimizer step went:
    the trainer's start-up under spans (observe/xla.py), printed once the
    first step has finished and served as ``startup`` of /v1/train/status.
    The time before the recorder and the package's dear imports ride the
    same line (observe/startup.py, xla.importing): a trainer's process
    starts with both."""
    import importlib
    import sys

    from llm_fine_tune_distributed_tpu.observe import xla
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    # a recorder of this test's own: the test run's first mark_warm() froze the process's
    monkeypatch.setattr(xla, "_RECORDER", xla.SpanRecorder())
    # an import that lasts, with one below it, wrapped as train/__init__.py and train/checkpoints.py wrap theirs
    # (this process made those long ago)
    slow = tmp_path / "slow_to_import"
    slow.mkdir()
    (slow / "__init__.py").write_text(
        "import time\ntime.sleep(0.06)\nfrom llm_fine_tune_distributed_tpu.observe.xla import importing\n"
        "with importing('slow_to_import.below'):\n    from slow_to_import import below\n")
    (slow / "below.py").write_text("import time\ntime.sleep(0.06)\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    with xla.importing("slow_to_import"):
        importlib.import_module("slow_to_import")
    del sys.modules["slow_to_import"], sys.modules["slow_to_import.below"]
    data_dir, dataset_file = qa_parquet
    config = make_config(
        tmp_path / "out", data_dir, dataset_file, epochs=1, eval_steps=2, save_steps=100,
        system_prompt="Be brief.", use_native_loader=False,
    )
    trainer = SFTTrainer(config)
    assert trainer.telemetry.status()["startup"] is None  # no step yet
    trainer.train()
    startup = trainer.telemetry.status()["startup"]
    phases = startup["phases_s"]
    assert list(phases) == ["process/before_recorder", "import", "startup/data", "startup/weights", "startup/optimizer",
                            "startup/opt_state", "startup/first_step",
                            "train_step/load"]  # in order of start; no restore: nothing was resumed
    assert all(v >= 0.0 for v in phases.values())
    assert phases["train_step/load"] <= phases["startup/first_step"]  # the step's load is inside its first step
    assert phases["startup/opt_state"] <= phases["startup/optimizer"]  # the state's program is inside its builder's span
    assert phases["import"] >= 0.12  # the nested import's seconds once, inside its parent's
    nested = phases["startup/opt_state"] + phases["train_step/load"]
    assert startup["since_process_start_s"] >= sum(phases.values()) - nested
    assert {"cache_hits", "cache_misses", "compile_requests_use_cache"} <= set(startup)
    line = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("[train] start-up: ")]
    assert len(line) == 1 and "startup/weights" in line[0] and "train_step/load" in line[0]
    assert line[0].startswith("[train] start-up: process/before_recorder ") and "import/nested" not in line[0]
    assert line[0].index("startup/optimizer") < line[0].index("startup/opt_state") < line[0].index("startup/first_step")
    spans = trainer.compile_ledger.setup()["spans"]
    by_name = {s["name"]: s for s in spans}
    nested, = [s for s in spans if s.get("module") == "slow_to_import.below"]
    assert nested["name"] == "import/nested"  # recorded, and left out of the line
    assert by_name["startup/opt_state"]["parent"] == by_name["startup/optimizer"]["id"]
    # set-up ended at the warm boundary, with the eval programs' loads in it; the step's load says it was no AOT compile
    snap = trainer.compile_ledger.snapshot()
    assert snap["warmed"] and "setup" not in snap  # a scrape's snapshot stays at its totals
    assert trainer.compile_ledger.setup()["spans"][0]["end_ns"] is not None
    assert snap["programs"]["train_step"]["aot"] is False and "aot_error" not in snap["programs"]["train_step"]
    json.dumps(trainer.telemetry.status())  # the status stays JSON-ready


@pytest.mark.slow
def test_train_serves_live_plane_and_clean_lineage(qa_parquet, tmp_path):  # noqa: F811
    from llm_fine_tune_distributed_tpu.train.publish import (
        list_published,
        load_manifest,
    )
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    out = tmp_path / "out"
    publish_dir = str(tmp_path / "publish")
    config = make_config(
        out, data_dir, dataset_file,
        epochs=1, train_port=0, publish_dir=publish_dir,
    )
    trainer = SFTTrainer(config)
    box = {}

    def run():
        box["summary"] = trainer.train()

    th = threading.Thread(target=run)
    th.start()
    try:
        plane = _wait_plane(trainer)
        # live scrape WHILE stepping
        seen_step = 0
        import time as _time

        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 300 and th.is_alive():
            st = _get_json(plane.port, "/v1/train/status")
            seen_step = max(seen_step, int(st["step"]))
            if seen_step >= 2:
                break
            _time.sleep(0.2)
        assert seen_step >= 2, "never observed live progress over HTTP"
        body, ctype = _get(plane.port, "/metrics")
        assert ctype.startswith("text/plain")
        assert "# TYPE training_loss gauge" in body
        assert "training_step_seconds_bucket" in body
        assert 'training_anomalies_total{kind="non_finite"} 0' in body
        fl = _get_json(plane.port, "/v1/train/flight?limit=512")
        assert any(e["kind"] == "step" for e in fl["events"])
    finally:
        th.join(600)
    assert not th.is_alive()
    assert "summary" in box
    # the boundary flushes left a readable history even mid-run artifacts
    with open(out / "training_history.json") as f:
        assert json.load(f)
    # every publish of this healthy run is stamped clean with this run's id
    pubs = list_published(publish_dir)
    assert pubs, "no publish landed"
    for _, path in pubs:
        m = load_manifest(path)
        assert m["run_id"] == trainer.telemetry.run_id
        assert m["hparams_digest"] == trainer.telemetry.hparams_digest
        assert m["anomaly_clean"] is True


def _nan_at_step(trainer, bad_step):
    """Wrap the jitted train step so one step's loss comes back NaN —
    divergence injection without touching the model."""
    real = trainer.train_step
    holder = {"n": 0}

    def wrapped(state, batch):
        state, metrics = real(state, batch)
        holder["n"] += 1
        if holder["n"] == bad_step:
            metrics = dict(metrics)
            metrics["loss"] = jnp.float32(float("nan"))
        return state, metrics

    trainer.train_step = wrapped


@pytest.mark.slow
def test_injected_nan_flips_anomaly_clean(qa_parquet, tmp_path):  # noqa: F811
    from llm_fine_tune_distributed_tpu.train.publish import (
        list_published,
        load_manifest,
    )
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    publish_dir = str(tmp_path / "publish")
    config = make_config(
        tmp_path / "out", data_dir, dataset_file,
        epochs=1, save_steps=4, eval_steps=100, logging_steps=2,
        publish_dir=publish_dir, anomaly_window_steps=100,
    )
    trainer = SFTTrainer(config)
    _nan_at_step(trainer, 2)  # lands on a logging boundary (logging_steps=2)
    trainer.train()
    snap = trainer.telemetry.sentinels.snapshot()
    assert snap["counts"]["non_finite"] >= 1
    assert any(
        e["kind"] == "anomaly" and e["anomaly"] == "non_finite"
        for e in trainer.telemetry.recorder.events()
    )
    pubs = list_published(publish_dir)
    assert pubs
    assert load_manifest(pubs[0][1])["anomaly_clean"] is False


@pytest.mark.slow
def test_publish_require_clean_suppresses_dirty_publish(qa_parquet, tmp_path):  # noqa: F811
    from llm_fine_tune_distributed_tpu.train.publish import list_published
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer

    data_dir, dataset_file = qa_parquet
    publish_dir = str(tmp_path / "publish")
    config = make_config(
        tmp_path / "out", data_dir, dataset_file,
        epochs=1, save_steps=4, eval_steps=100, logging_steps=2,
        publish_dir=publish_dir, anomaly_window_steps=1000,
        publish_require_clean=True,
    )
    trainer = SFTTrainer(config)
    _nan_at_step(trainer, 2)
    trainer.train()
    assert list_published(publish_dir) == []
    st = trainer.telemetry.status()
    assert st["counters"]["publishes_skipped_dirty"] >= 1
    assert st["counters"]["publishes"] == 0


@pytest.mark.slow
def test_lineage_endpoint_after_train_and_deploy(qa_parquet, tmp_path):  # noqa: F811
    """The full loop: train+publish, boot a server watching the publish
    dir, deploy over HTTP, then GET /v1/lineage maps the resident weight
    generation back to the producing run."""
    from llm_fine_tune_distributed_tpu.train.publish import (
        list_published,
        load_manifest,
    )
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer
    from tests.test_server import _start_server

    data_dir, dataset_file = qa_parquet
    out = tmp_path / "out"
    publish_dir = str(tmp_path / "publish")
    config = make_config(
        out, data_dir, dataset_file,
        epochs=1, eval_steps=100, save_steps=100, publish_dir=publish_dir,
    )
    trainer = SFTTrainer(config)
    trainer.train()
    pubs = list_published(publish_dir)
    assert pubs
    manifest = load_manifest(pubs[-1][1])
    assert manifest["run_id"] == trainer.telemetry.run_id

    base = _start_server(
        str(out / "best_model"),
        publish_watch_dir=publish_dir,
        publish_poll_s=3600.0,  # deploy on demand via POST, not the poller
    )
    req = urllib.request.Request(f"{base}/v1/deploy", data=b"{}", method="POST")
    with urllib.request.urlopen(req, timeout=600) as r:
        dep = json.loads(r.read())
    assert dep.get("kind") == "deploy", dep
    assert dep["run_id"] == trainer.telemetry.run_id
    with urllib.request.urlopen(f"{base}/v1/lineage", timeout=30) as r:
        lin = json.loads(r.read())
    gen = str(lin["resident_generation"])
    rec = lin["generations"][gen]
    assert rec["run_id"] == trainer.telemetry.run_id
    assert rec["step"] == manifest["step"]
    assert rec["anomaly_clean"] is True
    assert rec["metrics"]
