"""HTTP serving (infer/server.py): healthz + /v1/generate against a tiny
model dir — the serving capability the reference only templates
(examples/openshift-deploy.yaml, SURVEY.md C21)."""

import json
import os
import socket
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.hf_io import save_hf_checkpoint
from llm_fine_tune_distributed_tpu.models.transformer import init_params


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    d = tmp_path_factory.mktemp("serve") / "best_model"
    save_hf_checkpoint(params, str(d))
    ByteChatMLTokenizer().save_pretrained(str(d))
    with open(d / "config.json", "w") as f:
        json.dump(
            {
                "model_type": mc.name,
                "vocab_size": mc.vocab_size,
                "hidden_size": mc.hidden_size,
                "intermediate_size": mc.intermediate_size,
                "num_hidden_layers": mc.num_layers,
                "num_attention_heads": mc.num_heads,
                "num_key_value_heads": mc.num_kv_heads,
                "rope_theta": mc.rope_theta,
                "max_position_embeddings": mc.max_position_embeddings,
                "rms_norm_eps": mc.rms_norm_eps,
                "tie_word_embeddings": mc.tie_word_embeddings,
                "no_rope_layers": list(mc.no_rope_layers),
            },
            f,
        )
    return str(d)


def _start_server(model_dir, timeout_s=120, **serve_kwargs):
    """Start serve() on a free port in a daemon thread; wait for /healthz."""
    from llm_fine_tune_distributed_tpu.infer.server import serve

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    t = threading.Thread(
        target=serve, args=(model_dir, "127.0.0.1", port),
        kwargs=serve_kwargs, daemon=True,
    )
    t.start()
    base = f"http://127.0.0.1:{port}"
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(f"{base}/healthz", timeout=2) as r:
                if r.status == 200:
                    return base
        except OSError:
            time.sleep(0.25)
    raise RuntimeError("server did not become healthy")


@pytest.fixture(scope="module")
def server(model_dir):
    return _start_server(model_dir)


def test_healthz(server):
    with urllib.request.urlopen(f"{server}/healthz") as r:
        assert r.read() == b"ok"


def test_generate(server):
    req = urllib.request.Request(
        f"{server}/v1/generate",
        data=json.dumps(
            {"question": "How many cups in a gallon?", "max_new_tokens": 8, "greedy": True}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        payload = json.loads(r.read())
    assert isinstance(payload["answer"], str)
    # the generated ids ride the response: what a client compares when the
    # tokenizer cannot decode them (byte tokenizer, ids >= 256)
    ids = payload["token_ids"]
    assert 1 <= len(ids) <= 8 and all(isinstance(t, int) for t in ids)


def test_generate_token_ids_identical_for_identical_greedy_requests(server):
    body = json.dumps(
        {"question": "Which way is north?", "max_new_tokens": 8, "greedy": True}
    ).encode()
    got = []
    for _ in range(2):
        req = urllib.request.Request(
            f"{server}/v1/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            got.append(json.loads(r.read())["token_ids"])
    assert got[0] and got[0] == got[1]


def test_bad_request(server):
    req = urllib.request.Request(
        f"{server}/v1/generate", data=b'{"nope": 1}',
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 400


def test_unknown_path_404(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(f"{server}/nope", timeout=10)
    assert e.value.code == 404


def test_profile_disabled_404(server):
    """Without --profile-dir the endpoint doesn't exist."""
    req = urllib.request.Request(f"{server}/v1/profile", data=b"{}")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 404


@pytest.mark.slow
def test_profile_capture_endpoint(model_dir, tmp_path):
    """POST /v1/profile starts a bounded jax.profiler capture: one at a
    time (409 while running), 400 on a bad duration, auto-stop frees the
    next capture into a FRESH subdirectory, and stopped captures leave a
    non-empty trace dir (the artifact tensorboard loads). slow: a second
    server startup plus real wall-clock captures; the ProfilerCapture
    unit tests cover the same semantics in tier-1."""
    base = _start_server(model_dir, profile_dir=str(tmp_path / "profiles"))

    def post(body):
        req = urllib.request.Request(
            f"{base}/v1/profile", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    first = post({"duration_s": 2.0})
    assert first["profiling"] is True
    trace_dir = first["trace_dir"]
    assert os.path.isdir(trace_dir)
    with pytest.raises(urllib.error.HTTPError) as e:
        post({"duration_s": 1.0})  # one capture at a time
    assert e.value.code == 409
    with pytest.raises(urllib.error.HTTPError) as e:
        post({"duration_s": -3})
    assert e.value.code == 400
    # the timer auto-stops the first capture; the next start then succeeds
    second = None
    deadline = time.time() + 60
    while second is None and time.time() < deadline:
        try:
            second = post({"duration_s": 0.2})
        except urllib.error.HTTPError as err:
            assert err.code == 409
            time.sleep(0.25)
    assert second is not None and second["trace_dir"] != trace_dir

    def has_files(d):
        return any(files for _, _, files in os.walk(d))

    deadline = time.time() + 60
    while time.time() < deadline and not (
        has_files(trace_dir) and has_files(second["trace_dir"])
    ):
        time.sleep(0.25)
    assert has_files(trace_dir) and has_files(second["trace_dir"])


@pytest.mark.slow
def test_concurrent_generate_batched(server):
    """Several simultaneous identical-config requests all succeed and agree
    (greedy + shared seed -> the batcher groups them; batched greedy rows
    are bit-identical to solo decode)."""
    def ask(q):
        req = urllib.request.Request(
            f"{server}/v1/generate",
            data=json.dumps(
                {"question": q, "max_new_tokens": 6, "greedy": True}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=180) as r:
            return json.loads(r.read())["answer"]

    questions = [f"question {i}?" for i in range(4)]
    answers = [None] * 4
    threads = [
        threading.Thread(target=lambda i=i: answers.__setitem__(i, ask(questions[i])))
        for i in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=200)
    assert all(isinstance(a, str) for a in answers), answers
    # same question solo must give the same greedy answer
    assert ask(questions[0]) == answers[0]


def test_serve_int8(model_dir):
    """--quantize int8 serving path answers requests."""
    base = _start_server(model_dir, quantize="int8")
    req = urllib.request.Request(
        f"{base}/v1/generate",
        data=json.dumps({"question": "q?", "max_new_tokens": 4, "greedy": True}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        assert isinstance(json.loads(r.read())["answer"], str)


@pytest.mark.slow
def test_speculative_request_field(server):
    """POST /v1/generate accepts "speculative": K for greedy AND sampled
    requests (sampled verification is rejection sampling, infer/generate.py)."""
    def post(body):
        req = urllib.request.Request(
            f"{server}/v1/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        return urllib.request.urlopen(req, timeout=120)

    with post(
        {"question": "water?", "max_new_tokens": 4, "greedy": True, "speculative": 4}
    ) as r:
        body = json.loads(r.read())
        assert isinstance(body["answer"], str)
        # acceptance-rate telemetry rides the response so clients can see
        # whether the speculation they asked for pays off
        assert 0.0 <= body["speculative"]["acceptance_rate"] <= 1.0
        assert body["speculative"]["sequential_forwards"] >= 1
    with post({"question": "water?", "max_new_tokens": 4, "speculative": 4}) as r:
        body = json.loads(r.read())
        assert isinstance(body["answer"], str)
        assert "speculative" in body
    # non-speculative requests carry no speculative block
    with post({"question": "water?", "max_new_tokens": 4, "greedy": True}) as r:
        assert "speculative" not in json.loads(r.read())


def test_stream_sse(server):
    """POST /v1/stream: SSE events with text deltas whose concatenation
    equals the non-streamed answer for the same greedy request."""
    body = {"question": "How many cups in a gallon?", "max_new_tokens": 8, "greedy": True}
    req = urllib.request.Request(
        f"{server}/v1/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        generated = json.loads(r.read())
    answer = generated["answer"]

    sreq = urllib.request.Request(
        f"{server}/v1/stream", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(sreq, timeout=120) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        raw = r.read().decode()
    events = [
        json.loads(line[len("data: "):])
        for line in raw.splitlines()
        if line.startswith("data: ")
    ]
    assert events and events[-1].get("done") is True
    text = "".join(e.get("delta", "") for e in events)
    # decode_reply strips; the streamed deltas carry the raw decode
    assert text.strip() == answer
    assert events[-1]["n_tokens"] >= 1
    # the stream's closing event carries the ids it streamed: the same
    # ids the non-streamed greedy request returned
    assert events[-1]["token_ids"] == generated["token_ids"]
    assert events[-1]["n_tokens"] == len(events[-1]["token_ids"])


def test_stream_bad_request(server):
    req = urllib.request.Request(
        f"{server}/v1/stream", data=b"{}",
        headers={"Content-Type": "application/json"},
    )
    try:
        urllib.request.urlopen(req, timeout=30)
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_stream_speculative_400_names_alternatives(server):
    """"speculative" on /v1/stream is a 400 when the server was started
    WITHOUT --speculative (the engine has no fused verify step compiled)
    and the error names the supported routes."""
    body = {"question": "q?", "max_new_tokens": 4, "greedy": True, "speculative": 4}
    req = urllib.request.Request(
        f"{server}/v1/stream", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        urllib.request.urlopen(req, timeout=30)
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400
        msg = json.loads(e.read())["error"]
        assert "POST /v1/generate" in msg and "/v1/stream" in msg


def test_stats_endpoint(server):
    """GET /v1/stats: live engine counters after serving one request."""
    req = urllib.request.Request(
        f"{server}/v1/generate",
        data=json.dumps({"question": "q?", "max_new_tokens": 4, "greedy": True}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        r.read()
    with urllib.request.urlopen(f"{server}/v1/stats", timeout=30) as r:
        stats = json.loads(r.read())
    assert stats["engine"] == "continuous"
    assert stats["tokens_served"] >= 1
    assert stats["requests_completed"] >= 1
    assert stats["queue_depth"] == 0
    assert 0.0 <= stats["slot_occupancy"] <= 1.0


def test_stats_endpoint_window_engine(model_dir):
    """--engine window still serves /v1/stats (reduced: queue depth only)."""
    base = _start_server(model_dir, engine_kind="window")
    with urllib.request.urlopen(f"{base}/v1/stats", timeout=30) as r:
        stats = json.loads(r.read())
    assert stats["engine"] == "window"
    assert "queue_depth" in stats
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        text = r.read().decode()
    assert 'serving_info{engine="window"} 1' in text  # reduced, still valid


def test_stats_histograms_and_memory(server):
    """/v1/stats carries latency-percentile summaries and the HBM report."""
    with urllib.request.urlopen(f"{server}/v1/stats", timeout=30) as r:
        stats = json.loads(r.read())
    hists = stats["histograms"]
    for name in ("ttft_s", "inter_token_s", "queue_wait_s", "decode_tick_s"):
        assert {"count", "mean", "p50", "p90", "p99"} <= set(hists[name])
    assert stats["uptime_s"] > 0.0
    assert stats["tokens_per_s_1m"] >= 0.0
    assert isinstance(stats["device_memory"], dict)  # {} on CPU
    # residency breakdown (engine.memory_breakdown) — platform-independent
    report = stats["device_memory_report"]
    assert set(report) == {
        "weight_bytes", "kv_pool_bytes", "kv_scale_bytes",
        "bytes_saved_vs_bf16",
    }
    assert report["weight_bytes"] > 0
    assert report["bytes_saved_vs_bf16"] == 0  # unquantized server


def test_metrics_endpoint_prometheus(server):
    """GET /metrics: Prometheus text exposition with the latency histograms
    after at least one request has been served."""
    req = urllib.request.Request(
        f"{server}/v1/generate",
        data=json.dumps({"question": "q?", "max_new_tokens": 4, "greedy": True}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        r.read()
    with urllib.request.urlopen(f"{server}/metrics", timeout=30) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        assert "version=0.0.4" in r.headers["Content-Type"]
        text = r.read().decode()
    assert "# TYPE serving_tokens_served_total counter" in text
    assert "# TYPE serving_ttft_seconds histogram" in text
    assert "# TYPE serving_inter_token_seconds histogram" in text
    count_lines = [
        line for line in text.splitlines()
        if line.startswith("serving_ttft_seconds_count")
    ]
    assert count_lines and int(count_lines[0].split()[-1]) >= 1


def test_generate_with_trace(server):
    """'trace': true -> the response carries the request's lifecycle span
    timeline (received -> ... -> completed, nondecreasing offsets)."""
    req = urllib.request.Request(
        f"{server}/v1/generate",
        data=json.dumps({
            "question": "q?", "max_new_tokens": 4, "greedy": True,
            "trace": True,
        }).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        payload = json.loads(r.read())
    trace = payload["trace"]
    spans = [e["span"] for e in trace["events"]]
    for expected in ("received", "queued", "admitted", "first_token", "completed"):
        assert expected in spans, spans
    offsets = [e["t_s"] for e in trace["events"]]
    assert offsets == sorted(offsets)
    assert trace["total_s"] >= 0.0
    # without the flag the response stays lean
    lean = urllib.request.Request(
        f"{server}/v1/generate",
        data=json.dumps({"question": "q?", "max_new_tokens": 4, "greedy": True}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(lean, timeout=120) as r:
        assert "trace" not in json.loads(r.read())


def test_slo_endpoint(server):
    """GET /v1/slo: the burn-rate report over the four pinned objectives,
    each with a fast and slow window."""
    with urllib.request.urlopen(f"{server}/v1/slo", timeout=30) as r:
        report = json.loads(r.read())
    assert report["engine"] == "continuous"
    assert report["compliant"] in (True, False)
    assert set(report["objectives"]) == {
        "ttft_p99", "inter_token_p99", "error_rate", "availability",
    }
    for obj in report["objectives"].values():
        assert set(obj["windows"]) == {"fast", "slow"}
        for w in obj["windows"].values():
            assert w["burn_rate"] >= 0.0
            assert 0.0 <= w["bad_fraction"] <= 1.0


def test_history_endpoint_series_and_errors(server):
    """GET /v1/history?metric=&window=: counter series carry per-sample
    deltas, gauges don't; bad queries are 400s naming the problem."""
    url = f"{server}/v1/history?metric=queue_depth&window=60"
    with urllib.request.urlopen(url, timeout=30) as r:
        series = json.loads(r.read())
    assert series["metric"] == "queue_depth"
    assert series["kind"] == "gauge"
    assert series["window_s"] == 60.0
    assert isinstance(series["samples"], list)
    url = f"{server}/v1/history?metric=tokens_served"
    with urllib.request.urlopen(url, timeout=30) as r:
        series = json.loads(r.read())
    assert series["kind"] == "counter"
    assert series["window_s"] is None
    for point in series["samples"]:
        assert {"age_s", "value", "delta"} <= set(point)
    for bad in (
        "/v1/history",  # missing ?metric
        "/v1/history?metric=not_a_metric",
        "/v1/history?metric=queue_depth&window=-5",
        "/v1/history?metric=queue_depth&window=abc",
    ):
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{server}{bad}", timeout=30)
        assert e.value.code == 400


def test_flight_endpoint(server):
    """GET /v1/flight: the live flight-recorder ring — admissions from
    served requests appear, ?limit= truncates, limit<=0 is a 400."""
    req = urllib.request.Request(
        f"{server}/v1/generate",
        data=json.dumps(
            {"question": "q?", "max_new_tokens": 4, "greedy": True}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        r.read()
    with urllib.request.urlopen(f"{server}/v1/flight", timeout=30) as r:
        events = json.loads(r.read())["events"]
    assert events and all("kind" in e and "t_s" in e for e in events)
    with urllib.request.urlopen(f"{server}/v1/flight?limit=2", timeout=30) as r:
        assert len(json.loads(r.read())["events"]) <= 2
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(f"{server}/v1/flight?limit=0", timeout=30)
    assert e.value.code == 400


def test_slo_history_flight_404_on_window_engine(model_dir):
    """The window engine has no metric ring / flight recorder; the SLO
    surfaces answer 404, not 500."""
    base = _start_server(model_dir, engine_kind="window")
    for path in ("/v1/slo", "/v1/history?metric=queue_depth", "/v1/flight"):
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{base}{path}", timeout=30)
        assert e.value.code == 404


# ------------------------------------------------- engine-level speculation


def test_speculative_flag_validation_at_startup():
    """Bad speculation flag combinations fail AT STARTUP with a clear
    message (parity with infer/cli.py), before the model even loads — so
    the model_dir can be bogus here and the check still runs."""
    from llm_fine_tune_distributed_tpu.infer.server import serve

    with pytest.raises(ValueError, match="--draft-dir requires --speculative"):
        serve("/nonexistent", draft_dir="/also/nonexistent")
    with pytest.raises(ValueError, match="window engine"):
        serve("/nonexistent", speculative_k=4, engine_kind="window")


@pytest.fixture(scope="module")
def spec_server(model_dir):
    """A continuous engine started with --speculative 4: speculative
    requests (streaming included) ride the fused slot batch."""
    return _start_server(model_dir, speculative_k=4, slots=4)


def test_speculative_server_generate_reports_draft_counts(spec_server):
    """On a --speculative server, /v1/generate speculation rides the slot
    engine and the response carries the request's OWN draft counts."""
    body = {
        "question": "water water water water?", "max_new_tokens": 12,
        "greedy": True, "speculative": 4,
    }
    req = urllib.request.Request(
        f"{spec_server}/v1/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        payload = json.loads(r.read())
    assert isinstance(payload["answer"], str)
    spec = payload["speculative"]
    assert 0.0 <= spec["acceptance_rate"] <= 1.0
    assert spec["draft_tokens_proposed"] >= spec["draft_tokens_accepted"] >= 0
    # slot engines have no whole-batch sequential-forward count
    assert "sequential_forwards" not in spec


def test_speculative_server_stream_accepts_k(spec_server):
    """/v1/stream accepts 'speculative': K on a --speculative engine, and
    the streamed deltas concatenate to the non-streamed greedy answer."""
    body = {
        "question": "water water water water?", "max_new_tokens": 12,
        "greedy": True, "speculative": 4,
    }
    req = urllib.request.Request(
        f"{spec_server}/v1/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        answer = json.loads(r.read())["answer"]
    sreq = urllib.request.Request(
        f"{spec_server}/v1/stream", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(sreq, timeout=120) as r:
        assert r.status == 200
        raw = r.read().decode()
    events = [
        json.loads(line[len("data: "):])
        for line in raw.splitlines()
        if line.startswith("data: ")
    ]
    assert events and events[-1].get("done") is True
    text = "".join(e.get("delta", "") for e in events)
    assert text.strip() == answer


def test_speculative_server_stats_counters(spec_server):
    """GET /v1/stats surfaces the draft counters + derived acceptance rate
    after speculative traffic has been served."""
    with urllib.request.urlopen(f"{spec_server}/v1/stats", timeout=30) as r:
        stats = json.loads(r.read())
    assert stats["draft_tokens_proposed"] >= 1
    assert 0 <= stats["draft_tokens_accepted"] <= stats["draft_tokens_proposed"]
    assert 0.0 <= stats["draft_acceptance_rate"] <= 1.0
    assert stats["mean_tokens_per_step"] > 0.0


# ------------------------------------------------- self-healing + drain


def _start_controlled(model_dir, **serve_kwargs):
    """_start_server variant returning (base, serve_thread, control): the
    control dict carries the drain entry points, since a signal handler
    can only be installed on the main thread (not a test worker)."""
    from llm_fine_tune_distributed_tpu.infer.server import serve

    control = {}
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    t = threading.Thread(
        target=serve, args=(model_dir, "127.0.0.1", port),
        kwargs={"control": control, **serve_kwargs}, daemon=True,
    )
    t.start()
    base = f"http://127.0.0.1:{port}"
    deadline = time.time() + 120
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(f"{base}/healthz", timeout=2) as r:
                if r.status == 200:
                    return base, t, control
        except OSError:
            time.sleep(0.25)
    raise RuntimeError("server did not become healthy")


def _post(base, path, body, timeout=120):
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def test_drain_finishes_in_flight_and_exits(model_dir):
    """The SIGTERM path: drain flips /healthz to 503 draining, sheds new
    admissions with 503 + Retry-After, lets the in-flight request finish,
    and returns from serve() (process exit 0) within the drain timeout."""
    base, serve_thread, control = _start_controlled(
        model_dir, drain_timeout_s=60.0
    )
    answers = []
    inflight = threading.Thread(
        target=lambda: answers.append(json.loads(_post(
            base, "/v1/generate",
            {"question": "q?", "max_new_tokens": 48, "greedy": True},
        ).read())["answer"])
    )
    inflight.start()
    time.sleep(0.3)  # let it admit
    control["begin_drain"]()  # what the SIGTERM handler calls

    with pytest.raises(urllib.error.HTTPError) as he:
        urllib.request.urlopen(f"{base}/healthz", timeout=10)
    assert he.value.code == 503
    assert json.loads(he.value.read())["status"] == "draining"
    assert int(he.value.headers["Retry-After"]) >= 1

    with pytest.raises(urllib.error.HTTPError) as pe:
        _post(base, "/v1/generate",
              {"question": "late?", "max_new_tokens": 4, "greedy": True},
              timeout=30)
    assert pe.value.code == 503
    assert json.loads(pe.value.read())["error"]["kind"] == "draining"
    assert int(pe.value.headers["Retry-After"]) >= 1

    inflight.join(timeout=180)
    assert answers and isinstance(answers[0], str)  # in-flight unharmed
    serve_thread.join(timeout=120)
    assert not serve_thread.is_alive()  # serve() returned -> clean exit 0


def test_queue_overflow_maps_to_429(model_dir):
    """Admission-queue overflow surfaces as HTTP 429 with a finite integer
    Retry-After header and a structured queue_overflow body."""
    base, _, control = _start_controlled(
        model_dir, slots=1, max_queue_depth=1
    )
    body = {"question": "q?", "max_new_tokens": 256, "greedy": True}
    holders = [
        threading.Thread(target=lambda: _post(base, "/v1/generate", body).read())
        for _ in range(2)
    ]
    holders[0].start()  # occupies the single slot
    # wait until it is actually admitted before queueing the second
    engine = control["cont_engine"]
    deadline = time.time() + 60
    while time.time() < deadline:
        if engine.stats_snapshot()["live_slots"] >= 1:
            break
        time.sleep(0.02)
    holders[1].start()  # fills the depth-1 queue
    while time.time() < deadline:
        if engine.stats_snapshot()["queue_depth"] >= 1:
            break
        time.sleep(0.02)
    with pytest.raises(urllib.error.HTTPError) as he:
        _post(base, "/v1/generate",
              {"question": "third?", "max_new_tokens": 4, "greedy": True},
              timeout=30)
    assert he.value.code == 429
    err = json.loads(he.value.read())["error"]
    assert err["kind"] == "queue_overflow"
    assert err["retryable"] is True
    assert int(he.value.headers["Retry-After"]) >= 1
    for t in holders:
        t.join(timeout=180)


def test_stream_emits_error_event_then_engine_recovers(model_dir):
    """A decode failure mid-stream ends the SSE body with a terminal
    ``event: error`` chunk (structured, not silent truncation) — and the
    supervised engine serves the next request normally."""
    base, _, control = _start_controlled(
        model_dir, restart_backoff_s=0.01, restart_backoff_max_s=0.02
    )
    # warm the jit caches so the fault lands in steady-state decode
    _post(base, "/v1/generate",
          {"question": "warm?", "max_new_tokens": 4, "greedy": True}).read()
    control["cont_engine"].faults.fail_decode_next(1)
    with _post(base, "/v1/stream",
               {"question": "q?", "max_new_tokens": 16, "greedy": True}) as r:
        assert r.status == 200  # headers were already committed
        raw = r.read().decode()
    assert "event: error" in raw
    lines = raw.splitlines()
    err = json.loads(lines[lines.index("event: error") + 1][len("data: "):])
    assert err["kind"] == "engine_restarting"
    assert err["retryable"] is True
    # recovered in-process: the next request decodes fine
    answer = json.loads(_post(
        base, "/v1/generate",
        {"question": "after?", "max_new_tokens": 4, "greedy": True},
    ).read())["answer"]
    assert isinstance(answer, str)
    assert control["cont_engine"].stats_snapshot()["engine_restarts"] >= 1


def test_healthz_unhealthy_once_circuit_opens(model_dir):
    """circuit_threshold=1: the first decode failure opens the breaker, the
    engine goes terminally unhealthy, and /healthz reports 503 with the
    structured terminal error — the orchestrator's recycle signal."""
    base, _, control = _start_controlled(
        model_dir, circuit_threshold=1, restart_backoff_s=0.01
    )
    _post(base, "/v1/generate",
          {"question": "warm?", "max_new_tokens": 4, "greedy": True}).read()
    control["cont_engine"].faults.fail_decode_next(1)
    with pytest.raises(urllib.error.HTTPError) as pe:
        _post(base, "/v1/generate",
              {"question": "q?", "max_new_tokens": 16, "greedy": True},
              timeout=60)
    assert pe.value.code == 503
    assert json.loads(pe.value.read())["error"]["kind"] == "circuit_open"
    deadline = time.time() + 30
    while time.time() < deadline and control["cont_engine"].healthy:
        time.sleep(0.02)
    with pytest.raises(urllib.error.HTTPError) as he:
        urllib.request.urlopen(f"{base}/healthz", timeout=10)
    assert he.value.code == 503
    body = json.loads(he.value.read())
    assert body["status"] == "unhealthy"
    assert body["circuit_state"] == "open"
    assert body["error"]["kind"] == "circuit_open"


# ------------------------------------------------- multi-tenant LoRA serving


def test_adapter_flag_validation_at_startup():
    """Bad adapter flag combinations fail AT STARTUP, before the model
    loads (parity with the --speculative checks above), naming what IS
    supported."""
    from llm_fine_tune_distributed_tpu.infer.server import serve

    with pytest.raises(ValueError, match="continuous|paged"):
        serve("/nonexistent", adapter_dir="/whatever", engine_kind="window")
    with pytest.raises(ValueError, match="--adapter-dir not found"):
        serve("/nonexistent", adapter_dir="/no/such/dir")


@pytest.fixture(scope="module")
def adapter_root(tmp_path_factory):
    """Two PEFT adapters built against the same tiny base the model_dir
    checkpoint holds (init_params PRNGKey 0), with non-zero B."""
    from llm_fine_tune_distributed_tpu.config import TrainConfig
    from llm_fine_tune_distributed_tpu.parallel.lora import (
        add_lora_params,
        save_lora_adapter,
    )

    mc = get_preset("tiny")
    base = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    root = tmp_path_factory.mktemp("srv_adapters")
    for name, seed in (("acme", 1), ("globex", 2)):
        params = add_lora_params(
            base, jax.random.PRNGKey(seed), rank=4, alpha=8.0
        )

        # large-magnitude B so the adapted greedy path visibly diverges
        # from base (tiny random weights need a big shove to flip argmax)
        def bump(node, scale=0.5 * seed):
            if isinstance(node, dict):
                if "lora_b" in node:
                    node = dict(node)
                    node["lora_b"] = jnp.ones_like(node["lora_b"]) * scale
                    return node
                return {k: bump(v) for k, v in node.items()}
            return node

        save_lora_adapter(
            bump(params), str(root / name),
            TrainConfig(freeze_strategy="lora", lora_rank=4, lora_alpha=8.0),
        )
    return str(root)


@pytest.fixture(scope="module")
def adapter_server(model_dir, adapter_root):
    return _start_server(
        model_dir, adapter_dir=adapter_root, slots=4, max_adapters=4
    )


def test_generate_with_adapter(adapter_server):
    """The 'adapter' request field selects the tenant's LoRA delta: the
    adapted greedy answer differs from the base answer for the same
    request, and the base answer is unchanged by adapter traffic."""
    body = {"question": "What is 2+2?", "max_new_tokens": 8, "greedy": True}
    with _post(adapter_server, "/v1/generate", body) as r:
        base_answer = json.loads(r.read())["answer"]
    with _post(
        adapter_server, "/v1/generate", {**body, "adapter": "acme"}
    ) as r:
        acme_answer = json.loads(r.read())["answer"]
    assert acme_answer != base_answer
    with _post(adapter_server, "/v1/generate", body) as r:
        assert json.loads(r.read())["answer"] == base_answer


def test_generate_unknown_adapter_404_lists_known(adapter_server):
    with pytest.raises(urllib.error.HTTPError) as he:
        _post(
            adapter_server, "/v1/generate",
            {"question": "q?", "max_new_tokens": 4, "adapter": "ghost"},
            timeout=30,
        )
    assert he.value.code == 404
    err = json.loads(he.value.read())["error"]
    assert err["kind"] == "unknown_adapter"
    assert set(err["known_adapters"]) == {"acme", "globex"}


def test_adapter_without_registry_404(server):
    """The plain server (no --adapter-dir) rejects adapter requests with
    a structured error telling the operator which flag is missing."""
    with pytest.raises(urllib.error.HTTPError) as he:
        _post(
            server, "/v1/generate",
            {"question": "q?", "max_new_tokens": 4, "adapter": "acme"},
            timeout=30,
        )
    assert he.value.code == 404
    err = json.loads(he.value.read())["error"]
    assert err["kind"] == "unknown_adapter"
    assert "--adapter-dir" in err["message"]


def test_stream_with_adapter(adapter_server):
    """SSE streaming rides the shared batch WITH the tenant's delta: the
    streamed deltas concatenate to the non-streamed adapted answer."""
    body = {
        "question": "How many cups in a gallon?", "max_new_tokens": 8,
        "greedy": True, "adapter": "acme",
    }
    with _post(adapter_server, "/v1/generate", body) as r:
        answer = json.loads(r.read())["answer"]
    with _post(adapter_server, "/v1/stream", body) as r:
        raw = r.read().decode()
    events = [
        json.loads(line[len("data: "):])
        for line in raw.splitlines()
        if line.startswith("data: ")
    ]
    assert events and events[-1].get("done") is True
    assert "".join(e.get("delta", "") for e in events).strip() == answer


def test_adapter_stats_and_metrics_per_tenant(adapter_server):
    """/v1/stats carries the per-tenant map and pool gauges; /metrics
    carries the tenant-labelled series."""
    with urllib.request.urlopen(f"{adapter_server}/v1/stats", timeout=30) as r:
        stats = json.loads(r.read())
    assert stats["per_tenant"]["acme"]["requests"] >= 1
    assert stats["per_tenant"]["acme"]["tokens"] >= 1
    assert stats["adapters_resident"] >= 1
    assert stats["adapter_loads"] >= 1
    with urllib.request.urlopen(f"{adapter_server}/metrics", timeout=30) as r:
        text = r.read().decode()
    assert 'serving_tenant_tokens_total{tenant="acme"}' in text
    assert "serving_adapters_resident" in text


def test_adapter_field_window_engine_400(model_dir):
    """A window-engine server rejects 'adapter' with a 400 naming the
    supported alternatives (validation parity with 'speculative')."""
    base = _start_server(model_dir, engine_kind="window")
    with pytest.raises(urllib.error.HTTPError) as he:
        _post(
            base, "/v1/generate",
            {"question": "q?", "max_new_tokens": 4, "adapter": "acme"},
            timeout=30,
        )
    assert he.value.code == 400
    msg = json.loads(he.value.read())["error"]
    assert "--adapter-dir" in msg and "continuous" in msg
