"""XLA runtime introspection (observe/xla.py) and its wiring.

What this file pins, layer by layer:

- ``CompileLedger``: dedup by (program, shapes), re-record bumps the
  count, the ``mark_warm()`` boundary turns every later record into a
  ``recompiles_after_warmup`` tick with a listener notification, and
  ``merge`` unions DISTINCT ledgers (fleet replicas sharing one
  Generator share one ledger object — identity dedup);
- ``instrument()``: first call registers with the ledger (AOT path with
  cost analysis, or plain-jit wall timing), later calls don't re-record,
  and outputs are identical either way; the AOT path's seconds by stage
  add up to ``compile_s``, say hit or miss of the persistent cache, and a
  fallback says why;
- the span recorder: parent links (JAX's own compile events land under
  the span open on their thread), the bounded list whose counters go on,
  the set-up section frozen by the first ``mark_warm()`` (nothing is
  recorded after it) while later compiles still count as recompiles,
  listeners installed once, and the section the process's own, in no
  ledger's snapshot;
- utilization math: ``utilization_from_cost`` clamps to [0, 1] and
  returns 0.0 on unknowns; ``device_peak_specs`` knows the v5e by its real
  ``device_kind``, gives a CPU (0, 0) and raises on an unknown accelerator;
- the zero-recompile acceptance gate: both slot engines driven through
  mixed traffic (speculative K, two LoRA adapters, prefix hits AND
  misses, an injected crash + recovery), warm-marked, then the SAME
  traffic again — no hot-path program may compile post-warmup;
- fleet trace propagation: one RequestTrace spans the router decision,
  a failed hop, and the completing replica — scripted and real;
- ``ProfilerCapture``: one capture at a time (busy rejection), auto-stop,
  flight-recorder events.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from llm_fine_tune_distributed_tpu.data.tokenizer import ByteChatMLTokenizer
from llm_fine_tune_distributed_tpu.infer import (
    EngineFleet,
    GenerationConfig,
    Generator,
)
from llm_fine_tune_distributed_tpu.infer.engine import (
    ContinuousBatchingEngine,
    PagedContinuousBatchingEngine,
)
from llm_fine_tune_distributed_tpu.infer.errors import RetryableEngineError
from llm_fine_tune_distributed_tpu.models.configs import get_preset
from llm_fine_tune_distributed_tpu.models.transformer import init_params
from llm_fine_tune_distributed_tpu.observe import xla
from llm_fine_tune_distributed_tpu.observe.tracing import RequestTrace
from llm_fine_tune_distributed_tpu.observe.xla import (
    CaptureBusyError,
    CompileLedger,
    ProfilerCapture,
    SpanRecorder,
    annotate,
    device_peak_specs,
    instrument,
    utilization_from_cost,
)

GREEDY = GenerationConfig(max_new_tokens=6, do_sample=False)


@pytest.fixture(scope="module")
def generator():
    mc = get_preset("tiny")
    params = init_params(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
    return Generator(
        params, mc, ByteChatMLTokenizer(), compute_dtype=jnp.float32, eos_token_ids=[]
    )


def _prompts():
    tok = ByteChatMLTokenizer()
    return [tok.encode(t) for t in ("alpha", "beta bravo", "the quick brown fox")]


# --------------------------------------------------------- compile ledger


def test_ledger_dedup_by_program_and_shapes():
    led = CompileLedger()
    led.record("slot_step", "(4, 96)", 0.5)
    led.record("slot_step", "(4, 96)", 0.25)  # cache rebuild, same sig
    led.record("slot_step", "(8, 96)", 0.1)  # new shape bucket
    led.record("paged_step", "(4, 96)", 0.2)
    snap = led.snapshot()
    assert snap["programs"]["slot_step"]["compiles"] == 3
    assert snap["programs"]["slot_step"]["compile_s"] == pytest.approx(0.85)
    assert snap["programs"]["paged_step"]["compiles"] == 1
    assert snap["total_compiles"] == 4
    assert snap["total_compile_s"] == pytest.approx(1.05)
    assert snap["recompiles_after_warmup"] == 0
    assert snap["warmed"] is False


def test_ledger_warmup_boundary_counts_and_notifies():
    led = CompileLedger()
    seen = []
    led.add_listener(lambda prog, sig, dt, gen: seen.append((prog, sig, gen)))
    led.record("slot_step", "(4,)", 0.1)
    assert seen == []  # pre-warm compiles are expected, not events
    led.mark_warm()
    assert led.warmed
    led.current_generation = 3
    led.record("slot_step", "(8,)", 0.2)  # NEW shape after warm: still a bug
    led.record("slot_step", "(4,)", 0.05)  # rebuild of a known sig: also
    snap = led.snapshot()
    assert snap["recompiles_after_warmup"] == 2
    assert seen == [("slot_step", "(8,)", 3), ("slot_step", "(4,)", 3)]
    # a broken listener never breaks a record
    led.add_listener(lambda *a: (_ for _ in ()).throw(RuntimeError("x")))
    led.record("slot_step", "(16,)", 0.01)
    assert led.snapshot()["recompiles_after_warmup"] == 3


def test_ledger_merge_dedups_shared_ledgers():
    shared = CompileLedger()
    shared.record("paged_step", "(4,)", 1.0)
    other = CompileLedger()
    other.record("paged_step", "(4,)", 0.5)
    other.mark_warm()
    # two replicas sharing one Generator present the SAME ledger twice
    merged = CompileLedger.merge([shared, shared, other, None])
    assert merged["programs"]["paged_step"]["compiles"] == 2  # not 3
    assert merged["total_compile_s"] == pytest.approx(1.5)
    assert merged["warmed"] is False  # all must be warm
    shared.mark_warm()
    assert CompileLedger.merge(iter([shared, other]))["warmed"] is True
    empty = CompileLedger.merge([])
    assert empty["total_compiles"] == 0 and empty["warmed"] is False


def test_ledger_cost_for_prefers_most_recent():
    led = CompileLedger()
    led.record("slot_step", "(4,)", 0.1, flops=100.0, bytes_accessed=10.0)
    led.record("spec_slot_step", "(4,)", 0.1, flops=300.0, bytes_accessed=30.0)
    led.record("draft_slot_step", "(4,)", 0.1, flops=999.0, bytes_accessed=99.0)
    assert led.cost_for(("slot_step", "spec_slot_step")) == (300.0, 30.0)
    assert led.cost_for(("missing",)) == (0.0, 0.0)
    no_cost = CompileLedger()
    no_cost.record("slot_step", "(4,)", 0.1)  # no cost analysis attached
    assert no_cost.cost_for(("slot_step",)) == (0.0, 0.0)


def test_utilization_from_cost_clamps_and_zeroes():
    mfu, bw = utilization_from_cost(5e12, 5e11, 0.01, 1e15, 1e14)
    assert mfu == pytest.approx(0.5)
    assert bw == pytest.approx(0.5)
    # faster-than-roofline measurements clamp instead of reporting >100%
    assert utilization_from_cost(1e18, 1e18, 0.01, 1e12, 1e12) == (1.0, 1.0)
    # any unknown input -> 0.0, never a division error
    assert utilization_from_cost(0.0, 0.0, 0.01, 1e12, 1e12) == (0.0, 0.0)
    assert utilization_from_cost(1e12, 1e12, 0.0, 1e12, 1e12) == (0.0, 0.0)
    assert utilization_from_cost(1e12, 1e12, 0.01, 0.0, 0.0) == (0.0, 0.0)


class _StubDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize(
    "device, expected",
    [
        # the kind libtpu really reports for a v5e; Google Cloud "TPU v5e"
        (_StubDevice("tpu", "TPU v5 lite"), (197e12, 8.19e11)),
        # a CPU is a known device with no roofline: (0, 0), never invented
        (_StubDevice("cpu", "cpu"), (0.0, 0.0)),
        (None, (0.0, 0.0)),  # the test process's own first device, a CPU
    ],
)
def test_device_peak_specs_known_devices(device, expected, monkeypatch):
    # the old environment override is gone: it must change nothing
    monkeypatch.setenv("SERVE_PEAK_FLOPS", "2e14")
    assert device_peak_specs(device) == expected


def test_device_peak_specs_unknown_accelerator_raises():
    """An accelerator that is not in the table is an error, not a zero."""
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        device_peak_specs(_StubDevice("tpu", "TPU v9 imaginary"))


# ------------------------------------------------------------- instrument


@pytest.mark.parametrize("aot", [True, False])
def test_instrument_records_once_and_preserves_output(aot):
    led = CompileLedger()
    fn = jax.jit(lambda x: x * 2 + 1)
    x = jnp.arange(8, dtype=jnp.float32)
    wrapped = instrument("double", fn, led, aot=aot)
    first = wrapped(x)
    assert jnp.array_equal(first, fn(x))
    for _ in range(3):  # steady state: no re-records
        assert jnp.array_equal(wrapped(x), first)
    snap = led.snapshot()
    assert snap["programs"]["double"]["compiles"] == 1
    assert snap["programs"]["double"]["compile_s"] > 0.0
    if aot:  # the AOT path attaches cost analysis
        flops, nbytes = led.cost_for(("double",))
        assert nbytes > 0.0


def test_instrument_aot_falls_back_on_unlowerable_fn():
    led = CompileLedger()
    wrapped = instrument("plain", lambda x: x + 1, led, aot=True)  # no .lower
    assert wrapped(41) == 42
    assert wrapped(1) == 2
    assert led.snapshot()["programs"]["plain"]["compiles"] == 1


def test_annotate_is_a_usable_context():
    with annotate("admit"):
        pass  # a TraceAnnotation and a record in the recorder; it must just work


def test_instrument_aot_fallback_is_recorded_with_its_reason():
    led = CompileLedger()
    wrapped = instrument("plain", lambda x: x + 1, led, aot=True)  # no .lower
    assert wrapped(41) == 42
    program = led.snapshot()["programs"]["plain"]
    assert program["aot"] is False and program["aot_error"].startswith("AttributeError")
    assert "trace_s" not in program  # a first call's wall time, and it says so
    ok = CompileLedger()
    instrument("double", jax.jit(lambda x: x * 2), ok)(jnp.arange(4.0))
    assert ok.snapshot()["programs"]["double"]["aot"] is True
    assert "aot_error" not in ok.snapshot()["programs"]["double"]


# ------------------------------------------------------------ span recorder


@pytest.fixture
def recorder(monkeypatch):
    """A recorder of this test's own in place of the process's (which the
    first ``mark_warm()`` of the test run has long frozen)."""
    fresh = SpanRecorder()
    monkeypatch.setattr(xla, "_RECORDER", fresh)
    return fresh


def test_annotate_is_a_span_with_parent_links(recorder):
    with annotate("tick") as tick:
        with annotate("prefill", program="paged_step") as prefill:
            pass
        with annotate("sample"):
            pass
    spans = {s["name"]: s for s in recorder.section()["spans"]}
    assert spans["setup"]["id"] == 0 and spans["setup"]["end_ns"] is None  # set-up still lasts
    assert spans["tick"]["parent"] == 0  # nothing open on the thread: under the root
    assert spans["prefill"]["parent"] == spans["sample"]["parent"] == spans["tick"]["id"]
    assert spans["prefill"]["program"] == "paged_step"
    for s in (spans["tick"], spans["prefill"], spans["sample"]):
        assert s["thread"] == threading.get_ident()
        assert spans["setup"]["start_ns"] < s["start_ns"] <= s["end_ns"] <= time.time_ns()  # the epoch clock
    assert spans["tick"]["start_ns"] <= spans["prefill"]["start_ns"] <= spans["sample"]["start_ns"]
    assert tick.record is spans["tick"] or tick.record == spans["tick"]  # the span's own record is what is kept
    # another thread's span does not take this thread's open span as its parent
    with annotate("tick"):
        t = threading.Thread(target=lambda: annotate("elsewhere").__enter__().__exit__(None, None, None))
        t.start()
        t.join()
    assert [s for s in recorder.section()["spans"] if s["name"] == "elsewhere"][0]["parent"] == 0


def test_a_span_that_raises_is_closed_and_says_so(recorder):
    with pytest.raises(ValueError):
        with annotate("prefill"):
            raise ValueError("x")
    with annotate("sample"):
        pass
    spans = {s["name"]: s for s in recorder.section()["spans"]}
    assert spans["prefill"]["error"] == "ValueError" and spans["sample"]["parent"] == 0


def _busy():
    """A function of this call's own (JAX finds nothing of it in its caches) with enough
    operations that its lowering lasts a millisecond, and is kept."""
    def busy(x):
        for i in range(60):
            x = jnp.sin(x) * (i + 1.0)
        return x
    return jax.jit(busy)


def test_jax_compile_events_land_under_the_open_program_span(recorder):
    led = CompileLedger()
    wrapped = instrument("busy", _busy(), led)
    wrapped(jnp.arange(8, dtype=jnp.float32))
    spans = recorder.section()["spans"]
    by_name = {s["name"]: s for s in spans if s["name"].startswith("busy/")}
    assert set(by_name) == {"busy/load", "busy/compile", "busy/first_dispatch"}
    load = by_name["busy/load"]
    assert by_name["busy/compile"]["parent"] == by_name["busy/first_dispatch"]["parent"] == load["id"]
    # tracing and lowering are ONE call (fn.lower): its stages are JAX's own spans of the function, under the load
    trace, = [s for s in spans if s["name"] == "jit/trace" and s["fun_name"] == "busy"]
    lower, = [s for s in spans if s["name"] == "jit/lower" and s["fun_name"] == "busy"]
    assert trace["parent"] == lower["parent"] == load["id"]
    assert load["start_ns"] <= trace["start_ns"] < trace["end_ns"] <= lower["start_ns"] < lower["end_ns"]
    assert lower["end_ns"] <= by_name["busy/compile"]["start_ns"] + 1000
    compile_span = [s for s in spans if s["name"] == "jit/compile" and s["parent"] == by_name["busy/compile"]["id"]][0]
    assert compile_span["cache"] == by_name["busy/compile"]["cache"] in ("hit", "miss", "off")
    section = recorder.section()
    names = {f["fun_name"]: f["spans"] for f in section["by_function"]}
    assert names["busy"] == 3 and len(names) <= 20  # every stage counts for its function, kept or not
    assert section["counters"]["spans"] == len(section["spans"]) - 1 + section["counters"]["spans_brief"]
    section["spans"].clear()  # the caller's own copy
    assert len(recorder.section()["spans"]) == len(spans)


def test_stage_seconds_add_up_to_compile_s(recorder):
    led = CompileLedger()
    wrapped = instrument("busy", _busy(), led)
    wrapped(jnp.arange(8, dtype=jnp.float32))
    wrapped(jnp.arange(16, dtype=jnp.float32))  # a second signature: the stages add up over both
    p = led.snapshot()["programs"]["busy"]
    assert p["compiles"] == 2
    assert p["trace_s"] + p["lower_s"] + p["backend_compile_s"] == pytest.approx(p["compile_s"], abs=1e-5)
    spans = recorder.section()["spans"]
    lowering = [s for s in spans if s["name"] == "jit/lower" and s["fun_name"] == "busy"]
    assert sum(s["end_ns"] - s["start_ns"] for s in lowering) / 1e9 == pytest.approx(p["lower_s"], abs=1e-5)
    compiling = [s for s in spans if s["name"] == "busy/compile"]
    assert sum(s["end_ns"] - s["start_ns"] for s in compiling) / 1e9 == pytest.approx(p["backend_compile_s"], abs=1e-3)
    # once set-up is over a new signature's entry has its seconds, and no split: nothing is recorded to split by
    led.mark_warm()
    late = CompileLedger()
    instrument("late", _busy(), late)(jnp.arange(32, dtype=jnp.float32))
    q = late.snapshot()["programs"]["late"]
    assert q["compile_s"] >= q["backend_compile_s"] > 0.0 and "lower_s" not in q and "trace_s" not in q


_CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from llm_fine_tune_distributed_tpu.observe.xla import CompileLedger, instrument
led = CompileLedger()
def probe(x):
    for _ in range(60):
        x = jnp.tanh(x) @ x.T
    return x
instrument("probe", jax.jit(probe), led)(jnp.ones((32, 32)))
led.mark_warm()
setup = led.setup()
spans = [s for s in setup["spans"] if s["name"] == "probe/compile"]
print(json.dumps({"program": led.snapshot()["programs"]["probe"], "span_cache": spans[0]["cache"],
                  "counters": setup["counters"]}))
"""


def test_cache_miss_in_a_fresh_directory_then_hit_in_a_second_process(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    reads = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _CACHE_PROBE, str(tmp_path / "cache")], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        reads.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = reads
    assert cold["program"]["cache"] == cold["span_cache"] == "miss"
    assert cold["counters"]["cache_misses"] >= 1 and cold["counters"]["cache_hits"] == 0
    assert warm["program"]["cache"] == warm["span_cache"] == "hit"
    assert warm["counters"]["cache_misses"] == 0 and warm["counters"]["cache_hits"] >= 1
    assert warm["counters"]["cache_retrieval_time_sec"] > 0.0
    for read in reads:
        p = read["program"]
        assert p["trace_s"] + p["lower_s"] + p["backend_compile_s"] == pytest.approx(p["compile_s"], abs=1e-5)


def test_the_list_is_bounded_and_the_counters_go_on(monkeypatch):
    small = SpanRecorder(max_spans=3)
    monkeypatch.setattr(xla, "_RECORDER", small)
    for _ in range(5):
        with annotate("tick"):
            pass
    small.add_jit_stage("jit/compile", 1.0, 3.5, "jit(late)")  # JAX's seconds; its name for a compile
    small.add_jit_stage("jit/trace", 1.0, 1.0005, "late")  # under a millisecond: counted, never kept
    section = small.section()
    assert len(section["spans"]) == 1 + 3  # the root and what the list holds: process/before_recorder, two ticks
    assert [s["name"] for s in section["spans"]] == ["setup", "process/before_recorder", "tick", "tick"]
    assert section["counters"]["spans"] == 8 and section["counters"]["spans_dropped"] == 4
    assert section["counters"]["spans_brief"] == 1
    assert section["counters"]["jit_seconds"] == pytest.approx(2.5005)
    assert section["by_function"] == [{"fun_name": "late", "seconds": 2.5005, "spans": 2}]
    roomy = SpanRecorder()
    roomy.add_jit_stage("jit/trace", 1.0, 1.0005, "brief")
    roomy.add_jit_stage("jit/trace", 1.0, 1.002, "long_enough")
    assert [s["fun_name"] for s in roomy.section()["spans"][2:]] == ["long_enough"]


def test_mark_warm_freezes_the_setup_section_and_later_compiles_still_count(recorder):
    led = CompileLedger()
    wrapped = instrument("double", jax.jit(lambda x: x * 2), led)
    wrapped(jnp.arange(4.0))
    with annotate("startup/weights"):
        pass
    assert led.setup()["spans"][0]["end_ns"] is None
    with annotate("startup/first_step") as still_open:
        led.mark_warm()
    frozen = led.setup()
    root = frozen["spans"][0]
    assert root["name"] == "setup" and root["end_ns"] >= max(s["end_ns"] for s in frozen["spans"][1:])
    assert still_open.record["name"] not in {s["name"] for s in frozen["spans"]}  # it ended after set-up did
    wrapped(jnp.arange(32.0))  # a new shape after warm-up: a recompile, and no part of set-up
    with annotate("tick", slot=3) as tick:
        tick.set(tokens=1)
    assert tick.record is None  # set-up is over: a span is its TraceAnnotation alone
    assert led.snapshot()["recompiles_after_warmup"] == 1
    assert led.snapshot()["programs"]["double"]["compiles"] == 2
    assert led.setup() == frozen and recorder.counters == frozen["counters"]  # nothing is recorded any more
    assert len(recorder._spans) == len(frozen["spans"]) - 1
    CompileLedger().mark_warm()  # a second ledger's warm-up does not move the end of set-up
    assert led.setup()["spans"][0]["end_ns"] == root["end_ns"]
    phases = led.setup_phases()
    assert list(phases["phases_s"])[:3] == ["process/before_recorder", "double/load", "double/compile"]
    assert "startup/weights" in phases["phases_s"]
    assert "jit/trace" not in phases["phases_s"]
    assert phases["since_process_start_s"] == pytest.approx((root["end_ns"] - root["start_ns"]) / 1e9, abs=1e-3)


def test_listeners_are_installed_once_and_the_section_is_the_processes_own(recorder):
    from jax._src import monitoring

    ledgers = [CompileLedger() for _ in range(3)]
    xla.install_compile_listeners()
    assert monitoring.get_event_time_span_listeners().count(xla._on_compile_span) == 1
    assert monitoring.get_event_listeners().count(xla._on_cache_event) == 1
    assert monitoring.get_event_duration_listeners().count(xla._on_cache_seconds) == 1
    _busy()(jnp.arange(5.0))  # no ledger's program: still one set of spans, not three
    mine = [s for s in recorder.section()["spans"] if s["name"] == "jit/compile" and s["fun_name"] == "busy"]
    assert len(mine) == 1
    # the section is the process's: the same under every ledger, and in no snapshot (a scrape reads those)
    assert ledgers[0].setup() == ledgers[2].setup() == CompileLedger.setup() == recorder.section()
    merged = CompileLedger.merge(ledgers)
    assert "setup" not in merged and "setup" not in ledgers[0].snapshot()
    assert set(merged) == set(ledgers[0].snapshot())


# ------------------------------------------- zero-recompile acceptance gate


def test_zero_recompile_guard_mixed_traffic(generator, tmp_path):
    """THE gate: a fresh Generator's engines are driven through every hot
    path — paged prefix miss + hit, speculative drafting, dense decode
    under two LoRA adapters and the base model, and a crash + recovery on
    each engine — then warm-marked; the identical traffic replayed must
    not compile a single new program, and the ledger is visible in both
    engines' ``stats_snapshot()``."""
    from llm_fine_tune_distributed_tpu.config import TrainConfig
    from llm_fine_tune_distributed_tpu.infer.adapters import AdapterRegistry
    from llm_fine_tune_distributed_tpu.parallel.lora import (
        add_lora_params,
        save_lora_adapter,
    )

    mc = get_preset("tiny")
    # fresh Generator: its ledger's warm mark must not leak into (or from)
    # the module fixture's shared jit caches
    gen = Generator(
        generator.params, mc, ByteChatMLTokenizer(),
        compute_dtype=jnp.float32, eos_token_ids=[],
    )
    for i, name in enumerate(("acme", "globex")):
        lora = add_lora_params(
            generator.params, jax.random.PRNGKey(20 + i), rank=4, alpha=8.0
        )
        save_lora_adapter(
            lora, str(tmp_path / name),
            TrainConfig(freeze_strategy="lora", lora_rank=4, lora_alpha=8.0),
        )
    kw = dict(
        slots=4, buf_len=96, prompt_bucket=16,
        restart_backoff_s=0.01, restart_backoff_max_s=0.02,
    )
    dense = ContinuousBatchingEngine(
        gen, adapters=AdapterRegistry(
            generator.params, str(tmp_path), max_adapters=4
        ), **kw,
    )
    paged = PagedContinuousBatchingEngine(
        gen, block_len=16, prefill_chunk=32, speculative_k=2, **kw,
    )
    assert dense.compile_ledger is paged.compile_ledger  # shared Generator

    tok = ByteChatMLTokenizer()
    prefix = tok.encode("the quick brown fox jumps over the lazy dog")
    spec_gen = GenerationConfig(
        max_new_tokens=8, do_sample=False, speculative_lookup=2
    )
    rep_prompt = tok.encode("ab") * 8  # repetitive: prompt-lookup fires
    prompts = _prompts()

    def traffic():
        paged.submit(prefix + tok.encode(" one"), GREEDY, timeout=240)
        paged.submit(prefix + tok.encode(" two"), GREEDY, timeout=240)  # hit
        paged.submit(rep_prompt, spec_gen, timeout=240)  # fused draft/verify
        dense.submit(prompts[0], GREEDY, timeout=240, adapter="acme")
        dense.submit(prompts[1], GREEDY, timeout=240, adapter="globex")
        dense.submit(prompts[2], GREEDY, timeout=240)  # base model
        for engine in (paged, dense):  # crash + recovery per engine
            engine.faults.fail_decode_next(1)
            with pytest.raises(RetryableEngineError):
                engine.submit(prompts[0], GREEDY, timeout=60)
            assert engine.submit(prompts[0], GREEDY, timeout=240) is not None

    # two warmup passes: pass 1 compiles the cold-cache shapes (prefix
    # misses, first prefills), pass 2 the warm-cache shapes (deeper
    # resident runs shorten the suffix prefill) — after it, a third
    # identical pass can need nothing new
    traffic()
    traffic()
    warm = paged.stats_snapshot()["compile"]
    assert warm["total_compiles"] > 0 and not warm["warmed"]
    # speculative_k on the paged engine routes EVERY tick through the
    # fused draft/verify program, so plain paged_step never compiles
    assert {"spec_paged_step", "paged_final", "slot_step"} <= set(
        warm["programs"]
    )
    paged.mark_compile_warm()  # one shared ledger: marks both engines
    traffic()  # steady state: same shapes, same programs, zero compiles

    for engine in (paged, dense):
        snap = engine.stats_snapshot()
        comp = snap["compile"]
        assert comp["warmed"] is True
        assert comp["recompiles_after_warmup"] == 0, comp
        assert comp["total_compiles"] == warm["total_compiles"]
        # utilization gauges ride the same snapshot (0.0 on CPU: no
        # roofline to measure against, never an invented number)
        assert 0.0 <= snap["model_flops_utilization"] <= 1.0
        assert 0.0 <= snap["hbm_bandwidth_utilization"] <= 1.0
    # post-warmup recompiles would also be on the flight-recorder timeline
    assert not [
        e for e in paged.recorder.events() if e["kind"] == "recompile"
    ]


# ------------------------------------------------ fleet trace propagation


class _FakeResult:
    def __init__(self, result, trace=None):
        self.result = result
        self.trace = trace


class _TracingReplica:
    """Scripted replica that OPTS IN to trace adoption — the surface a real
    engine presents to the fleet's trace propagation."""

    SUPPORTS_TRACE = True
    block_len = 0

    def __init__(self, index, raises=None):
        self.index = index
        self.slot_count = 2
        self.raises = raises
        self.healthy = True
        self.draining = False
        self.recovering = False
        self.queue_depth = 0
        self.live_slots = 0
        self.circuit_state = "closed"
        self.seen_trace = None

    def predicted_drain_s(self):
        return 1.0

    def prefix_match_len(self, keys):
        return 0

    def submit_full(self, prompt_ids, gen, seed=0, timeout=None, trace=None):
        self.seen_trace = trace
        if self.raises is not None:
            raise self.raises
        if trace is not None:
            trace.request_id = 1
            trace.mark("completed")
        return _FakeResult(list(prompt_ids) + [self.index], trace=trace)


def test_fleet_failover_is_one_trace():
    """A scripted failover produces ONE trace: the router's decision span
    for the first placement, the failover span naming the error, the
    second decision span, and the sibling's completion — all under one
    trace id."""
    dead = _TracingReplica(0, raises=RetryableEngineError("restart casualty"))
    ok = _TracingReplica(1)
    fleet = EngineFleet([dead, ok], routing="round-robin")
    req = fleet.submit_full([5], GREEDY)
    assert req.result == [5, 1]
    # both hops adopted the SAME trace object
    assert dead.seen_trace is ok.seen_trace is req.trace
    spans = [s for s, _ in req.trace.events]
    assert spans == [
        "router_decision replica=0 policy=round-robin reason=round_robin "
        "score=0",
        "failover replica=0 error=RetryableEngineError",
        "router_decision replica=1 policy=round-robin reason=round_robin "
        "score=0",
        "completed",
    ]
    times = [t for _, t in req.trace.events]
    assert times == sorted(times)
    d = req.trace.to_dict()
    assert d["trace_id"] == req.trace.trace_id
    assert len(d["trace_id"]) == 16


def test_router_decision_span_carries_score():
    """Affinity placements stamp the winning rule's strength into the
    span (resident prefix blocks / adapter residency / negative load)."""
    reps = [_TracingReplica(0), _TracingReplica(1)]
    reps[0].prefix_match_len = lambda keys: 3  # replica 0 holds 3 blocks
    for rep in reps:
        rep.block_len = 4
    fleet = EngineFleet(reps, routing="prefix")
    req = fleet.submit_full([1, 2, 3, 4, 5, 6, 7, 8, 9], GREEDY)
    span = [s for s, _ in req.trace.events][0]
    assert span == (
        "router_decision replica=0 policy=prefix reason=prefix_affinity "
        "score=3"
    )


def test_fleet_trace_lands_in_replica_jsonl(generator, tmp_path):
    """End to end on the real engines: the completing replica's trace
    JSONL record carries the propagated trace id AND the router span the
    fleet stamped before the engine ever saw the request."""
    fleet = EngineFleet(
        [
            PagedContinuousBatchingEngine(
                generator, slots=4, buf_len=96, prompt_bucket=16,
                block_len=16, prefill_chunk=32,
                restart_backoff_s=0.01, restart_backoff_max_s=0.02,
                trace_log=str(tmp_path / f"traces_{i}.jsonl"),
            )
            for i in range(2)
        ],
        routing="prefix",
    )
    req = fleet.submit_full(_prompts()[0], GREEDY, timeout=240)
    assert req.result is not None
    spans = [s for s, _ in req.trace.events]
    assert spans[0].startswith("router_decision replica=")
    for expected in ("received", "queued", "admitted", "completed"):
        assert expected in spans, spans
    home = fleet.recent_placements()[0][0]
    deadline = time.monotonic() + 10.0
    records = []
    while not records and time.monotonic() < deadline:
        path = str(tmp_path / f"traces_{home}.jsonl")
        if os.path.exists(path):
            with open(path) as f:
                records = [json.loads(line) for line in f]
        time.sleep(0.01)
    assert len(records) == 1
    assert records[0]["trace_id"] == req.trace.trace_id
    rspans = [e["span"] for e in records[0]["events"]]
    assert rspans[0].startswith("router_decision replica=")
    assert "completed" in rspans


def test_request_trace_ids_are_unique_and_propagate():
    a, b = RequestTrace(), RequestTrace()
    assert a.trace_id != b.trace_id
    pinned = RequestTrace(trace_id="abcd1234abcd1234")
    assert pinned.to_dict()["trace_id"] == "abcd1234abcd1234"


# -------------------------------------------------------- profiler capture


def test_profiler_capture_busy_and_autostop(tmp_path):
    events = []
    cap = ProfilerCapture(
        str(tmp_path), on_event=lambda kind, **f: events.append((kind, f))
    )
    with pytest.raises(ValueError):
        cap.start(0.0)
    trace_dir = cap.start(30.0)
    assert cap.active == trace_dir
    assert os.path.isdir(trace_dir)
    with pytest.raises(CaptureBusyError):
        cap.start(1.0)  # one capture at a time
    assert cap.stop() == trace_dir
    assert cap.active is None
    assert cap.stop() is None  # idempotent
    # a second capture gets a FRESH subdirectory
    second = cap.start(0.05)
    assert second != trace_dir
    # generous: under full-suite load stop_trace serializes TraceMe events
    # from every still-ticking engine fixture — on a starved single-core
    # runner ONE stop_trace has been observed to take ~60s, so the budget
    # must cover a full serialization, not just scheduler jitter
    deadline = time.monotonic() + 120.0
    while cap.active is not None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert cap.active is None  # the timer auto-stopped it
    kinds = [k for k, _ in events]
    assert kinds == [
        "profile_start", "profile_stop", "profile_start", "profile_stop",
    ]
    assert events[0][1]["dir"] == trace_dir
    # the capture produced a loadable (non-empty) trace directory
    assert any(files for _, _, files in os.walk(trace_dir))
