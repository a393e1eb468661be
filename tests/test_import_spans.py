"""Set-up's spans that no call site makes (``observe/startup.py``), on a CPU:

- imports under ``importing()`` while set-up lasts: ``import`` with what it pulls in as ``import/nested`` under it,
  one that raises says so and raises the same, nothing stands in ``sys.meta_path`` or for a loader, and after
  ``mark_warm()`` it makes no span and reads no clock;
- ``process/before_recorder``, back-filled from the process's start to the import of the recorder's module;
- the two builders of the state that do device work (``startup/opt_state``, ``startup/quantize_trunk``);
- ``CompileLedger.setup_phases()``, the operator's line: the new names in, ``import/nested`` out;
- a process of its own that only calls ``enable_compile_cache()`` and imports the trainer's package.
"""

import importlib
import json
import os
import subprocess
import sys
import textwrap
import threading
import uuid

import jax
import jax.numpy as jnp
import pytest

from llm_fine_tune_distributed_tpu.observe import startup, xla
from llm_fine_tune_distributed_tpu.observe.xla import CompileLedger, SpanRecorder, annotate, importing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def recorder(monkeypatch):
    """A recorder of this test's own in place of the process's (long frozen by the test run's first
    ``mark_warm()``)."""
    fresh = SpanRecorder()
    monkeypatch.setattr(xla, "_RECORDER", fresh)
    return fresh


@pytest.fixture
def modules(tmp_path, monkeypatch):
    """``make(body, name=None, **submodules) -> name``: a module (a package where it has submodules) of this test's
    own under a name nothing else has, importable, and out of ``sys.modules`` again afterwards."""
    made = []
    monkeypatch.syspath_prepend(str(tmp_path))

    def make(body="", name=None, **submodules):
        name = name or f"fake_{uuid.uuid4().hex[:12]}"
        made.append(name)
        if submodules:
            (tmp_path / name).mkdir()
            (tmp_path / name / "__init__.py").write_text(textwrap.dedent(body).format(me=name))
            for sub, text in submodules.items():
                (tmp_path / name / f"{sub}.py").write_text(textwrap.dedent(text).format(me=name))
        else:
            (tmp_path / f"{name}.py").write_text(textwrap.dedent(body).format(me=name))
        importlib.invalidate_caches()
        return name

    yield make
    for name in list(sys.modules):
        if name.split(".")[0] in made:
            del sys.modules[name]


def imports_of(recorder, name):
    return [s for s in recorder.section()["spans"] if s.get("module", "").split(".")[0] == name]


def counted(recorder):
    return recorder.section()["counters"]


SLOW = "import time\ntime.sleep(0.06)\n"


def test_an_import_under_importing_is_an_import_span_with_what_it_pulls_in_nested_under_it(recorder, modules):
    # the package's own pattern: the outer module wraps its dear import, and is itself imported under a wrap
    name = modules(SLOW + "from llm_fine_tune_distributed_tpu.observe.xla import importing\n"
                   "with importing('{me}.second'):\n    from {me} import second\n", second=SLOW)
    with annotate("startup/weights") as weights:
        with importing(name):
            importlib.import_module(name)
    outer, inner = sorted(imports_of(recorder, name), key=lambda s: s["start_ns"])
    assert (outer["name"], outer["module"]) == ("import", name)
    assert (inner["name"], inner["module"]) == ("import/nested", f"{name}.second")
    assert outer["parent"] == weights.record["id"] and inner["parent"] == outer["id"]  # under whatever was open
    assert outer["thread"] == inner["thread"] == threading.get_ident()
    assert outer["start_ns"] <= inner["start_ns"] < inner["end_ns"] <= outer["end_ns"]
    assert outer["end_ns"] - outer["start_ns"] >= 120e6 and inner["end_ns"] - inner["start_ns"] >= 60e6
    # the process's CPU seconds over the span: a sleeping body spends next to none of its wall time
    assert 0.0 <= inner["cpu_s"] <= outer["cpu_s"] < 0.12 + 1.0
    # no hook: an import under no `importing` is no span, and nothing stands in sys.meta_path for this package
    importlib.import_module(modules(SLOW))
    assert len([s for s in recorder.section()["spans"] if s["name"].startswith("import")]) == 2
    assert not [f for f in sys.meta_path if type(f).__module__.startswith("llm_fine_tune_distributed_tpu")]


def test_the_next_import_after_a_nested_one_is_under_no_import(recorder, modules):
    first, second = modules(SLOW), modules("VALUE = 3\n")
    with importing(first):
        importlib.import_module(first)
    with importing(second):
        assert importlib.import_module(second).VALUE == 3
    spans = [s for s in recorder.section()["spans"] if s["name"].startswith("import")]
    assert [(s["name"], s["module"]) for s in spans] == [("import", first), ("import", second)]  # brief or not: kept
    assert spans[0]["parent"] == spans[1]["parent"] == 0


def test_an_import_that_raises_closes_its_span_with_error_and_raises_the_same(recorder, modules):
    name = modules(SLOW + "raise LookupError('no such table')\n")
    with pytest.raises(LookupError, match="no such table"):
        with importing(name):
            importlib.import_module(name)
    span, = imports_of(recorder, name)
    assert span["name"] == "import" and span["error"] == "LookupError" and span["end_ns"] > span["start_ns"]
    assert name not in sys.modules and "cpu_s" in span
    with annotate("startup/data"):  # the thread's stack of open spans is whole again
        pass
    assert [s for s in recorder.section()["spans"] if s["name"] == "startup/data"][0]["parent"] == 0
    again = modules(SLOW)  # and the next import is under no import
    with importing(again):
        importlib.import_module(again)
    assert imports_of(recorder, again)[0]["name"] == "import"


def test_the_modules_loader_is_the_import_systems_own(recorder, modules):
    name = modules("def check():\n    return __spec__.loader, __loader__\n")
    with importing(name):
        module = importlib.import_module(name)
    assert type(module.__loader__).__name__ == "SourceFileLoader" and module.__spec__.loader is module.__loader__
    assert module.check() == (module.__loader__, module.__loader__)


def test_after_mark_warm_importing_makes_no_span_and_reads_no_clock(recorder, modules, monkeypatch):
    CompileLedger().mark_warm()
    frozen = recorder.section()

    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read after set-up")

    monkeypatch.setattr(xla, "time", NoClock())
    name = modules(SLOW)
    with importing(name) as span:
        importlib.import_module(name)
    assert span._span.record is None and recorder.section() == frozen


def test_imports_on_two_threads_nest_each_under_its_own(recorder, modules):
    elsewhere = modules(SLOW)

    def load():
        with importing(elsewhere):
            importlib.import_module(elsewhere)

    outer = modules(SLOW)
    with importing(outer):
        importlib.import_module(outer)
        t = threading.Thread(target=load)
        t.start()
        t.join(30)
        assert not t.is_alive()
    spans = {s["module"]: s for s in recorder.section()["spans"] if "module" in s}
    assert spans[outer]["name"] == "import"
    # the other thread had no import open: its module is no child of this thread's, by name or by parent
    assert spans[elsewhere]["name"] == "import" and spans[elsewhere]["parent"] == 0
    assert spans[elsewhere]["thread"] != spans[outer]["thread"]


def test_the_packages_three_sites_name_what_they_import():
    """``train/__init__.py``, ``train/checkpoints.py`` and ``parallel/optimizer.py`` wrap their dear imports; the
    modules they bring are there whatever set-up's state (this process imported them long ago)."""
    import inspect

    from llm_fine_tune_distributed_tpu import train
    from llm_fine_tune_distributed_tpu.parallel import optimizer
    from llm_fine_tune_distributed_tpu.train import checkpoints

    assert "with importing(__name__):" in inspect.getsource(train) and train.SFTTrainer and train.TrainState
    assert 'with importing("orbax.checkpoint"):' in inspect.getsource(checkpoints) and checkpoints.ocp.__name__ == "orbax.checkpoint"
    assert 'with importing("optax"):' in inspect.getsource(optimizer) and optimizer.optax.__name__ == "optax"


def test_before_recorder_runs_from_the_roots_start_to_the_import_of_the_recorders_module(recorder):
    root, first = recorder.section()["spans"][:2]
    assert first["name"] == "process/before_recorder" and first["id"] == 1 and first["parent"] == 0
    assert first["start_ns"] == root["start_ns"] and first["end_ns"] == xla._IMPORTED_NS > first["start_ns"]
    # this test run imported jax long before the recorder's module and had started no backend by then
    # (tests/conftest.py sets the platform, nothing more); the CPU seconds are the process's until that import
    assert first["jax_imported"] is True and first["backend_started"] is False
    assert 0.0 < first["cpu_s"] and "fun_name" not in first and "program" not in first
    assert counted(recorder)["spans"] == 1  # it is counted as the span it is
    jax.devices()
    assert startup.facts_at_import()["backend_started"] is True  # asked again with one up
    phases = CompileLedger.setup_phases()["phases_s"]
    assert list(phases) == ["process/before_recorder"]
    assert phases["process/before_recorder"] == pytest.approx((xla._IMPORTED_NS - root["start_ns"]) / 1e9, abs=1e-3)
    # a recorder whose root starts after that import has an empty one, not a negative one
    late = SpanRecorder(start_ns=xla._IMPORTED_NS + 5).section()["spans"][1]
    assert late["start_ns"] == late["end_ns"] == xla._IMPORTED_NS + 5


def test_setup_phases_lists_import_in_order_of_start_and_leaves_the_nested_out(recorder, modules):
    name = modules(SLOW + "from llm_fine_tune_distributed_tpu.observe.xla import importing\n"
                   "with importing('{me}.second'):\n    from {me} import second\n", second=SLOW)
    with annotate("startup/data"):
        pass
    with importing(name):
        importlib.import_module(name)
    with annotate("startup/weights"):
        other = modules(SLOW)
        with importing(other):
            importlib.import_module(other)
    assert {"import", "import/nested"} <= {s["name"] for s in recorder.section()["spans"]}
    phases = CompileLedger.setup_phases()["phases_s"]
    assert list(phases) == ["process/before_recorder", "startup/data", "import", "startup/weights"]
    assert 0.18 <= phases["import"] < 0.18 + 0.5  # two top-level imports; the nested one's seconds once, in its parent's
    json.dumps(CompileLedger.setup_phases())


def test_the_opt_states_builder_is_a_span_while_set_up_lasts(recorder):
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llm_fine_tune_distributed_tpu.config import MeshConfig
    from llm_fine_tune_distributed_tpu.parallel.optimizer import init_opt_state
    from llm_fine_tune_distributed_tpu.runtime.mesh import make_mesh

    mesh = make_mesh(MeshConfig(data=1, fsdp=-1, tensor=1, seq=1))
    trainable = jax.device_put({"w": jnp.ones((8, 4)), "b": jnp.zeros((4,))}, NamedSharding(mesh, P()))
    with annotate("startup/optimizer") as outer:
        state = init_opt_state(optax.adam(1e-3), trainable, mesh)
    assert jax.tree_util.tree_leaves(state)
    span, = [s for s in recorder.section()["spans"] if s["name"] == "startup/opt_state"]
    assert span["parent"] == outer.record["id"]
    inside = [s for s in recorder.section()["spans"] if s["parent"] == span["id"]]
    assert {s["name"] for s in inside} <= {"jit/trace", "jit/lower", "jit/compile"}  # the state's one program
    CompileLedger().mark_warm()
    init_opt_state(optax.adam(1e-3), trainable, mesh)  # after set-up: a TraceAnnotation alone
    assert len([s for s in recorder.section()["spans"] if s["name"] == "startup/opt_state"]) == 1


def test_the_trunks_quantizer_is_a_span_that_says_what_it_quantized(recorder):
    from llm_fine_tune_distributed_tpu.parallel.freeze import quantize_trunk_int8

    frozen = {f"model/layers/{i}/self_attn/q_proj/kernel": jnp.ones((16, 16)) * (i + 1) for i in range(3)}
    frozen["model/layers/0/input_layernorm/scale"] = jnp.ones((16,))
    quantized, n = quantize_trunk_int8(frozen, boundary=2)
    assert n == 2 and "model/layers/2/self_attn/q_proj/kernel" in quantized
    span, = [s for s in recorder.section()["spans"] if s["name"] == "startup/quantize_trunk"]
    assert span["parent"] == 0 and span["boundary"] == 2 and span["quantized"] == 2


_PROBE = """
import json
from llm_fine_tune_distributed_tpu.runtime.compile_cache import enable_compile_cache
enable_compile_cache()
import llm_fine_tune_distributed_tpu.train
from llm_fine_tune_distributed_tpu.observe.xla import CompileLedger
led = CompileLedger()
phases = led.setup_phases()
led.mark_warm()
import wave  # a module of the standard library that nothing above imports: after set-up
setup = led.setup()
import sys
print(json.dumps({"phases": phases, "counters": setup["counters"], "wave": type(wave.__loader__).__name__,
                  "finders": [type(f).__name__ for f in sys.meta_path if "llm_fine_tune" in type(f).__module__],
                  "nested": [s for s in setup["spans"] if s["name"] == "import/nested"],
                  "spans": [s for s in setup["spans"] if s["name"] in ("process/before_recorder", "import")]}))
"""


def test_a_process_that_enables_the_cache_and_imports_the_trainers_package_reports_that_import(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    read = json.loads(out.stdout.strip().splitlines()[-1])
    before, *imports = read["spans"]
    assert before["name"] == "process/before_recorder" and before["backend_started"] is False
    package, = [s for s in imports if s["module"] == "llm_fine_tune_distributed_tpu.train"]
    assert package["name"] == "import" and package["parent"] == 0 and package["cpu_s"] > 0.0
    assert (package["end_ns"] - package["start_ns"]) / 1e9 <= read["phases"]["phases_s"]["import"] + 1e-3  # rounded there
    assert list(read["phases"]["phases_s"])[:2] == ["process/before_recorder", "import"]
    assert "import/nested" not in read["phases"]["phases_s"]
    counters = read["counters"]
    assert counters["spans_dropped"] == 0 and read["wave"] == "SourceFileLoader" and read["finders"] == []
    nested = {s["module"] for s in read["nested"]}
    assert "orbax.checkpoint" in nested  # the trainer's checkpoints, under the package's own span
