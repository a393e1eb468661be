"""The program's edge (PR 21): which device a measurement may run on, where
compiled programs are cached, and that the chip smoke refuses a CPU.

No test here starts a child that would probe for a chip: the decisions are
tested as functions of the platform and the environment, and the one child
(`chip_smoke.py`) is started with ``JAX_PLATFORMS=cpu`` already set.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from llm_fine_tune_distributed_tpu.runtime import compile_cache
from llm_fine_tune_distributed_tpu.runtime.device import (
    NoAcceleratorError,
    cpu_requested,
    on_accelerator,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize(
    "platform, environ, expected",
    [
        ("tpu", {}, True),
        ("tpu", {"JAX_PLATFORMS": "cpu"}, True),  # what ran is what counts
        ("cpu", {"JAX_PLATFORMS": "cpu"}, False),  # a rehearsal, asked for
        ("cpu", {"JAX_PLATFORMS": " CPU "}, False),
    ],
)
def test_on_accelerator_decision(platform, environ, expected):
    assert on_accelerator(platform, environ) is expected


@pytest.mark.parametrize(
    "environ", [{}, {"JAX_PLATFORMS": ""}, {"JAX_PLATFORMS": "tpu,cpu"}]
)
def test_a_cpu_nobody_asked_for_is_an_error(environ):
    """bench.py, decode_bench.py and serve_bench.py all decide through this:
    finding a CPU is a failure, not a tiny fallback run."""
    assert not cpu_requested(environ)
    with pytest.raises(NoAcceleratorError, match="JAX_PLATFORMS=cpu"):
        on_accelerator("cpu", environ)


def test_bench_recipe_refuses_or_rehearses(monkeypatch):
    """bench.py's own decision, in process: with JAX_PLATFORMS=cpu (set by
    conftest.py) the tiny rehearsal recipe; with it unset, the error that
    main() turns into a non-zero exit."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    import jax

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    platform, preset, *_ = bench._recipe()
    assert (platform, preset) == ("cpu", "tiny")
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(NoAcceleratorError):
        bench._recipe()
    cache_before = jax.config.jax_compilation_cache_dir
    try:
        with pytest.raises(SystemExit) as exc:
            bench.main()
    finally:  # main() switched the persistent cache on: not for the suite
        jax.config.update("jax_compilation_cache_dir", cache_before)
    assert exc.value.code not in (0, None)


def test_compile_cache_honours_the_environment(monkeypatch):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    # JAX reads the variable itself: the helper set no directory in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        first = compile_cache.enable_compile_cache()
        assert first == os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == first  # no pid, no time
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_sets_nothing_for_an_installed_package(monkeypatch, tmp_path):
    """``pip install .`` puts the package under site-packages: the directory
    above it is no checkout, and the helper must not write beside it."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "_CHECKOUT", str(tmp_path))
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert list(tmp_path.iterdir()) == []


def test_chip_smoke_refuses_a_cpu():
    """On a CPU the smoke exits non-zero and prints no result line. The
    kernels phase comes first and fails in seconds, before any model runs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    last = proc.stdout.strip().splitlines()[-1]
    try:
        assert json.loads(last).get("ok") is not True
    except json.JSONDecodeError:
        pass  # not a result line at all
    assert '"ok": true' not in proc.stdout
