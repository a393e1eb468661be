"""Where compiled programs are kept between processes.

A cold SmolLM3-3B step program is minutes of XLA compile; the trainer, the
server and the bench scripts are separate processes that compile many of the
same programs. JAX's persistent compilation cache keys an entry by its
directory among other things, so the directory must not move: it is the one
``JAX_COMPILATION_CACHE_DIR`` names when the caller set it (JAX reads that
itself and this module sets nothing), and otherwise one fixed path inside the
checkout — never a temp name, a pid or a time. An installed package
(``pip install .``, as deploy/Dockerfile does) has no checkout around it:
there the directory beside site-packages is not ours to write, so without the
variable nothing is set and JAX keeps its own default (no persistent cache).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str | None:
    """Call first thing in an entry point, before anything compiles. Returns
    the directory the process caches in, or None when it set none (an
    installed package without ``JAX_COMPILATION_CACHE_DIR``). From here on the
    process's span recorder hears JAX's compile and cache events
    (``observe/xla.install_compile_listeners``): what set-up compiles, and
    what it finds in the cache, is counted from the first program."""
    from llm_fine_tune_distributed_tpu.observe.xla import install_compile_listeners

    install_compile_listeners()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if not os.path.isfile(os.path.join(_CHECKOUT, "pyproject.toml")):
        return None  # not a checkout: site-packages' parent is not ours
    import jax

    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
