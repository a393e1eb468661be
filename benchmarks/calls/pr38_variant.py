"""PR 38: one ingredient of the change at a time, IN PLACE, so that every variant runs from one path and finds
the same entries in the compile cache (a kernel's serialized body carries its file's path). Rewrites
``<tree>/llm_fine_tune_distributed_tpu/observe/xla.py`` (and ``runtime/compile_cache.py``) of the tree it is given:

    parent    both files as the parent commit has them (``_parent/``'s)
    change    both files as this PR has them (the repo's own)
    nosplit   the change, with ``_first_call`` calling ``fn.lower(...)`` under the lower span (no separate ``trace``)
    nolisten  the change, with ``install_compile_listeners()`` doing nothing
    noannot   the change, with a span that enters no ``TraceAnnotation``

    python benchmarks/calls/pr38_variant.py <tree> <variant>
"""
import os
import shutil
import sys

tree, variant = sys.argv[1], sys.argv[2]
FILES = ("llm_fine_tune_distributed_tpu/observe/xla.py", "llm_fine_tune_distributed_tpu/runtime/compile_cache.py")
source = "_parent" if variant == "parent" else "."
for f in FILES:
    shutil.copy(os.path.join(source, f), os.path.join(tree, f))
path = os.path.join(tree, FILES[0])
text = open(path).read()


def swap(old, new):
    global text
    assert text.count(old) == 1, old
    text = text.replace(old, new)


if variant == "nosplit":
    swap("                        traced = self._fn.trace(*args, **kwargs)\n", "                        pass\n")
    swap("                        lowered = traced.lower()\n", "                        lowered = self._fn.lower(*args, **kwargs)\n")
elif variant == "nolisten":
    swap("    global _listeners_installed\n", "    global _listeners_installed\n    return\n")
elif variant == "noannot":
    swap("        self._annotation = jax.profiler.TraceAnnotation(name)\n",
         "        import contextlib\n        self._annotation = contextlib.nullcontext()\n")
else:
    assert variant in ("parent", "change"), variant
open(path, "w").write(text)
print(f"{tree}: {variant}")
