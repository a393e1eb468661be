"""Per-layer metrics of the train step BY SCOPE: which part of the step the
device's busy time went to.

The program names its step's operations with ``jax.named_scope``
(``observe/xla.py`` ``STEP_SCOPES``: ``embed``, ``layer<i>`` with ``attn`` and
``mlp`` inside, ``final_norm``, ``loss_head``, ``grad_accum``, ``optimizer``),
and JAX writes the transforms into the same path: on the chip an operation's
``tf_op`` reads

    jit(train_step)/while/body/closed_call/jvp(layer34)/mlp/dot_general:
    .../transpose(jvp(layer34))/jvp(layer34)/checkpoint/mlp/dot_general:
    .../transpose(jvp(layer34))/jvp(layer34)/checkpoint/rematted_computation/mlp/mul:
    jit(train_step)/optimizer/sub:

forward, backward and recomputed, in that order. ``xplane_meta`` reads the
``tf_op`` of every device operation from the run's trace, this module joins it
to the self time ``trace.py`` measured for the operation of the same name,
and a metric's file says which class it adds up. The frozen/trainable split
is not a scope: it is the layer's index against the configuration's depth
less the recipe's ``unfreeze_last_n_layers``.

XLA gives a fusion one ``op_name``, its root's: where it fuses operations of
two scopes, the fusion's whole time counts for the root's scope.

A reader returns None where it finds nothing to read: no trace, or a trace
in which no operation carries any scope of the vocabulary (a program from
before the scopes, or an executable that a compile cache keyed without debug
information handed over from such a program). In a trace that has scopes, a
class with no operation reads 0.0.
"""

from __future__ import annotations

import functools
import glob
import os
import re

from benchmarks.chipbench import xplane_meta

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

# a path component is a scope's name, bare or inside the transforms JAX wraps
# around it: jvp(layer3), transpose(jvp(layer3)); jit(name) is a function's name
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_LAYER = re.compile(r"^layer(\d+)$")
_CLASS_OF = {"embed": "embed", "final_norm": "loss_head", "loss_head": "loss_head",
             "grad_accum": "optimizer", "optimizer": "optimizer"}
CLASSES = ("frozen", "tail", "loss_head", "optimizer", "embed")
RECOMPUTED = "rematted_computation"
BACKWARD = "transpose("


def bare(component: str) -> str:
    while True:
        m = _WRAPPED.match(component)
        if m is None:
            return component
        component = m.group(1)


def classify(tf_op: str, first_trainable: int):
    """(class, backward, recomputed) of one operation's ``tf_op``. The class
    is one of ``CLASSES``, from the outermost component of the path that is a
    scope of the vocabulary, or None where there is none; a layer's whole
    index counts (``layer1`` is not ``layer12``)."""
    path = tf_op.split(";", 1)[0].rsplit(":", 1)[0]
    found = None
    for component in path.split("/"):
        name = bare(component)
        m = _LAYER.match(name)
        if m is not None:
            found = "frozen" if int(m.group(1)) < first_trainable else "tail"
        else:
            found = _CLASS_OF.get(name)
        if found is not None:
            break
    return found, BACKWARD in path, RECOMPUTED in path


def first_trainable_layer(config: dict, recipe: dict) -> int:
    if recipe.get("freeze_strategy") != "last_n_and_head":
        return 0  # lora, full: every layer is differentiated
    return max(0, int(config["num_hidden_layers"]) - int(recipe["unfreeze_last_n_layers"]))


def newest_xplane(root: str = ROOT):
    """The newest trace under ``.chipbench_trace/``: the run that asks has
    just written it (``run.py`` keeps one directory a cell and empties it
    before it traces)."""
    files = glob.glob(os.path.join(root, ".chipbench_trace", "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=2)
def _metadata(path: str, mtime: float):
    return xplane_meta.read(path)


def seconds_by_class(op_seconds: dict, metadata: dict, first_trainable: int):
    """{(class, backward, recomputed): seconds} over the operations of
    ``op_seconds`` (``trace.py``: self time by the event's name); an operation
    without ``tf_op`` counts under (None, False, False)."""
    out = {}
    for name, secs in op_seconds.items():
        key = classify(metadata.get(name, {}).get("tf_op", ""), first_trainable)
        out[key] = out.get(key, 0.0) + secs
    return out


def scope_time_pct(sources, spec, xplane_path=None):
    """Share of the device's busy time, in percent, in the operations the
    metric's file selects: ``classes`` (of ``CLASSES``; every operation when
    the key is absent), ``pass`` (``forward``: neither under ``transpose(``
    nor recomputed; ``backward``: under ``transpose(``, its recompute
    included; absent: both) and ``recomputed`` (true: only operations whose
    path says so)."""
    red = sources.get("trace")
    if not red or red["busy_s"] <= 0:
        return None
    path = xplane_path or newest_xplane()
    if path is None:
        return None
    by_class = seconds_by_class(
        red["op_seconds"], _metadata(path, os.path.getmtime(path)),
        first_trainable_layer(sources["config"], sources["traffic"]["recipe"]),
    )
    if not any(cls is not None for cls, _, _ in by_class):
        return None  # the program has no scopes: nothing to read
    classes, which, recomputed = spec.get("classes"), spec.get("pass"), spec.get("recomputed")
    secs = 0.0
    for (cls, backward, remat), s in by_class.items():
        if classes is not None and cls not in classes:
            continue
        if which == "forward" and (backward or remat):
            continue
        if which == "backward" and not backward:
            continue
        if recomputed and not remat:
            continue
        secs += s
    return 100.0 * secs / red["busy_s"]


def train_step_load_s(sources, spec):
    """Wall seconds of the step program's ``lower().compile()`` as the
    program's own ``CompileLedger`` recorded them: a compile on a cold cache,
    a load on a warm one."""
    ledger = sources.get("compile_ledger") or {}
    program = ledger.get("programs", {}).get(spec.get("program", "train_step"))
    return None if program is None else program["compile_s"]
