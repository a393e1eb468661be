"""Parallel device->host transfer.

The artifact-export and checkpoint paths move 2-6 GB from the device at end
of training. Leaves are fetched through a small thread pool — any huge leaf
split into row blocks, so that one 0.5 GB embedding table cannot serialize
the pool — because one device->host stream need not saturate the host's link
to the chip (the GIL releases during each copy). What the pool buys on a
locally attached v5e: not measured; it was tuned on a slow remote link that
no longer exists.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict

import numpy as np

_DEFAULT_WORKERS = 4
_SPLIT_BYTES = 128 * 1024 * 1024


def _leaf_spans(leaf, split_bytes: int):
    """Row spans to fetch a leaf in. The SLICING happens inside the worker
    (just before its np.asarray), not here: a JAX slice is a device COPY,
    and pre-materializing every block of every big leaf would spike
    transient HBM by the total large-leaf size — on a chip already near the
    ceiling that is an OOM (r5 review finding). Lazy slicing bounds the
    transient to workers x split_bytes."""
    nbytes = getattr(leaf, "nbytes", 0)
    shape = getattr(leaf, "shape", ())
    if nbytes <= split_bytes or not shape or shape[0] < 2:
        return [None]  # fetch whole
    rows = shape[0]
    n_blocks = min(rows, max(2, -(-nbytes // split_bytes)))
    step = -(-rows // n_blocks)
    return [(i, min(i + step, rows)) for i in range(0, rows, step)]


def parallel_device_get(
    flat: Dict[str, Any], workers: int = _DEFAULT_WORKERS, split_bytes: int = _SPLIT_BYTES
) -> Dict[str, np.ndarray]:
    """{name: device_array} -> {name: np.ndarray}, fetched concurrently.

    Only valid for process-local (fully addressable) arrays — multi-process
    resharding must happen before this (trainer._host_fetch does). Large
    leaves are sliced into row blocks on device (cheap view-copies) so their
    transfer parallelizes too.
    """
    jobs = []  # (key, span) — leaves looked up at fetch time, sliced lazily
    for k, v in flat.items():
        for span in _leaf_spans(v, split_bytes):
            jobs.append((k, span))

    def fetch(job):
        k, span = job
        leaf = flat[k]
        piece = leaf if span is None else leaf[span[0] : span[1]]
        arr = np.asarray(piece)
        del piece  # free the device block before the next one is sliced
        return k, span, arr

    out: Dict[str, Any] = {}
    with ThreadPoolExecutor(max_workers=workers) as pool:
        for k, span, arr in pool.map(fetch, jobs):
            if span is None:
                out[k] = arr
            else:
                out.setdefault(k, []).append((span, arr))
    for k, v in list(out.items()):
        if isinstance(v, list):
            v.sort(key=lambda p: p[0][0])
            out[k] = np.concatenate([arr for _, arr in v], axis=0)
    return out


def parallel_device_get_tree(tree, workers: int = _DEFAULT_WORKERS,
                             split_bytes: int = _SPLIT_BYTES):
    """Pytree version of :func:`parallel_device_get`. Holds no reference to
    the input leaves after returning, so a caller that drops its own
    reference (e.g. the background checkpoint saver's on-device snapshot)
    frees the device buffers immediately — before any slow downstream write."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    fetched = parallel_device_get(
        {str(i): leaf for i, leaf in enumerate(leaves)},
        workers=workers, split_bytes=split_bytes,
    )
    del leaves, tree
    return jax.tree_util.tree_unflatten(
        treedef, [fetched[str(i)] for i in range(len(fetched))]
    )
