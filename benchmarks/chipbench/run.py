#!/usr/bin/env python3
"""One run of one cell: ``python benchmarks/chipbench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

One process. It fails (exit 2, no result line) when JAX finds no TPU or fewer
chips than the cell asks for; ``--rehearse 1`` is the only way onto a CPU: a
rehearsal of the control flow at ``configs/tiny.json`` and the mix's
``rehearsal`` sizes, whose line says ``"platform": "cpu"`` and whose numbers
are not rates. Everything that belongs to one configuration, one traffic mix,
one per-layer metric or one cell lives in a file of its own, found by the
name in ``BENCHMARK.json``: see README.md.
"""

from __future__ import annotations

import time

_T0 = time.time()

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def process_age_s() -> float:
    """Seconds since this process started (interpreter start-up included)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time() - _T0


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearse: bool) -> dict:
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    parked = os.path.join(HERE, "parked", name + ".json")
    if name not in cells and os.path.exists(parked):
        # a cell kept for a later benchmark PR: it runs by name, and no check runs it
        for group, entries in load_json(parked).items():
            if group in bench:
                bench[group] = bench[group] + entries
        cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = dict(cells[name])
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(ROOT, config_entry["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = load_json(HERE, "limits", name + ".json")
    if rehearse:
        mix = _merge(mix, mix.get("rehearsal", {}))
        cfg = _merge(load_json(HERE, "configs", "tiny.json"), mix.get("rehearsal_config", {}))
        limits = _merge(limits, limits.get("rehearsal", {}))
    return {"bench": bench, "name": name, "chips": int(cell["chips"]), "config": cfg,
            "traffic": mix, "limits": limits}


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def metrics_for(cell: dict, group: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell reports:
    those that list it under ``workloads``, and those without the key."""
    out = []
    for m in cell["bench"][group]:
        if "workloads" not in m or cell["name"] in m["workloads"]:
            out.append(m)
    return out


class Harness:
    """What a kind's runner gets from the harness: the window's marks, host
    spans on the profiler's clock, the device trace, and the memory peak."""

    def __init__(self, args, cell):
        self.args = args
        self.cell = cell
        self.trace_dir = os.path.join(ROOT, ".chipbench_trace", cell["name"])
        self.trace_s = float(cell["traffic"].get("trace_s", 10.0))
        self.tracing = False
        self.setup_s = None
        self._stopper = None

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"chipbench/{name}")

    def start_window(self) -> None:
        self.setup_s = process_age_s()
        if self.args.trace:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.tracing = True

    def trace_tick(self, elapsed_s: float, background: bool = False) -> None:
        """Called between units of work: ends the trace once ``trace_s`` of
        the window have been traced (a trace of the whole window is too large
        to bring back, and tracing slows the host). Writing the trace out
        takes seconds: a load generator asks for it in the ``background`` so
        that it goes on sending."""
        if self.tracing and elapsed_s >= self.trace_s:
            self.tracing = False
            import jax

            if background:
                import threading

                self._stopper = threading.Thread(target=jax.profiler.stop_trace)
                self._stopper.start()
            else:
                jax.profiler.stop_trace()

    def stop_window(self) -> None:
        self.trace_tick(float("inf"))
        if self._stopper is not None:
            self._stopper.join()
            self._stopper = None

    def memory_peak(self) -> int:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
        return int(max(peaks))

    def memory_held(self) -> int:
        """Buffers in use now plus the scratch the chip keeps reserved for
        the loaded programs, on the fullest chip: what the cell holds."""
        import jax

        held = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            held.append(stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0))
        return int(max(held))


def enable_cache():
    """The program's own cache switch ($JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache), with every program kept however quickly it
    compiled, so that a second run of a cell finds them all."""
    import jax

    from llm_fine_tune_distributed_tpu.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, bool(args.rehearse))

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not (args.rehearse and platform == "cpu"):
        print(f"chipbench: JAX found platform {platform!r}, not a TPU; a measurement does not "
              "fall back to it (--rehearse 1 is the CPU rehearsal)", file=sys.stderr)
        return 2
    if platform == "tpu" and len(devices) < cell["chips"]:
        print(f"chipbench: cell {cell['name']} needs {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = None
    if platform == "tpu":
        table = load_json(HERE, "peaks.json")
        if devices[0].device_kind not in table:
            print(f"chipbench: no published peaks for device kind {devices[0].device_kind!r} in "
                  "peaks.json", file=sys.stderr)
            return 2
        peaks = table[devices[0].device_kind]

    cache_dir = enable_cache()

    harness = Harness(args, cell)
    kind = cell["traffic"]["kind"]
    runner = importlib.import_module(f"benchmarks.chipbench.kind_{kind}")
    result = runner.run(cell, args, harness)

    end_to_end = dict(result["end_to_end"], setup_s=harness.setup_s)
    device = {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(result["memory_peak_bytes"]),
    }
    line = {
        "correct": bool(result["checks"].correct),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
    }
    metrics = {}
    if not args.trace:
        for m in metrics_for(cell, "end_to_end"):
            if m["name"] in end_to_end:
                metrics[m["name"]] = {"value": end_to_end[m["name"]], "unit": m["unit"]}
    else:
        from benchmarks.chipbench import trace as trace_mod

        sources = dict(result["sources"], peaks=peaks, end_to_end=end_to_end, config=cell["config"],
                       traffic=cell["traffic"])
        reduced = trace_mod.reduce_dir(harness.trace_dir, chips=cell["chips"])
        sources["trace"] = reduced
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                                 "idle_gaps": reduced["idle_gaps"][:10]}
        for m in metrics_for(cell, "per_layer"):
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            module, func = spec["reader"].rsplit(".", 1)
            reader = getattr(importlib.import_module(f"benchmarks.chipbench.{module}"), func)
            value = reader(sources, spec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = result["checks"].rows
    line["compile_cache_dir"] = cache_dir
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # daemon threads of the serving engine must not outlive the run
