"""PR 38: do the recorder's spans and a profiler capture line up? One set-up of a training cell's
``Program`` (state from the seed, the first step) under ``jax.profiler.start_trace`` begun BEFORE
``Program(...)``; then every ``train_step/*`` span of the recorder beside the xplane's
``TraceAnnotation`` of the same name. An xplane counts from the capture's start, the recorder from the
epoch, on the same clock: ``offset`` is the epoch read when ``start_trace`` returned.

Read on the chip (call A, when all five spans were annotations): durations equal to 18 us over 35 s,
and ``start_apart_us`` 47,511.8 to 47,523.8 for all five: the capture's zero lies 47.5 ms before
``start_trace`` returns there (0.01 ms on a CPU), and with that ONE offset the spans line up to 12 us.
Since then the two stages of the one ``fn.lower(...)`` call are JAX's own ``jit/trace`` and ``jit/lower``
events (no annotations); ``train_step/load``, ``/compile`` and ``/first_dispatch`` are the spans a capture shows.

    python benchmarks/calls/pr38_capture.py <cell> <seed>
"""
import glob
import importlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402

from benchmarks.chipbench import run, traffic  # noqa: E402

cell = run.load_cell(sys.argv[1], jax.devices()[0].platform == "cpu")
run.enable_cache()
kind = importlib.import_module(f"benchmarks.chipbench.kind_{cell['traffic']['kind']}")
out = tempfile.mkdtemp()
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.host_tracer_level = 2
jax.profiler.start_trace(out, profiler_options=options)
offset = time.time_ns()
program = kind.Program(cell["config"], cell["traffic"])
state = program.make_state(int(sys.argv[2]))
batch = program.put_batch(traffic.sft_batch(cell["traffic"], cell["config"]["vocab_size"], int(sys.argv[2]), 0))
state, metrics = program.step_fn(state, batch)
print("loss", float(metrics["loss"]), flush=True)
jax.profiler.stop_trace()
program.ledger.mark_warm()
spans = {s["name"]: s for s in program.ledger.setup()["spans"] if s["name"].startswith("train_step/")}
path = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
for plane in jax.profiler.ProfileData.from_file(path).planes:
    for line in plane.lines:
        for event in line.events:
            if event.name in spans:
                mine = spans[event.name]
                print(json.dumps({
                    "span": event.name, "seconds": round((mine["end_ns"] - mine["start_ns"]) / 1e9, 6),
                    "xplane_seconds": round(event.duration_ns / 1e9, 6),
                    "start_apart_us": round((event.start_ns + offset - mine["start_ns"]) / 1e3, 1),
                    "plane": plane.name, "line": line.name}), flush=True)
print(json.dumps({"xplane_bytes": os.path.getsize(path), "device": jax.devices()[0].device_kind}))
