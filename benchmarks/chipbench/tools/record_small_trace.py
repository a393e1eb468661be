"""Record the small trace that ``tests/test_chipbench.py`` checks ``trace.py``
on: three calls of one small jitted function on the chip inside a
``chipbench/`` host span. Writes ``chiprun_out/small.xplane.pb`` and
``chiprun_out/small.expected.json`` (the reduction's readings at recording
time, which the test pins). Run on the chip; copy both into ``testdata/``."""
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chipbench import trace  # noqa: E402

work = os.path.join(ROOT, ".chipbench_trace", "_small")
shutil.rmtree(work, ignore_errors=True)
f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
x = jnp.ones((1024, 1024), jnp.bfloat16)
f(x).block_until_ready()
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.host_tracer_level = 2
jax.profiler.start_trace(work, profiler_options=options)
with jax.profiler.TraceAnnotation("chipbench/small"):
    for _ in range(3):
        f(x).block_until_ready()
        time.sleep(0.002)
jax.profiler.stop_trace()
path = trace.find_xplane(work)
out = os.path.join(ROOT, "chiprun_out")
os.makedirs(out, exist_ok=True)
shutil.copy(path, os.path.join(out, "small.xplane.pb"))
red = trace.reduce_planes(trace.read_planes(path), chips=1)
with open(os.path.join(out, "small.expected.json"), "w") as fh:
    json.dump({"busy_s": red["busy_s"], "window_s": red["window_s"], "top_op": red["device_ops"][0][0],
               "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"],
               "device": jax.devices()[0].device_kind}, fh, indent=1)
print(os.path.getsize(path), "bytes;", red["device_ops"][:3], red["idle_gaps"][:3])
