"""Record the scoped trace that ``tests/test_scopes.py`` pins
``readers/scopes.py`` on: three calls, inside a ``chipbench/`` host span, of
one small jitted function built as the train step is built (two ``layer<i>``
scopes from the program's own ``scope()`` helper, each layer under
``jax.checkpoint``, a ``loss_head`` with its own matmul, a ``grad`` with
respect to the input, the second layer's weight and the head only, an
``optimizer`` update clipped by the global norm): layer 0 is a frozen layer
with a forward, an activation gradient and its recompute, layer 1 the
trainable tail. Writes ``chiprun_out/scoped.xplane.pb``,
``chiprun_out/scoped.expected.json`` (the readers' shares at recording time,
which the test pins, and the ``tf_op`` of every device operation) and prints
the operations. Run on the chip; copy both into ``testdata/``."""
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

from benchmarks.chipbench import run, trace, xplane_meta  # noqa: E402
from benchmarks.chipbench.readers import scopes  # noqa: E402
from llm_fine_tune_distributed_tpu.observe.xla import scope  # noqa: E402

CONFIG = {"num_hidden_layers": 2}
RECIPE = {"freeze_strategy": "last_n_and_head", "unfreeze_last_n_layers": 1}
METRICS = ("frozen_fwd_time_pct.train", "frozen_bwd_time_pct.train", "tail_time_pct.train",
           "loss_head_time_pct.train", "optimizer_time_pct.train", "remat_time_pct.train",
           "scoped_time_pct.train")


def loss(x, w1, head, w0):
    h = x
    for i, w in enumerate((w0, w1)):
        with scope("layer", i):
            h = jax.checkpoint(lambda h, w: jnp.tanh(h @ w))(h, w)
    with scope("loss_head"):  # an unembed of its own, as the step's head has
        logits = (h @ head).astype(jnp.float32)
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1))


@jax.jit
def step(x, w1, head, w0):
    gx, g1, gh = jax.grad(loss, argnums=(0, 1, 2))(x, w1, head, w0)
    with scope("optimizer"):  # clipped by the global norm: it needs every gradient whole, so it fuses into none
        clip = 1.0 / jnp.maximum(1.0, jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in (gx, g1, gh))))
        return tuple((p - 0.1 * clip * g).astype(p.dtype) for p, g in ((x, gx), (w1, g1), (head, gh)))


def main():
    work = os.path.join(ROOT, ".chipbench_trace", "_scoped")
    shutil.rmtree(work, ignore_errors=True)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (4096, 2048), jnp.bfloat16)
    w0 = jax.random.normal(keys[1], (2048, 2048), jnp.bfloat16) * 0.02
    w1 = jax.random.normal(keys[2], (2048, 2048), jnp.bfloat16) * 0.02
    head = jax.random.normal(keys[3], (2048, 4096), jnp.bfloat16) * 0.02
    jax.block_until_ready(step(x, w1, head, w0))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(work, profiler_options=options)
    with jax.profiler.TraceAnnotation("chipbench/scoped"):
        for _ in range(3):
            jax.block_until_ready(step(x, w1, head, w0))
            time.sleep(0.002)
    jax.profiler.stop_trace()
    path = trace.find_xplane(work)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "scoped.xplane.pb"))
    red = trace.reduce_planes(trace.read_planes(path), chips=1)
    sources = {"trace": red, "config": CONFIG, "traffic": {"recipe": RECIPE}}
    shares = {}
    for name in METRICS:
        spec = run.load_json(os.path.dirname(HERE), "metrics", name + ".json")
        shares[name] = scopes.scope_time_pct(sources, spec, xplane_path=path)
    meta = xplane_meta.read(path)
    ops = sorted(((secs, trace.short_name(name), meta.get(name, {}).get("tf_op", ""))
                  for name, secs in red["op_seconds"].items()), reverse=True)
    with open(os.path.join(out, "scoped.expected.json"), "w") as fh:
        json.dump({"busy_s": red["busy_s"], "shares": shares, "config": CONFIG, "recipe": RECIPE,
                   "tf_ops": {name: tf_op for _, name, tf_op in ops},
                   "device": jax.devices()[0].device_kind,
                   "recorded_by": "tools/record_scoped_trace.py on the chip"}, fh, indent=1)
    print(os.path.getsize(path), "bytes; busy_s", red["busy_s"])
    for secs, name, tf_op in ops:
        print(f"{secs * 1e6:9.1f} us  {name:28s} {tf_op}")
    print(json.dumps(shares))


if __name__ == "__main__":
    main()
