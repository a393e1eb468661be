"""PR 38, review round, on a CPU (Python's own time in a bare process, no device metric): does the recorder make the Mistral
step's `backward` dearer to trace, as call G read it inside the harness's process (0.71 -> 1.25 s)? The cell's step over abstract
state, its flash kernels traced as on a TPU, `bare` and with the change's listeners and one span open (`spans`); JAX's own events
of 50 ms or more. Read: `backward` 0.444, 0.400 bare and 0.453, 0.409 under the spans: not reproduced outside the harness.

    JAX_PLATFORMS=cpu python benchmarks/calls/pr38_trace_ab.py bare|spans
"""
import json, os, sys, time
sys.path.insert(0, os.getcwd())
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from llm_fine_tune_distributed_tpu.observe.scaling import abstract_train_setup
from llm_fine_tune_distributed_tpu.observe import xla
variant = sys.argv[1]
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
jax.default_backend = lambda: "tpu"
seen = []
def probe(event, s, e, **kw):
    if e - s >= 0.05: seen.append((event.split("/")[-1], str(kw.get("fun_name")), round(e - s, 3)))
jax.monitoring.register_event_time_span_listener(probe)
recipe = dict(freeze_strategy="last_n_and_head", unfreeze_last_n_layers=2, attention_impl="flash", remat_policy="dots_no_batch")
setup = abstract_train_setup({"data": 1, "fsdp": 1, "tensor": 1, "seq": 1}, "mistral_7b", devices=topo.devices[:1], accum=16, seq=2048,
                             per_dp_batch=1, param_dtype="bfloat16", train_kwargs=recipe, model_overrides=dict(num_layers=16))
t0 = time.perf_counter()
if variant == "bare":
    lowered = setup.step.lower(setup.state, setup.batch)
else:
    xla.install_compile_listeners()
    with xla.annotate("train_step/load", program="train_step"):
        lowered = setup.step.lower(setup.state, setup.batch)
print(json.dumps({"variant": variant, "lower_call_s": round(time.perf_counter() - t0, 3), "stages": [s for s in seen if s[1] in ("train_step", "jit(train_step)", "backward", "forward", "wrapped")]}))
