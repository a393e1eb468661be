"""Times on the chip (PYTHONPATH=. python benchmarks/chipbench/tools/calls/pr30_kernels.py, at the end of the proof call): the streamed flash kernels against the resident ones on a row both can take,
and the window's against the causal ones at the Mellum cell's shapes. Forward and forward+backward, median of 10."""
import statistics, time
import jax, jax.numpy as jnp
from llm_fine_tune_distributed_tpu.ops import flash_attention as fa

def timed(fn, *args):
    out = fn(*args); jax.block_until_ready(out)
    ts = []
    for _ in range(10):
        t = time.perf_counter(); jax.block_until_ready(fn(*args)); ts.append(time.perf_counter() - t)
    return statistics.median(ts) * 1e3

def case(name, rows, seq, hq, hkv, d, dv, window, force_stream):
    cap = fa._VMEM_CAP_BYTES
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (rows, seq, hq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (rows, seq, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (rows, seq, hkv, dv), jnp.bfloat16)
    try:
        if force_stream:
            fa._VMEM_CAP_BYTES = 0
        label = fa.program_label(q, k, v, sliding_window=window)
        f = jax.jit(lambda q, k, v: fa.pallas_flash_attention(q, k, v, sliding_window=window))
        g = jax.jit(jax.grad(lambda q, k, v: fa.pallas_flash_attention(q, k, v, sliding_window=window).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        print(f"{name}: {label}: forward {timed(f, q, k, v):.3f} ms, forward+backward {timed(g, q, k, v):.3f} ms", flush=True)
    finally:
        fa._VMEM_CAP_BYTES = cap

print(jax.devices()[0].device_kind)
case("moonlight 4x4096 16h 192/128", 4, 4096, 16, 16, 192, 128, None, False)
case("moonlight 4x4096 16h 192/128", 4, 4096, 16, 16, 192, 128, None, True)
case("smollm3 2x4096 16/4 128", 2, 4096, 16, 4, 128, 128, None, False)
case("smollm3 2x4096 16/4 128", 2, 4096, 16, 4, 128, 128, None, True)
case("mellum 4x8192 32/4 128 global", 4, 8192, 32, 4, 128, 128, None, False)
case("mellum 4x8192 32/4 128 window", 4, 8192, 32, 4, 128, 128, 1024, False)
