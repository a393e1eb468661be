"""Run one cell of kind ``sft_ssd`` with a fault planted in the model on the program's side only, the reference left
as it is. ``correct`` has to come out false. The benchmark's own runs never run this (``tools/fault.py`` plants the
faults every training cell shares).

``--fault no_decay``: ``a = 1``, a state that never forgets (``A`` handed to the scan as 0). ``--fault
norm_before_gate``: ``N(y) * silu(z)``, the other Mamba-2 order. ``--fault sqrt_scale``: the attention layers' scores
at ``head_dim ** -0.5``, what every other model here uses, in place of the config's ``attention_multiplier``.
``--fault unit_residual``: ``residual_multiplier`` 1.

``python benchmarks/chipbench/tools/fault_ssd.py --fault no_decay --workload <cell> --seed <n> --seconds <s> --trace 0 [--rehearse 1]``
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from benchmarks.chipbench import run  # noqa: E402

FAULTS = ("no_decay", "norm_before_gate", "sqrt_scale", "unit_residual")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    at = argv.index("--fault")
    fault = argv[at + 1]
    del argv[at:at + 2]
    if fault not in FAULTS:
        raise SystemExit(f"fault_ssd.py: --fault is one of {FAULTS}")
    import jax
    import jax.numpy as jnp

    from llm_fine_tune_distributed_tpu.models import transformer
    from llm_fine_tune_distributed_tpu.ops import ssd

    scan, gated_norm, scaled, residual = ssd.ssd_scan, ssd.gated_norm, transformer._scaled_queries, transformer._residual

    def norm_then_gate(y, z, weight, eps, *, groups=1):
        rows, s, inner = y.shape
        y32 = y.astype(jnp.float32).reshape(rows, s, groups, inner // groups)
        normed = (y32 * jax.lax.rsqrt(jnp.mean(jnp.square(y32), axis=-1, keepdims=True) + eps)).reshape(rows, s, inner)
        return (normed * weight.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)

    if fault == "no_decay":
        ssd.ssd_scan = lambda x, dt, a, *rest, **kw: scan(x, dt, a * 0.0, *rest, **kw)
    elif fault == "norm_before_gate":
        ssd.gated_norm = norm_then_gate
    elif fault == "sqrt_scale":
        transformer._scaled_queries = lambda xq, config: xq
    else:
        transformer._residual = lambda y, config: y
    try:
        return run.main(argv)
    finally:
        ssd.ssd_scan, ssd.gated_norm, transformer._scaled_queries, transformer._residual = scan, gated_norm, scaled, residual


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
