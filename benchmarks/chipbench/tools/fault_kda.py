"""Run one cell of kind ``sft_kda_moe`` with the mechanism it exists for taken
out on the program's side only, the reference left as it is: the decay applied
as ONE scalar a head (the mean over the head's channels) instead of a vector.
``correct`` has to come out false. The benchmark's own runs never run this
(``tools/fault.py`` plants the faults every training cell shares).

``python benchmarks/chipbench/tools/fault_kda.py --workload <cell> --seed <n> --seconds <s> --trace 0 [--rehearse 1]``
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from benchmarks.chipbench import run  # noqa: E402


def main(argv=None) -> int:
    from llm_fine_tune_distributed_tpu.ops import gated_delta

    rule = gated_delta.gated_delta_rule

    def mean_decay(q, k, v, g, beta, **kw):
        return rule(q, k, v, g.mean(axis=-1) if g.ndim == 4 else g, beta, **kw)

    gated_delta.gated_delta_rule = mean_decay
    try:
        return run.main(argv)
    finally:
        gated_delta.gated_delta_rule = rule


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
