"""Run one cell of kind ``sft_eva`` with a fault planted in EVA attention on
the program's side only, the reference left as it is. ``correct`` has to come
out false. The benchmark's own runs never run this (``tools/fault.py`` plants
the faults every training cell shares).

``--fault no_summaries``: no query sees any summary (R empty): every layer is
plain attention inside aligned windows. ``--fault own_window_summaries``: a
query sees its OWN window's summaries too (``c < 128 (w + 1)``): a leak of
later tokens that the reference does not have. Both replace the operator's one
rule of which windows' summaries a query reads (``ops/eva_attention._windows_seen``),
which both of its forms count by. ``--fault first_head_only``: the loss is head
0's alone (the usual next-token loss), the seven other heads get no gradient.

``python benchmarks/chipbench/tools/fault_eva.py --fault no_summaries --workload <cell> --seed <n> --seconds <s> --trace 0 [--rehearse 1]``
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from benchmarks.chipbench import run  # noqa: E402

FAULTS = ("no_summaries", "own_window_summaries", "first_head_only")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    at = argv.index("--fault")
    fault = argv[at + 1]
    del argv[at:at + 2]
    if fault not in FAULTS:
        raise SystemExit(f"fault_eva.py: --fault is one of {FAULTS}")
    import jax.numpy as jnp

    from llm_fine_tune_distributed_tpu.ops import eva_attention
    from llm_fine_tune_distributed_tpu.train import step

    seen, ahead, make_loss_fn = eva_attention._windows_seen, step.heads_ahead, step.make_loss_fn

    def first_head(x, heads):  # the masks: heads 1.. count for nothing (the ids pass as they are)
        out = ahead(x, heads)
        return out * (jnp.arange(heads) == 0) if heads > 1 and jnp.issubdtype(out.dtype, jnp.floating) else out

    def of_first_head(model_config, *args, **kwargs):  # ... and head 0 for the whole loss, not for its eighth
        loss_fn = make_loss_fn(model_config, *args, **kwargs)

        def scaled(*batch):
            loss, stats = loss_fn(*batch)
            return loss * model_config.num_pred_heads, stats

        return scaled

    if fault == "no_summaries":
        eva_attention._windows_seen = lambda w: w * 0
    elif fault == "own_window_summaries":
        eva_attention._windows_seen = lambda w: w + 1
    else:
        step.heads_ahead, step.make_loss_fn = first_head, of_first_head
    try:
        return run.main(argv)
    finally:
        eva_attention._windows_seen, step.heads_ahead, step.make_loss_fn = seen, ahead, make_loss_fn


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
