"""Run one training cell with a fault planted on the program's side only, the
reference left as it is: what a limit that no lower precision moves is held
against. ``correct`` has to come out false, by the limit named. The
benchmark's own runs never run this.

``--fault half_batch``: the second half of every microbatch's rows (of the
step's microbatches, where a microbatch is one row) counts for nothing in the
program's loss (their ``loss_mask`` is zero), the reference trains on all of
them: a part of the batch left out. Fails ``loss_abs_gap``.

``--fault unchanged_state``: the program's learning rate is zero, so its step
returns the parameters it was given. Fails ``param_change_worst_leaf_gap``,
which reads 1.0.

``python benchmarks/chipbench/tools/fault.py --fault half_batch --workload <cell> --seed <n> --seconds <s> --trace 0``
"""
import copy
import importlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from benchmarks.chipbench import kind_sft, run  # noqa: E402

FAULTS = ("half_batch", "unchanged_state")


def _half_batch(put_batch):
    def put(self, batch):
        mask = batch["loss_mask"].copy()  # [accum, rows, seq]
        if mask.shape[1] > 1:
            mask[:, mask.shape[1] // 2:, :] = 0.0
        elif mask.shape[0] > 1:  # one row a microbatch: the second half of the microbatches
            mask[mask.shape[0] // 2:] = 0.0
        else:
            raise SystemExit("fault.py: half_batch needs two rows or two microbatches a step")
        return put_batch(self, dict(batch, loss_mask=mask))

    return put


def _zero_learning_rate(init):
    def __init__(self, cfg, mix):
        mix = copy.deepcopy(mix)
        mix["recipe"]["learning_rate"] = 0.0
        init(self, cfg, mix)

    return __init__


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    at = argv.index("--fault")
    fault = argv[at + 1]
    del argv[at:at + 2]
    if fault not in FAULTS:
        raise SystemExit(f"fault.py: --fault is one of {FAULTS}")
    cell = argv[argv.index("--workload") + 1]
    kind = run.load_cell(cell, False)["traffic"]["kind"]
    program = importlib.import_module(f"benchmarks.chipbench.kind_{kind}").Program
    put_batch, init = kind_sft.Program.put_batch, program.__init__
    if fault == "half_batch":
        kind_sft.Program.put_batch = _half_batch(put_batch)  # every training kind's Program inherits it
    else:
        program.__init__ = _zero_learning_rate(init)
    try:
        return run.main(argv)
    finally:
        kind_sft.Program.put_batch, program.__init__ = put_batch, init


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
