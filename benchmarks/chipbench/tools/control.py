"""Run one cell with its control switched on: the mix's ``control`` override
(the nearest precision below the one the configuration states, on the
program's own path: the int8 frozen trunk for training, the int8 KV pool for
serving) merged into the mix, then the rest of a run as ``run.py`` makes it.
``correct`` has to come out false. The benchmark's own runs never run this.

``python benchmarks/chipbench/tools/control.py --workload <cell> --seed <n> --seconds <s> --trace 0``
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from benchmarks.chipbench import run  # noqa: E402


def main(argv=None) -> int:
    load = run.load_cell

    def load_with_control(name, rehearse):
        cell = load(name, rehearse)
        cell["traffic"] = run._merge(cell["traffic"], cell["traffic"]["control"])
        return cell

    run.load_cell = load_with_control
    try:
        return run.main(argv)
    finally:
        run.load_cell = load


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
