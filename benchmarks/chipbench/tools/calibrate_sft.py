"""Read the numbers an sft cell's ``correct`` compares, for many seeds in one
process (set-up is long): the program's, and on the first ``--control`` seeds
also those of the control (the mix's ``control`` override: the int8 frozen
trunk). Limits are set from these readings (PERF.md section 2). Each row also
keeps the first gradient's error leaf by leaf: where the control changes more
than the precision (with a tied table the int8 trunk also drops the table's
gradient through the trunk), its leaves whose gradient path is unchanged are
read apart. Gradient clipping carries such a change to every leaf (without the
table's gradient the global norm is 0.27, not 2.04, and nothing is clipped), so
a control's row also keeps, by leaf, the scale ``alpha`` that fits its gradient
to the reference's and the error that is left once that scale is taken out:
what the lower precision alone does. ``--steps 1`` reads the first step's
numbers alone, in half the time; ``--only-control 1`` skips the sound program.

``python benchmarks/chipbench/tools/calibrate_sft.py --workload <cell> --seeds 1,2,3 --control 3``
"""
import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import check, kind_sft, run  # noqa: E402


def fitted_errs(program: dict, reference: dict, reference_norms: dict) -> dict:
    """By leaf: (alpha, error of program / alpha), with alpha the least-squares
    scale of the program's leaf on the reference's."""
    out = {}
    for path, ref in reference.items():
        got = program[path]
        alpha = float(np.vdot(got, ref) / np.vdot(ref, ref))
        err = check.leaf_rel_errs({path: got / alpha}, {path: ref}, reference_norms)[path]
        out[path] = [alpha, err]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--only-control", type=int, default=0)
    args = ap.parse_args()
    cell = run.load_cell(args.workload, bool(args.rehearse))
    cfg, mix, limits = cell["config"], cell["traffic"], cell["limits"]
    steps = args.steps or int(limits["steps"])
    loose = {k: float("inf") for k in limits if k.endswith(("_gap", "_err"))}
    loose["first_grad_worst_leaf_rel_err"] = float("inf")

    import jax

    run.enable_cache()
    programs = {} if args.only_control else {"program": kind_sft.Program(cfg, mix)}
    if args.control:
        programs["control"] = kind_sft.Program(cfg, run._merge(mix, mix["control"]))
    out_path = os.path.join(ROOT, "chiprun_out", f"calibrate_{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        ref = kind_sft.reference_readings(cfg, mix, seed, steps, keep_first_grad=True)
        t_ref = time.time() - t0
        for which, program in programs.items():
            if which == "control" and n >= args.control:
                continue
            state = program.make_state(seed)
            state, read = kind_sft.program_readings(program, state, seed, steps, keep_first_grad=True)
            del state
            print(f"--- seed {seed} {which}", flush=True)
            checks = kind_sft.compare(read, ref, loose)
            row = {"seed": seed, "which": which, "reference_s": t_ref,
                   "device": jax.devices()[0].device_kind,
                   "numbers": {r["name"]: r["value"] for r in checks.rows},
                   "notes": {r["name"]: r["note"] for r in checks.rows},
                   "first_grad_rel_err_by_leaf": check.leaf_rel_errs(
                       read["first_grad"], ref["first_grad"], ref["first_grad_norms"])}
            if which == "control":
                row["first_grad_alpha_and_fitted_err_by_leaf"] = fitted_errs(
                    read["first_grad"], ref["first_grad"], ref["first_grad_norms"])
            with open(out_path, "a") as f:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
