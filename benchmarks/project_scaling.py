#!/usr/bin/env python
"""v5e-16 scaling projection for the flagship SFT recipe.

Compiles the EXACT benchmark train step (SmolLM3-3B, per-chip batch 2,
grad-accum 16, seq 1024, bf16 masters — bench.py's measured recipe) over
16 virtual devices for each candidate mesh, accounts the compiled program's
per-step collective bytes (observe/comm_accounting.py), and projects per-step
time on a real v5e-16 slice with the link model in observe/scaling.py:

    step_time = measured_single_chip_compute + exposed_collective_time

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=16 JAX_PLATFORMS=cpu \
      python benchmarks/project_scaling.py

Prints a markdown table plus one JSON line per mesh. The single-chip rate it
scales from is an input, PROJ_MEASURED_SPS: samples/sec/chip from a chip run
of bench.py (the 10.126 this script once defaulted to was taken over a
device link that is gone; on the attached v5e: not measured).

Honesty notes:
- the CPU backend's SPMD partitioner emits all-reduce+slice where TPU emits
  reduce-scatter, and lacks TPU's while-loop all-reduce sinking pass — so the
  accounted bytes are an UPPER bound on what the TPU program moves;
- 0% compute/communication overlap is assumed (every collective exposed);
  XLA's latency-hiding scheduler typically hides FSDP gathers behind the
  matmuls they feed, so real steps land at or below the projection;
- attention is the XLA impl for the CPU compile (the Pallas flash kernel
  does not lower on CPU); attention collectives are unaffected (none ride
  the mesh axes used here).
"""

import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llm_fine_tune_distributed_tpu.observe.scaling import (  # noqa: E402
    V5E,
    abstract_train_setup,
    project_step_time,
)

# bench.py's measured single-chip recipe + its end-of-round-2 result.
# The same rate is assumed for the larger-microbatch variants; validate on
# the real chip with  BENCH_BATCH=8 BENCH_ACCUM=4 python bench.py  (larger
# microbatches change HBM pressure, not per-sample matmul FLOPs).
if "PROJ_MEASURED_SPS" not in os.environ:
    raise SystemExit(
        "project_scaling.py: set PROJ_MEASURED_SPS to the samples/sec/chip a "
        "chip run of bench.py measured — there is no default to project from"
    )
MEASURED_SAMPLES_PER_SEC_PER_CHIP = float(os.environ["PROJ_MEASURED_SPS"])
SEQ = 1024
BASELINE_AGG_4GPU = 6.78 * 4                 # derived 4xL40S aggregate (bench.py)

# (mesh, per_dp_batch, accum): the single-chip sweep picked microbatch 2 x
# accum 16 because full remat + optimizer state crowd a lone chip's 16 GB;
# under 16-way FSDP the param/optimizer bytes shard away, so LARGER
# microbatches become affordable — and FSDP's all-gather volume scales with
# the NUMBER of microbatches, not their size, so accum 4 x microbatch 8 moves
# 4x fewer param bytes per step for the same 512-sample step.
MESHES = [
    ({"data": 2, "fsdp": 8}, 2, 16),
    ({"data": 4, "fsdp": 4}, 2, 16),
    ({"fsdp": 16}, 2, 16),
    ({"fsdp": 8, "tensor": 2}, 2, 16),
    ({"data": 4, "fsdp": 4}, 8, 4),
    ({"fsdp": 16}, 8, 4),
    ({"data": 4, "fsdp": 4}, 16, 2),
]


def main():
    n = 16
    rows = []
    print(f"devices: {len(jax.devices())} ({jax.devices()[0].platform})")
    for shape, per_dp_batch, accum in MESHES:
        dp = 1
        for ax in ("data", "fsdp"):
            dp *= shape.get(ax, 1)
        setup = abstract_train_setup(
            shape,
            preset=os.environ.get("PROJ_PRESET", "smollm3_3b"),
            accum=accum,
            seq=SEQ,
            per_dp_batch=per_dp_batch,
            param_dtype="bfloat16",
            train_kwargs={
                "compute_dtype": "bfloat16",
                "remat_policy": "dots_no_batch",
            },
        )
        rep = setup.comm_report()
        unattributed = [c for c in rep.collectives if c.axes == ("?",)]
        assert not unattributed, f"unattributed collectives on {shape}"
        samples_per_step = per_dp_batch * accum * dp
        proj = project_step_time(
            rep,
            shape,
            single_chip_samples_per_sec=MEASURED_SAMPLES_PER_SEC_PER_CHIP,
            samples_per_step=samples_per_step,
        )
        # optimistic companion: full overlap (real steps land in between)
        proj_hi = project_step_time(
            rep,
            shape,
            single_chip_samples_per_sec=MEASURED_SAMPLES_PER_SEC_PER_CHIP,
            samples_per_step=samples_per_step,
            overlap_fraction=1.0,
        )
        row = {
            "mesh": shape,
            "microbatch": per_dp_batch,
            "accum": accum,
            "wire_MB_per_step_per_chip": round(rep.total_wire_bytes() / 1e6, 1),
            "wire_by_axis_MB": {
                "x".join(k): round(v / 1e6, 1)
                for k, v in rep.wire_bytes_by_axis().items()
            },
            "compute_s": round(proj.compute_s, 4),
            "exposed_comm_s": round(proj.exposed_comm_s, 4),
            "step_s_0pct_overlap": round(proj.step_s, 4),
            "samples_per_sec_0pct": round(proj.samples_per_sec, 1),
            "samples_per_sec_100pct": round(proj_hi.samples_per_sec, 1),
            "scaling_efficiency_0pct": round(proj.scaling_efficiency, 3),
            "vs_4xL40S_aggregate": round(proj.samples_per_sec / BASELINE_AGG_4GPU, 2),
        }
        rows.append(row)
        print(json.dumps(row))

    print("\n| mesh | wire MB/step/chip | comm ms | samples/s (0% ovl) | samples/s (100% ovl) | eff. | x 4xL40S |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        mesh_s = " ".join(f"{k}={v}" for k, v in r["mesh"].items())
        mesh_s += f" mb={r['microbatch']} acc={r['accum']}"
        print(
            f"| {mesh_s} | {r['wire_MB_per_step_per_chip']} | "
            f"{r['exposed_comm_s']*1e3:.1f} | {r['samples_per_sec_0pct']} | "
            f"{r['samples_per_sec_100pct']} | {r['scaling_efficiency_0pct']:.0%} | "
            f"{r['vs_4xL40S_aggregate']}x |"
        )


if __name__ == "__main__":
    main()
