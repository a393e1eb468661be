"""Find the knee of a serving cell once: one engine, then for each offered
rate a ramp and a short window of the cell's own mix; prints, per rate, the
tokens/s completed, the tails, and how long after the window its last request
finished (a queue that grew shows as a long tail and a late finish).

``python benchmarks/chipbench/tools/sweep_serve.py --workload <cell> --seed 1 --rates 2,4,6,8 --seconds 20``
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)

from benchmarks.chipbench import kind_serve, run, traffic  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args()
    cell = run.load_cell(args.workload, bool(args.rehearse))
    cfg, mix = cell["config"], cell["traffic"]

    import jax

    run.enable_cache()
    flat, engine = kind_serve.build_engine(cfg, mix, args.seed)
    kind_serve.warm_up(engine, mix, cfg["vocab_size"])
    out_path = os.path.join(ROOT, "chiprun_out", f"sweep_{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix_r = run._merge(mix, {"arrivals": {"rate_per_s": rate}})
        schedule = traffic.serve_schedule(mix_r, cfg["vocab_size"], args.seed + n, args.seconds)
        before = kind_serve.counters(engine)
        clients = kind_serve.offer(engine, schedule, args.seconds)
        after = kind_serve.counters(engine)
        s = kind_serve.summarize(clients, args.seconds)
        s.pop("done")
        s.update(rate=rate, seconds=args.seconds, device=jax.devices()[0].device_kind,
                 decode_steps=after["decode_steps"] - before["decode_steps"],
                 tokens_served=after["tokens_served"] - before["tokens_served"],
                 memory_peak_bytes=max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()),
                 recompiles=engine.compile_ledger.snapshot()["recompiles_after_warmup"])
        print(json.dumps(s), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(s) + "\n")
    os._exit(0)


if __name__ == "__main__":
    main()
