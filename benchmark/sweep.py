"""Finds the knee of a serving cell once, by a sweep on the chip:

    python3 benchmark/sweep.py --workload <cell> --rates 4,6,8 --seconds 12

One engine, one process; at each rate a window of the cell's mix and its
drain.  The knee is the highest rate at which the queue is no longer at the
window's end than at its middle and no request is refused.  The cell's file
then states 0.8 of it.  The benchmark's own runs never search for a rate.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, stats  # noqa: E402


def main(argv=None, need_tpu=True, spec_path=None, data_dirs=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from benchmark.generators import open_loop
    files = harness.Files(spec_path, data_dirs)
    cell = files.cell(args.workload)
    devices = harness.require_chips(cell["chips"], need_tpu)
    import jax
    jax.config.update("jax_default_prng_impl", "rbg")
    harness.place_cache()
    system, out = None, []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        ns = argparse.Namespace(seed=args.seed + k, seconds=args.seconds,
                                trace=0)
        run = harness.Run(files, cell, ns, time.perf_counter(), devices)
        run.traffic = dict(run.traffic, rate_per_s=rate)
        if system is None:
            system = open_loop.ServeSystem(run)
            system.warmup()
        plan, done, t0, t_end = open_loop.serve(run, system)
        for rec in done:
            rec["tokens"] = rec["n"]
        e2e = stats.serve_end_to_end(done, args.seconds,
                                     (t_end - t0) * 1e3)
        mid = [s["queue_before"] for s in run.engine_steps
               if 0.4 <= (s["t0"] - t0) / args.seconds <= 0.6]
        end = [s["queue_before"] for s in run.engine_steps
               if 0.8 <= (s["t0"] - t0) / args.seconds <= 1.0]
        ttft = [(r["first"] - r["due"]) * 1e3 for r in done
                if r["first"] is not None]
        rec = {"rate": rate, "requests": len(done),
               "failed": sum(r["failed"] for r in done),
               "queue_mid_mean": stats.mean(mid),
               "queue_end_mean": stats.mean(end),
               "queue_end_max": max(end) if end else None,
               "ttft_p50_ms": stats.percentile(ttft, 50),
               "ttft_p95_ms": e2e["serve_ttft_p95_ms"],
               "itl_p95_ms": e2e["serve_itl_p95_ms"],
               "tokens_per_s": e2e["serve_tokens_per_s"],
               "drain_s": t_end - t0 - args.seconds,
               "occupancy_mean": stats.mean(
                   s["active_before"] for s in run.engine_steps),
               "pure_decode_step_ms": stats.stat(
                   [s["dur"] * 1e3 for s in run.engine_steps
                    if s["pure_decode"]], "median")}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    system.free()
    return out


if __name__ == "__main__":
    main()
