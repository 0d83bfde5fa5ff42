#!/usr/bin/env python
"""What ISSUE 38 left to a chip measurement, beside the benchmark's own line:

  costs    what the `seated` arg adds to an admission's span and what the
           collector's hook adds to a collection, in us on this host (no
           profiler session), and the collections a window made, by
           generation, over its engine steps;
  cell     one traced run of a benchmark cell through `benchmark/run.py`'s
           own `main` (the result line is printed as the driver sees it),
           and from the same run: how long the profiler's start held the
           loop, the ring's wait on the chip over the traced stretch alone
           beside `device_idle_pct` (the difference: gaps inside programs),
           the collections of 10 ms or more and whether one lies in the
           run's longest engine step, and where the requests that set the
           two queue-wait p95s waited.

    chiprun -- python3 probes/seat_probe.py --workload gpt2m-serve-chat \
        --seed 3800000011 --out chiprun_out/seat_chat.json

Writes one JSON object to --out and prints `SEAT{json}` after the result
line.  Needs the chip, as the benchmark does.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def best_of(fn, n, reps=5):
    """Least seconds a call of `fn` over `reps` loops of `n` calls."""
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        out.append((time.perf_counter() - t0) / n)
    return min(out)


def costs():
    from paddle_tpu.observability import tracer as T
    ring = T.Tracer(max_events=1024)
    slots = {i: None for i in range(47)}

    def without():
        with ring.span("serving_admit", args={"request": 1, "slot": 2,
                                              "plen": 3}):
            pass

    def with_seated():
        with ring.span("serving_admit", args={"request": 1, "slot": 2,
                                              "plen": 3,
                                              "seated": len(slots)}):
            pass

    n = 100_000
    span_us = (best_of(without, n) * 1e6, best_of(with_seated, n) * 1e6)
    m = 20_000
    hooked = best_of(lambda: gc.collect(0), m)
    gc.callbacks.remove(T._on_gc)
    try:
        bare = best_of(lambda: gc.collect(0), m)
    finally:
        gc.callbacks.append(T._on_gc)
    return {"admit_span_us": span_us[0], "admit_span_seated_us": span_us[1],
            "seated_arg_us": span_us[1] - span_us[0],
            "young_collection_us": bare * 1e6,
            "hook_us_per_collection": (hooked - bare) * 1e6}


def within(t0, t1, lo, hi):
    return max(0.0, min(t1, hi) - max(t0, lo))


def cell(args, **main_kw):
    from benchmark import harness, run as bench_run
    from benchmark.readers import host_hold
    from paddle_tpu.observability import get_tracer
    runs = []

    class Kept(harness.Run):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            runs.append(self)

    harness.Run = Kept
    result = {}
    emit = harness.emit

    def keep(res):
        result.update(res)
        emit(res)
    harness.emit = keep
    seen = []             # (ended, generation) of every collection

    def count(phase, info):
        if phase == "stop":
            seen.append((time.perf_counter(), info["generation"]))
    gc.callbacks.append(count)
    try:
        bench_run.main(["--workload", args.workload, "--seed",
                        str(args.seed), "--seconds", str(args.seconds),
                        "--trace", "1"], **main_kw)
    finally:
        gc.callbacks.remove(count)
    run = runs[0]
    lo, hi = run.window
    events = get_tracer().events()
    stall = host_hold.profiler_start(run)
    in_window = [g for t, g in seen if lo <= t <= hi]
    steps = [st for st in run.engine_steps if st["t1"] <= hi]
    out = {"workload": args.workload, "seed": args.seed,
           "collections_in_window": [in_window.count(g) for g in (0, 1, 2)],
           "engine_steps_in_window": len(steps),
           "profiler_start_ms": (stall[1] - stall[0]) * 1e3 if stall
           else None,
           "profiler_start_at_s": stall[0] - lo if stall else None}
    metrics = {k: v["value"] for k, v in result.get("metrics", {}).items()}
    out["metrics"] = metrics
    tr = run.traced or {}
    if "t1" in tr:
        waits = {"spans": ["serving_prefill_wait", "serving_token_pull"],
                 "complement": True}
        ring_idle = host_hold.share(events, (tr["t0"], tr["t1"]), None,
                                    waits)
        idle = next((v for k, v in metrics.items()
                     if k.startswith("device_idle_pct")), None)
        out["traced_stretch"] = {
            "seconds": tr["t1"] - tr["t0"], "ring_chip_wait_pct": ring_idle,
            "device_idle_pct": idle,
            "in_program_gaps_pct": (idle - ring_idle
                                    if None not in (idle, ring_idle)
                                    else None)}
    gcs = [ev for ev in events if ev[0] == "python_gc"
           and lo <= ev[1] <= hi]
    worst = max(run.engine_steps, key=lambda st: st["dur"],
                default=None)
    out["python_gc_in_window"] = {
        "count": len(gcs), "seconds": sum(ev[2] for ev in gcs),
        "by_generation": {g: sum(ev[6]["generation"] == g for ev in gcs)
                          for g in (0, 1, 2)},
        "longest_ms": max((ev[2] * 1e3 for ev in gcs), default=0.0),
        "over_10ms": [{"at_s": ev[1] - lo, "ms": ev[2] * 1e3,
                       "generation": ev[6]["generation"],
                       "collected": ev[6]["collected"]}
                      for ev in gcs if ev[2] >= 0.010]}
    if worst is not None:
        out["longest_engine_step"] = {
            "at_s": worst["t0"] - lo, "ms": worst["dur"] * 1e3,
            "admitted": worst["admitted"],
            "gc_ms_inside": sum(within(ev[1], ev[1] + ev[2], worst["t0"],
                                       worst["t1"]) for ev in gcs) * 1e3}
    if stall is not None:
        out["gc_ms_in_profiler_start"] = sum(
            within(ev[1], ev[1] + ev[2], *stall) for ev in gcs) * 1e3
    # the requests that set the queue-wait p95s, and the profiler's start
    reqs = [r for r in run.requests if r.get("queue_wait") is not None]
    reqs.sort(key=lambda r: -r["queue_wait"])
    top = reqs[:max(1, len(reqs) // 20 + 1)]
    out["queue_wait_top_from_due"] = [
        {"due_s": r["due"] - lo, "wait_ms": r["queue_wait"] * 1e3,
         "overlaps_profiler_start": bool(stall) and within(
             r["due"], r["admit_step"], *stall) > 0} for r in top]
    sched = sorted((ev for ev in events if ev[0] == "serving_queue_wait"
                    and lo <= ev[1] + ev[2] <= hi), key=lambda ev: -ev[2])
    out["queue_wait_top_from_submit"] = [
        {"submitted_s": ev[1] - lo, "wait_ms": ev[2] * 1e3,
         "overlaps_profiler_start": bool(stall) and within(
             ev[1], ev[1] + ev[2], *stall) > 0}
        for ev in sched[:max(1, len(sched) // 20 + 1)]]
    c = out["costs"] = costs()
    c["hook_us_per_engine_step"] = (c["hook_us_per_collection"]
                                    * len(in_window) / max(1, len(steps)))
    c["seated_arg_us_per_engine_step"] = c["seated_arg_us"] * sum(
        st["admitted"] for st in steps) / max(1, len(steps))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3800000011)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out = cell(args)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print("SEAT" + json.dumps({k: out[k] for k in (
        "workload", "profiler_start_ms", "traced_stretch",
        "python_gc_in_window", "longest_engine_step", "costs")
        if k in out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
