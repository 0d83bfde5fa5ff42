#!/usr/bin/env python
"""Observability overhead + export-cost probe (ISSUE-5 acceptance artifact).

Two questions, answered with numbers:

1. **Overhead**: does full host-span instrumentation (the profiler hook
   routing every eager dispatch through the observability tracer — ring
   buffer + aggregates under a lock) cost < 3% of eager MLP train-step
   throughput?  Bare and instrumented legs run interleaved (3 reps each,
   best-of, same data/seed) so scheduler noise can't masquerade as
   overhead; losses must match bitwise across legs.
2. **Export cost**: how long do a 10k-span chrome://tracing export and a
   Prometheus text exposition of a populated registry take?  Published as
   `export_ms` (sum) with a per-exporter breakdown; both outputs are
   parsed/validated before timing counts.

Runs on CPU (JAX_PLATFORMS=cpu) so the numbers
reproduce in tier-1's environment.  Prints one `OBS{json}` line; any bar
miss lists under "failures" and exits 1 (bench quarantines under
`unpublished_failed_bars`).  `--steps <= 5` is the smoke mode: machinery
only, the noise-sensitive overhead bar is not enforced.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

OVERHEAD_BAR_PCT = 3.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300,
                    help="timed eager MLP train steps per rep")
    ap.add_argument("--spans", type=int, default=10_000,
                    help="span count for the chrome-trace export leg")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved reps per leg (best-of)")
    args = ap.parse_args()
    smoke = args.steps <= 5

    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu import observability as obs
    from paddle_tpu.utils import profiler as prof

    rng = np.random.RandomState(0)
    mlp_x = rng.randn(32, 64).astype("float32")
    mlp_y = rng.randint(0, 10, (32,)).astype("int64")

    def build():
        paddle.seed(0)
        model = nn.Sequential(
            nn.Linear(64, 128), nn.ReLU(),
            nn.Linear(128, 128), nn.ReLU(),
            nn.Linear(128, 10))
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters())
        xb = paddle.to_tensor(mlp_x)
        yb = paddle.to_tensor(mlp_y)

        def step():
            loss = F.cross_entropy(model(xb), yb)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        return step

    def run_leg(instrumented: bool):
        step = build()
        if instrumented:
            prof.start_profiler()
        try:
            for _ in range(min(3, args.steps)):  # warm the dispatch cache
                step()
            t0 = time.perf_counter()
            loss = None
            for _ in range(args.steps):
                loss = step()
            loss.block_until_ready()
            dt = time.perf_counter() - t0
        finally:
            if instrumented:
                prof.stop_profiler(profile_path=os.devnull)
        return args.steps / dt, float(loss)

    # interleaved best-of: ambient machine noise hits both legs equally
    best = {"bare": 0.0, "instrumented": 0.0}
    losses = {}
    for _ in range(max(1, args.reps)):
        for tag, instrumented in (("bare", False), ("instrumented", True)):
            sps, loss = run_leg(instrumented)
            best[tag] = max(best[tag], sps)
            losses.setdefault(tag, loss)
    overhead_pct = (1.0 - best["instrumented"] / best["bare"]) * 100.0

    failures = []
    if losses["bare"] != losses["instrumented"]:
        failures.append(
            f"parity: bare loss {losses['bare']} != instrumented "
            f"{losses['instrumented']}")
    if not smoke and overhead_pct >= OVERHEAD_BAR_PCT:
        failures.append(
            f"overhead {overhead_pct:.2f}% >= {OVERHEAD_BAR_PCT}% bar")

    # ---- export leg: 10k spans -> chrome trace; populated registry ->
    # Prometheus text ------------------------------------------------------
    tracer = obs.get_tracer()
    tracer.clear()
    n_spans = args.spans if not smoke else 200
    for i in range(n_spans // 2):
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
    reg = obs.get_registry()
    h = reg.histogram("probe_latency_seconds", "probe fill")
    for i in range(2000 if not smoke else 50):
        h.observe((i % 97) / 1000.0)
    reg.counter("probe_events_total", "probe fill").inc(123)

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "trace.json")
        t0 = time.perf_counter()
        obs.export_chrome_trace(path)
        chrome_ms = (time.perf_counter() - t0) * 1e3
        with open(path) as f:
            doc = json.load(f)
        if len(doc["traceEvents"]) != n_spans // 2 * 2:
            failures.append(
                f"chrome trace has {len(doc['traceEvents'])} events, "
                f"expected {n_spans // 2 * 2}")

    t0 = time.perf_counter()
    text = obs.prometheus_text()
    prometheus_ms = (time.perf_counter() - t0) * 1e3
    if "probe_latency_seconds_bucket" not in text \
            or "probe_events_total 123" not in text:
        failures.append("prometheus exposition missing expected series")

    out = {
        "overhead_pct": round(overhead_pct, 2),
        "export_ms": round(chrome_ms + prometheus_ms, 2),
        "chrome_export_ms": round(chrome_ms, 2),
        "prometheus_export_ms": round(prometheus_ms, 2),
        "spans_exported": n_spans // 2 * 2,
        "bare_steps_per_sec": round(best["bare"], 2),
        "instrumented_steps_per_sec": round(best["instrumented"], 2),
        "steps": args.steps, "reps": args.reps, "smoke": smoke,
        "bar_overhead_pct": OVERHEAD_BAR_PCT,
        "config": "eager MLP 64-128-128-10 b32 SGD; profiler-hook tracer "
                  "spans on every dispatch vs bare",
    }
    if failures:
        out["failures"] = failures
    print("OBS" + json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
