#!/usr/bin/env python
"""Continuous-batching serving probe (ISSUE-4 acceptance artifact).

A Poisson stream of requests with mixed prompt/output lengths hits a tiny
GPT on CPU, twice:

- **sequential leg**: requests processed one at a time, in arrival order,
  each owning a whole `generation.generate` call — the pre-serving model of
  inference.  Its API yields tokens only when the call returns, so TTFT is
  completion time (head-of-line blocking made visible).
- **serving leg**: the same arrival schedule submitted to a
  `serving.ServingEngine` (slot-based KV pool, bucketed prefill + one
  decode program, background loop), tokens streamed per decode step.

Both legs are warmed before timing (every distinct solo (prompt_len,
max_new) shape, and the engine's len(buckets)+1 programs) so the comparison
isolates scheduling, not compilation; compile counts are reported
separately.  Every request is greedy, and each serving stream must be
BIT-IDENTICAL to the solo leg's output for the same prompt — a wrong-KV /
wrong-mask bug cannot hide behind throughput.

Bars (default mode, CPU-reproducible): serving tokens/sec >= 1.5x
sequential, serving p50 TTFT < sequential p50 TTFT, parity exact.
`--steps N` (N <= 5) is the CI smoke mode: parity still enforced, perf
bars skipped.  Prints one `SERVE{json}` line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40,
                    help="number of requests (<=5 switches to smoke mode: "
                         "parity-only bars)")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode iterations per compiled call")
    ap.add_argument("--rate", type=float, default=300.0,
                    help="Poisson arrival rate, requests/sec (default well "
                         "above either leg's service rate: continuous "
                         "batching is a story about saturation)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.serving import ServingEngine

    n_req = max(1, args.steps)
    smoke = n_req <= 5

    # full mode runs a model big enough that b=1 decode is weight-traffic
    # bound — the regime continuous batching exists for (a toy-sized model
    # is op-overhead bound and the solo fused scan is unbeatable there,
    # on CPU and TPU alike).  Smoke mode shrinks the model: it only checks
    # parity and wiring, not the perf bars.
    if smoke:
        dims = dict(vocab_size=96, hidden_size=48, num_hidden_layers=2,
                    num_attention_heads=2)
        slots = min(args.slots, 4)
    else:
        dims = dict(vocab_size=512, hidden_size=384, num_hidden_layers=4,
                    num_attention_heads=8)
        slots = args.slots
    cfg = models.GPTConfig(hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           max_position_embeddings=128, **dims)
    paddle.seed(11)
    model = models.GPTForPretraining(cfg)
    model.eval()

    rng = np.random.RandomState(args.seed)
    vocab = dims["vocab_size"]
    plens = [4, 7, 12]
    budgets = [24, 40, 56]
    reqs = []
    for i in range(n_req):
        plen = plens[int(rng.randint(len(plens)))]
        reqs.append({
            "prompt": rng.randint(0, vocab, (plen,)).astype(np.int32),
            "max_new": budgets[int(rng.randint(len(budgets)))],
        })
    # Poisson arrivals: exponential inter-arrival gaps, first at t=0
    gaps = rng.exponential(1.0 / args.rate, size=n_req)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)

    # -- warmup: every program either leg will run, outside the clocks ----
    for plen, mn in sorted({(r["prompt"].shape[0], r["max_new"])
                            for r in reqs}):
        model.generate(paddle.to_tensor(
            np.zeros((1, plen), np.int32)), max_new_tokens=mn)
    solo_programs = len(model.__dict__.get("_generate_jit_cache", {}))

    # -- sequential leg (also produces the parity oracle) -----------------
    seq_ttft, seq_tokens = [], []
    t0 = time.monotonic()
    for i, r in enumerate(reqs):
        now = time.monotonic() - t0
        if now < arrivals[i]:
            time.sleep(arrivals[i] - now)
        out, _ = model.generate(
            paddle.to_tensor(r["prompt"][None]),
            max_new_tokens=r["max_new"])
        toks = np.asarray(out.numpy())[0].tolist()
        done = time.monotonic() - t0
        # the sequential API yields nothing until generate returns: TTFT
        # is completion minus arrival (queue wait included)
        seq_ttft.append(done - arrivals[i])
        seq_tokens.append(toks)
    seq_wall = (time.monotonic() - t0) - float(arrivals[0])
    total_tokens = sum(len(t) for t in seq_tokens)
    seq_tps = total_tokens / seq_wall

    # -- serving leg -------------------------------------------------------
    engine = ServingEngine(model, max_slots=slots, max_len=80,
                           prefill_buckets=(8, 16), decode_chunk=args.chunk,
                           max_queue_depth=max(64, n_req))
    engine.warmup()
    engine.reset_metrics()
    engine.start()
    resps = [None] * n_req
    t0 = time.perf_counter()        # the clock of Response.finished_at

    def submitter():
        for i, r in enumerate(reqs):
            now = time.perf_counter() - t0
            if now < arrivals[i]:
                time.sleep(arrivals[i] - now)
            resps[i] = engine.submit(r["prompt"], r["max_new"])

    sub = threading.Thread(target=submitter)
    sub.start()
    sub.join()
    serve_tokens = [resps[i].tokens(timeout=300.0) for i in range(n_req)]
    t_end = max(r.finished_at for r in resps)
    engine.close()
    serve_wall = (t_end - t0) - float(arrivals[0])
    serve_tps = total_tokens / serve_wall
    serve_ttft = [r.ttft for r in resps]

    def p50(xs):
        return sorted(xs)[len(xs) // 2]

    parity_failures = [
        i for i in range(n_req) if serve_tokens[i] != seq_tokens[i]]
    out = {
        "tokens_per_sec": round(serve_tps, 1),
        "ttft_p50_ms": round(p50(serve_ttft) * 1e3, 2),
        "sequential": {"tokens_per_sec": round(seq_tps, 1),
                       "ttft_p50_ms": round(p50(seq_ttft) * 1e3, 2),
                       "compiled_programs": solo_programs},
        "speedup_vs_sequential": round(serve_tps / seq_tps, 2),
        "compile_counts": engine.compile_counts(),
        "metrics": {k: (round(v, 3) if isinstance(v, float) else v)
                    for k, v in engine.metrics().items()
                    if k != "compile_counts"},
        "requests": n_req, "total_tokens": total_tokens,
        "arrival_rate_per_sec": args.rate, "smoke": smoke,
        "slots": slots, "decode_chunk": args.chunk,
        "workload": "greedy, prompt_len in {4,7,12}, max_new in "
                    "{24,40,56}, Poisson arrivals, GPT "
                    f"({dims['hidden_size']}h/{dims['num_hidden_layers']}L/"
                    f"{vocab}v), cpu",
    }
    failures = []
    if parity_failures:
        failures.append(f"parity: requests {parity_failures[:5]} diverged "
                        "from solo generate")
    cc = engine.compile_counts()
    if cc["total"] > cc["bound"]:
        failures.append(f"compiled {cc['total']} programs > bound "
                        f"{cc['bound']}")
    if not smoke:
        if out["speedup_vs_sequential"] < 1.5:
            failures.append(
                f"speedup {out['speedup_vs_sequential']} < 1.5x bar")
        if out["ttft_p50_ms"] >= out["sequential"]["ttft_p50_ms"]:
            failures.append("serving p50 TTFT not below sequential")
    if failures:
        out["failures"] = failures
    print("SERVE" + json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
