#!/usr/bin/env python
"""Multi-tenant gateway probe (ISSUE-6 acceptance artifact).

A Poisson stream of mixed-priority requests hits the ServingGateway at
~3x the engine's measured saturation rate, with chaos armed:

- `PDTPU_FAULT_SLOW_DECODE` host-latency injection in the decode loop
  (overload on CPU without a big model),
- `PDTPU_FAULT_NAN_LOGITS` poisoning one high-priority request's decode
  (the engine's per-slot non-finite guard under gateway traffic),
- mid-stream cancels of a handful of low-priority requests,
- tight deadlines on a slice of the low lane.

Robustness bars (full mode, CPU-reproducible):

- the HIGH lane's p99 TTFT stays under --ttft-bar-ms while >= 30% of the
  offered low-priority work is shed or preempted (the SLO story: cheap
  early rejection + preemption protect the paying lane),
- >= 80% of high-priority requests are actually served (the p99 cannot
  be bought by shedding the high lane),
- every completed greedy stream — INCLUDING every preempted-and-resumed
  one — is bit-identical to a solo `generation.generate` of the same
  prompt, and at least one resumed stream completes to prove the KV
  save/restore path end-to-end,
- every submitted request reaches a terminal state (finished or a typed
  error) — no consumer hangs,
- engine compile count stays at the PR-4 bound (preempt/restore adds no
  compiled programs).

`--steps N` (N <= 5) is the CI smoke: parity + terminal-state only, no
chaos, perf bars skipped.  Prints one `GATE{json}` line; exits 1 on any
bar miss.
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
    ap.add_argument("--steps", type=int, default=60,
                    help="number of main-phase requests (<=5 switches to "
                         "smoke mode: parity/terminal only)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ttft-bar-ms", type=float, default=600.0,
                    help="high-lane p99 TTFT bar under 3x overload")
    ap.add_argument("--overload", type=float, default=3.0,
                    help="arrival rate as a multiple of measured capacity")
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.serving import (ServingEngine, ServingGateway,
                                    TenantConfig, ShedPolicy,
                                    PRIORITY_HIGH, PRIORITY_LOW,
                                    NonFiniteLogitsError)
    from paddle_tpu.utils import faults

    n_req = max(1, args.steps)
    smoke = n_req <= 5
    n_cal = 0 if smoke else 8

    rng = np.random.RandomState(args.seed)
    dims = dict(vocab_size=96, hidden_size=48, num_hidden_layers=2,
                num_attention_heads=2)
    cfg = models.GPTConfig(hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           max_position_embeddings=128, **dims)
    paddle.seed(11)
    model = models.GPTForPretraining(cfg)
    model.eval()

    # -- request plan (decided up front: the NaN target is baked at engine
    #    construction and needs a known submission sequence number) -------
    plens = [4, 7, 12]
    budgets = [16, 24, 32]
    plan = []
    for i in range(n_req):
        hi = (not smoke and rng.rand() < 0.25) or (smoke and i == 0)
        plan.append({
            "prompt": rng.randint(0, dims["vocab_size"],
                                  (plens[int(rng.randint(len(plens)))],)
                                  ).astype(np.int32),
            "max_new": budgets[int(rng.randint(len(budgets)))],
            "priority": PRIORITY_HIGH if hi else PRIORITY_LOW,
            "tenant": ("gold" if hi else
                       ("bronze", "free")[int(rng.randint(2))]),
        })
    lo_idx = [i for i, p in enumerate(plan)
              if p["priority"] == PRIORITY_LOW]
    hi_idx = [i for i, p in enumerate(plan)
              if p["priority"] == PRIORITY_HIGH]
    # chaos targets (full mode): one poisoned hi request, a few low
    # cancels, tight deadlines on a slice of the low lane
    poison_i = hi_idx[len(hi_idx) // 2] if (not smoke and hi_idx) else None
    cancel_set = set(rng.choice(lo_idx, size=min(4, len(lo_idx)),
                                replace=False)) if not smoke else set()
    deadline_set = set(i for i in lo_idx[::7]
                       if i not in cancel_set) if not smoke else set()

    if not smoke:
        faults.enable("slow_decode", "3:2")  # 3ms every 2nd decode call
        if poison_i is not None:
            faults.enable("nan_logits", str(n_cal + poison_i))

    # -- engine + gateway -------------------------------------------------
    engine = ServingEngine(model, max_slots=args.slots, max_len=80,
                           prefill_buckets=(8, 16),
                           decode_chunk=args.chunk,
                           max_queue_depth=max(64, n_req))
    engine.warmup()
    # zero-post-warmup-compiles contract (ISSUE-9 satellite): the whole
    # gateway run — preemption, restore, shedding, chaos — must add no
    # serving compiles after warmup, engine counters AND the compiled-
    # program registry agreeing (the test_dist_serving assertion, under
    # gateway traffic)
    gw = ServingGateway(
        engine,
        tenants={"gold": TenantConfig(weight=4.0, max_priority=1),
                 "bronze": TenantConfig(weight=2.0, max_priority=0),
                 "free": TenantConfig(weight=1.0, max_priority=0)},
        shed=ShedPolicy(max_lane_depth=8, max_est_wait=1.0,
                        ttft_slo=args.ttft_bar_ms / 1e3),
        preempt=True)

    # -- solo oracle (also warms every solo shape, outside the clocks) ----
    oracle = {}
    for r in plan:
        key = (r["prompt"].tobytes(), r["max_new"])
        if key not in oracle:
            out, _ = model.generate(paddle.to_tensor(r["prompt"][None]),
                                    max_new_tokens=r["max_new"])
            oracle[key] = np.asarray(out.numpy())[0].tolist()

    # -- calibration: measured saturation throughput, chaos included ------
    if smoke:
        rate = 50.0
    else:
        t0 = time.monotonic()
        cal = [gw.submit(rng.randint(0, dims["vocab_size"], (7,)), 24,
                         tenant="bronze") for _ in range(n_cal)]
        gw.run_until_drained(timeout=120)
        for c in cal:
            c.tokens(timeout=5)  # all must have completed cleanly
        cal_wall = time.monotonic() - t0
        rate = args.overload * n_cal / cal_wall

    # -- main phase: Poisson arrivals at `overload`x saturation -----------
    gaps = rng.exponential(1.0 / rate, size=n_req)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)
    resps = [None] * n_req
    gw.start()
    t0 = time.monotonic()

    def submitter():
        for i, r in enumerate(plan):
            now = time.monotonic() - t0
            if now < arrivals[i]:
                time.sleep(arrivals[i] - now)
            kw = {}
            if i in deadline_set:
                kw["deadline"] = 1.0
            resps[i] = gw.submit(r["prompt"], r["max_new"],
                                 tenant=r["tenant"],
                                 priority=r["priority"], **kw)

    def canceller():
        # mid-stream cancels: fire while the victims are queued/decoding
        for i in sorted(cancel_set):
            while resps[i] is None and time.monotonic() - t0 < 30:
                time.sleep(0.002)
            time.sleep(0.02)
            if resps[i] is not None:
                resps[i].cancel()

    sub = threading.Thread(target=submitter)
    can = threading.Thread(target=canceller)
    sub.start()
    can.start()
    sub.join()
    can.join()

    # -- terminal-state guarantee: every response must finish or error ---
    hung = []
    deadline_all = time.monotonic() + 180.0
    for i, r in enumerate(resps):
        if not r._done.wait(timeout=max(0.0, deadline_all
                                        - time.monotonic())):
            hung.append(i)
    gw_metrics = gw.metrics()
    cc = engine.compile_counts()
    post_warmup = engine.post_warmup_compiles()
    gw.close()

    # -- classify ---------------------------------------------------------
    def preempts(i):
        return getattr(resps[i].request, "preempts", 0)

    def resumes(i):
        return getattr(resps[i].request, "resumes", 0)

    completed, shed, rate_limited, errored = [], [], [], []
    for i, r in enumerate(resps):
        if r.error is None:
            completed.append(i)
        else:
            name = type(r.error).__name__
            if name == "SheddedError":
                shed.append(i)
            elif name == "RateLimitedError":
                rate_limited.append(i)
            else:
                errored.append(i)
    parity_failures = []
    resumed_checked = 0
    for i in completed:
        want = oracle[(plan[i]["prompt"].tobytes(), plan[i]["max_new"])]
        if resps[i].tokens(timeout=5) != want:
            parity_failures.append(i)
        elif resumes(i) > 0:
            resumed_checked += 1
    lo_shed = sum(1 for i in shed if plan[i]["priority"] == PRIORITY_LOW)
    lo_preempted = sum(1 for i in range(n_req)
                       if plan[i]["priority"] == PRIORITY_LOW
                       and preempts(i) > 0)
    shed_rate = ((lo_shed + lo_preempted) / len(lo_idx)) if lo_idx else 0.0
    hi_ttfts = sorted(resps[i].ttft for i in hi_idx
                      if resps[i].ttft is not None)
    hi_served_frac = (len(hi_ttfts) / len(hi_idx)) if hi_idx else 1.0
    p99_hi = (hi_ttfts[min(len(hi_ttfts) - 1,
                           int(0.99 * len(hi_ttfts)))] * 1e3
              if hi_ttfts else None)
    poison_ok = True
    if poison_i is not None and resps[poison_i].error is not None:
        poison_ok = isinstance(resps[poison_i].error, NonFiniteLogitsError)

    out = {
        "p99_ttft_hi_ms": None if p99_hi is None else round(p99_hi, 2),
        "shed_rate": round(shed_rate, 3),
        "requests": n_req, "hi_requests": len(hi_idx),
        "lo_requests": len(lo_idx),
        "completed": len(completed), "shed": len(shed),
        "rate_limited": len(rate_limited), "errored": len(errored),
        "preempted": sum(1 for i in range(n_req) if preempts(i) > 0),
        "resumed": sum(1 for i in range(n_req) if resumes(i) > 0),
        "resumed_streams_parity_checked": resumed_checked,
        "hi_served_frac": round(hi_served_frac, 3),
        "cancelled_targets": len(cancel_set),
        "deadline_targets": len(deadline_set),
        "compile_counts": cc,
        "post_warmup_compiles": post_warmup,
        "arrival_rate_per_sec": round(rate, 1),
        "overload_factor": args.overload,
        "gateway_metrics": {k: v for k, v in gw_metrics.items()
                            if k not in ("engine", "tenants")},
        "smoke": smoke, "slots": args.slots, "decode_chunk": args.chunk,
        "chaos": None if smoke else
                 "slow_decode=3ms:2, nan_logits on hi request, "
                 f"{len(cancel_set)} mid-stream cancels, "
                 f"{len(deadline_set)} tight deadlines",
        "workload": "greedy, prompt_len in {4,7,12}, max_new in "
                    "{16,24,32}, 25% high-priority, Poisson arrivals at "
                    f"{args.overload}x measured saturation, GPT "
                    f"(48h/2L/96v), cpu",
    }
    failures = []
    if hung:
        failures.append(f"requests {hung[:5]} never reached a terminal "
                        "state (hang)")
    if parity_failures:
        failures.append(f"parity: requests {parity_failures[:5]} diverged "
                        "from solo generate")
    if cc["total"] > cc["bound"]:
        failures.append(f"compiled {cc['total']} programs > bound "
                        f"{cc['bound']} (preempt/resume must add none)")
    if post_warmup != 0:
        failures.append(f"{post_warmup} post-warmup serving compiles "
                        "under gateway traffic (registry-asserted; "
                        "must be 0)")
    if not poison_ok:
        failures.append("poisoned request errored with the wrong type: "
                        f"{type(resps[poison_i].error).__name__}")
    if not smoke:
        if p99_hi is None or p99_hi >= args.ttft_bar_ms:
            failures.append(f"high-lane p99 TTFT {p99_hi} ms >= "
                            f"{args.ttft_bar_ms} ms bar")
        if shed_rate < 0.30:
            failures.append(f"shed/preempt rate {shed_rate} < 0.30 of "
                            "low-priority work under overload")
        if hi_served_frac < 0.80:
            failures.append(f"only {hi_served_frac:.0%} of high-priority "
                            "requests served (p99 bought by shedding)")
        if resumed_checked < 1:
            failures.append("no preempted-and-resumed stream completed "
                            "for the bit-identity check")
    if failures:
        out["failures"] = failures
    faults.reset()
    print("GATE" + json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
