#!/usr/bin/env python
"""Prefix-cache probe (ISSUE-17 acceptance artifact).

The prefix cache's claim is a REUSE claim: templated traffic (system
prompts, few-shot scaffolds, multi-turn history) shares long token
prefixes, so a radix cache over the paged block pool should (a) collapse
warm-prefix TTFT to the cost of the uncached suffix, and (b) multiply
the resident-decode capacity of a FIXED block budget, because N requests
sharing a template charge the pool for its blocks ONCE.  This probe
measures exactly that on CPU, against the no-cache paged engine:

- **cold leg**: `ServingEngine(kv="paged")` — every admission prefills
  the full prompt at its bucket.  Sequential closed-loop requests give
  the cold TTFT baseline.
- **warm leg**: `ServingEngine(kv="paged", prefix_cache=True)` — same
  requests; after the first instance of each template, admissions adopt
  the cached chain and prefill only the suffix bucket.  Warm TTFT is
  measured over repeat instances only.
- **traffic leg**: Poisson batches over K templates drive the warm
  engine; the hit-rate curve is recorded per batch.
- **capacity leg**: both engines get the SAME small `num_blocks`; a
  burst of template-sharing requests is driven to saturation and peak
  resident slots compared.
- **fleet leg**: a 2-replica `FleetRouter(prefix_affinity=True)` routes
  sessionless templated traffic; each template must concentrate on one
  replica (cache locality survives the router).

Every warm stream must be BIT-IDENTICAL to the cold leg's stream for
the same request, and NOTHING may compile after warmup (program
registry asserted) — reuse can never hide a wrong-KV bug.

Bars (full mode, CPU-reproducible):
  warm_ttft_ratio   mean warm TTFT / mean cold TTFT   <= 0.5
  capacity_ratio    peak resident warm / cold         >= 2.0
  hit_rate          final traffic-leg block hit rate  >= 0.5
  parity            every stream identical            (always enforced)
  compiles          zero post-warmup, bound unchanged (always enforced)

`--steps N` (N <= 5) is the CI smoke mode: tiny shapes, parity/bound
only.  Prints one `PREFIX{json}` line; exit 1 on any bar miss.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24,
                    help="requests per timed leg (<=5 switches to smoke)")
    ap.add_argument("--templates", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import observability
    from paddle_tpu.serving import FleetRouter, ServingEngine

    from paddle_tpu import models

    n_req = max(1, args.steps)
    smoke = n_req <= 5

    if smoke:
        dims = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2)
        max_len, bs, buckets = 64, 8, (8, 32)
        tlen, sufs, budget = 16, (3, 5), 4
        max_pos = 96
        n_templates = 2
    else:
        dims = dict(vocab_size=256, hidden_size=128, num_hidden_layers=4,
                    num_attention_heads=4)
        max_len, bs, buckets = 256, 8, (8, 224)
        tlen, sufs, budget = 192, (3, 5, 7), 8
        max_pos = 288
        n_templates = max(1, args.templates)
    cfg = models.GPTConfig(hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           max_position_embeddings=max_pos, **dims)
    paddle.seed(11)
    model = models.GPTForPretraining(cfg)
    model.eval()

    rng = np.random.RandomState(args.seed)
    vocab = dims["vocab_size"]
    templates = [rng.randint(0, vocab, (tlen,)).astype(np.int32)
                 for _ in range(n_templates)]
    # templated request mix: template + short unique suffix (the
    # "user turn"); template 0 is hottest (Zipf-ish weights)
    weights = np.array([1.0 / (i + 1) for i in range(n_templates)])
    weights /= weights.sum()
    reqs = []
    for _ in range(n_req):
        t = int(rng.choice(n_templates, p=weights))
        suf = rng.randint(0, vocab,
                          (int(rng.choice(sufs)),)).astype(np.int32)
        reqs.append({"template": t,
                     "prompt": np.concatenate([templates[t], suf]),
                     "max_new": budget})

    def build(prefix, num_blocks=None, slots=4):
        return ServingEngine(model, max_slots=slots, max_len=max_len,
                             prefill_buckets=buckets, decode_chunk=4,
                             kv="paged", block_size=bs,
                             num_blocks=num_blocks,
                             prefix_cache=prefix,
                             max_queue_depth=max(64, 4 * n_req))

    reg = observability.get_program_registry()

    def serving_compiles():
        return {k: v["compiles"] for k, v in reg.snapshot().items()
                if k.startswith("serving_")}

    # the program registry is process-global, so each leg snapshots it
    # AFTER its own engines warm and asserts nothing compiled during
    # that leg's traffic (warming a later engine legitimately bumps the
    # shared program names)
    compile_violations = []

    def check_no_compiles(tag, mark):
        after = serving_compiles()
        if after != mark:
            diff = {k: (mark.get(k), v) for k, v in after.items()
                    if mark.get(k) != v}
            compile_violations.append(f"{tag}: {diff}")

    # -- cold + warm legs: sequential closed-loop TTFT ------------------
    cold_eng = build(False)
    warm_eng = build(True)
    cold_eng.warmup()
    warm_eng.warmup()
    compiles_mark = serving_compiles()

    def run_seq(eng, rec_ttft):
        streams = []
        for r in reqs:
            resp = eng.submit(r["prompt"], r["max_new"])
            while eng.has_work():
                eng.step()
            rec_ttft.append(resp.ttft)
            streams.append(resp.tokens(timeout=5))
        return streams

    cold_ttfts, warm_ttfts = [], []
    cold_streams = run_seq(cold_eng, cold_ttfts)
    warm_streams = run_seq(warm_eng, warm_ttfts)
    parity_failures = [i for i in range(n_req)
                       if warm_streams[i] != cold_streams[i]]
    seen = set()
    cold_sel, warm_sel = [], []
    for i, r in enumerate(reqs):
        if r["template"] in seen:
            cold_sel.append(cold_ttfts[i])
            warm_sel.append(warm_ttfts[i])
        seen.add(r["template"])
    warm_ttft_ratio = (sum(warm_sel) / max(1e-12, sum(cold_sel))
                       if warm_sel else None)
    warm_stats = warm_eng.prefix_cache.stats()
    check_no_compiles("ttft-legs", compiles_mark)

    # -- traffic leg: Poisson batches -> hit-rate curve -----------------
    hit_curve = []
    if not smoke:
        traffic_eng = build(True)
        traffic_eng.warmup()
        mark = serving_compiles()
        i = 0
        while i < 2 * n_req:
            burst = 1 + int(rng.poisson(2.0))
            for _ in range(burst):
                r = reqs[i % n_req]
                traffic_eng.submit(r["prompt"], r["max_new"])
                i += 1
            while traffic_eng.has_work():
                traffic_eng.step()
            hit_curve.append(round(traffic_eng.prefix_cache.hit_rate(), 3))
        traffic_hit_rate = traffic_eng.prefix_cache.hit_rate()
        check_no_compiles("traffic-leg", mark)
        traffic_eng.close()
    else:
        traffic_hit_rate = warm_eng.prefix_cache.hit_rate()

    # -- capacity leg: fixed block budget, template burst ---------------
    # per request: prompt tlen+suf (template blocks + ~1) + decode
    # growth; the budget fits ~2 cold residents, so >=2x means the
    # cache let the SAME pool hold the template once, not per-slot
    # the no-cache engine charges every admission its full prefill
    # bucket; size the pool so exactly two such requests fit resident,
    # then throw a template-sharing burst at both engines — the cache
    # pays for the template ONCE, so it must hold >= 2x the residents
    cold_admit_blocks = buckets[-1] // bs
    cap_blocks = 2 * cold_admit_blocks + cold_admit_blocks // 2
    budget_cap = 12 if smoke else 16   # > decode_chunk: spans steps
    burst_n = 4 if smoke else 6
    peaks = {}
    for kind, prefix in (("cold", False), ("warm", True)):
        eng = build(prefix, num_blocks=cap_blocks, slots=8)
        eng.warmup()
        mark = serving_compiles()
        tmpl = templates[0]
        if prefix:
            # one pass to populate the cache (sequential, then idle)
            r0 = eng.submit(np.concatenate(
                [tmpl, rng.randint(0, vocab, (3,)).astype(np.int32)]),
                budget_cap)
            while eng.has_work():
                eng.step()
            assert r0.done()
        burst = [eng.submit(np.concatenate(
            [tmpl, rng.randint(0, vocab,
                               (int(rng.choice(sufs)),)).astype(np.int32)]),
            budget_cap) for _ in range(burst_n)]
        peak = 0
        while eng.has_work():
            peak = max(peak, eng.scheduler.occupancy())
            eng.step()
            peak = max(peak, eng.scheduler.occupancy())
        assert all(b.done() for b in burst)
        peaks[kind] = peak
        check_no_compiles(f"capacity-{kind}", mark)
        eng.close()
    capacity_ratio = peaks["warm"] / max(1, peaks["cold"])

    # -- fleet leg: prefix-affine routing -------------------------------
    fleet_stats = None
    if not smoke:
        replicas = [build(True, slots=4) for _ in range(2)]
        fleet = FleetRouter(replicas, prefix_affinity=True,
                            prefix_affinity_tokens=tlen)
        fleet.warmup()
        mark = serving_compiles()
        for i in range(n_req):
            r = reqs[i % n_req]
            fleet.submit(r["prompt"], r["max_new"])
            fleet.run_until_drained(timeout=600)
        per_replica = [rep.engine.prefix_cache.stats()
                       for rep in fleet.manager.replicas()]
        # a template's blocks must live on ONE replica: nodes split,
        # not duplicated (total nodes ~= single-engine warm footprint)
        fleet_stats = {
            "replica_hit_rates": [round(s["hit_rate"], 3)
                                  for s in per_replica],
            "total_nodes": sum(s["nodes"] for s in per_replica),
            "hit_rate": round(
                sum(s["hits"] for s in per_replica)
                / max(1, sum(s["hits"] + s["misses"]
                             for s in per_replica)), 3),
            "affinity_keys": len(fleet._affinity),
        }
        check_no_compiles("fleet-leg", mark)
        fleet.close()

    cold_cc = cold_eng.compile_counts()
    warm_cc = warm_eng.compile_counts()
    cold_eng.close()
    warm_eng.close()

    out = {
        "warm_ttft_ratio": (round(warm_ttft_ratio, 3)
                            if warm_ttft_ratio is not None else None),
        "cold_ttft_ms": round(1e3 * sum(cold_sel) / max(1, len(cold_sel)),
                              2),
        "warm_ttft_ms": round(1e3 * sum(warm_sel) / max(1, len(warm_sel)),
                              2),
        "capacity_ratio": round(capacity_ratio, 2),
        "peak_resident": peaks,
        "hit_rate": round(traffic_hit_rate, 3),
        "hit_rate_curve": hit_curve,
        "warm_cache": {k: (round(v, 3) if isinstance(v, float) else v)
                       for k, v in warm_stats.items()},
        "fleet": fleet_stats,
        "compile_counts": {"cold": cold_cc, "warm": warm_cc},
        "requests": n_req, "smoke": smoke,
        "workload": f"{n_templates} templates x {tlen} tokens + "
                    f"{list(sufs)}-token suffixes, budget {budget}, "
                    f"greedy, GPT ({dims['hidden_size']}h/"
                    f"{dims['num_hidden_layers']}L/{vocab}v), "
                    f"block_size={bs}, buckets={list(buckets)}, cpu",
    }
    failures = []
    if parity_failures:
        failures.append(f"parity: requests {parity_failures[:5]} diverged "
                        "between the warm and cold legs")
    for v in compile_violations:
        failures.append(f"post-warmup compiles detected ({v})")
    for leg, cc in (("cold", cold_cc), ("warm", warm_cc)):
        if cc["total"] > cc["bound"]:
            failures.append(f"{leg} leg compiled {cc['total']} programs > "
                            f"bound {cc['bound']}")
    if not smoke:
        if warm_ttft_ratio is None or warm_ttft_ratio > 0.5:
            failures.append(f"warm_ttft_ratio {out['warm_ttft_ratio']} "
                            "> 0.5x bar")
        if capacity_ratio < 2.0:
            failures.append(f"capacity_ratio {out['capacity_ratio']} "
                            "< 2.0x bar")
        if traffic_hit_rate < 0.5:
            failures.append(f"hit_rate {out['hit_rate']} < 0.5 bar")
    if failures:
        out["failures"] = failures
    print("PREFIX" + json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
