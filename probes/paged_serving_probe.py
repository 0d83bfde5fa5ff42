#!/usr/bin/env python
"""Paged-vs-fixed serving probe (ISSUE-8 acceptance artifact).

The paged KV pool's claim is a DENSITY claim: block-granular allocation
lets mixed-length requests share HBM, so the same KV byte budget holds
more resident decodes than the fixed `(max_slots, max_len)` slot pool —
without giving back throughput.  This probe measures exactly that on
CPU:

- **fixed leg**: `ServingEngine(kv="fixed", max_slots=F, max_len=512)` —
  every resident request charges the full 512 rows of KV.
- **paged leg**: `ServingEngine(kv="paged")` with `num_blocks` chosen so
  its block pool holds EXACTLY the same KV rows/bytes as the fixed leg
  (kv_bytes_ratio below proves it), but `max_slots` unconstrained — the
  block allocator, not the slot-row geometry, bounds residency.

Both legs serve the SAME saturated batch of mixed 32–512-token greedy
requests (prompt 16, budgets spanning the full range), warmed before the
clocks, and every paged stream must be BIT-IDENTICAL to the fixed leg's
stream for the same request — density can never hide a wrong-KV bug.

Bars (full mode, CPU-reproducible):
  resident_slots_ratio  peak resident paged / fixed  >= 2.0
  tokens_per_sec_ratio  paged tps / fixed tps        >= 0.9
  kv_bytes_ratio        paged pool bytes / fixed     == 1.0 (+-1%)
  parity                every stream identical       (always enforced)
  compile bound         len(buckets)+1 on both legs  (always enforced)

`--steps N` (N <= 5) is the CI smoke mode: tiny shapes, parity/bound
only.  Prints one `PAGED{json}` line; exit 1 on any bar miss.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=32,
                    help="number of requests (<=5 switches to smoke mode)")
    ap.add_argument("--fixed-slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=16,
                    help="decode iterations per compiled call")
    ap.add_argument("--block-size", type=int, default=32)
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

    if smoke:
        dims = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2)
        max_len, plen, bs, fixed_slots = 64, 8, 8, 2
        budgets = [8, 24, 48]
        max_pos = 96
    else:
        dims = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4)
        max_len, plen, bs, fixed_slots = 512, 16, args.block_size, \
            args.fixed_slots
        # totals (plen + budget) span the full 32..512 mixed range
        budgets = [16, 56, 152, 344, 488]
        max_pos = 520
    cfg = models.GPTConfig(hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           max_position_embeddings=max_pos, **dims)
    paddle.seed(11)
    model = models.GPTForPretraining(cfg)
    model.eval()

    rng = np.random.RandomState(args.seed)
    vocab = dims["vocab_size"]
    reqs = [{"prompt": rng.randint(0, vocab, (plen,)).astype(np.int32),
             "max_new": budgets[int(rng.randint(len(budgets)))]}
            for _ in range(n_req)]
    total_tokens = sum(r["max_new"] for r in reqs)
    bucket = 32 if not smoke else 8

    def pool_bytes(pools):
        return int(sum(k.size * k.dtype.itemsize + v.size * v.dtype.itemsize
                       for k, v in pools))

    def build(kind):
        nb_rows = fixed_slots * max_len           # the shared KV budget
        if kind == "fixed":
            eng = ServingEngine(model, max_slots=fixed_slots,
                                max_len=max_len, prefill_buckets=(bucket,),
                                decode_chunk=args.chunk,
                                max_queue_depth=max(64, n_req))
        else:
            eng = ServingEngine(model, max_slots=2 * fixed_slots,
                                max_len=max_len, prefill_buckets=(bucket,),
                                decode_chunk=args.chunk, kv="paged",
                                block_size=bs, num_blocks=nb_rows // bs,
                                max_queue_depth=max(64, n_req))
        eng.warmup()
        return eng

    def one_rep(eng, rec):
        eng.reset_metrics()
        resps = [eng.submit(r["prompt"], r["max_new"]) for r in reqs]
        t0 = time.perf_counter()    # the clock of Response.finished_at
        while eng.has_work():                      # saturated drive
            eng.step()
            rec["peak_resident_slots"] = max(
                rec.get("peak_resident_slots", 0),
                eng.scheduler.occupancy())
        wall = max(r.finished_at for r in resps) - t0
        rec["tokens_per_sec"] = max(rec.get("tokens_per_sec", 0.0),
                                    total_tokens / wall)
        return [r.tokens(timeout=5) for r in resps]

    # INTERLEAVED best-of-N timed reps: the shared bench box carries
    # transient co-tenant load, and a single ~10s window can eat 5%+ of
    # either leg — alternating fixed/paged reps and taking each leg's
    # best makes the RATIO robust to slow drift.  Streams from the last
    # rep feed the parity check.
    engines = {"fixed": build("fixed"), "paged": build("paged")}
    fixed, paged = {}, {}
    for _ in range(1 if smoke else 3):
        fixed_streams = one_rep(engines["fixed"], fixed)
        paged_streams = one_rep(engines["paged"], paged)
    for kind, rec in (("fixed", fixed), ("paged", paged)):
        eng = engines[kind]
        rec["kv_bytes"] = pool_bytes(eng._pools)
        rec["compile_counts"] = eng.compile_counts()
        rec["kv_pool"] = eng.metrics()["kv_pool"]
        eng.close()

    parity_failures = [i for i in range(n_req)
                       if paged_streams[i] != fixed_streams[i]]
    out = {
        "resident_slots_ratio": round(
            paged["peak_resident_slots"]
            / max(1, fixed["peak_resident_slots"]), 2),
        "kv_bytes_ratio": round(paged["kv_bytes"] / fixed["kv_bytes"], 4),
        "tokens_per_sec_ratio": round(
            paged["tokens_per_sec"] / fixed["tokens_per_sec"], 3),
        "fixed": {k: (round(v, 1) if isinstance(v, float) else v)
                  for k, v in fixed.items()},
        "paged": {k: (round(v, 1) if isinstance(v, float) else v)
                  for k, v in paged.items()},
        "requests": n_req, "total_tokens": total_tokens, "smoke": smoke,
        "workload": f"greedy, prompt {plen}, totals "
                    f"{sorted({plen + b for b in budgets})}, saturated "
                    f"submit, GPT ({dims['hidden_size']}h/"
                    f"{dims['num_hidden_layers']}L/{vocab}v), "
                    f"block_size={bs}, cpu",
    }
    failures = []
    if parity_failures:
        failures.append(f"parity: requests {parity_failures[:5]} diverged "
                        "between the paged and fixed legs")
    for leg, rec in (("fixed", fixed), ("paged", paged)):
        cc = rec["compile_counts"]
        if cc["total"] > cc["bound"]:
            failures.append(f"{leg} leg compiled {cc['total']} programs > "
                            f"bound {cc['bound']}")
    if not smoke:
        if abs(out["kv_bytes_ratio"] - 1.0) > 0.01:
            failures.append(f"kv budgets differ: ratio "
                            f"{out['kv_bytes_ratio']} != 1.0")
        if out["resident_slots_ratio"] < 2.0:
            failures.append(f"resident_slots_ratio "
                            f"{out['resident_slots_ratio']} < 2.0x bar")
        if out["tokens_per_sec_ratio"] < 0.9:
            failures.append(f"tokens_per_sec_ratio "
                            f"{out['tokens_per_sec_ratio']} < 0.9x bar")
    if failures:
        out["failures"] = failures
    print("PAGED" + json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
