#!/usr/bin/env python
"""Speculative decoding + int8 weight-only quantization probe (ISSUE-7
acceptance artifact).

Three serving legs over the same greedy request set on a tiny GPT (CPU):

- **baseline leg**: the PR-4 continuous-batching engine (no draft) —
  the non-speculative tokens/sec reference.
- **speculative leg**: the same engine fronted by a draft model with
  ``spec_tokens`` proposals per tick.  The draft/target pair is
  CONSTRUCTED for high agreement: the draft is the target's first
  block(s) + final LN + tied head, and the target's remaining blocks have
  their residual contributions scaled by a small epsilon — so the draft
  is an accurate predictor the way a distilled production draft would be.
  The probe therefore measures the speculative PIPELINE (per-tick
  dispatch amortization, accept/reject commit, program bound) at a
  realistic accept rate, not draft training quality.  Published:
  ``accept_rate`` and ``tokens_per_sec_ratio`` (spec vs baseline).
- **quant leg**: the target converted by
  ``quantization.quantize_for_serving`` (int8 weight-only, per-channel
  scales, dequant-at-use) served WITHOUT a draft — isolating the
  quantization effect.  Published: ``int8_tokens_per_sec_ratio`` and
  ``max_logit_err`` (quantized vs fp32 logits on a fixed batch).

Every leg is warmed before timing.  Parity bars (all modes): every
baseline AND speculative greedy stream bit-identical to solo
`generation.generate` of the target; every quant-leg stream bit-identical
to solo generate of the QUANTIZED model (int8 changes the function, so
its oracle is itself — the fp32 gap is bounded separately by
``max_logit_err``); compile counts at the len(buckets)+1 bound on every
engine.  Perf bars (full mode only): tokens_per_sec_ratio >= 1.5 with
accept_rate >= 0.6, and max_logit_err <= 0.05 * max|fp32 logit|.
``--steps N`` (N <= 5) is the CI smoke mode: parity bars only.  Prints
one ``SPEC{json}`` line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40,
                    help="number of requests (<=5 switches to smoke mode: "
                         "parity-only bars)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--spec-tokens", type=int, default=10)
    ap.add_argument("--chunk", type=int, default=4,
                    help="baseline decode iterations per compiled call")
    ap.add_argument("--eps", type=float, default=0.02,
                    help="residual scale of the target's extra blocks "
                         "(draft accuracy knob)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.quantization import quantize_for_serving
    from paddle_tpu.serving import ServingEngine

    n_req = max(1, args.steps)
    smoke = n_req <= 5

    # full mode: decode must be in the regime speculation exists for — a
    # target deep enough that the verify's batched per-token cost is well
    # under a solo step's, and an 8:1 target:draft depth ratio (the shape
    # of production pairs).  Smoke mode shrinks everything and only
    # checks parity + wiring, not the perf bars.
    if smoke:
        dims = dict(vocab_size=96, hidden_size=48, num_hidden_layers=2,
                    num_attention_heads=2)
        draft_layers, slots = 1, min(args.slots, 4)
    else:
        dims = dict(vocab_size=512, hidden_size=256, num_hidden_layers=8,
                    num_attention_heads=8)
        draft_layers, slots = 1, args.slots

    def build(layers):
        cfg = models.GPTConfig(hidden_dropout_prob=0.0,
                               attention_probs_dropout_prob=0.0,
                               max_position_embeddings=128,
                               **{**dims, "num_hidden_layers": layers})
        return models.GPTForPretraining(cfg)

    # draft = target's first `draft_layers` blocks + embeddings + ln_f
    # (shared weights); target's EXTRA blocks get their residual outputs
    # scaled by eps -> target ~= draft + small perturbation, the
    # high-agreement regime a trained draft model lives in
    paddle.seed(11)
    target = build(dims["num_hidden_layers"])
    tsd = {k: v.numpy().copy() for k, v in target.state_dict().items()}
    for i in range(draft_layers, dims["num_hidden_layers"]):
        for nm in (f"gpt.blocks.{i}.proj.weight",
                   f"gpt.blocks.{i}.ffn_out.weight"):
            tsd[nm] = tsd[nm] * args.eps
        for nm in (f"gpt.blocks.{i}.proj.bias",
                   f"gpt.blocks.{i}.ffn_out.bias"):
            tsd[nm] = np.zeros_like(tsd[nm])
    target.set_state_dict(tsd)
    target.eval()
    draft = build(draft_layers)
    draft.set_state_dict({k: tsd[k] for k, _ in draft.state_dict().items()})
    draft.eval()

    rng = np.random.RandomState(args.seed)
    vocab = dims["vocab_size"]
    plens = [4, 7, 12]
    # budgets sized several speculative ticks deep: a slot finishing
    # mid-tick discards the tail of that tick's commits, so budgets must
    # dwarf spec_tokens for the measured ratio to reflect steady state
    budgets = [40, 56, 72]
    reqs = [{"prompt": rng.randint(
                 0, vocab, (plens[int(rng.randint(len(plens)))],)
             ).astype(np.int32),
             "max_new": budgets[int(rng.randint(len(budgets)))]}
            for _ in range(n_req)]

    def solo(model, prompt, max_new):
        out, _ = model.generate(paddle.to_tensor(
            np.asarray(prompt, np.int32)[None]), max_new_tokens=max_new)
        return np.asarray(out.numpy())[0].tolist()

    oracle = [solo(target, r["prompt"], r["max_new"]) for r in reqs]
    total_tokens = sum(len(t) for t in oracle)

    def run_leg(engine):
        engine.warmup()
        engine.reset_metrics()
        t0 = time.monotonic()
        resps = [engine.submit(r["prompt"], r["max_new"]) for r in reqs]
        engine.run_until_drained(timeout=600)
        wall = time.monotonic() - t0
        streams = [r.tokens(timeout=5) for r in resps]
        met = engine.metrics()
        cc = engine.compile_counts()
        engine.close()
        return streams, total_tokens / wall, met, cc

    failures = []

    def check(streams, want, cc, leg):
        bad = [i for i in range(n_req) if streams[i] != want[i]]
        if bad:
            failures.append(f"{leg} parity: requests {bad[:5]} diverged")
        if cc["total"] > cc["bound"]:
            failures.append(f"{leg} compiled {cc['total']} programs > "
                            f"bound {cc['bound']}")

    eng_opts = dict(max_slots=slots, max_len=96, prefill_buckets=(8, 16),
                    max_queue_depth=max(64, n_req))

    base_streams, base_tps, _, base_cc = run_leg(
        ServingEngine(target, decode_chunk=args.chunk, **eng_opts))
    check(base_streams, oracle, base_cc, "baseline")

    spec_streams, spec_tps, spec_met, spec_cc = run_leg(
        ServingEngine(target, draft_model=draft,
                      spec_tokens=args.spec_tokens, **eng_opts))
    check(spec_streams, oracle, spec_cc, "speculative")
    accept_rate = spec_met["spec"]["accept_rate"] or 0.0

    # -- quant leg: fp32 reference logits FIRST, then convert in place ----
    probe_ids = paddle.to_tensor(
        rng.randint(0, vocab, (4, 12)).astype(np.int32))
    ref_logits = target(probe_ids).numpy()
    qtarget = quantize_for_serving(target)  # in place; fp32 legs are done
    q_logits = qtarget(probe_ids).numpy()
    max_logit_err = float(np.abs(q_logits - ref_logits).max())
    logit_scale = float(np.abs(ref_logits).max())
    q_oracle = [solo(qtarget, r["prompt"], r["max_new"]) for r in reqs]
    q_streams, q_tps, _, q_cc = run_leg(
        ServingEngine(qtarget, decode_chunk=args.chunk, **eng_opts))
    check(q_streams, q_oracle, q_cc, "quant")

    out = {
        "spec_decode": {
            "accept_rate": round(accept_rate, 3),
            "tokens_per_sec_ratio": round(spec_tps / base_tps, 2),
            "tokens_per_sec": round(spec_tps, 1),
            "baseline_tokens_per_sec": round(base_tps, 1),
            "spec_tokens": args.spec_tokens,
            "ticks": spec_met["spec"]["ticks"],
            "compile_counts": spec_cc,
        },
        "quant": {
            "int8_tokens_per_sec_ratio": round(q_tps / base_tps, 2),
            "tokens_per_sec": round(q_tps, 1),
            "max_logit_err": round(max_logit_err, 5),
            "max_logit_err_rel": round(max_logit_err
                                       / max(logit_scale, 1e-9), 4),
            "compile_counts": q_cc,
        },
        "requests": n_req, "total_tokens": total_tokens, "smoke": smoke,
        "slots": slots,
        "workload": f"greedy, prompt_len in {plens}, max_new in "
                    f"{budgets}, GPT "
                    f"({dims['hidden_size']}h/{dims['num_hidden_layers']}L/"
                    f"{vocab}v), draft {draft_layers}L shared-weight, "
                    f"eps={args.eps}, cpu",
    }
    if not smoke:
        if accept_rate < 0.6:
            failures.append(f"accept_rate {accept_rate:.3f} < 0.6 bar")
        if out["spec_decode"]["tokens_per_sec_ratio"] < 1.5:
            failures.append(
                f"spec speedup {out['spec_decode']['tokens_per_sec_ratio']}"
                " < 1.5x bar")
        if max_logit_err > 0.05 * logit_scale:
            failures.append(
                f"max_logit_err {max_logit_err:.5f} > 5% of logit scale "
                f"{logit_scale:.3f}")
    if failures:
        out["failures"] = failures
    print("SPEC" + json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
