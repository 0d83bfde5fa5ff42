#!/usr/bin/env python
"""Program-lifecycle probe (ISSUE-9 acceptance artifact): second-process
serving cold start with a warm program store + AOT program set vs a cold
one.

Two python SUBPROCESSES boot the same speculative serving stack (GPT
target + small draft, spec_tokens, two prefill buckets) through the real
deployment API — ``Config.enable_serving(model_provider=...)`` →
``create_predictor`` → first streamed token:

- **cold leg**: fresh empty ``PDTPU_PROGRAM_CACHE_DIR`` — pays full
  tracing + XLA compilation for the whole program family (and writes
  both the store entries and, after measurement, the AOT program-set
  artifact via ``predictor.save_program_set``).
- **warm leg**: same store dir (now populated) +
  ``enable_serving(program_set=...)`` — boots from the serialized native
  executables with ZERO model tracing and ZERO XLA compilation.

Bars (full mode, CPU-reproducible):

- warm-leg cold start (enable_serving → first token) >= ``--bar``x
  (default 5x) faster than the cold leg,
- ZERO post-warmup compiles in BOTH legs under mixed traffic — spec
  on/off x greedy/sampling combos — asserted by the compiled-program
  registry AND the engine trace counters (`post_warmup_compiles()`),
- compile count at the len(prefill_buckets)+1 bound in both legs,
- every warm-leg stream bit-identical to its cold-leg twin (greedy AND
  sampled), and every greedy stream bit-identical to a solo
  `generation.generate` of the same prompt.

``--steps N`` (N <= 5) is the CI smoke: a tiny model, parity +
zero-post-warmup-compile assertions only, the speed bar skipped.  Prints
one ``PROGCACHE{json}`` line; exits 1 on any bar miss.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time


def _leg_env(workdir: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PDTPU_PROGRAM_CACHE_DIR"] = os.path.join(workdir, "store")
    return env


def _model_dims(smoke: bool) -> dict:
    if smoke:
        return dict(vocab_size=64, hidden_size=16, target_layers=2,
                    draft_layers=1, heads=2)
    # deep-narrow on purpose: XLA compile + python trace scale with op
    # count while the warm leg's executable load does not scale with
    # either python or optimization time — the regime a real fleet model
    # is in (minutes of compile, seconds of load)
    return dict(vocab_size=512, hidden_size=128, target_layers=20,
                draft_layers=2, heads=4)


def _traffic_plan(dims):
    import numpy as np
    rng = np.random.RandomState(5)
    short = rng.randint(1, dims["vocab_size"], (4,)).astype(np.int32)
    mid = rng.randint(1, dims["vocab_size"], (6,)).astype(np.int32)
    longer = rng.randint(1, dims["vocab_size"], (12,)).astype(np.int32)
    # spec on/off x greedy/sampling x both buckets share the two traces
    return [
        dict(prompt=short, max_new=6),                      # timed request
        dict(prompt=mid, max_new=6, spec=False),
        dict(prompt=short, max_new=6, decode_strategy="sampling",
             temperature=0.8, top_k=5, seed=11),
        dict(prompt=mid, max_new=6, decode_strategy="sampling",
             temperature=1.2, top_p=0.9, seed=12, spec=False),
        dict(prompt=longer, max_new=6),
        dict(prompt=longer, max_new=6, decode_strategy="sampling",
             top_k=3, seed=13),
    ]


def run_leg(args):
    """One boot measurement in a clean subprocess (cold or warm)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference, jit, models
    from paddle_tpu.programs import store_stats

    smoke = args.steps <= 5
    dims = _model_dims(smoke)
    workdir = args.workdir
    gcfg = models.GPTConfig(
        vocab_size=dims["vocab_size"], hidden_size=dims["hidden_size"],
        num_hidden_layers=dims["target_layers"],
        num_attention_heads=dims["heads"], hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, max_position_embeddings=128)
    dcfg = models.GPTConfig(
        vocab_size=dims["vocab_size"], hidden_size=dims["hidden_size"],
        num_hidden_layers=dims["draft_layers"],
        num_attention_heads=dims["heads"], hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, max_position_embeddings=128)

    # model + draft are enable_serving INPUTS, built and weight-restored
    # before the measured window: the window isolates what this layer
    # changes (program tracing + compilation vs store/program-set load) —
    # arch construction and the npz weight restore are byte-identical
    # work in both legs
    prefix = os.path.join(workdir, "weights")
    pset = os.path.join(workdir, "pset.pdprograms")
    plan = _traffic_plan(dims)
    paddle.seed(3)
    model = models.GPTForPretraining(gcfg)
    model.eval()
    if not os.path.exists(prefix + ".pdiparams.npz"):
        # the jit.save weights artifact every replica restores from
        # (created once by the cold leg, before its measured window)
        jit.save(model, prefix)
    data = np.load(prefix + ".pdiparams.npz")
    model.set_state_dict({k: data[k] for k in data.files})
    paddle.seed(4)
    draft = models.GPTForPretraining(dcfg)
    draft.eval()

    # ---- the measured window: enable_serving -> first streamed token ----
    engine_opts = dict(model=model, draft_model=draft,
                       spec_tokens=3, max_slots=2, max_len=48,
                       prefill_buckets=(8, 16), decode_chunk=2,
                       warmup=True, start=False)
    if args.leg == "warm":
        engine_opts["program_set"] = pset
    cfg = inference.Config(prefix)
    t0 = time.perf_counter()
    cfg.enable_serving(**engine_opts)
    pred = inference.create_predictor(cfg)
    eng = pred.engine
    first = plan[0]
    resp = eng.submit(first["prompt"], first["max_new"])
    while resp.first_token_at is None and eng.has_work():
        eng.step()
    boot_s = time.perf_counter() - t0

    # ---- mixed traffic: spec on/off x sampling combos -------------------
    resps = [resp]
    for r in plan[1:]:
        kw = {k: v for k, v in r.items() if k not in ("prompt", "max_new")}
        resps.append(eng.submit(r["prompt"], r["max_new"], **kw))
    eng.run_until_drained(timeout=600)
    streams = [r.tokens(timeout=10) for r in resps]
    result = {
        "leg": args.leg,
        "boot_s": boot_s,
        "streams": streams,
        "post_warmup_compiles": eng.post_warmup_compiles(),
        "compile_counts": eng.compile_counts(),
        "program_set_kinds": (eng.program_set_info or {}).get("kinds"),
        "store": store_stats(),
    }

    if args.leg == "cold":
        # greedy solo oracles (parity vs generation.generate) — outside
        # the timed window, oracle compiles land in the store too
        model = eng.model
        solo = {}
        for i, r in enumerate(plan):
            if r.get("decode_strategy", "greedy_search") == "greedy_search":
                out, _ = model.generate(
                    paddle.to_tensor(np.asarray(r["prompt"])[None]),
                    max_new_tokens=r["max_new"])
                solo[str(i)] = np.asarray(out.numpy())[0].tolist()
        result["solo"] = solo
        # the AOT program-set artifact the warm leg boots from
        pred.save_program_set(pset)
        result["program_set_bytes"] = os.path.getsize(pset)
    pred.close()
    with open(os.path.join(workdir, f"leg_{args.leg}.json"), "w") as f:
        json.dump(result, f)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=32,
                    help="<=5 switches to smoke mode (tiny model, parity "
                         "and zero-compile assertions only, no speed bar)")
    ap.add_argument("--bar", type=float, default=5.0,
                    help="required cold/warm cold-start ratio (full mode)")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--leg", choices=("cold", "warm"), default=None,
                    help="internal: run one boot leg in this process")
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    if args.leg:
        return run_leg(args)

    smoke = args.steps <= 5
    tmp = None
    if args.workdir is None:
        tmp = tempfile.TemporaryDirectory(prefix="pdtpu_progcache_")
        args.workdir = tmp.name
    os.makedirs(os.path.join(args.workdir, "store"), exist_ok=True)
    env = _leg_env(args.workdir)

    legs = {}
    for leg in ("cold", "warm"):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--leg", leg,
             "--steps", str(args.steps), "--workdir", args.workdir],
            capture_output=True, text=True, timeout=1200, env=env)
        if proc.returncode != 0:
            print("PROGCACHE" + json.dumps({
                "failures": [f"{leg} leg crashed: "
                             f"{(proc.stderr or proc.stdout)[-600:]}"]}),
                flush=True)
            return 1
        with open(os.path.join(args.workdir, f"leg_{leg}.json")) as f:
            legs[leg] = json.load(f)

    cold, warm = legs["cold"], legs["warm"]
    ratio = cold["boot_s"] / warm["boot_s"] if warm["boot_s"] > 0 else None
    failures = []
    for leg in ("cold", "warm"):
        pwc = legs[leg]["post_warmup_compiles"]
        if pwc != 0:
            failures.append(f"{leg} leg: {pwc} post-warmup compiles under "
                            "mixed spec/sampling traffic (must be 0)")
        cc = legs[leg]["compile_counts"]
        if cc["total"] > cc["bound"]:
            failures.append(f"{leg} leg compiled {cc['total']} programs > "
                            f"bound {cc['bound']}")
    if warm["streams"] != cold["streams"]:
        bad = [i for i, (a, b) in enumerate(zip(warm["streams"],
                                                cold["streams"])) if a != b]
        failures.append(f"warm-loaded streams diverged from cold-compiled "
                        f"ones at requests {bad} (must be bit-identical)")
    for i, toks in cold.get("solo", {}).items():
        if cold["streams"][int(i)] != toks:
            failures.append(f"cold greedy stream {i} diverged from solo "
                            "generate")
    if not smoke and (ratio is None or ratio < args.bar):
        failures.append(f"cold/warm cold-start ratio {ratio and round(ratio, 2)} "
                        f"< {args.bar}x bar")

    out = {
        "cold_start_ratio": None if ratio is None else round(ratio, 2),
        "post_warmup_compiles": max(cold["post_warmup_compiles"],
                                    warm["post_warmup_compiles"]),
        "cold_start_s": round(cold["boot_s"], 3),
        "warm_start_s": round(warm["boot_s"], 3),
        "program_set_kinds": warm.get("program_set_kinds"),
        "program_set_bytes": cold.get("program_set_bytes"),
        "compile_counts": cold["compile_counts"],
        "store_cold": {k: cold["store"][k] for k in
                       ("entries", "hits", "misses")},
        "store_warm": {k: warm["store"][k] for k in
                       ("entries", "hits", "misses")},
        "streams_checked": len(cold["streams"]),
        "greedy_solo_checked": len(cold.get("solo", {})),
        "smoke": smoke,
        "workload": "speculative serving boot (GPT target + draft, "
                    "spec on/off x greedy/sampling mixed traffic), "
                    "enable_serving -> first token, cpu",
    }
    if failures:
        out["failures"] = failures
    print("PROGCACHE" + json.dumps(out), flush=True)
    if tmp is not None:
        tmp.cleanup()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
