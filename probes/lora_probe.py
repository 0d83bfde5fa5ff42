#!/usr/bin/env python
"""Batched-LoRA probe (PR-20 acceptance artifact).

The subsystem's claim is a MULTIPLEXING claim: one base model serves
many tenant fine-tunes because the per-slot adapter id is a DYNAMIC
input of the same compiled prefill/decode programs — heterogeneous
adapters batch in one tick, a new adapter is a registry page-in (never
a compile), and adapter id 0 is the base model bit-for-bit.  This probe
measures exactly that on CPU, against the single-model ceiling:

- **parity leg**: a no-LoRA engine and a LoRA engine serve the same
  base prompts (must be bit-identical); every adapter stream from a
  heterogeneous batch — 8 DISTINCT adapters resident in one decode
  tick — must be bit-identical to its solo single-adapter oracle.
- **eager leg**: the train-side wrapper's logits vs the dense
  merged-weight oracle (`W + scaling*A@B` substituted into a plain
  model) — `max_logit_err` is the offline-merge contract.
- **throughput leg**: Poisson mixed-adapter traffic on the LoRA engine
  vs the SAME traffic (no adapter stamps) on the plain engine; the
  ratio (`mixed_adapter_tokens_ratio`) is what multi-tenancy costs.
- **swap leg**: with adapters resident and traffic served, the BASE
  weights flip via `swap_weights` (the PR-19 refresh path).  Loaded
  adapters must survive the flip — the post-flip adapter stream is
  bit-identical to a fresh engine built on the new base serving the
  same adapter — with ZERO compiles (`swap_zero_compiles`).
- **ship leg**: export a fresh adapter and hot-load it into (a) the
  live in-process engine and (b) a FLEET of one in-process replica +
  one REMOTE `--listen` worker over the chunked sha256-verified
  channel.  `adapter_ship_to_first_token_s` is the fleet wall time
  from "artifact on disk" to the first token decoded under the new
  adapter — and the hot-load must require NO rollout (same replica
  ids, zero restarts, every replica reports the adapter sha in its
  health snapshot).

Nothing may compile after warmup in ANY leg, and the LoRA engine's
compile bound must equal the plain engine's (`len(buckets)+1`): an
adapter is data, not a program.

Bars (full mode, CPU-reproducible):
  mixed_adapter_tokens_ratio  lora mixed / single-model ceiling >= 0.8
  distinct_adapters           max distinct adapter ids in a tick >= 8
  max_logit_err               eager vs merged-dense oracle      <= 1e-4
  swap_zero_compiles          base flip keeps adapters, no compile
  parity                      every stream identical            (always)
  compiles                    zero post-warmup, bound unchanged (always)
  no_rollout                  fleet hot-load restarts nothing

`--steps N` (N <= 5) is the CI smoke mode: tiny shapes, 3 adapters,
parity/eager/bound only (swap/ship legs skipped).  Prints one
`LORA{json}` line; exit 1 on any bar miss.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import subprocess
import sys
import threading
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24,
                    help="requests in the timed leg (<=5 switches to smoke)")
    ap.add_argument("--adapters", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import tempfile

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import lora, models, observability
    from paddle_tpu.serving import FleetRouter, ServingEngine

    n_req = max(1, args.steps)
    smoke = n_req <= 5

    if smoke:
        dims = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2)
        max_len, buckets, max_pos = 64, (8,), 96
        slots, n_adapters, budget = 4, 3, 8
        targets = ("qkv",)
    else:
        dims = dict(vocab_size=256, hidden_size=128, num_hidden_layers=4,
                    num_attention_heads=4)
        max_len, buckets, max_pos = 64, (8, 32), 96
        slots, n_adapters, budget = 8, max(1, args.adapters), 16
        targets = ("qkv", "proj")
    rank = 4 if smoke else 8
    cfg = models.GPTConfig(hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           max_position_embeddings=max_pos, **dims)

    def model_for(c, seed):
        paddle.seed(seed)
        m = models.GPTForPretraining(c)
        m.eval()
        return m

    def base_model(seed=11):
        return model_for(cfg, seed)

    def make_adapter(seed, path, c=cfg, base_seed=11, r=None, tg=None):
        """Export a deterministic NONZERO adapter (a fresh wrap has B=0
        and would be the base model verbatim)."""
        r = rank if r is None else r
        tg = targets if tg is None else tg
        m = model_for(c, base_seed)
        paths = lora.apply_lora(m, rank=r, targets=tg)
        rng = np.random.default_rng(seed)
        for lyr in m.sublayers(include_self=True):
            if isinstance(lyr, lora.LoRALinear):
                lyr.lora_A._data = paddle.to_tensor(rng.normal(
                    0, 0.2, lyr.lora_A.shape).astype("float32"))._data
                lyr.lora_B._data = paddle.to_tensor(rng.normal(
                    0, 0.2, lyr.lora_B.shape).astype("float32"))._data
        return m, paths, lora.export_adapter(m, path)

    d = tempfile.mkdtemp(prefix="lora_probe_")
    names = [f"t{i}" for i in range(n_adapters)]
    artifacts = {}
    eager_model = None
    eager_paths = None
    for i, name in enumerate(names):
        path = os.path.join(d, f"{name}.npz")
        m, paths, sha = make_adapter(100 + i, path)
        artifacts[name] = path
        if i == 0:
            eager_model, eager_paths = m, paths

    # -- eager leg: wrapper vs dense merged oracle ----------------------
    merged = base_model()
    for p in eager_paths:
        w = functools.reduce(getattr, p.split("."), eager_model)
        dense = functools.reduce(getattr, p.split("."), merged)
        dense.weight._data = paddle.to_tensor(
            np.asarray(w.merged_weight()))._data
    rng = np.random.RandomState(args.seed)
    ids = paddle.to_tensor(rng.randint(
        1, dims["vocab_size"], (2, 16)).astype(np.int64))
    max_logit_err = float(np.max(np.abs(
        eager_model(ids).numpy() - merged(ids).numpy())))

    # -- engines --------------------------------------------------------
    lcfg = lora.LoRAConfig(rank=rank, max_adapters=n_adapters,
                           targets=targets)
    ekw = dict(max_slots=slots, max_len=max_len, prefill_buckets=buckets,
               decode_chunk=4, max_queue_depth=max(64, 4 * n_req))
    plain = ServingEngine(base_model(), **ekw)
    eng = ServingEngine(base_model(), lora=lcfg, **ekw)
    plain.warmup()
    eng.warmup()
    for name in names:
        eng.load_adapter(name, artifacts[name])

    reg = observability.get_program_registry()

    def serving_compiles():
        return {k: v["compiles"] for k, v in reg.snapshot().items()
                if k.startswith("serving_")}

    compiles_mark = serving_compiles()
    compile_violations = []

    def check_no_compiles(tag, mark=None):
        after = serving_compiles()
        mark = compiles_mark if mark is None else mark
        if after != mark:
            diff = {k: (mark.get(k), v) for k, v in after.items()
                    if mark.get(k) != v}
            compile_violations.append(f"{tag}: {diff}")

    def drain(e, track=None):
        peak = 0
        while e.has_work():
            if track is not None:
                peak = max(peak, len({r.aid for r in e._slots.values()
                                      if r.aid}))
            e.step()
        return peak

    def solo(e, prompt, adapter=None, n=None):
        resp = e.submit(prompt, budget if n is None else n, adapter=adapter)
        drain(e)
        return resp

    # -- parity leg -----------------------------------------------------
    prompts = [rng.randint(1, dims["vocab_size"],
                           (int(rng.choice((5, 12, 24) if not smoke
                                           else (5, 6))),)).astype(np.int32)
               for _ in range(max(n_req, n_adapters))]
    parity_failures = []
    for i in range(min(4, len(prompts))):
        a = solo(plain, prompts[i]).tokens(timeout=5)
        b = solo(eng, prompts[i]).tokens(timeout=5)
        if a != b:
            parity_failures.append(f"base prompt {i}: lora engine diverged")
    oracle = {n: solo(eng, prompts[0], adapter=n).tokens(timeout=5)
              for n in names}
    if len(set(map(tuple, oracle.values()))) < len(names):
        parity_failures.append("distinct adapters produced equal streams")
    mix = [eng.submit(prompts[0], budget, adapter=n) for n in names]
    distinct_adapters = drain(eng, track=True)
    for n, r in zip(names, mix):
        if r.tokens(timeout=5) != oracle[n]:
            parity_failures.append(
                f"adapter {n}: mixed-batch stream != solo oracle")
    check_no_compiles("parity-leg")

    # -- throughput leg: mixed Poisson traffic vs ceiling ---------------
    tokens_per_sec = {}
    if not smoke:
        reqs = [{"prompt": prompts[i % len(prompts)],
                 "adapter": names[int(rng.randint(0, n_adapters))]}
                for i in range(2 * n_req)]
        for kind, e, stamp in (("ceiling", plain, False),
                               ("lora", eng, True)):
            drain(e)
            done = []
            t0 = time.monotonic()
            i = 0
            while i < len(reqs):
                burst = 1 + int(rng.poisson(2.0))
                for _ in range(burst):
                    r = reqs[i % len(reqs)]
                    done.append(e.submit(
                        r["prompt"], budget,
                        adapter=r["adapter"] if stamp else None))
                    i += 1
                drain(e)
            dt = time.monotonic() - t0
            new_tokens = sum(len(r.tokens(timeout=5)) for r in done)
            tokens_per_sec[kind] = new_tokens / max(1e-9, dt)
        check_no_compiles("throughput-leg")
    ratio = (tokens_per_sec["lora"] / max(1e-9, tokens_per_sec["ceiling"])
             if tokens_per_sec else None)

    # -- ship leg (engine): artifact on disk -> first token -------------
    ship_engine_s = None
    if not smoke:
        fresh = os.path.join(d, "fresh.npz")
        make_adapter(999, fresh)
        t0 = time.monotonic()
        eng.load_adapter("fresh", fresh)
        resp = eng.submit(prompts[0], budget, adapter="fresh")
        t_submit = time.monotonic()
        drain(eng)
        ship_engine_s = (t_submit - t0) + resp.ttft
        if not resp.done() or not resp.tokens(timeout=5):
            parity_failures.append("shipped adapter produced no tokens")
        check_no_compiles("ship-leg")

    plain_cc = plain.compile_counts()
    lora_cc = eng.compile_counts()
    plain.close()
    eng.close()

    # The swap and fleet legs run on the TINY shapes regardless of mode:
    # they measure lifecycle properties (adapters survive a base flip,
    # ship-to-first-token across a real remote worker), not throughput,
    # and the remote worker has to warm up in its own process.
    tcfg = models.GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, max_position_embeddings=128)
    tkw = dict(max_slots=4, max_len=64, prefill_buckets=(8,),
               decode_chunk=2)
    t_prompt = np.arange(1, 7, dtype=np.int32)

    # -- swap leg: base flip preserves loaded adapters, zero compiles ---
    swap_zero_compiles = None
    if not smoke:
        from paddle_tpu.jit import state_arrays
        tpath = os.path.join(d, "swap_t.npz")
        make_adapter(555, tpath, c=tcfg, base_seed=11, r=4, tg=("qkv",))
        tl = lora.LoRAConfig(rank=4, max_adapters=4, targets=("qkv",))
        live = ServingEngine(model_for(tcfg, 11), lora=tl, **tkw)
        live.warmup()
        live.load_adapter("t", tpath)
        solo(live, t_prompt, adapter="t", n=8)  # traffic BEFORE the flip
        # oracle: a fresh engine built directly on the NEW base serving
        # the same adapter (the artifact records the OLD training base,
        # so the oracle opts out of the base-hash pin — the flip is a
        # deliberate base transform, exactly the documented opt-out)
        onew = ServingEngine(
            model_for(tcfg, 12),
            lora=lora.LoRAConfig(rank=4, max_adapters=4, targets=("qkv",),
                                 check_base_hash=False), **tkw)
        onew.warmup()
        onew.load_adapter("t", tpath)
        want_ad = solo(onew, t_prompt, adapter="t", n=8).tokens(timeout=5)
        want_b = solo(onew, t_prompt, n=8).tokens(timeout=5)
        onew.close()
        swap_mark = serving_compiles()
        live.swap_weights(state_arrays(model_for(tcfg, 12)),
                          weights_sha="v2")
        got_ad = solo(live, t_prompt, adapter="t", n=8).tokens(timeout=5)
        got_b = solo(live, t_prompt, n=8).tokens(timeout=5)
        swap_zero_compiles = serving_compiles() == swap_mark
        if got_ad != want_ad:
            parity_failures.append(
                "swap leg: post-flip adapter stream != fresh-engine-on-"
                "new-base oracle (adapters must survive swap_weights)")
        if got_b != want_b:
            parity_failures.append(
                "swap leg: post-flip base stream != new base")
        if live.metrics()["lora"]["loaded"] != 1:
            parity_failures.append(
                "swap leg: registry dropped adapters across the flip")
        live.close()

    # -- ship leg (fleet): in-process + remote worker, no rollout -------
    ship_fleet_s = None
    no_rollout = None
    if not smoke:
        tspec = {"model": {"factory": "paddle_tpu.serving.worker:build_gpt",
                           "kwargs": dict(
                               vocab_size=64, hidden_size=32,
                               num_hidden_layers=2, num_attention_heads=2,
                               hidden_dropout_prob=0.0,
                               attention_probs_dropout_prob=0.0,
                               max_position_embeddings=128, seed=11)},
                 "engine": dict(tkw, prefill_buckets=[8]),
                 "lora": lora.LoRAConfig(rank=4, max_adapters=4,
                                         targets=("qkv",)).spec()}
        env = dict(os.environ)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = (root + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else root)
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving.worker",
             "--listen", "127.0.0.1:0", "--index", "0"],
            stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, env=env,
            start_new_session=True)
        fleet = None
        try:
            addr = None
            while True:
                line = proc.stdout.readline()
                if not line:
                    raise RuntimeError("remote worker exited before "
                                       "listening")
                if "worker listening on" in line:
                    addr = line.strip().rsplit(" ", 1)[-1]
                    break
            threading.Thread(target=lambda: proc.stdout.read(),
                             daemon=True).start()
            fleet = FleetRouter([ServingEngine(
                model_for(tcfg, 11),
                lora=lora.LoRAConfig(rank=4, max_adapters=4,
                                     targets=("qkv",)), **tkw)])
            fleet.add_worker(tspec, address=addr, boot_timeout_s=240.0)
            fleet.warmup()
            rids0 = sorted(r.id for r in fleet.manager.replicas())
            fpath = os.path.join(d, "fleet_t.npz")
            make_adapter(777, fpath, c=tcfg, base_seed=11, r=4,
                         tg=("qkv",))
            # artifact on disk -> shipped to EVERY replica (the remote
            # one over the chunked verified channel) -> first token
            t0 = time.monotonic()
            fleet.load_adapter("ft", fpath)
            resp = fleet.submit(t_prompt, 8, adapter="ft")
            deadline = time.monotonic() + 120
            while not resp.tokens_so_far() and not resp.done():
                fleet.step()
                if time.monotonic() > deadline:
                    break
            ship_fleet_s = time.monotonic() - t0
            if not resp.tokens_so_far():
                parity_failures.append(
                    "fleet ship leg: no first token within 120s")
            while not resp.done() and time.monotonic() < deadline:
                fleet.step()
            # hot-load must not be a rollout: same replica set, zero
            # restarts, and every replica's health snapshot reports the
            # adapter's artifact sha
            deadline = time.monotonic() + 30
            snaps = {}
            while time.monotonic() < deadline:
                fleet.step()  # lets worker status frames carry metrics
                snaps = fleet.health()["replicas"]
                if all("ft" in (s.get("adapters") or {})
                       for s in snaps.values()):
                    break
                time.sleep(0.02)
            rids1 = sorted(r.id for r in fleet.manager.replicas())
            restarts = sum(int(s.get("restarts") or 0)
                           for s in snaps.values())
            no_rollout = (rids0 == rids1 and restarts == 0)
            if not all("ft" in (s.get("adapters") or {})
                       for s in snaps.values()):
                parity_failures.append(
                    "fleet ship leg: a replica's health snapshot never "
                    "listed the shipped adapter sha")
            if not no_rollout:
                parity_failures.append(
                    f"fleet ship leg: hot-load caused a rollout "
                    f"(replicas {rids0} -> {rids1}, restarts {restarts})")
        finally:
            if fleet is not None:
                fleet.close()
            proc.kill()
            proc.wait(timeout=10)

    ship_s = ship_fleet_s if ship_fleet_s is not None else ship_engine_s
    out = {
        "mixed_adapter_tokens_ratio": (round(ratio, 3)
                                       if ratio is not None else None),
        "tokens_per_sec": {k: round(v, 1)
                           for k, v in tokens_per_sec.items()},
        "adapter_ship_to_first_token_s": (round(ship_s, 4)
                                          if ship_s is not None else None),
        "adapter_ship_breakdown_s": {
            "engine": (round(ship_engine_s, 4)
                       if ship_engine_s is not None else None),
            "fleet_with_remote": (round(ship_fleet_s, 4)
                                  if ship_fleet_s is not None else None)},
        "swap_zero_compiles": swap_zero_compiles,
        "no_rollout": no_rollout,
        "max_logit_err": max_logit_err,
        "distinct_adapters_in_tick": distinct_adapters,
        "adapters": n_adapters,
        "compile_counts": {"plain": plain_cc, "lora": lora_cc},
        "requests": n_req, "smoke": smoke,
        "workload": f"{n_adapters} rank-{rank} adapters on "
                    f"{list(targets)}, budget {budget}, greedy, GPT "
                    f"({dims['hidden_size']}h/{dims['num_hidden_layers']}L/"
                    f"{dims['vocab_size']}v), buckets={list(buckets)}, "
                    f"{slots} slots, cpu",
    }
    failures = list(parity_failures)
    for v in compile_violations:
        failures.append(f"post-warmup compiles detected ({v})")
    for leg, cc in (("plain", plain_cc), ("lora", lora_cc)):
        if cc["total"] > cc["bound"]:
            failures.append(f"{leg} engine compiled {cc['total']} "
                            f"programs > bound {cc['bound']}")
    if lora_cc["bound"] != plain_cc["bound"]:
        failures.append(f"lora compile bound {lora_cc['bound']} != plain "
                        f"bound {plain_cc['bound']}: adapters must not "
                        "widen the program family")
    if max_logit_err > 1e-4:
        failures.append(f"max_logit_err {max_logit_err} > 1e-4 bar")
    if not smoke:
        if ratio is None or ratio < 0.8:
            failures.append(f"mixed_adapter_tokens_ratio "
                            f"{out['mixed_adapter_tokens_ratio']} "
                            f"< 0.8x bar")
        if distinct_adapters < min(8, n_adapters):
            failures.append(f"only {distinct_adapters} distinct adapters "
                            f"in one tick < {min(8, n_adapters)} bar")
        if swap_zero_compiles is not True:
            failures.append("swap_zero_compiles bar: the base flip "
                            "compiled (or the leg never ran)")
    if failures:
        out["failures"] = failures
    print("LORA" + json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
