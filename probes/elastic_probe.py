#!/usr/bin/env python
"""Train->serve loop probe (ISSUE-18 acceptance artifact).

Two phases against in-process fleets (FleetRouter over ServingEngines,
tiny GPT, CPU):

1. **Continuous refresh** — Poisson greedy traffic against a 3-replica
   fleet while a WeightPublisher pushes checkpoints into the watch
   directory and a background FleetRefresher walks them through the
   artifact/oracle/canary gates.  Bars: the mid-traffic publish reaches
   EVERY replica (``refresh_to_first_token_s`` = publish -> first
   served token from the new weights); zero dropped or hung streams
   across the whole phase; every stream bit-identical to the solo
   oracle of a weight set that was legitimately serving when it ran
   (old weights before the flip, new after; streams riding the canary
   window of the diverge leg may match the diverged oracle — counted,
   never failed); ZERO post-warmup compiles fleet-wide (flips reuse
   every compiled program); a ``PDTPU_FAULT_PUBLISH_CORRUPT`` publish
   is quarantined at the artifact gate with NOTHING flipped, and a
   ``PDTPU_FAULT_CANARY_DIVERGE`` publish flips one canary, rolls it
   back, and the fleet reconverges onto the last verified weights —
   with probe streams serving bit-identical throughout both legs
   (``rollbacks_ok``).
2. **Elastic capacity** (skipped in smoke) — a fresh 1-replica fleet
   behind a ServingGateway with an Autoscaler polling
   ``gw.scale_signals()``.  A diurnal Poisson replay
   (trough -> 3x-overload peak -> trough, rates calibrated from the
   measured per-request service time) must make the autoscaler spawn
   under the peak and drain back down in the tail.  Bars: shed rate
   < 1% (``shed_rate_elastic``); integrated worker-hours <= 0.7x the
   static-max fleet over the same window (``worker_hours_ratio``);
   no scale-flap (every action pair >= cooldown apart, at most 2
   up/down direction reversals); >= 1 scale-up and the fleet back at
   min_replicas after the tail; every admitted stream bit-identical
   to the solo oracle.

`--steps N` (N <= 5) is the CI smoke: phase 1 with reduced traffic,
no phase 2, no perf bars.  Prints one `ELASTIC{json}` line; exits 1
on any bar miss.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24,
                    help="phase-1 traffic requests (<=5 switches to "
                         "smoke mode)")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--refresh-bar-s", type=float, default=30.0,
                    help="publish -> first new-weights token bar")
    ap.add_argument("--worker-hours-bar", type=float, default=0.7)
    ap.add_argument("--shed-bar", type=float, default=0.01)
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.jit import state_arrays
    from paddle_tpu.serving import (Autoscaler, FleetRouter, FleetRefresher,
                                    ServingEngine, ServingGateway,
                                    ShedPolicy, SheddedError,
                                    WeightPublisher)
    from paddle_tpu.serving.fleet import BOOTING, DEGRADED, HEALTHY
    from paddle_tpu.utils import faults

    n_req = max(1, args.steps)
    smoke = n_req <= 5

    rng = np.random.RandomState(args.seed)
    vocab = 64
    cfg = models.GPTConfig(vocab_size=vocab, hidden_size=32,
                           num_hidden_layers=2, num_attention_heads=2,
                           hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           max_position_embeddings=128)
    SEED_OLD, SEED_NEW, SEED_DIV, SEED_BAD = 11, 99, 77, 13

    def tiny_model(seed):
        paddle.seed(seed)
        m = models.GPTForPretraining(cfg)
        m.eval()
        return m

    model_old = tiny_model(SEED_OLD)
    model_new = tiny_model(SEED_NEW)
    model_div = tiny_model(SEED_DIV)

    def make_engine(mdl=model_old, **kw):
        kw.setdefault("max_slots", args.slots)
        kw.setdefault("max_len", 64)
        return ServingEngine(mdl, prefill_buckets=(8,),
                             decode_chunk=args.chunk,
                             max_queue_depth=512, **kw)

    plens = [4, 7]

    oracle = {}

    def want(mdl, prompt, max_new):
        key = (id(mdl), prompt.tobytes(), max_new)
        if key not in oracle:
            out, _ = mdl.generate(paddle.to_tensor(prompt[None]),
                                  max_new_tokens=max_new)
            oracle[key] = np.asarray(out.numpy())[0].tolist()
        return oracle[key]

    def draw_prompt():
        return rng.randint(0, vocab, (plens[int(rng.randint(len(plens)))],)
                           ).astype(np.int32)

    failures = []
    out = {"smoke": smoke, "replicas": args.replicas, "slots": args.slots,
           "decode_chunk": args.chunk,
           "workload": f"greedy, prompt_len in {plens}, Poisson arrivals, "
                       f"GPT (32h/2L/{vocab}v), cpu"}

    # ------------------------------------------------------------------
    # phase 1: continuous refresh under traffic + the two rollback legs
    # ------------------------------------------------------------------
    # the refresher's oracle engine warms FIRST: its compiles land in
    # the global program registry before the fleet takes its warmup
    # marks, so zero-post-warmup below measures only the flips
    orc = make_engine()
    orc.warmup()
    fleet = FleetRouter([make_engine() for _ in range(args.replicas)])
    fleet.warmup()
    fleet.start()
    pubdir = tempfile.mkdtemp(prefix="pdtpu_elastic_pub_")
    canary_prompt = [1, 2, 3]
    refresher = FleetRefresher(fleet, pubdir, orc,
                               canary_prompts=(canary_prompt,),
                               canary_max_new_tokens=8,
                               poll_interval_s=0.1, flip_timeout_s=60.0)
    refresher.start()
    publisher = WeightPublisher(pubdir)

    traffic = []          # (prompt, max_new, resp)
    stop_traffic = threading.Event()
    rate_rps = 3.0 if smoke else 5.0

    def traffic_loop():
        while not stop_traffic.is_set():
            p = draw_prompt()
            traffic.append((p, 12, fleet.submit(p, 12)))
            time.sleep(float(rng.exponential(1.0 / rate_rps)))

    tthread = threading.Thread(target=traffic_loop, daemon=True)
    tthread.start()

    def shas():
        return [getattr(r.engine, "weights_sha", None)
                for r in fleet.manager.replicas((HEALTHY,))]

    def wait_for(pred, timeout, what):
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            if pred():
                return True
            time.sleep(0.02)
        failures.append(f"timed out waiting for {what}")
        return False

    probe_prompt = np.asarray(canary_prompt, dtype=np.int32)
    want_old8 = want(model_old, probe_prompt, 8)
    want_new8 = want(model_new, probe_prompt, 8)

    time.sleep(0.5 if smoke else 1.5)   # traffic on the boot weights

    # -- the good publish: measure publish -> first new-weights token
    t_pub = time.monotonic()
    pub = publisher.publish(state=state_arrays(model_new))
    refresh_to_first = None
    deadline = t_pub + args.refresh_bar_s
    while time.monotonic() < deadline:
        resp = fleet.submit(probe_prompt, 8)
        toks = resp.tokens(timeout=30)
        if toks == want_new8:
            refresh_to_first = time.monotonic() - t_pub
            break
        if toks != want_old8:
            failures.append(f"mid-refresh probe stream matched neither "
                            f"oracle: {toks}")
            break
        time.sleep(0.05)
    if refresh_to_first is None and not failures:
        failures.append(f"no new-weights token within "
                        f"{args.refresh_bar_s}s of the publish")
    out["refresh_to_first_token_s"] = (
        None if refresh_to_first is None else round(refresh_to_first, 3))

    wait_for(lambda: all(s == pub["sha256"] for s in shas())
             and len(shas()) == args.replicas, 60,
             "every replica on the published weights")

    rollbacks_ok = True

    # -- corrupt publish: artifact gate, nothing flips
    faults.enable("publish_corrupt", "1")
    bad = publisher.publish(state=state_arrays(tiny_model(SEED_BAD)))
    faults.disable("publish_corrupt")
    if not wait_for(lambda: bad["sha256"]
                    in refresher.status()["quarantined"], 30,
                    "corrupt publish quarantined"):
        rollbacks_ok = False
    if not all(s == pub["sha256"] for s in shas()):
        failures.append("corrupt publish leaked onto a replica")
        rollbacks_ok = False
    resp = fleet.submit(probe_prompt, 8)
    if resp.tokens(timeout=30) != want_new8:
        failures.append("fleet not serving verified weights after the "
                        "corrupt publish")
        rollbacks_ok = False

    # -- canary-diverging publish: one canary flips, rolls back,
    # fleet reconverges onto the last verified weights
    faults.enable("canary_diverge")
    div = publisher.publish(state=state_arrays(model_div))
    if not wait_for(lambda: div["sha256"]
                    in refresher.status()["quarantined"], 60,
                    "diverging publish quarantined"):
        rollbacks_ok = False
    faults.disable("canary_diverge")
    if not wait_for(lambda: all(s == pub["sha256"] for s in shas())
                    and len(shas()) == args.replicas, 60,
                    "rollback convergence onto the verified weights"):
        rollbacks_ok = False
    resp = fleet.submit(probe_prompt, 8)
    if resp.tokens(timeout=30) != want_new8:
        failures.append("fleet not serving verified weights after the "
                        "canary rollback")
        rollbacks_ok = False

    stop_traffic.set()
    tthread.join(timeout=10)

    # every traffic stream terminated, bit-identical to the oracle of a
    # weight set that was legitimately serving at some point in its
    # lifetime (the diverged set only inside the canary window)
    dropped = 0
    transient_canary = 0
    for p, mx, resp in traffic:
        try:
            toks = resp.tokens(timeout=60)
        except Exception as e:  # noqa: BLE001 — any terminal error
            failures.append(f"traffic stream errored: {type(e).__name__}: "
                            f"{e}")
            dropped += 1
            continue
        if toks == want(model_div, p, mx):
            transient_canary += 1
        elif toks not in (want(model_old, p, mx), want(model_new, p, mx)):
            failures.append("traffic stream matched no legitimate oracle")
            dropped += 1
    pwc = fleet.post_warmup_compiles()
    if pwc != 0:
        failures.append(f"post-warmup compiles after refresh: {pwc}")
    c = fleet.manager.counters()
    if c.get("rollbacks", 0) < 2:
        failures.append(f"expected >= 2 recorded rollbacks, "
                        f"got {c.get('rollbacks')}")
        rollbacks_ok = False
    health = fleet.health()
    if health.get("routable_verified") != args.replicas:
        failures.append(f"routable_verified != {args.replicas}: "
                        f"{health.get('routable_verified')}")
    out.update({
        "traffic_streams": len(traffic),
        "dropped_streams": dropped,
        "transient_canary_streams": transient_canary,
        "post_warmup_compiles": pwc,
        "weight_refreshes": c.get("weight_refreshes"),
        "rollbacks": c.get("rollbacks"),
        "rollbacks_ok": bool(rollbacks_ok and dropped == 0),
    })

    refresher.close()
    fleet.close()
    orc.close()

    # ------------------------------------------------------------------
    # phase 2: diurnal Poisson replay against the autoscaled gateway
    # ------------------------------------------------------------------
    out["shed_rate_elastic"] = None
    out["worker_hours_ratio"] = None
    if not smoke:
        min_reps, max_reps = 1, 3
        # long decodes (96 new tokens) keep the per-request service time
        # high enough that a 3x-capacity peak stays at a modest absolute
        # request rate on any host speed
        replay_new = 96

        def elastic_engine():
            return make_engine(max_slots=1, max_len=128)

        fleet2 = FleetRouter([elastic_engine()])
        fleet2.warmup()
        gw = ServingGateway(fleet2, shed=ShedPolicy(max_lane_depth=400))
        gw.start()

        def spawn():
            eng = elastic_engine()
            eng.warmup()
            return fleet2.add_replica(eng)

        # calibrate the replay rates from the measured service time so
        # the peak genuinely overloads one replica on any host speed
        t0 = time.monotonic()
        for _ in range(6):
            gw.submit(draw_prompt(), replay_new).tokens(timeout=60)
        svc = max(0.01, (time.monotonic() - t0) / 6.0)
        capacity = 1.0 / svc                       # 1 slot per replica
        peak_rps = 3.0 * capacity
        trough_rps = max(0.2, capacity / 8.0)
        peak_dur = min(8.0, 200.0 / peak_rps)      # bound total requests
        cooldown_s = 1.5
        asc = Autoscaler(fleet2, gw.scale_signals, spawn,
                         min_replicas=min_reps, max_replicas=max_reps,
                         scale_up_est_wait_s=max(0.2, 2.0 * svc),
                         breach_ticks=2, idle_ticks=8,
                         cooldown_s=cooldown_s)
        asc.start(tick_interval_s=0.05)

        live_samples = []                          # (t, live_count)
        stop_sampler = threading.Event()

        def sampler():
            while not stop_sampler.is_set():
                live = [r for r in fleet2.manager.replicas(
                    (BOOTING, HEALTHY, DEGRADED))]
                live_samples.append((time.monotonic(), len(live)))
                stop_sampler.wait(0.05)

        sthread = threading.Thread(target=sampler, daemon=True)
        sthread.start()

        shed0 = gw.scale_signals()["shed_total"]
        replay = []
        segments = [(6.0, trough_rps), (peak_dur, peak_rps),
                    (10.0, trough_rps)]
        t_start = time.monotonic()
        for dur, rps in segments:
            t_end = time.monotonic() + dur
            while time.monotonic() < t_end:
                p = draw_prompt()
                replay.append((p, replay_new, gw.submit(p, replay_new)))
                time.sleep(float(rng.exponential(1.0 / rps)))
        # idle tail: the autoscaler must drain back to min_replicas
        wait_for(lambda: len(fleet2.manager.replicas((HEALTHY,)))
                 <= min_reps, 20.0, "scale-down back to min_replicas")
        stop_sampler.set()
        sthread.join(timeout=5)
        t_total = max(1e-6, time.monotonic() - t_start)

        sheds = 0
        for p, mx, resp in replay:
            try:
                toks = resp.tokens(timeout=90)
            except Exception as e:  # noqa: BLE001 — shed or real failure
                if isinstance(e, SheddedError):
                    sheds += 1
                else:
                    failures.append(f"replay stream errored: "
                                    f"{type(e).__name__}: {e}")
                continue
            if toks != want(model_old, p, mx):
                failures.append("replay stream not bit-identical to the "
                                "solo oracle")
        shed_rate = sheds / max(1, len(replay))
        shed_total = gw.scale_signals()["shed_total"] - shed0
        # integrate live replicas over the window vs the static-max fleet
        worker_s = 0.0
        for (ta, na), (tb, _nb) in zip(live_samples, live_samples[1:]):
            worker_s += na * (tb - ta)
        ratio = worker_s / (max_reps * t_total)
        st = asc.status()
        reversals = sum(1 for a, b in zip(asc.actions, asc.actions[1:])
                        if a["dir"] != b["dir"])
        min_gap = min((b["t"] - a["t"] for a, b
                       in zip(asc.actions, asc.actions[1:])),
                      default=None)
        if shed_rate >= args.shed_bar:
            failures.append(f"shed rate {shed_rate:.3f} >= "
                            f"{args.shed_bar} bar")
        if ratio > args.worker_hours_bar:
            failures.append(f"worker-hours ratio {ratio:.3f} > "
                            f"{args.worker_hours_bar} bar")
        if st["scale_ups"] < 1:
            failures.append("the peak never triggered a scale-up")
        if reversals > 2:
            failures.append(f"scale-flap: {reversals} direction "
                            "reversals")
        if min_gap is not None and min_gap < cooldown_s - 1e-3:
            failures.append(f"actions only {min_gap:.2f}s apart "
                            f"(cooldown {cooldown_s}s)")
        out.update({
            "shed_rate_elastic": round(shed_rate, 4),
            "worker_hours_ratio": round(ratio, 3),
            "replay_requests": len(replay),
            "replay_sheds": sheds,
            "gateway_shed_total": shed_total,
            "peak_rps": round(peak_rps, 2),
            "peak_dur_s": round(peak_dur, 2),
            "trough_rps": round(trough_rps, 2),
            "service_time_s": round(svc, 4),
            "scale_ups": st["scale_ups"],
            "scale_downs": st["scale_downs"],
            "direction_reversals": reversals,
        })
        asc.close()
        gw.close()

    out["failures"] = failures
    print("ELASTIC" + json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
