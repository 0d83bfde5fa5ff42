#!/usr/bin/env python
"""Fleet serving chaos probe (ISSUE-12 acceptance artifact).

Three phases against a 3-replica in-process fleet (FleetRouter over
ServingEngines, tiny GPT, CPU):

1. **Failover** — Poisson greedy traffic (most requests opted into
   ``resubmit=True``), then a SIGKILL-equivalent loss of the busiest
   replica mid-decode (``PDTPU_FAULT_REPLICA_CRASH``).  Bars: ZERO hung
   consumers; every stream either completes bit-identical to its
   uninterrupted solo-generate oracle (survivors untouched, lost
   opt-ins resubmitted and seamlessly continued) or — for the
   deliberate non-opt-ins resident on the dead replica — ends in the
   typed ReplicaLostError; failover stall (crash -> first
   post-crash token of every affected stream) p99 under the bar.
2. **Brownout** — ``PDTPU_FAULT_REPLICA_SLOW`` stretches one replica's
   steps far past the fleet's slow threshold; health fences it and its
   residents MIGRATE through the run-transfer codec.  Bars: fenced
   (degraded), >= 1 migration, every stream bit-identical, zero drops.
3. **Rolling restart** — save one warm replica's AOT program set, then
   ``fleet.rollout()`` boots a replacement from it for every replica
   (warm, shift traffic, drain, remove) under continuous submissions.
   Bars: zero dropped requests, all streams bit-identical, every new
   replica boots with every program from the program set
   (``program_set:exe``) and the fleet reports ZERO post-warmup
   compiles under post-rollout traffic.
4. **Process isolation** (ISSUE-13) — a MIXED fleet: one in-process
   replica + two SUBPROCESS workers booted from the phase-3 AOT
   program set.  A real SIGKILL of worker A mid-decode AND a
   ``PDTPU_FAULT_REPLICA_WEDGE`` hang of worker B (step blocks forever,
   socket stays up — only the out-of-band heartbeat can see it) must
   BOTH fence within the heartbeat threshold; every affected stream
   reaches a typed terminal or a bit-identical resubmitted completion
   vs the solo oracle; the supervisor restarts both workers from the
   program set (``program_set:exe``, zero post-warmup compiles) and
   they serve bit-identical again; zero hung consumers anywhere.
   Published as bench ``detail.fleet.{wedge_detect_ms,restart_ok}``.
5. **Network transparency** (ISSUE-15) — two STANDALONE remote workers
   (``--listen`` on ephemeral loopback ports) attached by ADDRESS and
   booted from weights + the phase-3 program set shipped over the wire
   (the spec factory is seeded differently from the shipped weights, so
   bit-identity to the solo oracle proves zero seeded rebuilds; zero
   post-warmup compiles proves the shipped program set covers serving).
   Poisson traffic under ``PDTPU_FAULT_NET_DELAY`` slowloris, then a
   ``PDTPU_FAULT_NET_DROP`` mid-frame cut (typed fence, bit-identical
   failover, supervised re-attach), then a hard
   ``PDTPU_FAULT_NET_PARTITION`` mid-decode: the manager fences on
   beat-frame age within 2x the threshold and resubmits onto the
   survivor; after the window heals the worker (which self-aborted its
   stale epoch — zero double-served tokens) accepts a higher-epoch
   re-attach and serves bit-identical again.  Worker PROCESSES survive
   all of it.  Published as bench
   ``detail.fleet.{partition_detect_ms,weight_ship_ok}``.

`--steps N` (N <= 5) is the CI smoke: phase 1 only, parity + terminal
states, no perf bars.  Prints one `FLEET{json}` line; exits 1 on any
bar miss.
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
    ap.add_argument("--steps", type=int, default=36,
                    help="phase-1 requests (<=5 switches to smoke mode)")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--failover-bar-ms", type=float, default=4000.0,
                    help="p99 crash->first-post-crash-token stall bar")
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.serving import (FleetRouter, ReplicaLostError,
                                    ServingEngine)
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
    paddle.seed(11)
    model = models.GPTForPretraining(cfg)
    model.eval()

    def make_engine(**kw):
        return ServingEngine(model, max_slots=args.slots, max_len=64,
                             prefill_buckets=(8,),
                             decode_chunk=args.chunk,
                             max_queue_depth=max(64, n_req), **kw)

    plens = [4, 7]
    budgets = [12, 16, 20]

    def draw_prompt():
        return rng.randint(0, vocab, (plens[int(rng.randint(len(plens)))],)
                           ).astype(np.int32)

    oracle = {}

    def want(prompt, max_new):
        key = (prompt.tobytes(), max_new)
        if key not in oracle:
            out, _ = model.generate(paddle.to_tensor(prompt[None]),
                                    max_new_tokens=max_new)
            oracle[key] = np.asarray(out.numpy())[0].tolist()
        return oracle[key]

    failures = []
    out = {"smoke": smoke, "replicas": args.replicas, "slots": args.slots,
           "decode_chunk": args.chunk,
           "workload": f"greedy, prompt_len in {plens}, max_new in "
                       f"{budgets}, Poisson arrivals, GPT (32h/2L/{vocab}v), "
                       "cpu"}

    fleet = FleetRouter([make_engine() for _ in range(args.replicas)],
                        slow_threshold_ms=None if smoke else 40.0)
    fleet.warmup()

    # ------------------------------------------------------------------
    # phase 1: Poisson traffic + SIGKILL-equivalent replica loss
    # ------------------------------------------------------------------
    plan = []
    for i in range(n_req):
        plan.append({
            "prompt": draw_prompt(),
            "max_new": budgets[int(rng.randint(len(budgets)))],
            # a couple of deliberate non-opt-ins prove the typed
            # terminal path; everything else opts into resubmission
            "resubmit": not (i % max(4, n_req // 3) == 1),
        })
    # two long ANCHOR streams pinned (session affinity) to one replica:
    # the crash targets their replica on its next step, so the loss is
    # guaranteed to land mid-decode — failover is exercised every run,
    # not only when the Poisson timing cooperates
    n_anchor = 2
    for _ in range(n_anchor):
        plan.append({"prompt": draw_prompt(), "max_new": max(budgets) + 4,
                     "resubmit": True})
    for r in plan:
        want(r["prompt"], r["max_new"])

    n_all = n_req + n_anchor
    resps = [None] * n_all
    progress = [[] for _ in range(n_all)]  # (t, token_count) on change
    last_counts = [0] * n_all
    watch_stop = threading.Event()

    def watcher():
        while not watch_stop.is_set():
            now = time.monotonic()
            for i, r in enumerate(resps):
                if r is None:
                    continue
                n = len(r.tokens_so_far())
                if n != last_counts[i]:
                    last_counts[i] = n
                    progress[i].append((now, n))
            time.sleep(0.002)

    fleet.start()
    gaps_mean = 0.0 if smoke else 1.0 / 50.0
    arrivals = (np.zeros(n_req) if smoke
                else np.cumsum(rng.exponential(gaps_mean, size=n_req)))
    t0 = time.monotonic()

    def submitter():
        for i in range(n_req):
            r = plan[i]
            wait = arrivals[i] - (time.monotonic() - t0)
            if wait > 0:
                time.sleep(wait)
            resps[i] = fleet.submit(
                r["prompt"], r["max_new"], resubmit=r["resubmit"],
                session=f"u{i % 5}")

    watch = threading.Thread(target=watcher, daemon=True)
    sub = threading.Thread(target=submitter)
    watch.start()
    sub.start()

    # pin the anchors to one replica, wait until they are decoding,
    # then kill exactly that replica on its next steps
    for j in range(n_anchor):
        i = n_req + j
        resps[i] = fleet.submit(plan[i]["prompt"], plan[i]["max_new"],
                                resubmit=True, session="crash-anchor")
    crash_t = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if all(len(resps[n_req + j].tokens_so_far()) > 0
               for j in range(n_anchor)):
            break
        time.sleep(0.002)
    victim = fleet._affinity.get("crash-anchor")
    affected_ids = [run.req.id for (rid, _s), run in fleet._slots.items()
                    if rid == victim]
    if victim is None or not affected_ids:
        failures.append("anchor streams never became resident — nothing "
                        "to crash into")
    else:
        for _ in range(20):
            rep = fleet.manager.get(victim)
            faults.enable("replica_crash", f"{victim}:{rep.steps + 1}")
            t_arm = time.monotonic()
            while time.monotonic() - t_arm < 1.0:
                if fleet.manager.get(victim).state == "crashed":
                    crash_t = time.monotonic()
                    break
                time.sleep(0.002)
            if crash_t is not None:
                break
        faults.disable("replica_crash")
        if crash_t is None:
            failures.append("replica_crash fault never fired")
    sub.join()

    # every consumer must reach a terminal state — never a hang
    hung = []
    term_deadline = time.monotonic() + 120
    for i, r in enumerate(resps):
        if r is None or not r._done.wait(
                timeout=max(0.0, term_deadline - time.monotonic())):
            hung.append(i)
    watch_stop.set()
    watch.join(timeout=2)

    parity_failures, typed_lost, wrong_errors, completed = [], [], [], 0
    req_ids = {resps[i].request.id: i for i in range(n_all)
               if resps[i] is not None}
    for i, r in enumerate(resps):
        if r is None or i in hung:
            continue
        if r.error is None:
            completed += 1
            if r.tokens(timeout=5) != want(plan[i]["prompt"],
                                           plan[i]["max_new"]):
                parity_failures.append(i)
        elif isinstance(r.error, ReplicaLostError):
            typed_lost.append(i)
            if plan[i]["resubmit"]:
                wrong_errors.append(
                    f"req {i} opted into resubmit but was lost: "
                    f"{r.error}")
        else:
            wrong_errors.append(f"req {i}: {type(r.error).__name__}: "
                                f"{r.error}")

    # failover stall: crash -> first post-crash token per affected stream
    failover_gaps = []
    if crash_t is not None:
        for rid_ in affected_ids:
            i = req_ids.get(rid_)
            if i is None:
                continue
            post = [t for (t, _n) in progress[i] if t > crash_t]
            if post:
                failover_gaps.append((post[0] - crash_t) * 1e3)
    failover_gaps.sort()
    p99 = (failover_gaps[min(len(failover_gaps) - 1,
                             int(0.99 * len(failover_gaps)))]
           if failover_gaps else None)
    c1 = fleet.manager.counters()
    out.update({
        "requests": n_req,
        "anchors": n_anchor,
        "completed": completed,
        "hung": len(hung),
        "typed_lost": len(typed_lost),
        "affected_streams": len(affected_ids),
        "resubmits": c1["resubmits"],
        "failover_p99_ms": None if p99 is None else round(p99, 1),
        "dropped_streams": len(hung) + len(wrong_errors)
        + len(parity_failures),
    })
    if hung:
        failures.append(f"requests {hung[:5]} never reached a terminal "
                        "state (hang)")
    if parity_failures:
        failures.append(f"parity: requests {parity_failures[:5]} diverged "
                        "from solo generate")
    if wrong_errors:
        failures.append("unexpected terminal errors: "
                        + "; ".join(wrong_errors[:3]))
    if crash_t is not None and c1["resubmits"] + len(typed_lost) < 1:
        failures.append("crash lost no resident run — failover "
                        "unexercised (anchors finished early?)")
    if not smoke:
        if crash_t is not None and not failover_gaps:
            failures.append("no affected stream produced a post-crash "
                            "token (failover unmeasured)")
        if p99 is not None and p99 >= args.failover_bar_ms:
            failures.append(f"failover p99 {p99:.0f}ms >= "
                            f"{args.failover_bar_ms}ms bar")

    # ------------------------------------------------------------------
    # phase 2: brownout — slow replica fenced, residents migrate
    # ------------------------------------------------------------------
    if not smoke and not hung:
        b_plan = [{"prompt": draw_prompt(), "max_new": 20}
                  for _ in range(6)]
        for r in b_plan:
            want(r["prompt"], r["max_new"])
        b_resps = [fleet.submit(r["prompt"], r["max_new"], session="pin")
                   for r in b_plan]
        # brown out the replica the pinned session actually landed on
        target = fleet._affinity["pin"]
        t_wait = time.monotonic() + 30
        while (fleet.manager.get(target).engine.scheduler.occupancy() == 0
               and time.monotonic() < t_wait):
            time.sleep(0.002)
        faults.enable("replica_slow", f"120:1:{target}")
        b_hung = [i for i, r in enumerate(b_resps)
                  if not r._done.wait(timeout=120)]
        faults.disable("replica_slow")
        b_parity = [i for i, r in enumerate(b_resps)
                    if i not in b_hung and (
                        r.error is not None
                        or r.tokens(timeout=5) != want(
                            b_plan[i]["prompt"], b_plan[i]["max_new"]))]
        c2 = fleet.manager.counters()
        out.update({
            "brownout_target": target,
            "brownout_state": fleet.manager.get(target).state,
            "brownout_migrated": c2["migrated"] - c1["migrated"],
            "brownout_streams": len(b_plan),
        })
        if b_hung:
            failures.append(f"brownout: requests {b_hung[:5]} hung")
        if b_parity:
            failures.append(f"brownout: requests {b_parity[:5]} dropped "
                            "or diverged")
        if fleet.manager.get(target).state not in ("degraded", "healthy"):
            failures.append("brownout: replica neither fenced nor "
                            f"recovered ({fleet.manager.get(target).state})")
        if c2["migrated"] - c1["migrated"] < 1:
            failures.append("brownout: no run migrated off the slow "
                            "replica")

    # ------------------------------------------------------------------
    # phase 3: rolling restart from a program set, zero drops
    # ------------------------------------------------------------------
    if not smoke and not hung:
        tmp = tempfile.mkdtemp(prefix="fleet_probe_ps_")
        donor = next(r for r in fleet.manager.replicas()
                     if r.state in ("healthy", "degraded")
                     and r.engine.warm)
        ps_path = donor.engine.save_program_set(
            os.path.join(tmp, "serving.ptps"))
        boot_sources = []

        def factory():
            eng = make_engine(program_set=ps_path)
            boot_sources.append(eng.warmup()["programs"])
            return eng

        r_plan = [{"prompt": draw_prompt(), "max_new": 12}
                  for _ in range(10)]
        for r in r_plan:
            want(r["prompt"], r["max_new"])
        r_resps = []

        def r_submitter():
            for i, r in enumerate(r_plan):
                r_resps.append(fleet.submit(r["prompt"], r["max_new"],
                                            session=f"v{i % 4}"))
                time.sleep(0.03)

        rt = threading.Thread(target=r_submitter)
        rt.start()
        time.sleep(0.06)
        try:
            fleet.rollout(factory, timeout=180)
            rollout_err = None
        except Exception as e:
            rollout_err = f"{type(e).__name__}: {e}"
        rt.join()
        r_hung = [i for i, r in enumerate(r_resps)
                  if not r._done.wait(timeout=120)]
        r_bad = [i for i, r in enumerate(r_resps)
                 if i not in r_hung and (
                     r.error is not None
                     or r.tokens(timeout=5) != want(
                         r_plan[i]["prompt"], r_plan[i]["max_new"]))]
        # post-rollout traffic must compile nothing on the booted fleet
        tail = fleet.submit(r_plan[0]["prompt"], r_plan[0]["max_new"])
        tail_ok = (tail._done.wait(timeout=60) and tail.error is None
                   and tail.tokens() == want(r_plan[0]["prompt"],
                                             r_plan[0]["max_new"]))
        pwc = fleet.post_warmup_compiles()
        exe_boots = sum(1 for src in boot_sources
                        if all(v == "program_set:exe"
                               for v in src.values()))
        out.update({
            "rollout_dropped": len(r_hung) + len(r_bad)
            + (0 if rollout_err is None else 1),
            "rollout_streams": len(r_plan),
            "rollout_post_warmup_compiles": pwc,
            "rollout_exe_boots": exe_boots,
            "rollout_replicas": len(boot_sources),
        })
        if rollout_err:
            failures.append(f"rollout failed: {rollout_err}")
        if r_hung or r_bad:
            failures.append(f"rollout dropped/diverged requests "
                            f"{(r_hung + r_bad)[:5]}")
        if not tail_ok:
            failures.append("post-rollout tail request failed")
        if pwc != 0:
            failures.append(f"{pwc} post-warmup compiles on the rolled "
                            "fleet (must be 0)")
        if exe_boots != len(boot_sources):
            failures.append(
                f"only {exe_boots}/{len(boot_sources)} replicas booted "
                "every program from the program set (program_set:exe)")

    # ------------------------------------------------------------------
    # phase 4: process isolation — subprocess workers, SIGKILL + wedge,
    # heartbeat fencing, supervised restart from the AOT program set
    # ------------------------------------------------------------------
    if not smoke and not hung:
        from paddle_tpu.serving import (ReplicaLostError as _RLE,
                                        RestartBackoff)
        import signal as _signal
        hb_timeout = 1.5
        w_failures = []
        spec = {
            "model": {"factory": "paddle_tpu.serving.worker:build_gpt",
                      "kwargs": dict(vocab_size=vocab, hidden_size=32,
                                     num_hidden_layers=2,
                                     num_attention_heads=2,
                                     hidden_dropout_prob=0.0,
                                     attention_probs_dropout_prob=0.0,
                                     max_position_embeddings=128,
                                     seed=11)},
            "engine": {"max_slots": args.slots, "max_len": 64,
                       "prefill_buckets": [8],
                       "decode_chunk": args.chunk,
                       "max_queue_depth": max(64, n_req)},
            "program_set": ps_path,
        }
        wfleet = FleetRouter(
            [make_engine()], heartbeat_timeout_s=hb_timeout,
            kill_grace_s=0.3,
            restart_backoff=RestartBackoff(max_restarts=2,
                                           base_delay=0.1,
                                           max_delay=0.5))
        wid_a = wfleet.add_worker(spec)
        wid_b = wfleet.add_worker(spec)
        wfleet.warmup()
        wfleet.start()
        rep_a = wfleet.manager.get(wid_a)
        rep_b = wfleet.manager.get(wid_b)
        first_exe = all(
            v == "program_set:exe"
            for r in (rep_a, rep_b)
            for v in ((r.engine.warmup_report or {}).get("programs")
                      or {}).values())

        def resident(rep, budget, resubmit):
            req, resp = rep.engine.make_request(
                np.arange(1, 6, dtype=np.int32), budget,
                resubmit=resubmit)
            want(np.arange(1, 6, dtype=np.int32), budget)
            rep.engine.scheduler.submit(req, resp)
            t_end = time.monotonic() + 60
            while (not len(resp.tokens_so_far())
                   and time.monotonic() < t_end):
                time.sleep(0.002)
            return resp

        budget = max(budgets) + 8
        w_prompt = np.arange(1, 6, dtype=np.int32)
        w_want = want(w_prompt, budget)
        # -- worker A: real SIGKILL mid-decode -------------------------
        rep_a.engine.set_fault("replica_slow",
                               f"80:1:{rep_a.lineage['index']}")
        a_opt = resident(rep_a, budget, True)
        a_no = resident(rep_a, budget, False)
        t_kill = time.monotonic()
        os.kill(rep_a.engine.pid, _signal.SIGKILL)
        t_end = time.monotonic() + 30
        while rep_a.state != "crashed" and time.monotonic() < t_end:
            time.sleep(0.002)
        kill_detect_ms = (time.monotonic() - t_kill) * 1e3
        # -- worker B: wedge (hang) — only the heartbeat can see it ----
        rep_b.engine.set_fault("replica_slow",
                               f"80:1:{rep_b.lineage['index']}")
        b_opt = resident(rep_b, budget, True)
        rep_b.engine.set_fault("replica_wedge",
                               f"{rep_b.lineage['index']}:0")
        t_wedge = time.monotonic()
        t_end = time.monotonic() + 30
        while rep_b.state != "wedged" and time.monotonic() < t_end:
            time.sleep(0.002)
        wedge_detect_ms = (time.monotonic() - t_wedge) * 1e3
        # -- every affected stream: typed terminal or bit-identical ----
        w_hung = 0
        for name, resp, expect_lost in (("a_opt", a_opt, False),
                                        ("a_no", a_no, True),
                                        ("b_opt", b_opt, False)):
            if not resp._done.wait(timeout=90):
                w_hung += 1
                w_failures.append(f"worker stream {name} hung")
                continue
            if expect_lost:
                if not isinstance(resp.error, _RLE):
                    w_failures.append(
                        f"worker stream {name}: expected typed "
                        f"ReplicaLostError, got {resp.error!r}")
            elif resp.error is not None:
                w_failures.append(f"worker stream {name}: {resp.error!r}")
            elif resp.tokens() != w_want:
                w_failures.append(
                    f"worker stream {name} diverged from solo oracle")
        if rep_a.state != "crashed":
            w_failures.append(f"SIGKILL not fenced (A={rep_a.state})")
        if rep_b.state != "wedged":
            w_failures.append(f"wedge not fenced (B={rep_b.state})")
        for nm, ms in (("kill", kill_detect_ms),
                       ("wedge", wedge_detect_ms)):
            if ms >= 2 * hb_timeout * 1e3:
                w_failures.append(
                    f"{nm} fenced in {ms:.0f}ms >= "
                    f"{2 * hb_timeout * 1e3:.0f}ms bar "
                    "(heartbeat threshold x2)")
        # -- supervisor: both workers restart from the program set -----
        t_end = time.monotonic() + 120
        restarted = []
        while time.monotonic() < t_end:
            restarted = [r for r in wfleet.manager.replicas()
                         if getattr(r, "kind", "") == "subprocess"
                         and r.state == "healthy"]
            if len(restarted) >= 2:
                break
            time.sleep(0.02)
        restart_exe = len(restarted) >= 2 and all(
            v == "program_set:exe"
            for r in restarted
            for v in ((r.engine.warmup_report or {}).get("programs")
                      or {}).values())
        tail_ok, pwc_ok = True, True
        for r in restarted[:2]:
            rq, rs = r.engine.make_request(w_prompt, budget)
            r.engine.scheduler.submit(rq, rs)
            if not rs._done.wait(timeout=90):
                tail_ok = False
                w_failures.append("post-restart tail stream hung")
            elif rs.error is not None or rs.tokens() != w_want:
                tail_ok = False
                w_failures.append("post-restart tail diverged/failed")
            if r.engine.post_warmup_compiles() != 0:
                pwc_ok = False
                w_failures.append(
                    f"restarted worker {r.id} reports "
                    f"{r.engine.post_warmup_compiles()} post-warmup "
                    "compiles (must be 0)")
        restart_ok = (len(restarted) >= 2 and first_exe and restart_exe
                      and tail_ok and pwc_ok and w_hung == 0)
        if len(restarted) < 2:
            w_failures.append(
                f"supervisor restarted only {len(restarted)}/2 workers")
        if not first_exe or not restart_exe:
            w_failures.append(
                "workers did not boot every program from the program "
                "set (program_set:exe)")
        wc = wfleet.manager.counters()
        out.update({
            "worker_kill_detect_ms": round(kill_detect_ms, 1),
            "wedge_detect_ms": round(wedge_detect_ms, 1),
            "heartbeat_timeout_ms": hb_timeout * 1e3,
            "worker_restarts": wc["worker_restarts"],
            "wedges": wc["wedges"],
            "restart_ok": restart_ok,
            "worker_streams_hung": w_hung,
        })
        failures.extend(w_failures)
        wfleet.close()

    # ------------------------------------------------------------------
    # phase 5: network transparency — remote TCP workers attached by
    # address, weights + program set shipped over the wire, net chaos
    # (delay slowloris, mid-frame drop, hard partition), healed
    # higher-epoch re-attach with zero double-served tokens
    # ------------------------------------------------------------------
    if not smoke and not hung:
        import subprocess as _subprocess
        from paddle_tpu import jit as _jit
        from paddle_tpu.serving.fleet import RemoteReplica
        from paddle_tpu.serving.transfer import file_sha256
        n_failures = []
        net_hb = 1.5
        # ship THIS model's saved weights under a factory seeded
        # DIFFERENTLY (23 != 11): bit-identity of every remote stream
        # to the solo oracle proves the shipped artifact — not a seeded
        # rebuild — is what the workers serve
        wdir = tempfile.mkdtemp(prefix="fleet_probe_wts_")
        _jit.save(model, os.path.join(wdir, "m"))
        wpath = os.path.join(wdir, "m.pdiparams.npz")
        w_sha = file_sha256(wpath)
        rspec = {
            "model": {"factory": "paddle_tpu.serving.worker:build_gpt",
                      "kwargs": dict(vocab_size=vocab, hidden_size=32,
                                     num_hidden_layers=2,
                                     num_attention_heads=2,
                                     hidden_dropout_prob=0.0,
                                     attention_probs_dropout_prob=0.0,
                                     max_position_embeddings=128,
                                     seed=23)},
            "engine": {"max_slots": args.slots, "max_len": 64,
                       "prefill_buckets": [8],
                       "decode_chunk": args.chunk,
                       "max_queue_depth": max(64, n_req)},
            "weights": wpath,
            "program_set": ps_path,
            "ship_program_set": True,
        }

        def spawn_worker(index):
            wenv = dict(os.environ)
            root = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            wenv["PYTHONPATH"] = (root + os.pathsep + wenv["PYTHONPATH"]
                                  if wenv.get("PYTHONPATH") else root)
            p = _subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.serving.worker",
                 "--listen", "127.0.0.1:0", "--index", str(index)],
                stdin=_subprocess.DEVNULL, stdout=_subprocess.PIPE,
                stderr=_subprocess.STDOUT, text=True, env=wenv,
                start_new_session=True)
            while True:
                line = p.stdout.readline()
                if not line:
                    raise RuntimeError(
                        "remote worker exited before listening")
                if "worker listening on" in line:
                    waddr = line.strip().rsplit(" ", 1)[-1]
                    break
            # keep draining stdout so the worker can never block on a
            # full pipe mid-probe
            threading.Thread(target=lambda: p.stdout.read(),
                             daemon=True).start()
            return waddr, p

        rfleet = FleetRouter(
            [make_engine()], heartbeat_timeout_s=net_hb,
            kill_grace_s=0.3,
            # a mid-partition re-attach just times out against a
            # blackholed socket: the first retry must land after the
            # partition window heals
            restart_backoff=RestartBackoff(max_restarts=3,
                                           base_delay=2.0,
                                           max_delay=3.0))
        workers = [spawn_worker(1), spawn_worker(2)]
        rrids = [rfleet.add_worker(dict(rspec), address=a,
                                   boot_timeout_s=180.0,
                                   manager_silence_s=2.0,
                                   ack_timeout_s=30.0)
                 for a, _p in workers]
        rfleet.warmup()
        rfleet.start()
        rreps = [rfleet.manager.get(rid) for rid in rrids]
        rsnaps = [r.snapshot() for r in rreps]
        shipped_bytes = sum(s.get("bytes_shipped") or 0 for s in rsnaps)
        ship_sha_ok = all(s.get("weights_sha") == w_sha for s in rsnaps)
        if not all((s.get("bytes_shipped") or 0) > 0 for s in rsnaps):
            n_failures.append("weights were not shipped over the wire")
        if not ship_sha_ok:
            n_failures.append("remote weights_sha != shipped artifact "
                              "sha256")

        # -- Poisson traffic under net-delay slowloris ------------------
        for r in rreps:
            r.engine.set_fault("net_delay", "2:5")
        faults.enable("net_delay", "2:5")
        d_plan = [{"prompt": draw_prompt(),
                   "max_new": budgets[int(rng.randint(len(budgets)))]}
                  for _ in range(8)]
        for r_ in d_plan:
            want(r_["prompt"], r_["max_new"])
        d_resps = []
        for i, r_ in enumerate(d_plan):
            d_resps.append(rfleet.submit(r_["prompt"], r_["max_new"],
                                         resubmit=True,
                                         session=f"net{i % 4}"))
            time.sleep(float(rng.exponential(1.0 / 50.0)))
        d_hung = [i for i, r_ in enumerate(d_resps)
                  if not r_._done.wait(timeout=120)]
        d_parity = [i for i, r_ in enumerate(d_resps)
                    if i not in d_hung and (
                        r_.error is not None
                        or r_.tokens(timeout=5) != want(
                            d_plan[i]["prompt"], d_plan[i]["max_new"]))]
        faults.disable("net_delay")
        for r in rreps:
            if r.state == "healthy":
                r.engine.set_fault("net_delay", None)
        pwc_remote = [r.engine.post_warmup_compiles() for r in rreps
                      if r.state == "healthy"]
        if d_hung:
            n_failures.append(f"net-delay traffic hung: {d_hung[:5]}")
        if d_parity:
            n_failures.append(
                f"net-delay traffic diverged/failed: {d_parity[:5]}")
        if any(p != 0 for p in pwc_remote):
            n_failures.append(
                f"remote workers compiled post-warmup {pwc_remote} "
                "(the shipped program set must cover serving)")

        # -- mid-frame drop: the next manager frame to SOME remote is
        # cut halfway and its socket dies mid-stream; the affected
        # replica fences typed, its opted-in resident fails over
        # bit-identical, and the supervisor re-attaches a fresh epoch
        drop_budget = max(budgets) + 8
        drop_prompt = np.arange(1, 6, dtype=np.int32)
        drop_want = want(drop_prompt, drop_budget)
        d_streams = []
        for r in rreps:
            r.engine.set_fault("replica_slow",
                               f"60:1:{r.lineage['index']}")
            rq, rs = r.engine.make_request(drop_prompt, drop_budget,
                                           resubmit=True)
            r.engine.scheduler.submit(rq, rs)
            d_streams.append(rs)
        t_end = time.monotonic() + 60
        while (not all(len(rs.tokens_so_far()) for rs in d_streams)
               and time.monotonic() < t_end):
            time.sleep(0.005)
        faults.enable("net_drop", "1")
        drop_bad = [i for i, rs in enumerate(d_streams)
                    if not rs._done.wait(timeout=120)
                    or rs.error is not None
                    or rs.tokens() != drop_want]
        faults.disable("net_drop")
        if drop_bad:
            n_failures.append(
                f"mid-frame drop: streams {drop_bad} hung/diverged")
        t_end = time.monotonic() + 120
        healthy_remotes = []
        while time.monotonic() < t_end:
            healthy_remotes = [r for r in rfleet.manager.replicas()
                               if isinstance(r, RemoteReplica)
                               and r.state == "healthy"]
            if len(healthy_remotes) >= 2:
                break
            time.sleep(0.02)
        if len(healthy_remotes) < 2:
            n_failures.append(
                f"only {len(healthy_remotes)}/2 remote workers healthy "
                "after the mid-frame drop re-attach")

        # -- hard partition mid-decode ---------------------------------
        part_detect_ms = None
        if healthy_remotes:
            vic = healthy_remotes[-1]
            vidx = vic.lineage["index"]
            vic.engine.set_fault("replica_slow", f"60:1:{vidx}")
            pq, presp = vic.engine.make_request(drop_prompt, drop_budget,
                                                resubmit=True)
            vic.engine.scheduler.submit(pq, presp)
            t_end = time.monotonic() + 60
            while (not len(presp.tokens_so_far())
                   and time.monotonic() < t_end):
                time.sleep(0.005)
            # arm the WORKER side first (that RPC frame must still get
            # through), then this side: both directions blackholed with
            # every process alive
            vic.engine.set_fault("net_partition", f"{vidx}:2.5")
            faults.enable("net_partition", f"{vidx}:2.5")
            t_arm = time.monotonic()
            t_end = time.monotonic() + 60
            while vic.state != "wedged" and time.monotonic() < t_end:
                time.sleep(0.002)
            if vic.state == "wedged":
                part_detect_ms = (time.monotonic() - t_arm) * 1e3
                if part_detect_ms >= 2 * net_hb * 1e3:
                    n_failures.append(
                        f"partition fenced in {part_detect_ms:.0f}ms "
                        f">= {2 * net_hb * 1e3:.0f}ms bar "
                        "(beat threshold x2)")
                if "heartbeat age" not in (vic.fence_reason or ""):
                    n_failures.append(
                        "partition fence is not beat-age based: "
                        f"{vic.fence_reason!r}")
            else:
                n_failures.append(
                    f"partition not fenced (state={vic.state})")
            if not presp._done.wait(timeout=120):
                n_failures.append("partitioned stream hung")
            elif presp.error is not None \
                    or presp.tokens() != drop_want:
                n_failures.append(
                    "partitioned stream failed or diverged "
                    f"({presp.error!r}) — lost or double-served tokens")
            faults.disable("net_partition")
            # heal: the worker self-aborted its residents on manager
            # silence and went back to listening; it must accept the
            # supervisor's HIGHER-epoch re-attach (the stale epoch died
            # cleanly — zero double-served tokens) and serve again
            healed = None
            t_end = time.monotonic() + 120
            while time.monotonic() < t_end:
                healed = next(
                    (r for r in rfleet.manager.replicas()
                     if isinstance(r, RemoteReplica)
                     and r.state == "healthy"
                     and r.lineage["index"] == vidx), None)
                if healed is not None:
                    break
                time.sleep(0.02)
            if healed is None:
                n_failures.append("partitioned worker never re-attached "
                                  "after the window healed")
            else:
                if (healed.lineage["epoch"] < 2
                        or healed.engine.epoch != healed.lineage["epoch"]):
                    n_failures.append(
                        "healed re-attach epoch not advanced "
                        f"({healed.lineage['epoch']})")
                healed.engine.set_fault("replica_slow", None)
                hq, hresp = healed.engine.make_request(drop_prompt,
                                                       drop_budget)
                healed.engine.scheduler.submit(hq, hresp)
                if (not hresp._done.wait(timeout=90)
                        or hresp.error is not None
                        or hresp.tokens() != drop_want):
                    n_failures.append(
                        "healed worker does not serve bit-identical")
        if any(p.poll() is not None for _a, p in workers):
            n_failures.append("a remote worker PROCESS died under net "
                              "chaos (must survive drops/partitions)")
        rc_counters = rfleet.manager.counters()
        weight_ship_ok = (shipped_bytes > 0 and ship_sha_ok
                          and not d_hung and not d_parity
                          and bool(pwc_remote)
                          and all(p == 0 for p in pwc_remote))
        out.update({
            "remote_workers": 2,
            "weight_bytes_shipped": shipped_bytes,
            "weight_ship_ok": weight_ship_ok,
            "partition_detect_ms": (None if part_detect_ms is None
                                    else round(part_detect_ms, 1)),
            "net_heartbeat_timeout_ms": net_hb * 1e3,
            "remote_resubmits": rc_counters["resubmits"],
            "remote_worker_restarts": rc_counters["worker_restarts"],
        })
        failures.extend(n_failures)
        rfleet.close()
        for _a, p in workers:
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:
                pass

    out["fleet_counters"] = fleet.manager.counters()
    out["health"] = {k: v for k, v in fleet.health().items()
                     if k != "replicas"}
    fleet.close()
    faults.reset()
    if failures:
        out["failures"] = failures
    print("FLEET" + json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
