"""Serving traffic, open loop: requests are due on a schedule fixed in the
mix, whether or not earlier ones have finished, and every time is taken from
the moment a request was due.

The mix's file gives the rate, the engine's shape and the two length
distributions.  Every seed gets the same arrival times and the same prompt
and output lengths in the same order (the distributions' quantiles, shuffled
once by the mix's `mix_seed`), and its own token ids: so two seeds do the
same work.  Parameters: rate_per_s, engine {max_slots, max_len,
prefill_buckets, decode_chunk}, prompt / output {median, sigma, min, max},
check_requests, trace_seconds, drain_timeout_s, and for the
flood kind `backlog` (see flood.py).
"""
import gc
import math
import statistics
import time

import numpy as np

from .. import harness, serve_check, stats, weights as W
from ..arch import build_program_model, load as load_arch


def lognormal_quantiles(n, spec):
    """n lengths: the (i + 0.5) / n quantiles of a log-normal, clipped."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf((i + .5) / n))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def schedule(traffic, seed, seconds, vocab):
    """-> [{"due", "prompt" (ids), "out"}] sorted by due time.

    The arrival times and the order of the prompt and output lengths are
    the mix's own (`mix_seed`), the same for every seed; the seed draws the
    token ids (and the weights).  A tail latency depends on which bursts
    meet which long prompts: seeds that shuffled the order read a 95th
    percentile of 190 to 790 ms at one rate, and seeds that walked one
    cycle from different starts 490 to 740 ms (my chip runs, PR 26), so
    neither did the same work."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    mix = np.random.RandomState(traffic.get("mix_seed", 0))
    prompts = mix.permutation(lognormal_quantiles(n, traffic["prompt"]))
    outs = mix.permutation(lognormal_quantiles(n, traffic["output"]))
    gaps = mix.permutation([-math.log(1.0 - (i + .5) / n)
                            / traffic["rate_per_s"] for i in range(n)])
    due = np.cumsum(gaps)                    # the last is due at sum(gaps)
    rng = np.random.RandomState(seed % (2 ** 32))
    return [{"due": float(due[i]),
             "prompt": rng.randint(0, vocab, int(prompts[i])).astype(np.int32),
             "out": int(outs[i])} for i in range(n)]


class ServeSystem:
    """The system under test: the program's model in eval mode and its
    serving engine, warmed for the mix's buckets."""

    def __init__(self, run):
        from paddle_tpu.serving import ServingEngine
        run.lap("import")
        cfg, e = run.config, run.traffic["engine"]
        self.arch = load_arch(cfg["arch"])
        self.d = self.arch.dims(cfg)
        self.layout = self.arch.layout(self.d)
        self.model = build_program_model(cfg)
        from .. import train_check
        train_check.load_weights(self.arch, self.d, self.model,
                                 W.make(self.layout, run.seed))
        self.model.eval()
        run.lap("model_built_and_weights")
        self.engine = ServingEngine(
            self.model, max_slots=e["max_slots"], max_len=e["max_len"],
            prefill_buckets=tuple(e["prefill_buckets"]),
            decode_chunk=e["decode_chunk"],
            max_queue_depth=e.get("max_queue_depth", 64))
        self.max_slots = e["max_slots"]

    def reseed(self, seed):
        """Other weights into the idle engine; its programs stay."""
        from .. import train_check
        train_check.load_weights(self.arch, self.d, self.model,
                                 W.make(self.layout, seed))
        self.engine.swap_weights(
            {k: v._data for k, v in self.model.state_dict().items()})

    def warmup(self):
        return self.engine.warmup()

    def submit(self, prompt, out):
        return self.engine.submit(prompt, out)

    def step(self):
        return self.engine.step()

    def has_work(self):
        return self.engine.has_work()

    def occupancy(self):
        return self.engine.scheduler.occupancy()

    def queue_depth(self):
        return self.engine.scheduler.queue_depth()

    def compiles_after_warmup(self):
        return self.engine.post_warmup_compiles()

    def free(self):
        self.engine.close()
        self.engine = self.model = None
        gc.collect()


def drive(run, system, plan, top_up=None):
    """The window and its drain.  `plan` is the schedule; `top_up(now,
    waiting)` (flood) returns how many more to submit now instead."""
    clock = time.perf_counter
    traffic = run.traffic
    # a traced run profiles the last trace_seconds of the window; the
    # trace is written out only after the drain (writing it takes tens of
    # seconds, in which arrivals would pile up and be refused)
    trace_at = run.seconds - traffic["trace_seconds"] if run.trace else None
    live, done, nxt = [], [], 0
    t0 = clock()
    run.window = (t0, t0 + run.seconds)
    deadline = t0 + run.seconds + traffic.get("drain_timeout_s", 60.0)
    while True:
        now = clock()
        if now > deadline:
            break
        if trace_at is not None and now - t0 >= trace_at:
            run.start_profile()
            trace_at = None
        if (run.traced is not None and "t1" not in run.traced
                and now - t0 >= run.seconds):
            run.end_mark()
        with run.span("generate_load"):
            while nxt < len(plan) and (
                    plan[nxt]["due"] <= now - t0 if top_up is None
                    else top_up(now - t0, system.queue_depth())):
                req = plan[nxt]
                rec = {"i": nxt, "due": t0 + req["due"] if top_up is None
                       else now, "submitted": clock(), "plen": len(
                           req["prompt"]), "out": req["out"], "first": None,
                       "last": None, "n": 0, "done": False, "failed": False,
                       "admit_step": None}
                nxt += 1
                try:
                    rec["resp"] = system.submit(req["prompt"], req["out"])
                    live.append(rec)
                except Exception as e:  # refused: counts as failed
                    rec.update(failed=True, error=repr(e)[:200])
                    done.append(rec)
        if top_up is not None and now - t0 >= run.seconds:
            nxt = len(plan)            # flood: nothing new after the close
        if not live and nxt >= len(plan):
            break
        if not system.has_work():
            wait = (t0 + plan[nxt]["due"] - clock()) if nxt < len(plan) \
                else 0.0005
            time.sleep(max(0.0, min(wait, 0.001)))
            continue
        step = {"t0": clock(), "active_before": system.occupancy(),
                "queue_before": system.queue_depth()}
        with run.span("engine_step"):
            system.step()
        t_seen = clock()
        admitted, rows, tokens_out, running = [], 0, 0, 0
        with run.span("collect_tokens"):
            still = []
            for rec in live:
                resp = rec["resp"]
                n = len(resp.tokens_so_far())
                if n > rec["n"]:
                    # rows this request held when the step's decode call
                    # read the cache: its prompt and the tokens before it
                    rows += rec["plen"] + rec["n"]
                    running += 1
                    if rec["first"] is None:
                        rec["first"] = t_seen
                        rec["admit_step"] = step["t0"]
                        admitted.append(rec["plen"])
                    tokens_out += n - rec["n"]
                    rec["last"], rec["n"] = t_seen, n
                if resp.done():
                    rec["done"] = True
                    rec["failed"] = resp.error is not None
                    rec["tokens_list"] = resp.tokens_so_far()
                    rec["finish"] = resp.finish_reason
                    done.append(rec)
                else:
                    still.append(rec)
            live = still
        step.update(t1=t_seen, dur=t_seen - step["t0"],
                    admitted=len(admitted), admitted_plens=admitted,
                    live_rows=rows, running=running, tokens=tokens_out,
                    active_after=system.occupancy(),
                    pure_decode=(not admitted and step["active_before"] > 0),
                    traced=(run.traced is not None
                            and "t1" not in run.traced))
        run.engine_steps.append(step)
    t_end = clock()
    if run.traced is not None:
        run.stop_profile()
    for rec in live:                   # never finished: failed
        rec["failed"] = True
        rec["tokens_list"] = rec["resp"].tokens_so_far()
        done.append(rec)
    done.sort(key=lambda r: r["i"])
    return done, t0, t_end


def finish(run, system, plan, done, t0, t_end):
    """After the window: memory, the engine freed, the reference over a
    sample of the finished requests, the run's record."""
    run.counters["compiles_after_warmup"] = system.compiles_after_warmup()
    from paddle_tpu import programs
    run.counters["store"] = programs.store_stats()
    run.extra["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
    arch, d, layout = system.arch, system.d, system.layout
    system.free()
    for rec in done:
        rec.pop("resp", None)
        rec["tokens"] = rec["n"]
        rec["queue_wait"] = (rec["admit_step"] - rec["due"]
                             if rec["admit_step"] is not None else None)
        rec["late"] = rec["submitted"] - rec["due"]
    run.requests = done
    t_ref = time.perf_counter()
    sample = serve_check.pick_sample(done, run.seed,
                                     run.traffic["check_requests"])
    numbers = serve_check.compare_sample(
        arch, d, layout, run.seed, plan, sample, run.traffic["engine"][
            "max_len"])
    run.extra["reference_s"] = time.perf_counter() - t_ref
    run.extra["checked_tokens"] = numbers.pop("checked_tokens")
    run.extra["mismatched_tokens"] = numbers.pop("mismatched_tokens")
    numbers["compiles_in_window"] = float(
        run.counters["compiles_after_warmup"])
    failed = sum(1 for r in done if r["failed"])
    numbers["failed_requests"] = float(failed)
    run.extra.update(
        max_slots=system.max_slots, drain_s=t_end - (t0 + run.seconds),
        generator_late_p95_ms=stats.percentile(
            [r["late"] * 1e3 for r in done], 95),
        requests=len(done), engine_steps=len(run.engine_steps))
    if run.engine_steps:
        # a stall of the host or the chip shows here (one run in nineteen
        # of the chat cell lost 4.8 s to one, cause not found: PERF.md)
        worst = max(run.engine_steps, key=lambda st: st["dur"])
        run.extra["longest_engine_step"] = {
            "ms": worst["dur"] * 1e3, "at_s": worst["t0"] - t0,
            "admitted": worst["admitted"]}
    worst_ms = (t_end - t0) * 1e3
    e2e = stats.serve_end_to_end(done, run.seconds, worst_ms)
    # tokens DELIVERED IN THE WINDOW, not those of the drain after it
    e2e["serve_tokens_per_s"] = sum(
        st["tokens"] for st in run.engine_steps
        if st["t1"] <= t0 + run.seconds) / run.seconds
    return {"attempted": len(done), "failed": failed, "end_to_end": e2e,
            "numbers": numbers}


def serve(run, system):
    """The mix's requests through the warmed system: plan, window, drain."""
    plan = schedule(run.traffic, run.seed, run.seconds,
                    run.config["serving"]["vocab_used"])
    run.setup_s = time.perf_counter() - run.t_start
    return (plan,) + drive(run, system, plan)


def run(run, serve=serve):
    system = ServeSystem(run)
    warm = system.warmup()
    run.lap("engine_warmed")
    run.counters["warmup_seconds"] = warm["seconds"]
    plan, done, t0, t_end = serve(run, system)
    return finish(run, system, plan, done, t0, t_end)
