"""Serving traffic above the knee: the queue is topped up after every
`engine.step()` so that `backlog` requests always wait behind the slots and
no slot waits for a request.  What counts is output tokens delivered in the
window over its seconds; tails swing here and are not judged.

The mix's file is the open loop's with `backlog` (requests kept waiting,
inside the engine's `max_queue_depth`) and `pool` (how many requests are
prepared: the same lengths in the same order for every seed) in
place of a rate.
"""
import time

from . import open_loop


def serve(run, system):
    traffic = dict(run.traffic, rate_per_s=run.traffic["pool"] / run.seconds)
    plan = open_loop.schedule(traffic, run.seed, run.seconds,
                              run.config["serving"]["vocab_used"])
    backlog = run.traffic["backlog"]
    run.setup_s = time.perf_counter() - run.t_start
    return (plan,) + open_loop.drive(
        run, system, plan,
        top_up=lambda now, waiting: now < run.seconds and waiting < backlog)


def run(run):
    return open_loop.run(run, serve)
