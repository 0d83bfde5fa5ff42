"""A statistic over the durations of the program's step spans that lie in
the measured window and did one kind of work alone: a step with a child of
the `with` names and no span of the `without` names below it at any depth
(e.g. a decode step that admitted nothing: the inside twin of the
benchmark's `engine_step` over `pure_decode` steps).  params: span, with,
without, stat, scale.  None where no such step is in the ring."""
from ..stats import stat


def values(events, window, params):
    """events: the ring's tuples (name, t0, dur, tid, id, parent, args)."""
    lo, hi = window
    steps = {ev[4]: ev[2] for ev in events
             if ev[0] == params["span"] and ev[1] >= lo
             and ev[1] + ev[2] <= hi + 1e-9}
    parent_of = {ev[4]: ev[5] for ev in events if ev[4] is not None}
    kept, dropped = set(), set()
    for ev in events:
        if ev[0] in params["with"] and ev[5] in steps:
            kept.add(ev[5])
        elif ev[0] in params["without"]:
            at = ev[5]
            while at is not None and at not in steps:
                at = parent_of.get(at)
            dropped.add(at)
    return [steps[sid] for sid in kept - dropped]


def read(run, params):
    from paddle_tpu.observability import get_tracer
    value = stat(values(get_tracer().events(), run.window, params),
                 params["stat"])
    return None if value is None else value * params.get("scale", 1.0)
