"""A statistic over the program's own spans: the tracer's ring
(`paddle_tpu.observability.get_tracer().events()`, on the harness's clock,
`time.perf_counter`), clipped to the measured window.

params: span (each span of this name that lies in the window gives one
value), stat ("mean" | "median" | "p95"), scale, and at most one of
  less      its duration less that of the named spans below it
  children  the summed duration of the named spans below it instead of its
            own (a span with none of them gives no value)
  arg       the value of this key of its `args` instead of a duration
and over (optional: divide each value by this run constant, e.g.
"max_slots").  "Below" is at any depth, by the spans' parent ids.

Returns None where the ring holds no such span, as in a program that does
not record it."""
from ..stats import stat


def values(events, window, params):
    """events: the ring's tuples (name, t0, dur, tid, id, parent, args)."""
    lo, hi = window
    name = params["span"]
    own = {ev[4]: ev for ev in events
           if ev[0] == name and ev[1] >= lo and ev[1] + ev[2] <= hi + 1e-9}
    if "arg" in params:
        return [ev[6][params["arg"]] for ev in own.values()
                if ev[6] and params["arg"] in ev[6]]
    below = params.get("less") or params.get("children")
    if not below:
        return [ev[2] for ev in own.values()]
    parent_of = {ev[4]: ev[5] for ev in events if ev[4] is not None}
    inner = {}                     # id of the span -> seconds below it
    for ev in events:
        if ev[0] not in below:
            continue
        at = ev[5]
        while at is not None and at not in own:
            at = parent_of.get(at)
        if at is not None:
            inner[at] = inner.get(at, 0.0) + ev[2]
    if "children" in params:
        return list(inner.values())
    return [ev[2] - inner.get(sid, 0.0) for sid, ev in own.items()]


def read(run, params):
    from paddle_tpu.observability import get_tracer
    vals = values(get_tracer().events(), run.window, params)
    if "over" in params:
        vals = [v / run.extra[params["over"]] for v in vals]
    value = stat(vals, params["stat"])
    return None if value is None else value * params.get("scale", 1.0)
