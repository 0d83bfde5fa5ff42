"""The share of the window's seat-seconds that prompts hold, from the
program's ring clipped to the measured window.  H: each admission span's
seconds x its `seated` (the runs that cannot advance while the prompt's
host preparation, program and first-token pull run).  D: each decode call's
seconds x its `active` (the runs it advances).  Value: 100 H / (H + D).
params: admit (the admission span), decode (the decode calls' spans).

None where no admission span carries `seated`, as in a program that does
not record it."""


def clipped(t0, dur, lo, hi):
    """The seconds of [t0, t0 + dur] inside [lo, hi]."""
    return max(0.0, min(t0 + dur, hi) - max(t0, lo))


def share(events, window, params):
    """events: the ring's tuples (name, t0, dur, tid, id, parent, args)."""
    lo, hi = window
    held = moving = 0.0
    seen = False
    for name, t0, dur, _, _, _, args in events:
        if not args:
            continue
        if name == params["admit"] and "seated" in args:
            seen = True
            held += clipped(t0, dur, lo, hi) * args["seated"]
        elif name in params["decode"] and "active" in args:
            moving += clipped(t0, dur, lo, hi) * args["active"]
    if not seen or held + moving <= 0.0:
        return None
    return 100.0 * held / (held + moving)


def read(run, params):
    from paddle_tpu.observability import get_tracer
    return share(get_tracer().events(), run.window, params)
