"""A share of the measured window from the program's ring: the seconds of
the named spans in the window, over W, the window less the profiler's start.
That start is the harness's, not the program's: from the end of the last
benchmark span (`run.spans`) that ended before the traced stretch to the
stretch's first moment (`run.traced["t0"]`); spans are left out over it too.

params: spans (names), complement (true: 100 (W - their seconds) / W, the
rest of the window: with the engine's waits on the chip, the host's hold on
the chip), absent (the value where the ring holds none of the spans, for a
hook that is always installed; without it None)."""
from .seat_seconds import clipped


def profiler_start(run):
    """(t0, t1) of the profiler's start inside the window, or None."""
    traced = run.traced
    if not traced or "t0" not in traced:
        return None
    lo, hi = run.window
    ends = [t1 for _, _, t1 in run.spans if t1 <= traced["t0"]]
    t0 = max([lo] + [t for t in ends if t >= lo])
    t1 = min(traced["t0"], hi)
    return (t0, t1) if t1 > t0 else None


def share(events, window, stall, params):
    """events: the ring's tuples; stall: (t0, t1) left out, or None."""
    lo, hi = window
    names = set(params["spans"])
    out = stall[1] - stall[0] if stall else 0.0
    seconds, seen = 0.0, False
    for name, t0, dur, *_ in events:
        if name not in names:
            continue
        seen = True
        seconds += clipped(t0, dur, lo, hi)
        if stall:
            seconds -= clipped(t0, dur, *stall)
    if not seen:
        return params.get("absent")
    w = hi - lo - out
    if params.get("complement"):
        seconds = w - seconds
    return 100.0 * seconds / w


def read(run, params):
    from paddle_tpu.observability import get_tracer
    return share(get_tracer().events(), run.window, profiler_start(run),
                 params)
