"""A statistic over the benchmark's own spans of one name, in the window.
params: span, stat ("mean" | "median" | "p95"), scale (seconds -> unit)."""
from ..stats import stat


def read(run, params):
    t0, t1 = run.window
    durs = [e - s for name, s, e in run.spans
            if name == params["span"] and s >= t0 and e <= t1 + 1e-9]
    value = stat(durs, params["stat"])
    return None if value is None else value * params.get("scale", 1.0)
