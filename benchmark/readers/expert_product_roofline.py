"""The grouped expert product's share of its roofline in the traced stretch.

Counted, a program call (a `serving_decode` or `serving_admit` span of the
program that began in the stretch and carries the routed counts): the larger
of its picks' operations at the peak and its hit experts' bytes at the peak
bandwidth (`arch.expert_product_cost`).  Measured: the device time of the
product's events, found by name.  How many such events a call makes is the
program's own count (`expert_products` in the span's args: it knows its
blocks and how many products it fused), so the calls counted can be held to
the events measured: they may differ by the call at either edge of the
stretch, and the count is then scaled to the events; beyond that the
program's count and the trace disagree and nothing is reported.
params: kernel (pattern of its events), spans (the spans that carry
`routed_here`, `experts_hit` and `expert_products`)."""
from .. import flops, trace_reduce
from ..arch import load as load_arch


def read(run, params):
    s, tr = run.trace_summary, run.traced
    if s is None or not tr or "t1" not in tr:
        return None
    measured, events = trace_reduce.pattern_seconds(s, params["kernel"])
    arch = load_arch(run.config["arch"])
    if not events or not hasattr(arch, "expert_product_cost"):
        return None
    from paddle_tpu.observability import get_tracer
    d = arch.dims(run.config)
    calls = []           # (began, least seconds, events expected) a call
    for ev in get_tracer().events():
        args = ev[6]
        if (ev[0] not in params["spans"] or not args
                or "expert_products" not in args
                or not tr["t0"] <= ev[1] <= tr["t1"]):
            continue
        ops, nbytes = arch.expert_product_cost(
            args["routed_here"], args["experts_hit"], d)
        calls.append((ev[1], flops.least_seconds(ops, nbytes, run.peaks)[0],
                      args["expert_products"]))
    calls.sort()
    expected = sum(n for _, _, n in calls)
    if not expected or abs(events - expected) > calls[0][2] + calls[-1][2]:
        return None
    least = sum(t for _, t, _ in calls)
    return 100.0 * least * (events / expected) / measured
