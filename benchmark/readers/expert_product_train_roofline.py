"""The held experts' products' share of their roofline in the traced
stretch of a training cell, forward and backward.  Counted, a step (a span
of the program that began in the stretch and carries the routed counts):
the larger of its picks' operations at the peak and its hit experts' bytes
at the peak bandwidth (`arch.expert_product_train_cost`).  Measured: the
device time of the products' events, found by name.  A step's span says
how many grouped products its forward made (`expert_products`), and its
backward makes `events_per_product - 1` times as many more (each chunk's
products again, then the inputs' and the weights' gradients); the events
counted may differ from that by the step at either edge of the stretch,
and the count is then scaled to the events; beyond that the program's
count and the trace disagree and nothing is reported.  params: kernel
(pattern of the events), span, events_per_product."""
from .. import flops, trace_reduce
from ..arch import load as load_arch


def read(run, params):
    s, tr = run.trace_summary, run.traced
    if s is None or not tr or "t1" not in tr:
        return None
    measured, events = trace_reduce.pattern_seconds(s, params["kernel"])
    arch = load_arch(run.config["arch"])
    if not events or not hasattr(arch, "expert_product_train_cost"):
        return None
    from paddle_tpu.observability import get_tracer
    d = arch.dims(run.config)
    each = params["events_per_product"]
    steps = []           # (began, least seconds, events expected) a step
    for ev in get_tracer().events():
        args = ev[6]
        if (ev[0] != params["span"] or not args
                or "expert_products" not in args
                or not tr["t0"] <= ev[1] <= tr["t1"]):
            continue
        ops, nbytes = arch.expert_product_train_cost(
            args["routed_here"], args["experts_hit"], d)
        steps.append((ev[1], flops.least_seconds(ops, nbytes, run.peaks)[0],
                      each * args["expert_products"]))
    if not steps:
        return None
    steps.sort()
    expected = sum(n for _, _, n in steps)
    if abs(events - expected) > steps[0][2] + steps[-1][2]:
        return None
    least = sum(t for _, t, _ in steps)
    return 100.0 * least * (events / expected) / measured
