"""A prompt's attention kernel's share of its roofline in the traced stretch.

Counted, a prefill call (a span of the program that began in the stretch and
carries its `bucket`): every layer's attention over the bucket at the least
(`arch.prefill_attention_cost`: the attended pairs' operations at the peak or
the rows' bytes at the peak bandwidth, whichever is larger).  Measured: the
device time of the kernel's events, found by name.  A call makes one event a
window a layer; the calls counted are held to the events measured as
`expert_product_roofline` does: they may differ by the call at either edge of
the stretch, and the count is then scaled to the events; beyond that nothing
is reported.  params: kernel (pattern of its events), span."""
from .. import flops, trace_reduce
from ..arch import load as load_arch


def read(run, params):
    s, tr = run.trace_summary, run.traced
    if s is None or not tr or "t1" not in tr:
        return None
    measured, events = trace_reduce.pattern_seconds(s, params["kernel"])
    arch = load_arch(run.config["arch"])
    if not events or not hasattr(arch, "prefill_attention_cost"):
        return None
    from paddle_tpu.observability import get_tracer
    d = arch.dims(run.config)
    calls = []           # (began, least seconds, events expected) a call
    for ev in get_tracer().events():
        args = ev[6]
        if (ev[0] != params["span"] or not args or "bucket" not in args
                or not tr["t0"] <= ev[1] <= tr["t1"]):
            continue
        ops, nbytes = arch.prefill_attention_cost(args["bucket"], d)
        calls.append((ev[1], d["L"] * flops.least_seconds(
            ops, nbytes, run.peaks)[0],
            d["L"] * -(-args["bucket"] // d["window"])))
    calls.sort()
    expected = sum(n for _, _, n in calls)
    if not expected or abs(events - expected) > calls[0][2] + calls[-1][2]:
        return None
    least = sum(t for _, t, _ in calls)
    return 100.0 * least * (events / expected) / measured
