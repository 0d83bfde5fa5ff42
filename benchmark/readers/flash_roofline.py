"""The flash kernels' share of their roofline in the traced stretch: the
least time for every forward and backward call's operations and bytes over
the kernels' device time.  params: forward, backward (patterns that find the
kernels' events in the trace, read off a trace by hand)."""
from .. import flops, trace_reduce
from ..arch import load as load_arch


def read(run, params):
    s, tr = run.trace_summary, run.traced
    if s is None or not tr or not tr.get("steps"):
        return None
    arch = load_arch(run.config["arch"])
    d = arch.dims(run.config)
    t = run.traffic
    measured = 0.0
    least = 0.0
    for key, backward in (("forward", False), ("backward", True)):
        seconds, calls = trace_reduce.pattern_seconds(s, params[key])
        if not calls:
            return None
        measured += seconds
        ops, nbytes = flops.flash_attention_cost(
            t["batch"], d["heads"], t["seq"], t["seq"],
            d["H"] // d["heads"], arch.CAUSAL, backward)
        per_call, _ = flops.least_seconds(ops, nbytes, run.peaks)
        # one call a layer a step, however many events the kernel splits into
        least += per_call * d["L"] * tr["steps"]
    return 100.0 * least / measured if measured else None
