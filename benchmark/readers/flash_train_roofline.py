"""The grouped flash kernel's share of its roofline in the traced stretch,
forward and backward: the least time of every layer's call of every step
(`arch.flash_call_cost`, from the pairs each layer's window keeps) over
the device time of the kernels' events, found by name.  params: forward,
backward (patterns of the two kernels' events)."""
from .. import flops, trace_reduce
from ..arch import load as load_arch


def read(run, params):
    s, tr = run.trace_summary, run.traced
    if s is None or not tr or not tr.get("steps"):
        return None
    arch = load_arch(run.config["arch"])
    if not hasattr(arch, "flash_call_cost"):
        return None
    d = arch.dims(run.config)
    t = run.traffic
    measured = least = 0.0
    for key, backward in (("forward", False), ("backward", True)):
        seconds, calls = trace_reduce.pattern_seconds(s, params[key])
        if not calls:
            return None
        measured += seconds
        for window in arch.layer_windows(d):
            ops, nbytes = arch.flash_call_cost(t["batch"], t["seq"], d,
                                               window, backward)
            least += flops.least_seconds(ops, nbytes, run.peaks)[0] \
                * tr["steps"]
    return 100.0 * least / measured
