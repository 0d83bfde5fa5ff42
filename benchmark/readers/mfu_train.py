"""Model operations of the steps in the traced stretch (forward and
backward, no recomputation) over its seconds, chips and the peak."""
from .. import flops
from ..arch import load as load_arch


def read(run, params):
    tr = run.traced
    if run.trace_summary is None or not tr or not tr.get("steps"):
        return None
    arch = load_arch(run.config["arch"])
    d = arch.dims(run.config)
    t = run.traffic
    ops = tr["steps"] * flops.train_flops(run.config["arch"], t["batch"],
                                          t["seq"], d)
    seconds = run.trace_summary["window_s"]
    return 100.0 * ops / (seconds * len(run.devices)
                          * run.peaks["bf16_flops_per_s"])
