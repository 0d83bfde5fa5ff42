"""`mfu_train` for an architecture that counts its own operations
(`arch.train_flops(batch, seq, d, picks_here)`, a routed model's experts
from the picks its `train_step` spans say fell here): the model operations
of the steps in the traced stretch, forward and backward with no
recomputation, over its seconds, chips and the peak.  params: span (the
span that carries `routed_here`).  None where no step of the stretch
carries it, as in a program that does not count its picks."""
from ..arch import load as load_arch


def read(run, params):
    tr = run.traced
    if run.trace_summary is None or not tr or not tr.get("steps"):
        return None
    from paddle_tpu.observability import get_tracer
    arch = load_arch(run.config["arch"])
    d = arch.dims(run.config)
    t = run.traffic
    picks = [ev[6]["routed_here"] for ev in get_tracer().events()
             if ev[0] == params["span"] and ev[6] and "routed_here" in ev[6]
             and tr["t0"] <= ev[1] <= tr["t1"]]
    if len(picks) != tr["steps"]:
        return None
    ops = sum(arch.train_flops(t["batch"], t["seq"], d, p) for p in picks)
    seconds = run.trace_summary["window_s"]
    return 100.0 * ops / (seconds * len(run.devices)
                          * run.peaks["bf16_flops_per_s"])
