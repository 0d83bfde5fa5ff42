"""The decode program's share of its memory roofline in the traced stretch:
the least bytes its calls need (per call: decode_chunk dependent steps, each
reading the weights once and the LIVE rows of the cache, not the whole
buffer) over the peak bandwidth, over the program's device time.  params:
program (pattern that finds the decode program's executions in the trace's
"XLA Modules" line)."""
from .. import flops, trace_reduce
from ..arch import load as load_arch


def read(run, params):
    s = run.trace_summary
    if s is None:
        return None
    measured, calls = trace_reduce.pattern_seconds(
        s, params["program"], line="modules")
    steps = [st for st in run.engine_steps
             if st.get("traced") and st["running"]]
    if not calls or not steps:
        return None
    d = load_arch(run.config["arch"]).dims(run.config)
    chunk = run.traffic["engine"]["decode_chunk"]
    nbytes = sum(flops.decode_call_bytes(d, st["live_rows"], chunk)
                 for st in steps)
    # engine steps and traced calls can differ by the one at either edge
    nbytes *= min(1.0, calls / len(steps))
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / measured
