"""`decode_roofline` for an architecture that counts its own bytes
(`arch.decode_call_bytes`): the least bytes of the decode program's calls in
the traced stretch over the peak bandwidth, over the program's device time.
A held expert counts only in the steps that routed a token to it (the step
records' `experts_hit`, from the program's `serving_decode` span), and the
cache's rows are capped at the window a request.  params: program."""
from .. import trace_reduce
from ..arch import load as load_arch


def read(run, params):
    s = run.trace_summary
    if s is None:
        return None
    measured, calls = trace_reduce.pattern_seconds(
        s, params["program"], line="modules")
    steps = [st for st in run.engine_steps
             if st.get("traced") and st["running"] and "experts_hit" in st]
    arch = load_arch(run.config["arch"])
    if not calls or not steps or not hasattr(arch, "decode_call_bytes"):
        return None
    d = arch.dims(run.config)
    chunk = run.traffic["engine"]["decode_chunk"]
    nbytes = sum(arch.decode_call_bytes(d, chunk, st["experts_hit"],
                                        st["rows_full"], st["rows_window"])
                 for st in steps)
    # engine steps and traced calls can differ by the one at either edge
    nbytes *= min(1.0, calls / len(steps))
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / measured
