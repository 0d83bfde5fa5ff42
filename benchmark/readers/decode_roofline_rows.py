"""`decode_roofline` from what the program says it must read: the least
bytes of the decode program's calls in the traced stretch over the peak
bandwidth, over the program's device time.  A call of `chunk` dependent
steps reads the weights once a step (`arch.decode_weight_bytes`) and the
cache rows its requests hold, which the program's span carries summed over
layers and the call's steps (`rows`, e.g. `kv_rows_live`), each
`arch.kv_row_bytes` wide.  It reads the same work whatever implements it: a
step that goes over more rows than are alive is not credited for them.
params: program (pattern of the program's events), span, rows."""
from .. import trace_reduce
from ..arch import load as load_arch


def read(run, params):
    s, tr = run.trace_summary, run.traced
    if s is None or not tr or "t1" not in tr:
        return None
    measured, calls = trace_reduce.pattern_seconds(
        s, params["program"], line="modules")
    arch = load_arch(run.config["arch"])
    if not calls or not hasattr(arch, "decode_weight_bytes"):
        return None
    from paddle_tpu.observability import get_tracer
    rows = [ev[6][params["rows"]] for ev in get_tracer().events()
            if ev[0] == params["span"] and ev[6] and params["rows"] in ev[6]
            and tr["t0"] <= ev[1] <= tr["t1"]]
    if not rows:
        return None
    d = arch.dims(run.config)
    chunk = run.traffic["engine"]["decode_chunk"]
    nbytes = (len(rows) * chunk * arch.decode_weight_bytes(d)
              + sum(rows) * arch.kv_row_bytes(d))
    # spans and traced calls can differ by the one at either edge
    nbytes *= min(1.0, calls / len(rows))
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / measured
